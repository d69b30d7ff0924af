"""A plain range selector sees every raw sample of (T - range, T], on the
interpreter and on the compiled route alike (ROADMAP B-m2, PR 42).

Both routes are held to `benchmark/reference/promql_counter_ref.py`
(numpy float64, Prometheus' rules written out, nothing of the program)
for every range function over a grid of (step, range, cadence) shapes
and sample layouts: on the cadence, off it by jitter, with a gap, a
reset, a counter at 2^40, a series that starts mid-range. One case holds
that the reference's `gridded` control (one sample a gcd(step, range)
cell: the program before this PR) is told apart, one that a subquery's
answer is still what it was, bit for bit."""

import importlib.util
import math
import os

import numpy as np
import pytest

from m3_tpu.query import Engine
from m3_tpu.query import plan as qplan
from m3_tpu.query import window as qwindow

S = 1_000_000_000
T0 = 1_700_000_400 * S
HERE = os.path.dirname(os.path.abspath(__file__))

_spec = importlib.util.spec_from_file_location(
    "promql_counter_ref", os.path.join(
        HERE, "..", "benchmark", "reference", "promql_counter_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)


class MemStorage:
    def __init__(self):
        self.series = []

    def add(self, tags, t, v):
        self.series.append((tags, np.asarray(t, np.int64),
                            np.asarray(v, np.float64)))

    def fetch_raw(self, matchers, start_ns, end_ns):
        out = {}
        for tags, t, v in self.series:
            if all(m.matches(tags.get(m.name, b"")) for m in matchers):
                keep = (t >= start_ns) & (t < end_ns)
                sid = b",".join(k + b"=" + x for k, x in sorted(tags.items()))
                out[sid] = {"tags": tags, "t": t[keep], "v": v[keep]}
        return out


def counters(seed, n, steps, start=0.0):
    """TSBS `net`-like monotonic walks: whole numbers growing by about 50
    a step."""
    rng = np.random.default_rng(seed)
    inc = np.abs(rng.normal(50.0, 10.0, (n, steps)))
    return np.floor(start + np.cumsum(inc, axis=1))


# name -> (cadence_s, step_s, range_s, what is done to the samples)
CASES = {
    "step_eq_range": (10, 60, 60, None),
    "step_gt_range": (10, 120, 60, None),
    "range_5x_step": (10, 60, 300, None),
    "step45_range2m": (10, 45, 120, None),
    "jittered": (10, 60, 60, "jitter"),
    "gap": (10, 60, 120, "gap"),
    "reset": (10, 60, 120, "reset"),
    "counter_2p40": (10, 60, 60, "big"),
    "starts_mid_range": (10, 60, 120, "late"),
}

FUNCS = {
    "rate": "rate(m[%s])", "increase": "increase(m[%s])",
    "irate": "irate(m[%s])", "delta": "delta(m[%s])",
    "idelta": "idelta(m[%s])", "changes": "changes(m[%s])",
    "resets": "resets(m[%s])", "deriv": "deriv(m[%s])",
    "predict_linear": "predict_linear(m[%s], 30)",
    "holt_winters": "holt_winters(m[%s], 0.5, 0.3)",
    "quantile_over_time": "quantile_over_time(0.7, m[%s])",
    "sum_over_time": "sum_over_time(m[%s])",
    "avg_over_time": "avg_over_time(m[%s])",
    "min_over_time": "min_over_time(m[%s])",
    "max_over_time": "max_over_time(m[%s])",
    "count_over_time": "count_over_time(m[%s])",
    "last_over_time": "last_over_time(m[%s])",
    "stddev_over_time": "stddev_over_time(m[%s])",
    "stdvar_over_time": "stdvar_over_time(m[%s])",
    "present_over_time": "present_over_time(m[%s])",
}
ARGS = {"predict_linear": (30.0,), "holt_winters": (0.5, 0.3),
        "quantile_over_time": (0.7,)}
N, STEPS_HELD, OUT_STEPS = 3, 90, 7


def build(case):
    """(storage, per-series (t_ns, v), query start/end/step/range ns)."""
    cadence, step, rng_s, twist = CASES[case]
    seed = sorted(CASES).index(case)
    vals = counters(100 + seed, N, STEPS_HELD,
                    start=float(2 ** 40) if twist == "big" else 1000.0)
    rng = np.random.default_rng(200 + seed)
    st = MemStorage()
    rows = []
    for i in range(N):
        t = T0 + np.arange(STEPS_HELD, dtype=np.int64) * cadence * S
        v = vals[i]
        if twist == "jitter":
            # scrape jitter of up to 1.5 s either way, on no common grid
            t = t + rng.integers(-1_500, 1_500, STEPS_HELD) * 1_000_037
        elif twist == "gap" and i != 1:
            keep = np.ones(STEPS_HELD, bool)
            keep[40 + 3 * i:49 + 3 * i] = False
            t, v = t[keep], v[keep]
        elif twist == "reset" and i != 1:
            v = v.copy()
            v[45 + i:] -= np.floor(v[45 + i] - 7)      # restarts near zero
        elif twist == "late" and i != 1:
            t, v = t[47 + i:], v[47 + i:]
        st.add({b"__name__": b"m", b"i": str(i).encode()}, t, v)
        rows.append((t, v))
    # output times off the cadence's phase by 3 s, as a dashboard's are
    end = T0 + (STEPS_HELD - 1) * cadence * S - 7 * S
    start = end - (OUT_STEPS - 1) * step * S
    return st, rows, start, end, step * S, rng_s * S


def want_for(func, rows, start, end, step, rng):
    times = (np.arange(start, end + 1, step) - T0) / 1e9
    return np.stack([ref.window_values(func, (t - T0) / 1e9, v, times,
                                       rng / 1e9, ARGS.get(func, ()))
                     for t, v in rows])


def rows_by_series(block):
    order = np.argsort([int(t.get(b"i")) for t in block.series_tags])
    return np.asarray(block.values, np.float64)[order]


def assert_close(got, want, func):
    assert got.shape == want.shape
    assert (np.isfinite(got) == np.isfinite(want)).all(), (got, want)
    m = np.isfinite(want)
    # the regressions and smoothers run in f32 on the device; everything
    # that differences a counter must hold to the benchmark's own limit
    rtol = 2e-3 if func in ("deriv", "predict_linear", "holt_winters",
                            "stddev_over_time", "stdvar_over_time") else 1e-5
    scale = np.abs(want[m]).max() if m.any() else 1.0
    np.testing.assert_allclose(got[m], want[m], rtol=rtol,
                               atol=rtol * 1e-2 * max(scale, 1e-9))


@pytest.fixture(autouse=True)
def no_plan_floor(monkeypatch):
    monkeypatch.setattr(qplan, "PLAN_MIN_CELLS", 1)


@pytest.mark.parametrize("func", sorted(FUNCS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_both_routes_answer_as_the_reference(case, func):
    st, rows, start, end, step, rng = build(case)
    want = want_for(func, rows, start, end, step, rng)
    q = FUNCS[func] % ("%ds" % (rng // S))
    eng = Engine(st, mesh=None)
    interp = eng.execute_range_ref(q, start, end, step)
    assert_close(rows_by_series(interp), want, func)
    compiled = eng.execute_range(q, start, end, step)
    assert eng.last_route()["route"] == "compiled", eng.last_route()
    assert_close(rows_by_series(compiled), want, func)


@pytest.mark.parametrize("case,packed", [
    ("step_eq_range", False), ("step45_range2m", False), ("gap", False),
    ("starts_mid_range", False), ("jittered", True)])
def test_the_layout_follows_the_samples(case, packed):
    st, rows, start, end, step, rng = build(case)
    eng = Engine(st, mesh=None)
    from m3_tpu.query import promql
    from m3_tpu.query.executor import QueryParams

    sel = promql.parse("rate(m[%ds])" % (rng // S)).args[0]
    rw = eng._eval_range_selector(sel, QueryParams(start, end, step))
    assert rw.packed is packed
    held = sum(int(((t > start - rng) & (t <= end)).sum()) for t, _ in rows)
    if not packed:
        cadence = CASES[case][0] * S
        assert rw.cell_ns == math.gcd(cadence, step)
        # every fetched sample has a lane of its own
        assert int(np.isfinite(rw.block.values).sum()) == held
    else:
        per_window = sum(int(((t > T - rng) & (t <= T)).sum())
                         for t, _ in rows
                         for T in range(start, end + 1, step))
        assert int(np.isfinite(rw.block.values).sum()) == per_window
        assert rw.W == rw.stride


def test_a_sum_of_rates_over_the_mesh_answers_as_the_reference():
    st, rows, start, end, step, rng = build("range_5x_step")
    want = np.nansum(want_for("rate", rows, start, end, step, rng), axis=0)
    eng = Engine(st)          # auto: the 8 virtual devices of conftest
    got = eng.execute_range_ref("sum(rate(m[300s]))", start, end, step)
    np.testing.assert_allclose(np.asarray(got.values)[0], want, rtol=1e-5)
    st, rows, start, end, step, rng = build("jittered")
    want = np.nansum(want_for("rate", rows, start, end, step, rng), axis=0)
    got = Engine(st).execute_range_ref("sum(rate(m[60s]))", start, end, step)
    np.testing.assert_allclose(np.asarray(got.values)[0], want, rtol=1e-5)


def test_predict_linear_under_an_offset_counts_from_the_windows_end():
    """DIVERGENCES.md: under `offset o` the regression's intercept is
    taken at the shifted window's end T - o, where Prometheus takes it at
    T; the two differ by slope * o."""
    st, rows, start, end, step, rng = build("range_5x_step")
    off = 60 * S
    got = rows_by_series(Engine(st, mesh=None).execute_range_ref(
        "predict_linear(m[300s] offset 60s, 30)", start, end, step))
    ours = want_for("predict_linear", rows, start - off, end - off, step, rng)
    slope = want_for("deriv", rows, start - off, end - off, step, rng)
    assert_close(got, ours, "predict_linear")
    upstream = ours + slope * (off / 1e9)
    assert (np.abs(got - upstream) > 100).all()     # ~5 a second x 60 s


@pytest.mark.parametrize("tie", ["first_sample_11s_in", "clamp_to_zero"])
def test_a_tie_at_1p1_intervals_is_never_within(tie):
    """DIVERGENCES.md: "within 1.1 mean intervals of the window's edge"
    is decided cross-multiplied, exactly, on both routes and in the
    reference, as upstream's float64 product decides it at a 10 s
    cadence; an f32 quotient on the chip decided it the other way (PR
    42's first traced run: one answer of 200 off by 1.1%)."""
    st = MemStorage()
    if tie == "first_sample_11s_in":
        # first sample 11 s after the window's open start, gaps of 10 s
        v = np.array([1000.0, 1050, 1100, 1150, 1200])
        t = T0 + (50 + 10 * np.arange(5)) * S
        T, rng = T0 + 99 * S, 60 * S
        if_within = 60.0 / 40.0                    # extrapolated by 11 + 9
    else:
        # a counter 55 above zero that grew by 250 in 50 s: zero lies
        # 50 * 55 / 250 = 11 s before its first sample
        v = np.array([55.0, 105, 155, 205, 255, 305])
        t = T0 + 10 * np.arange(6) * S
        T, rng = T0 + 55 * S, 90 * S
        if_within = (50.0 + 11 + 5) / 50.0
    assert not 11.0 < 10.0 * 1.1    # Go's float64 product: not within either
    st.add({b"__name__": b"m", b"i": b"0"}, t, v)
    want = ref.window_values("increase", (t - T0) / 1e9, v,
                             [(T - T0) / 1e9], rng / 1e9)
    # a tie is not within: the start is extrapolated by half an interval
    tail = (T - t[-1]) / 1e9
    assert want[0] == pytest.approx(
        (v[-1] - v[0]) * ((t[-1] - t[0]) / 1e9 + 5 + tail)
        / ((t[-1] - t[0]) / 1e9))
    assert abs(want[0] / (v[-1] - v[0]) - if_within) > 0.05
    eng = Engine(st, mesh=None)
    q = "increase(m[%ds])" % (rng // S)
    for run in (eng.execute_range_ref, eng.execute_range):
        got = np.asarray(run(q, T, T, S).values, np.float64)[0]
        np.testing.assert_allclose(got, want, rtol=1e-6)


def test_the_gridded_control_is_told_apart():
    """One sample a gcd(step, range) cell is what the program answered
    before PR 42; the reference's comparison must refuse it."""
    cfg = {"scale": 2, "cadence_s": 10,
           "schema": {"fields": ["bytes_sent", "bytes_recv"]}}
    labels = [{"__name__": "net", "field": f, "hostname": "host_%d" % h}
              for h in range(2) for f in cfg["schema"]["fields"]]
    vals = counters(7, 4, 60).astype(np.int64)
    t0_s = T0 // S
    req = {"hosts": [0, 1], "fields": [0, 1], "start_s": t0_s + 290,
           "end_s": t0_s + 590, "step_s": 60}
    for window_s, missing in ((60, True), (300, False)):
        cls = {"reference": {"fn": "rate", "window_s": window_s,
                             "group_by": ["field"], "group_fn": "sum"}}
        sound = ref.evaluate(cls, cfg, labels, vals, req, t0_s)
        same = ref.compare(ref.evaluate(cls, cfg, labels, vals, req, t0_s),
                           sound)
        assert (same["worst_rel_gap"], same["points_missing_or_extra"],
                same["label_sets_differ"]) == (0.0, 0, 0)
        c = ref.compare(ref.evaluate(cls, cfg, labels, vals, req, t0_s,
                                     control="gridded"), sound)
        if missing:       # range == step: ONE cell a window, no rate at all
            assert c["label_sets_differ"] == 2
        else:
            assert c["worst_rel_gap"] > 1e-3


# What the parent commit (d5eee74) answered, recorded there with this
# file's `build("range_5x_step")` storage: a subquery does not pass
# through the binding this PR changed, so its answers stay, to the bit.
SUBQUERY = "max_over_time(m[2m:10s])"
SUBQUERY_RATE = "rate(m[2m:10s])"
RECORDED = os.path.join(HERE, "data", "subquery_answers_parent.npz")


@pytest.mark.parametrize("route", ["interpreter", "compiled"])
@pytest.mark.parametrize("query", [SUBQUERY, SUBQUERY_RATE])
def test_a_subquery_answers_bit_for_bit_as_the_parent_did(query, route):
    st, _rows, start, end, step, _rng = build("range_5x_step")
    eng = Engine(st, mesh=None)
    run = eng.execute_range_ref if route == "interpreter" \
        else eng.execute_range
    got = rows_by_series(run(query, start, end, step))
    want = np.load(RECORDED)["%s|%s" % (query, route)]
    assert got.tobytes() == want.tobytes()


def test_no_gcd_of_step_and_range_under_query():
    root = os.path.join(HERE, "..", "m3_tpu", "query")
    for name in os.listdir(root):
        if name.endswith(".py"):
            text = open(os.path.join(root, name)).read()
            assert "gcd(params.step_ns, sel.range_ns)" not in text
            assert "gcd(p.step_ns, sel.range_ns)" not in text
    assert qwindow.range_windows is not None
