"""Targets scraped at Prometheus's own per-target offsets (a whole number
of milliseconds inside the interval, `offset + k * interval`), which no
TSBS cell has: every series of a host on that host's grid, no two hosts
on one.

* The engine's answers equal `benchmark/reference/promql_offset_ref.py`
  (numpy float64 per series over the series' own timestamps; nothing of
  the program) for every class of the `prom-mixed-thin` mix, on the
  interpreter and on the compiled route, with one host (one grid: the
  dense layout) and eight (eight grids: the packed one).
* A block of such rows seals at the MILLISECOND unit and `decode_rows`
  returns its timestamps and values bit for bit.
* A read sent after an acknowledged append sees it: with the bucket's
  index built before the append (the tail scan) and after the tail has
  outgrown it (a regroup), and the counters say which.
* The reference's controls and its write-frontier comparison tell what
  they must apart."""

import importlib.util
import json
import os

import numpy as np
import pytest

from m3_tpu.ops import decode_rows as decode_rows_mod
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.query import Engine
from m3_tpu.query import plan as qplan
from m3_tpu.query import promql
from m3_tpu.query.executor import QueryParams
from m3_tpu.storage import buffer as buffer_mod
from m3_tpu.storage.block import encode_block
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.utils import xtime
from m3_tpu.utils.instrument import ROOT

S = 1_000_000_000
MS = 1_000_000
T0 = 1_700_000_400 * S
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "benchmark")

_spec = importlib.util.spec_from_file_location(
    "promql_offset_ref", os.path.join(BENCH, "reference",
                                      "promql_offset_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

HOSTS, STEPS = 24, 400
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice"]
CFG = {"scale": HOSTS, "cadence_s": 10,
       "schema": {"measurement": "cpu", "fields": FIELDS}}
with open(os.path.join(BENCH, "traffic", "prom-mixed-thin.json")) as _f:
    MIX = [m["class"] for m in json.load(_f)["mix"]]


def load_class(name):
    with open(os.path.join(BENCH, "classes", name + ".json")) as f:
        return json.load(f)


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


@pytest.fixture(scope="module")
def fleet():
    """24 hosts x 10 fields, each host at a seeded millisecond offset,
    whole-number gauges in [0, 100]: (storage, labels, vals, offsets)."""
    rng = np.random.default_rng(46)
    off_ms = rng.integers(0, 10_000, HOSTS)
    vals = np.clip(np.cumsum(rng.normal(0, 3, (HOSTS * len(FIELDS), STEPS)),
                             axis=1) + 50, 0, 100).astype(np.uint8)
    st, labels = MemStorage(), []
    for h in range(HOSTS):
        t = ref.series_times_ns(CFG, T0 // S, off_ms[h], STEPS)
        for f, field in enumerate(FIELDS):
            lab = {"__name__": "cpu", "field": field,
                   "hostname": "host_%d" % h}
            labels.append(lab)
            st.add({k.encode(): v.encode() for k, v in lab.items()}, t,
                   vals[h * len(FIELDS) + f])
    return st, labels, vals, off_ms


@pytest.fixture
def no_plan_floor(monkeypatch):
    monkeypatch.setattr(qplan, "PLAN_MIN_CELLS", 1)


def request(cls, hosts, fields, end_s):
    """What `benchmark/harness/schedule.py::build_request` makes of a
    class and a draw."""
    q = cls["promql"].replace("$hosts", "|".join("host_%d" % h
                                                 for h in hosts))
    if fields is not None:
        q = q.replace("$fields", "|".join(FIELDS[f] for f in fields))
    if cls["endpoint"] == "query_range":
        start_s, step_s = end_s - int(cls["range_s"]), int(cls["step_s"])
    else:
        start_s, step_s = end_s, 1
    return q, {"hosts": hosts, "fields": fields, "start_s": start_s,
               "end_s": end_s, "step_s": step_s}


def tags_items(block):
    out = {}
    for t, row in zip(block.series_tags, np.asarray(block.values)):
        out[frozenset((k.decode(), v.decode()) for k, v in t.pairs)] = \
            np.asarray(row, np.float64)
    return out


@pytest.mark.parametrize("layout", ["dense", "packed"])
@pytest.mark.parametrize("route", ["interpreter", "compiled"])
@pytest.mark.parametrize("name", MIX)
def test_the_engine_answers_as_the_offset_reference(
        fleet, no_plan_floor, name, route, layout):
    st, labels, vals, off_ms = fleet
    cls = load_class(name)
    assert ":10s]" not in cls["promql"]      # a plain range selector
    rng = np.random.default_rng(sorted(MIX).index(name))
    n_hosts = 1 if layout == "dense" else 8
    hosts = sorted(int(h) for h in rng.choice(HOSTS, n_hosts, replace=False))
    nf = cls["draw"]["fields"]
    fields = sorted(int(f) for f in rng.choice(len(FIELDS), nf,
                                               replace=False)) if nf else None
    # a whole second inside the newest scrape interval, as a dashboard's
    # now is
    end_s = T0 // S + (STEPS - 1) * 10 - int(rng.integers(0, 120))
    q, req = request(cls, hosts, fields, end_s)
    want = ref.evaluate(cls, CFG, labels, vals, req, T0 // S,
                        offsets_ms=off_ms)
    eng = Engine(st, mesh=None)
    run = eng.execute_range_ref if route == "interpreter" \
        else eng.execute_range
    got = tags_items(run(q, req["start_s"] * S, req["end_s"] * S,
                         req["step_s"] * S))
    if route == "compiled":
        assert eng.last_route()["route"] == "compiled", eng.last_route()
    c = ref.compare(got, want)
    assert c["values"] > 0 and c["label_sets_differ"] == 0, c
    assert c["points_missing_or_extra"] == 0 and c["worst_rel_gap"] <= 1e-5, c
    # the layout is the one the host count implies, and is counted
    key = "query.range_selector.layouts{layout=%s}" % layout
    before = ROOT.snapshot().get(key, 0)
    sel = promql.parse(q)
    while not hasattr(sel, "range_ns") or not sel.range_ns:
        sel = sel.args[0] if getattr(sel, "args", None) else sel.expr
    rw = eng._eval_range_selector(sel, QueryParams(
        req["start_s"] * S, req["end_s"] * S, req["step_s"] * S))
    assert rw.packed is (layout == "packed")
    assert ROOT.snapshot()[key] == before + 1


@pytest.mark.parametrize("control,rows", [
    ("stale", {"points_missing_or_extra", "worst_rel_gap"}),
    ("aligned", {"points_missing_or_extra", "worst_rel_gap"}),
    ("bf16", {"worst_rel_gap"}),
])
def test_the_references_controls_are_told_apart(fleet, control, rows):
    _st, labels, vals, off_ms = fleet
    cls = load_class("single-groupby-5-8-1-plain")
    if control == "bf16":
        # a MAX of whole numbers up to 100 is exact in bfloat16 (thin's
        # mix too: PERF.md section 2); a mean is not
        cls = dict(cls, reference=dict(cls["reference"], window_fn="avg",
                                       group_fn="avg"))
    _q, req = request(cls, [1, 4, 5, 9, 12, 17, 20, 23], [0, 2, 3, 6, 8],
                      T0 // S + (STEPS - 1) * 10 - 3)
    want = ref.evaluate(cls, CFG, labels, vals, req, T0 // S,
                        offsets_ms=off_ms)
    broken = ref.evaluate(cls, CFG, labels, vals, req, T0 // S,
                          control=control, open_steps=40, offsets_ms=off_ms)
    c = ref.compare(broken, want)
    bad = {k for k in ("label_sets_differ", "points_missing_or_extra")
           if c[k]} | ({"worst_rel_gap"} if c["worst_rel_gap"] > 1e-5
                       else set())
    assert bad and bad <= rows, c
    assert ref.compare(want, want)["worst_rel_gap"] == 0.0


def test_the_reference_needs_no_shared_grid(fleet):
    """A series with holes and its own phase: the window (t - w, t] is
    found on the series' own timestamps."""
    _st, labels, vals, off_ms = fleet
    cls = load_class("single-groupby-1-1-1-plain")
    end_s = T0 // S + 390 * 10
    _q, req = request(cls, [3], [2], end_s)
    visible = np.ones((HOSTS, STEPS), bool)
    visible[3, 380:385] = False
    got = ref.evaluate(cls, CFG, labels, vals, req, T0 // S,
                       offsets_ms=off_ms, visible=visible)
    (row,) = got.values()
    t = ref.series_times_ns(CFG, T0 // S, off_ms[3], STEPS)
    v = vals[3 * len(FIELDS) + 2].astype(np.float64)
    for j, x in enumerate(range(req["start_s"], end_s + 1, 60)):
        inside = (t > (x - 60) * S) & (t <= x * S) & visible[3]
        assert row[j] == (v[inside].max() if inside.any() else np.nan) \
            or (np.isnan(row[j]) and not inside.any())


@pytest.mark.parametrize("case", ["acknowledged", "took_in_flight",
                                  "in_flight_below", "made_up",
                                  "lost_acknowledged", "empty_row_filled"])
def test_the_frontier_comparison_is_exact(case):
    """An output (row, step) that in-flight samples reach must equal the
    reference over the acknowledged samples or an in-flight value above
    it, and nothing else."""
    key = frozenset({("field", "usage_user")})
    want = {key: np.array([40.0, 55.0, np.nan])}
    cands = {key: {1: [70.0, 30.0], 2: [12.0]}}
    served = {
        "acknowledged": [40.0, 55.0, np.nan],
        "took_in_flight": [40.0, 70.0, 12.0],
        "in_flight_below": [40.0, 30.0, np.nan],   # 30 < 55 cannot be a max
        "made_up": [40.0, 62.0, np.nan],
        "lost_acknowledged": [40.0, np.nan, np.nan],
        "empty_row_filled": [40.0, 55.0, 13.0],
    }[case]
    c = ref.compare_frontier({key: np.array(served)}, want, cands)
    sound = (c["points_missing_or_extra"] == 0
             and c["label_sets_differ"] == 0 and c["worst_rel_gap"] <= 1e-5)
    assert sound is (case in ("acknowledged", "took_in_flight")), c
    assert c["frontier_pairs"] == 2
    if case == "took_in_flight":
        assert c["took_in_flight"] == 2
    if case == "acknowledged":
        assert c["took_in_flight"] == 0


def test_the_candidates_are_the_in_flight_samples_a_window_reaches(fleet):
    _st, labels, vals, off_ms = fleet
    cls = load_class("single-groupby-1-8-1-plain")
    hosts = [0, 2, 5, 7, 11, 13, 17, 19]
    end_s = T0 // S + 399 * 10 + 10
    _q, req = request(cls, hosts, [4], end_s)
    in_flight = [(5, 399), (6, 399), (7, 398)]
    cands = ref.candidates(cls, CFG, labels, vals, req, T0 // S, off_ms,
                           in_flight)
    (key,) = cands
    assert key == frozenset({("field", FIELDS[4])})
    got = sorted(v for vs in cands[key].values() for v in vs)
    assert got == sorted(float(vals[h * 10 + 4, k])
                         for h, k in in_flight if h in hosts)
    # host 5's newest scrape lies in the last window alone
    steps = (end_s - req["start_s"]) // 60
    assert float(vals[5 * 10 + 4, 399]) in cands[key][steps]
    avg = dict(cls, reference=dict(cls["reference"], window_fn="avg"))
    with pytest.raises(ValueError):
        ref.candidates(avg, CFG, labels, vals, req, T0 // S, off_ms,
                       in_flight)


@pytest.mark.parametrize("rows", [1, 5, 24])
def test_a_block_of_offset_rows_seals_at_the_millisecond_unit(fleet, rows):
    _st, _labels, vals, off_ms = fleet
    t = np.stack([ref.series_times_ns(CFG, T0 // S, off_ms[h], 120)
                  for h in range(rows)])
    v = vals[:rows * 10:10, :120].astype(np.float64)
    blk = encode_block(T0, np.arange(rows, dtype=np.int32), t, v,
                       np.full(rows, 120, np.int32))
    assert blk.time_unit == xtime.Unit.MILLISECOND
    ts, vs, _calls = decode_rows_mod.decode_rows(
        blk.words, blk.npoints, blk.window, blk.time_unit.nanos)
    assert ts[:, :120].tobytes() == t.tobytes()
    assert vs[:, :120].tobytes() == v.tobytes()
    # whole seconds still seal at the SECOND unit
    whole = encode_block(T0, np.arange(rows, dtype=np.int32),
                         t - (off_ms[:rows] * MS)[:, None], v,
                         np.full(rows, 120, np.int32))
    assert whole.time_unit == xtime.Unit.SECOND


def _buffer_counters():
    return {name: getattr(buffer_mod, attr).value() for name, attr in (
        ("indexed", "_READ_INDEXED"), ("tail_scans", "_READ_TAIL_SCANS"),
        ("tail_rows", "_READ_TAIL_ROWS"), ("builds", "_INDEX_BUILDS"),
        ("regroup_ns", "_INDEX_REGROUP_NS"))}


@pytest.mark.parametrize("route", ["tail_scan", "regroup"])
def test_a_read_after_an_acknowledged_append_sees_it(fleet, route):
    """Read-your-writes at the frontier of an open bucket that is being
    appended to: the append returns (the acknowledgement), the read that
    follows holds the sample, millisecond timestamp and value bit for
    bit, whether the bucket's index stood (the read scans the tail) or
    had been outgrown (the read regroups the bucket)."""
    _st, _labels, vals, off_ms = fleet
    now = {"t": T0}
    db = Database(ShardSet(2), clock=lambda: now["t"])
    ns = b"default"
    db.create_namespace(ns, NamespaceOptions(
        block_size_ns=20 * xtime.MINUTE, index_enabled=False))
    n = HOSTS * len(FIELDS)
    ids = [b"cpu.%03d" % s for s in range(n)]
    off = np.repeat(off_ms * MS, len(FIELDS))

    def scrape(k, series=slice(None)):
        now["t"] = T0 + (k + 1) * 10 * S
        db.write_batch(ns, ids[series], (T0 + k * 10 * S + off)[series],
                       vals[series, k].astype(np.float64))

    def held(s, upto):
        t, v = db.read(ns, ids[s], T0, T0 + 20 * xtime.MINUTE)
        want_t = T0 + np.arange(upto) * 10 * S + off[s]
        assert t.tobytes() == want_t.tobytes()
        assert v.tobytes() == vals[s, :upto].astype(np.float64).tobytes()

    for k in range(8):
        scrape(k)
    held(7, 8)                          # the first read builds the index
    c0 = _buffer_counters()
    if route == "tail_scan":
        scrape(8)                       # a tail shorter than the prefix
        held(7, 9)
        c = {k: v - c0[k] for k, v in _buffer_counters().items()}
        assert c["tail_scans"] == 1 and c["indexed"] == 0
        assert c["builds"] == 0 and c["regroup_ns"] == 0
        assert 0 < c["tail_rows"] <= n
    else:
        for k in range(8, 20):          # the tail outgrows the prefix
            scrape(k)
        held(7, 20)
        c = {k: v - c0[k] for k, v in _buffer_counters().items()}
        assert c["builds"] == 1 and c["regroup_ns"] > 0
        assert c["tail_scans"] == 0 and c["tail_rows"] == 0
        held(11, 20)                    # and the index now serves alone
        assert _buffer_counters()["indexed"] >= c0["indexed"] + 2


def test_a_tick_counts_the_unit_its_blocks_sealed_at(fleet):
    _st, _labels, vals, off_ms = fleet
    now = {"t": T0}
    db = Database(ShardSet(1), clock=lambda: now["t"])
    ns = b"default"
    db.create_namespace(ns, NamespaceOptions(
        block_size_ns=20 * xtime.MINUTE, index_enabled=False))
    ids = [b"cpu.%03d" % s for s in range(20)]
    off = np.repeat(off_ms[:2] * MS, 10)
    key = "storage.block.sealed{unit=millisecond}"
    before = ROOT.snapshot()[key]
    for k in range(120):
        now["t"] = T0 + (k + 1) * 10 * S
        db.write_batch(ns, ids, T0 + k * 10 * S + off,
                       vals[:20, k].astype(np.float64))
    now["t"] = T0 + 31 * xtime.MINUTE
    assert db.tick()["sealed"] == 1
    assert ROOT.snapshot()[key] == before + 1
    (blk,) = next(iter(db.namespace(ns).shards.values())).blocks.values()
    assert blk.time_unit == xtime.Unit.MILLISECOND


def test_a_fill_that_finds_no_quiet_moment_is_counted(monkeypatch):
    from m3_tpu.storage import block_cache
    from m3_tpu.utils import foreground

    cache = block_cache.DeviceBlockCache()
    key = "storage.block_cache.fill.quiet_timeouts"
    before = ROOT.snapshot()[key]
    monkeypatch.setattr(block_cache, "FILL_STANDS_BACK_S", 0.01)
    with foreground.serving:            # a request that never ends
        cache._filler = object()
        cache._fill_loop(block_cache.dscope.current())
    assert cache._filler is None
    assert ROOT.snapshot()[key] == before + 1
