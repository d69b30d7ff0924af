"""Temporal kernel tests: batched device output vs a scalar Prometheus-
semantics oracle (the algorithms in promql's extrapolatedRate /
linearRegression / holt_winters, which the reference's
src/query/functions/temporal package follows)."""

import math

import numpy as np
import pytest

from m3_tpu.ops import temporal

S = 1_000_000_000
STEP_NS = 10 * S
STEP_S = 10.0


def oracle_extrapolated(win_vals, win_times, window_start, window_end,
                        is_counter, is_rate, range_s):
    """Scalar port of promql extrapolatedRate over one window's samples."""
    samples = [(t, v) for t, v in zip(win_times, win_vals) if not math.isnan(v)]
    if len(samples) < 2:
        return math.nan
    t_first, v_first = samples[0]
    t_last, v_last = samples[-1]
    increase = v_last - v_first
    if is_counter:
        prev = v_first
        for t, v in samples[1:]:
            if v < prev:
                increase += prev
            prev = v
    dur_start = t_first - window_start
    dur_end = window_end - t_last
    sampled = t_last - t_first
    if sampled == 0:
        return math.nan
    avg = sampled / (len(samples) - 1)
    threshold = avg * 1.1
    if is_counter and increase > 0 and v_first >= 0:
        dur_zero = sampled * (v_first / increase)
        dur_start = min(dur_start, dur_zero)
    extrap = sampled
    extrap += dur_start if dur_start < threshold else avg / 2
    extrap += dur_end if dur_end < threshold else avg / 2
    out = increase * (extrap / sampled)
    return out / range_s if is_rate else out


def make_grid(rng, n_series=7, n_cells=40, nan_frac=0.2, counter=True,
              scale=1.0, offset=0.0):
    if counter:
        inc = rng.exponential(5.0 * scale, size=(n_series, n_cells))
        vals = np.cumsum(inc, axis=1) + offset
        # Inject counter resets in some series.
        for i in range(0, n_series, 3):
            vals[i, n_cells // 2:] = np.cumsum(inc[i, n_cells // 2:])
    else:
        vals = rng.normal(offset, 10 * scale, size=(n_series, n_cells))
    mask = rng.random((n_series, n_cells)) < nan_frac
    vals[mask] = np.nan
    return vals


def window_times(T_ext, W, t):
    """Sample times (s) of window ending at output step t; grid cell j is
    time (j - (W-1)) * STEP_S relative to the first output step."""
    return [(t + w - (W - 1)) * STEP_S for w in range(W)]


@pytest.mark.parametrize("fn,is_counter,is_rate", [
    (temporal.rate, True, True),
    (temporal.increase, True, False),
    (temporal.delta, False, False),
])
def test_rate_family_matches_oracle(rng, fn, is_counter, is_rate):
    W = 6
    range_ns = W * STEP_NS
    grid = make_grid(rng, counter=is_counter, offset=1e9 if is_counter else 50.0)
    out = fn(grid, W, STEP_NS, range_ns)
    T_out = grid.shape[1] - W + 1
    assert out.shape == (grid.shape[0], T_out)
    for s in range(grid.shape[0]):
        for t in range(T_out):
            times = window_times(grid.shape[1], W, t)
            window_end = times[-1]
            window_start = window_end - W * STEP_S
            exp = oracle_extrapolated(
                grid[s, t:t + W], times, window_start, window_end,
                is_counter, is_rate, W * STEP_S)
            got = out[s, t]
            if math.isnan(exp):
                assert math.isnan(got), (s, t, got)
            else:
                # f32 residual math: exact in residual space, so compare to
                # the oracle run on the same f64 inputs with loose-ish rtol.
                assert got == pytest.approx(exp, rel=2e-4, abs=1e-3), (s, t)


def test_rate_counter_reset_handled(rng):
    W = 4
    grid = np.array([[0.0, 10.0, 20.0, 5.0, 15.0, 25.0]])
    out = temporal.increase(grid, W, STEP_NS, W * STEP_NS)
    # Window covering the reset must add the pre-reset value (20).
    times = window_times(6, W, 2)
    exp = oracle_extrapolated(grid[0, 2:6], times, times[-1] - W * STEP_S,
                              times[-1], True, False, W * STEP_S)
    assert out[0, 2] == pytest.approx(exp, rel=1e-6)
    assert exp > 20  # reset correction kicked in


@pytest.mark.parametrize("kind,np_fn", [
    ("sum", np.nansum), ("min", np.nanmin), ("max", np.nanmax),
    ("avg", np.nanmean),
])
def test_over_time_matches_numpy(rng, kind, np_fn):
    W = 5
    grid = make_grid(rng, counter=False, offset=1e8)  # large offset: f64 path
    out = temporal.over_time(grid, W, kind)
    for s in range(grid.shape[0]):
        for t in range(out.shape[1]):
            win = grid[s, t:t + W]
            if np.all(np.isnan(win)):
                assert math.isnan(out[s, t])
            else:
                assert out[s, t] == pytest.approx(np_fn(win), rel=1e-6), (s, t)


def test_stddev_over_time_large_offset_precision(rng):
    """The f64-baseline split must survive mean >> stddev (the classic f32
    catastrophic cancellation case)."""
    W = 8
    base = 1e9
    grid = base + rng.normal(0, 1.0, size=(3, 30))
    out = temporal.over_time(grid, W, "stddev")
    for s in range(3):
        for t in range(out.shape[1]):
            win = grid[s, t:t + W]
            assert out[s, t] == pytest.approx(np.std(win), rel=1e-3)


def test_count_and_present(rng):
    W = 4
    grid = make_grid(rng, counter=False, nan_frac=0.5)
    cnt = temporal.over_time(grid, W, "count")
    pres = temporal.over_time(grid, W, "present")
    for s in range(grid.shape[0]):
        for t in range(cnt.shape[1]):
            n = np.isfinite(grid[s, t:t + W]).sum()
            if n == 0:
                assert math.isnan(cnt[s, t]) and math.isnan(pres[s, t])
            else:
                assert cnt[s, t] == n and pres[s, t] == 1.0


def test_quantile_over_time_exact_values(rng):
    W = 6
    grid = make_grid(rng, counter=False, nan_frac=0.3, offset=1e7)
    out = temporal.quantile_over_time(grid, W, 0.5)
    for s in range(grid.shape[0]):
        for t in range(out.shape[1]):
            win = grid[s, t:t + W]
            vals = np.sort(win[np.isfinite(win)])
            if vals.size == 0:
                assert math.isnan(out[s, t])
                continue
            pos = 0.5 * (vals.size - 1)
            lo, hi = int(np.floor(pos)), min(int(np.floor(pos)) + 1, vals.size - 1)
            exp = vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)
            assert out[s, t] == pytest.approx(exp, rel=1e-9), (s, t)


def test_irate_idelta(rng):
    W = 5
    grid = make_grid(rng, counter=True, nan_frac=0.3)
    out_ir = temporal.irate(grid, W, STEP_NS)
    out_id = temporal.idelta(grid, W, STEP_NS)
    for s in range(grid.shape[0]):
        for t in range(out_ir.shape[1]):
            win = grid[s, t:t + W]
            valid = np.flatnonzero(np.isfinite(win))
            if valid.size < 2:
                assert math.isnan(out_ir[s, t])
                continue
            i2, i1 = valid[-1], valid[-2]
            dv, dt = win[i2] - win[i1], (i2 - i1) * STEP_S
            exp_ir = (win[i2] if win[i2] < win[i1] else dv) / dt
            assert out_ir[s, t] == pytest.approx(exp_ir, rel=1e-4, abs=1e-6)
            assert out_id[s, t] == pytest.approx(dv, rel=1e-4, abs=1e-3)


def test_changes_resets():
    grid = np.array([[1.0, 1.0, 2.0, np.nan, 2.0, 1.0, 3.0]])
    W = 7
    ch = temporal.changes(grid, W)
    rs = temporal.resets(grid, W)
    # changes: 1->2 (yes), 2->2 across NaN (no), 2->1 (yes), 1->3 (yes)
    assert ch[0, 0] == 3
    assert rs[0, 0] == 1  # only 2->1


def test_deriv_predict_linear(rng):
    W = 8
    slope_true = 2.5
    t = np.arange(30) * STEP_S
    grid = 1e6 + slope_true * t[None, :] + rng.normal(0, 0.01, size=(2, 30))
    d = temporal.deriv(grid, W, STEP_NS)
    p = temporal.predict_linear(grid, W, STEP_NS, 60.0)
    for s in range(2):
        for i in range(d.shape[1]):
            assert d[s, i] == pytest.approx(slope_true, rel=1e-3)
            t_now = (i + W - 1) * STEP_S
            exp = 1e6 + slope_true * (t_now + 60.0)
            assert p[s, i] == pytest.approx(exp, rel=1e-6)


def test_holt_winters_matches_scalar(rng):
    W = 10
    sf, tf = 0.3, 0.6
    grid = make_grid(rng, counter=False, nan_frac=0.2, offset=100.0)
    out = temporal.holt_winters(grid, W, sf, tf)

    def scalar_hw(win):
        vals = [v for v in win if not math.isnan(v)]
        if len(vals) < 2:
            return math.nan
        s_prev, b_prev = vals[0], vals[1] - vals[0]
        # promql: s0=v0, b0=v1-v0, then smooth from the second sample on.
        for x in vals[1:]:
            s1 = sf * x + (1 - sf) * (s_prev + b_prev)
            b_prev = tf * (s1 - s_prev) + (1 - tf) * b_prev
            s_prev = s1
        return s_prev

    for s in range(grid.shape[0]):
        for t in range(out.shape[1]):
            exp = scalar_hw(grid[s, t:t + W])
            if math.isnan(exp):
                assert math.isnan(out[s, t])
            else:
                assert out[s, t] == pytest.approx(exp, rel=1e-3, abs=1e-3), (s, t)


def test_rate_no_cancellation_on_huge_counter():
    """A quiet window late in a high-total counter grid must not lose its
    tiny increase to f32 accumulation error (the windowed sums accumulate
    per window, never as a global running prefix)."""
    T, W = 139, 30
    # Busy prefix pushes the counter to ~1e13, then a quiet tail adds 1/step.
    busy = np.full(60, 2e11)
    quiet = np.full(T - 61, 1.0)
    increments = np.concatenate([[0.0], busy, quiet])
    grid = np.cumsum(increments)[None, :]
    out = temporal.increase(grid, W, STEP_NS, W * STEP_NS)
    # Last window covers only quiet cells: true increase = W-1 samples * 1.
    expected = (W - 1) * 1.0 * (W / (W - 1))  # extrapolated to full range
    assert out[0, -1] == pytest.approx(expected, rel=1e-3)
    assert (out[0, -5:] > 0).all()  # counter increase can never go negative


class TestWindowStatStrided:
    """The window-moment core consolidates to the output stride at the
    primitives: every strided plane equals the stride-1 plane sliced
    `[:, ::stride]` — masked-by-finiteness semantics, m2 in its two-pass
    form, empty windows included."""

    @pytest.mark.parametrize("stride", [2, 3])
    @pytest.mark.parametrize("stat",
                             ["count", "sum", "min", "max", "last", "m2"])
    def test_strided_equals_sliced(self, stat, stride):
        rng = np.random.default_rng(3)
        S, K, W = 13, 67, 6
        resid = rng.standard_normal((S, K)).astype(np.float32)
        resid[rng.random((S, K)) < 0.2] = np.nan
        resid[0] = np.nan  # one fully-empty series
        got_s, got_c = temporal._window_stat(resid, W, stat, stride)
        ref_s, ref_c = temporal._window_stat(resid, W, stat)
        got_s, got_c = np.asarray(got_s), np.asarray(got_c)
        ref_s = np.asarray(ref_s)[:, ::stride]
        ref_c = np.asarray(ref_c)[:, ::stride]
        assert got_s.shape == ref_s.shape == (S, (K - W) // stride + 1)
        np.testing.assert_array_equal(got_c, ref_c)
        assert (got_c[0] == 0).all()
        # The contract covers populated windows only: every caller masks
        # count==0 to NaN, and the raw empty-window planes are not part
        # of it ('last' gathers through a clipped index there).
        pop = ref_c > 0
        np.testing.assert_allclose(got_s[pop], ref_s[pop],
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("K,shape,first", [
        (3, (4, 0), None),          # narrower than the window: empty planes
        (1064, (4, 1059), 6.0)])    # a wide stride-1 grid, one program
    def test_grid_widths_at_the_edges(self, K, shape, first):
        """A grid narrower than its window has no output step; a wide
        stride-1 grid is served whole, whatever its number of steps."""
        resid = np.ones((4, K), np.float32)
        out, cnt = temporal._window_stat(resid, 6, "sum", 1)
        assert out.shape == cnt.shape == shape
        if first is not None:
            assert float(np.asarray(out)[0, 0]) == first
            assert (np.asarray(cnt) == 6).all()

    def test_empty_window_counts_zero_and_renders_nan(self):
        # a fully-NaN window inside a row whose column 0 is finite
        grid = np.array([[5.0, 1.0, np.nan, np.nan, np.nan, 2.0, 3.0, 4.0]])
        _, cnt = temporal._window_stat(grid.astype(np.float32), 3, "last")
        assert np.asarray(cnt)[0].tolist() == [2, 1, 0, 1, 2, 3]
        for kind in ("last", "sum", "count"):
            out = temporal.over_time(grid, 3, kind)
            assert np.isnan(out[0, 2]) and np.isfinite(out[0, [0, 1, 3]]).all()
