"""Benchmarks for the five BASELINE.json configs, one JSON line each.

Line 1 (the headline, per BASELINE.json's north star) measures the per-shard
ingest hot path — batched M3TSZ-semantics compression (delta-of-delta
timestamps + XOR/int-optimized values, src/dbnode/encoding/m3tsz/encoder.go:113)
fused with the 10s->1m Counter/Gauge rollup (src/aggregator/aggregation) —
over a 100k-series shard, as one jitted XLA program per block window.
Subsequent lines cover BASELINE configs #2-#5: Counter+Gauge 10s->1m/5m
rollups through the aggregator tier's flush (src/aggregator/aggregator/
generic_elem.go:264 Consume), PromQL rate()/sum_over_time through the query
executor (src/query/functions/temporal/rate.go), batched timer quantile
rollups (src/aggregator/aggregation/timer.go), and the full-shard flush
decode+merge+re-encode (src/dbnode/persist/fs merge path).

Each line: {"metric", "value", "unit", "vs_baseline", "extra"} where
vs_baseline compares against the recorded CPU baseline in
bench_baseline.json (same kernels on the host platform; the reference
publishes no absolute throughput numbers, BASELINE.md).

Process layout: a chip belongs to one process at a time, so the parent
stays off JAX and each config runs in its OWN child, one after another.
A child that finds no TPU fails — there is no CPU fallback, and a CPU
timing is never printed under a device metric's name. The parent exits
nonzero when any selected config produced no result. Children stamp
every phase (backend init / per-bench compile / steady state) to stderr
so a hang is attributable, and use the persistent compilation cache
(m3_tpu/utils/compile_cache.py).
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

import numpy as np

# Per-config child window, compile included.
_CHILD_TIMEOUT_S = int(os.environ.get("BENCH_CHILD_TIMEOUT_S", "600"))

_T0 = time.perf_counter()


def _phase(msg: str):
    print(f"bench-phase t+{time.perf_counter() - _T0:7.1f}s {msg}",
          file=sys.stderr, flush=True)


def _timed(fn, *args, iters: int):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------------------------------
# individual benches (run inside the child)
# ---------------------------------------------------------------------------


def bench_encode_rollup():
    """North star: M3TSZ encode + 1m rollup dps over a 100k-series shard.

    A generator: the headline result streams the moment the main device
    step is timed, BEFORE the fused-raw e2e segment — a failure in the
    second half then costs the e2e extras, not the north-star number.
    The enriched line re-emits under the same metric name and the parent
    keeps the last one."""
    import jax

    from m3_tpu.ops import tsz
    from m3_tpu.parallel import ingest

    n = int(os.environ.get("BENCH_SERIES", "100000"))
    w = int(os.environ.get("BENCH_WINDOW", "120"))
    iters = int(os.environ.get("BENCH_ITERS", "10"))
    rng = np.random.default_rng(7)
    _phase("encode: building batch")
    raw_ts, raw_vals, npoints = ingest.make_example_raw(n, w, rng)
    batch = ingest.make_batch_from_raw(raw_ts, raw_vals, npoints)
    max_words = ingest.tsz.max_words_for(w)
    batch = jax.device_put(batch)
    step = jax.jit(
        functools.partial(ingest.ingest_step, rollup_factor=6, max_words=max_words))
    _phase("encode: compiling")
    dt = _timed(step, batch, iters=iters)
    _phase("encode: steady state done")
    out = step(batch)
    nbits = np.asarray(out[1], dtype=np.int64)
    points = n * w
    dps = points / dt
    base_extra = {
        "bytes_per_datapoint": round(float(nbits.sum()) / 8.0 / points, 3),
        "reference_bytes_per_datapoint": 1.45,
        "series": n, "window": w,
    }
    yield {
        "metric": "m3tsz_encode_1m_rollup",
        "value": round(dps, 1),
        "unit": "datapoints/sec",
        "extra": dict(base_extra, e2e="pending (fused-raw segment follows)"),
    }
    # End-to-end: the FUSED raw path (ingest_step_raw) moves delta/int-mode/
    # mantissa prep AND the f32 value derivation into the same XLA program
    # as encode+rollup; per-block host work shrinks to two zero-copy pair
    # views of the buffers the caller already holds.
    _phase("encode: fused raw path (device prep)")
    t_prep0 = time.perf_counter()
    rawb = ingest.make_raw_batch(raw_ts, raw_vals, npoints)
    host_prep_s = time.perf_counter() - t_prep0
    rawb = jax.device_put(rawb)
    raw_step = jax.jit(functools.partial(
        ingest.ingest_step_raw, rollup_factor=6, max_words=max_words))
    out_raw = raw_step(rawb)
    assert bool(out_raw[-1]), "range_ok must hold for the bench batch"
    assert np.array_equal(np.asarray(out_raw[0]), np.asarray(out[0])), (
        "fused raw path must produce the identical streams")
    # ...and identical aggregates. The regression this guards is the fused
    # path's on-device f32 derivation (bits64.f64_bits_to_f32) silently
    # rounding differently from numpy's cast — so pin THAT directly,
    # elementwise and bit-exact on this backend:
    from m3_tpu.ops import bits64 as _b64
    _hi = _b64.PAIR_HI

    # The comparison runs ON DEVICE against the already-device-resident
    # numpy-cast reference (batch.values): one bool comes back to the
    # host, not a 48MB f32 plane.
    @jax.jit
    def _conv_matches(p, ref):
        import jax.numpy as _jnp
        got = jax.lax.bitcast_convert_type(
            _b64.f64_bits_to_f32(p[..., _hi], p[..., 1 - _hi]), _jnp.uint32)
        want = jax.lax.bitcast_convert_type(ref, _jnp.uint32)
        return _jnp.all(got == want)

    assert bool(_conv_matches(rawb.v_pairs, batch.values)), (
        "device f64->f32 bit conversion diverged from numpy cast")
    # With identical f32 inputs thus proven, order-INSENSITIVE aggregate
    # planes must match bit-for-bit across the two programs: count (integer
    # sums < 2^24 are exact in any order), min/max, the bit-gathered
    # last/first, and the sort-based quantiles. The accumulated planes
    # (sum, sumsq, m2) are compared under a reduction-reorder bound
    # instead: XLA tiles a f32 reduction differently in two different
    # programs (observed live on v5e: attempt A had blk.sum bit-equal and
    # blk.m2 off by ULPs, attempt B the reverse — per-program tiling, not
    # a data bug), and f32 addition is not associative.
    eps = 1.2e-7  # 2^-23
    for agg_i in (2, 3):
        for k, v in out_raw[agg_i].items():
            a = np.asarray(v, dtype=np.float64)
            b = np.asarray(out[agg_i][k], dtype=np.float64)
            if k in ("sum", "sumsq", "m2"):
                # Reorder bound: |err| <= depth * eps * L1(terms), with the
                # L1 mass bounded PER PLANE (a shared sumsq proxy
                # over-bounds sum/m2 by ~|v|x for these offset-valued
                # series, leaving those asserts vacuous): sum's terms are
                # |v| <= sqrt(n*sumsq) (Cauchy-Schwarz), sumsq's are v^2,
                # m2's are dev^2 = m2 itself. m2 additionally absorbs the
                # divide-ULP shift of mu between the two programs:
                # |d(m2)/d(mu)| terms give 2*sqrt(n*m2)*eps*|mu| +
                # n*(eps*mu)^2.
                n_pts = np.asarray(out[agg_i]["count"], dtype=np.float64)
                sumsq = np.asarray(out[agg_i]["sumsq"], dtype=np.float64)
                # Classical summation bound: n-term f32 sum reordering
                # moves the result by at most (n-1)*eps*L1(terms) for ANY
                # two association orders; depth = 2n keeps a 2x margin and
                # tracks the actual reduce length (window or rollup
                # factor) via the window's own count, so raising
                # BENCH_WINDOW scales the bound with it. No separate
                # relative slack — the L1 mass term IS the relative bound.
                depth = 2.0 * np.maximum(n_pts, 1.0) * eps
                if k == "sum":
                    atol = depth * np.sqrt(n_pts * sumsq) + 1e-12
                elif k == "sumsq":
                    atol = depth * sumsq + 1e-12
                else:
                    mu = np.divide(
                        np.asarray(out[agg_i]["sum"], dtype=np.float64),
                        np.maximum(n_pts, 1.0))
                    # a 1-ULP mu shift moves each dev by eps*|mu|; first-
                    # order m2 change 2*sum|dev|*eps|mu| <= 2*sqrt(n*m2)*
                    # eps*|mu|, second-order n*(eps*mu)^2 — these carry NO
                    # depth factor (they are not reorder noise).
                    mu_shift = eps * np.abs(mu)
                    atol = (depth * b
                            + 2.0 * np.sqrt(n_pts * np.maximum(b, 0.0))
                            * mu_shift + n_pts * mu_shift * mu_shift
                            + 1e-12)
                ok = np.abs(a - b) <= atol
                assert bool(np.all(ok)), (
                    f"fused aggregate {agg_i}.{k} diverged beyond the "
                    f"reduction-reorder bound (max abs diff "
                    f"{float(np.max(np.abs(a - b)))})")
            else:
                assert np.array_equal(np.asarray(v),
                                      np.asarray(out[agg_i][k])), (
                    f"fused aggregate {agg_i}.{k} diverged")
    assert np.array_equal(np.asarray(out_raw[4]), np.asarray(out[4])), (
        "fused quantiles diverged")
    dt_raw = _timed(raw_step, rawb, iters=iters)
    e2e_dps = points / (dt_raw + host_prep_s)
    _phase("encode: fused raw steady state done")
    yield {
        "metric": "m3tsz_encode_1m_rollup",
        "value": round(dps, 1),
        "unit": "datapoints/sec",
        "extra": dict(
            base_extra,
            host_prep_ms=round(host_prep_s * 1000, 1),
            prep="device-fused (ingest_step_raw); host = two zero-copy "
                 "pair views (f32 derived on device, bits64.f64_bits_to_f32)",
            fused_step_dps=round(points / dt_raw, 1),
            e2e_dps_with_host_prep=round(e2e_dps, 1),
        ),
    }


def bench_promql():
    """BASELINE config #3: rate() + sum_over_time over 1h of 10s data.

    Steady state models hot-block serving: the content-addressed device
    upload cache (m3_tpu/ops/temporal.py) keeps the gridded selector on
    device across queries, so iterations pay host fetch/grid + kernel +
    one result transfer. extra.phase_ms attributes the per-pair cost."""
    from m3_tpu.query import Engine

    n = int(os.environ.get("BENCH_QUERY_SERIES", "10000"))
    iters = int(os.environ.get("BENCH_QUERY_ITERS", "3"))
    s_ns = 1_000_000_000
    npts = 360  # 1h @ 10s
    rng = np.random.default_rng(11)
    t = (1_700_000_000 * s_ns + np.arange(npts, dtype=np.int64) * 10 * s_ns)
    vals = np.cumsum(rng.poisson(5.0, (n, npts)), axis=1).astype(np.float64)

    series = {}
    for i in range(n):
        sid = b"bench_metric{i=%d}" % i
        series[sid] = {
            "tags": {b"__name__": b"bench_metric", b"i": str(i).encode()},
            "t": t, "v": vals[i],
        }

    class _Storage:
        def fetch_raw(self, matchers, start_ns, end_ns):
            return series

    eng = Engine(_Storage())
    start = int(t[30])
    end = int(t[-1])
    step = 30 * s_ns

    def run_pair(e):
        # Both queries dispatch before either result materializes: query
        # 1's async D2H overlaps query 2's host fetch/grid/dispatch
        # (LazyBlock double-buffering), then both transfers complete.
        b1 = e.execute_range("rate(bench_metric[5m])", start, end, step)
        b2 = e.execute_range("sum_over_time(bench_metric[5m])", start, end, step)
        return b1.values, b2.values

    def timed_pairs(e, k):
        t0 = time.perf_counter()
        for _ in range(k):
            run_pair(e)
        return (time.perf_counter() - t0) / k

    _phase("promql: compiling")
    v1, v2 = run_pair(eng)
    b1 = eng.execute_range("rate(bench_metric[5m])", start, end, step)
    assert b1.n_series == n and v1.shape[0] == n and v2.shape[0] == n
    assert v1.shape[1] == b1.meta.steps
    _phase("promql: steady state")
    dt = timed_pairs(eng, iters)
    _phase("promql: done")
    dps = 2 * n * npts / dt
    placement = eng.placement_snapshot()
    # Attribution on accelerator platforms: the headline above is the
    # default `auto` placement (the product behavior); the forced pairs
    # record what each path costs on this hardware (ROADMAP C2 decides
    # placement's fate from them), and the results are asserted identical
    # across paths.
    forced_ms = {}
    import jax as _jax

    if _jax.default_backend() != "cpu":
        for mode in ("device", "host"):
            e2 = Engine(_Storage())
            e2._placement._mode = mode
            fv1, fv2 = run_pair(e2)  # compile/warm + correctness
            assert np.allclose(fv1, v1, equal_nan=True, rtol=1e-5), (
                f"{mode}-placed rate() diverged from adaptive result")
            assert np.allclose(fv2, v2, equal_nan=True, rtol=1e-5), (
                f"{mode}-placed sum_over_time() diverged")
            forced_ms[f"pair_{mode}_ms"] = round(
                timed_pairs(e2, max(iters, 2)) * 1000, 1)
        _phase("promql: forced-path attribution done")
    # Phase attribution: host fetch+grid for one selector eval, measured
    # standalone on the same extended grid the executor builds.
    from m3_tpu.query.block import BlockMeta, consolidate_series

    wgrid = 10 * s_ns
    W = 30
    ext_steps = (W - 1) + (b1.meta.steps - 1) * 3 + 1
    ext_meta = BlockMeta(start - (W - 1) * wgrid, wgrid, ext_steps)
    t0 = time.perf_counter()
    consolidate_series(series, ext_meta, wgrid)
    host_grid_ms = (time.perf_counter() - t0) * 1000
    return {
        "metric": "promql_rate_sum_over_time_1h",
        "value": round(dps, 1),
        "unit": "datapoints/sec",
        "extra": {"series": n, "points_per_series": npts,
                  "queries": ["rate(bench_metric[5m])",
                              "sum_over_time(bench_metric[5m])"],
                  "steps": b1.meta.steps,
                  # one f32 plane per query, strided to the output grid and
                  # baseline-corrected on device (nothing wider comes back
                  # to the host)
                  "result_wire_mb_per_pair": round(
                      n * b1.meta.steps * (4 + 4) / 2**20, 2),
                  "placement": placement,
                  **forced_ms,
                  "phase_ms": {
                      "pair_total": round(dt * 1000, 1),
                      "host_fetch_grid_cold_per_query": round(
                          host_grid_ms, 1),
                  }},
    }


def bench_promql_plan_agg():
    """Round 11: multi-shard grouped aggregation through the query engine —
    sum by (host) (rate(m[5m])) over ALL shards, the dashboard fan-in shape
    the per-shard sharded-agg fast path can't touch (grouping forces the
    host fan-in pre-plan-compiler: per-series rate kernel, full [S, T_out]
    result materialization, then a separate grouped reduce). The plan
    compiler fuses the whole physical plan into ONE program whose only
    host transfer is the [G, T_out] answer."""
    from m3_tpu.query import Engine

    n = int(os.environ.get("BENCH_PLAN_SERIES", "10000"))
    hosts = int(os.environ.get("BENCH_PLAN_HOSTS", "200"))
    iters = int(os.environ.get("BENCH_PLAN_ITERS", "5"))
    s_ns = 1_000_000_000
    npts = 360  # 1h @ 10s
    rng = np.random.default_rng(17)
    t = (1_700_000_000 * s_ns + np.arange(npts, dtype=np.int64) * 10 * s_ns)
    vals = np.cumsum(rng.poisson(5.0, (n, npts)), axis=1).astype(np.float64)

    series = {}
    for i in range(n):
        host = b"host-%03d" % (i % hosts)
        sid = b"bench_requests{host=%s,i=%d}" % (host, i)
        series[sid] = {
            "tags": {b"__name__": b"bench_requests", b"host": host,
                     b"i": str(i).encode()},
            "t": t, "v": vals[i],
        }

    class _Storage:
        def fetch_raw(self, matchers, start_ns, end_ns):
            return series

    eng = Engine(_Storage())
    start = int(t[30])
    end = int(t[-1])
    step = 30 * s_ns
    q = "sum by (host) (rate(bench_requests[5m]))"

    def run_query(e):
        return e.execute_range(q, start, end, step)

    _phase("plan_agg: compiling")
    b = run_query(eng)
    assert b.n_series == hosts, b.n_series
    vals_first = np.asarray(b.values)
    _phase("plan_agg: steady state")
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run_query(eng)
        out.values  # materialize
    dt = (time.perf_counter() - t0) / iters
    _phase("plan_agg: done")
    dps = n * npts / dt
    # Route attribution: did the steady state actually run compiled plans?
    from m3_tpu.utils.instrument import ROOT

    snap = ROOT.snapshot()
    compiled = {k: v for k, v in snap.items()
                if k.startswith(("query.plan", "telemetry.plan_cache"))}
    extra = {
        "series": n, "hosts": hosts, "points_per_series": npts,
        "query": q, "steps": int(out.meta.steps),
        "query_ms": round(dt * 1000, 2),
        "plan_counters": {k: v for k, v in sorted(compiled.items())},
    }
    # Compiled-vs-interpreter equivalence asserted in-bench when the
    # compiled route exists (post-change builds): the retained interpreter
    # is the oracle.
    if hasattr(eng, "execute_range_ref"):
        ref = eng.execute_range_ref(q, start, end, step)
        order = {bytes(t.id()): i for i, t in enumerate(ref.series_tags)}
        got = np.asarray(out.values)
        idx = [order[bytes(t.id())] for t in out.series_tags]
        assert np.allclose(got, np.asarray(ref.values)[idx],
                           rtol=1e-5, atol=1e-8, equal_nan=True), (
            "compiled plan diverged from the interpreter oracle")
        extra["oracle"] = "interpreter execute_range_ref, rtol 1e-5"
    del vals_first
    return {
        "metric": "promql_plan_agg",
        "value": round(dps, 1),
        "unit": "datapoints/sec",
        "extra": extra,
    }


def bench_timer_quantiles():
    """BASELINE config #4: batched timer quantile rollups (exact sort-based
    replacement for the reference's CM quantile sketches)."""
    import jax
    import jax.numpy as jnp

    from m3_tpu.ops import aggregation as agg

    n = int(os.environ.get("BENCH_TIMER_SERIES", "50000"))
    w = 120
    iters = int(os.environ.get("BENCH_TIMER_ITERS", "10"))
    rng = np.random.default_rng(13)
    values = jax.device_put(rng.lognormal(0, 1, (n, w)).astype(np.float32))
    mask = jax.device_put(np.ones((n, w), dtype=bool))

    @jax.jit
    def timer_step(v, m):
        q = agg.rollup_quantiles(v, m, 6, (0.5, 0.95, 0.99))
        s = agg.rollup_stats(v, m, 6)
        return q, s["sum"], s["count"], s["max"]

    _phase("timer: compiling")
    dt = _timed(timer_step, values, mask, iters=iters)
    _phase("timer: done")
    return {
        "metric": "timer_quantile_rollup",
        "value": round(n * w / dt, 1),
        "unit": "datapoints/sec",
        "extra": {"series": n, "window": w, "quantiles": [0.5, 0.95, 0.99]},
    }


class _ColumnarCapture:
    """The production flush-handler shape (Handler.handle_columnar, what
    ProducerHandler implements): a round's emissions arrive as columnar
    array slices in ONE call — the agg benches' timed loops measure the
    tier as deployed, not the per-datapoint compat shim."""

    def __init__(self, sink):
        self._sink = sink

    def __call__(self, mid, t, v, pol):
        self._sink.append(v)

    def handle_columnar(self, groups):
        extend = self._sink.extend
        for _ids, _ts, vs, _pol in groups:
            extend(vs.tolist())


def bench_counter_gauge():
    """BASELINE config #2: Counter+Gauge 10s -> 1m/5m rollup windows driven
    through the aggregator tier's flush (src/aggregator/aggregator/
    generic_elem.go:264 Consume; docker/m3aggregator config).

    Each metric carries TWO storage policies (1m and 5m), so every 10s
    datapoint is staged into both elems — the reference walks elems and
    folds one locked struct per bucket scalar-at-a-time; here elems only
    stage columnar and MetricList.flush reduces every closed bucket across
    all elems in one batched pass (host-exact f64 moments; counters/gauges
    need no quantiles, so the device quantile kernel is bypassed — the
    measured cost is the tier itself: collect + batched moments + emit)."""
    from m3_tpu.aggregator.elem import Elem, ElemKey
    from m3_tpu.aggregator.list import MetricList
    from m3_tpu.metrics.metric import MetricType
    from m3_tpu.metrics.policy import StoragePolicy

    n = int(os.environ.get("BENCH_CG_SERIES", "50000"))
    iters = int(os.environ.get("BENCH_CG_ITERS", "3"))
    s_ns = 1_000_000_000
    pol_1m = StoragePolicy.parse("1m:40h")
    pol_5m = StoragePolicy.parse("5m:40h")
    base_t = 1_700_000_000 * s_ns
    rng = np.random.default_rng(23)
    cvals = rng.poisson(5.0, (n // 2, 30)).astype(np.float64)  # 5m @ 10s
    gvals = rng.standard_normal((n - n // 2, 30))

    lists = {60: MetricList(60 * s_ns), 300: MetricList(300 * s_ns)}
    elems = []
    for i in range(n):
        mt = MetricType.COUNTER if i < n // 2 else MetricType.GAUGE
        vals = cvals[i] if i < n // 2 else gvals[i - n // 2]
        mid = b"bench.cg.%d" % i
        for res_s, pol in ((60, pol_1m), (300, pol_5m)):
            key = ElemKey(mid, pol)
            e = lists[res_s].get_or_create(key, lambda k=key, m=mt: Elem(k, m))
            elems.append((e, res_s, vals))

    def stage():
        # 5 minutes of 10s-cadence data: 1m elems get 5 windows x 6 values,
        # the 5m elem one window of 30 (columnar add_values — the staged
        # shape the ingest path produces).
        for e, res_s, vals in elems:
            if res_s == 60:
                for wi in range(5):
                    e.add_values(base_t + wi * 60 * s_ns, vals[wi * 6:(wi + 1) * 6])
            else:
                e.add_values(base_t, vals)

    emitted = []
    flush_fn = lambda mid, t, v, pol: emitted.append(v)  # noqa: E731
    target = base_t + 300 * s_ns
    total_vals = n * 30 * 2  # every datapoint staged into both policies

    _phase("counter_gauge: warmup flush")
    # warmup runs the per-datapoint compat sink: exercises that shim and
    # spot-checks exactness with deterministic emission order
    stage()
    t_flush = [lists[60].flush(target, flush_fn), lists[300].flush(target, flush_fn)]
    assert t_flush == [n * 5, n], t_flush
    assert len(emitted) == n * 6
    # spot-check exactness: counter windows sum, gauge windows last
    assert emitted[0] == float(cvals[0, :6].sum())
    _phase("counter_gauge: timing")
    col_fn = _ColumnarCapture(emitted)
    dts = []
    for _ in range(iters):
        stage()
        emitted.clear()
        t0 = time.perf_counter()
        w1 = lists[60].flush(target, col_fn)
        w5 = lists[300].flush(target, col_fn)
        dts.append(time.perf_counter() - t0)
        assert w1 + w5 == n * 6
        assert len(emitted) == n * 6
    dt = min(dts)
    _phase("counter_gauge: done")
    return {
        "metric": "counter_gauge_rollup",
        "value": round(total_vals / dt, 1),
        "unit": "datapoints/sec",
        "extra": {"metrics": n, "windows_flushed": n * 6,
                  "policies": ["1m:40h", "5m:40h"],
                  "input_cadence_s": 10,
                  "moments": "host f64 exact (no quantiles for counter/gauge)"},
    }


def _agg10x_build(n, lists_mod, elem_mod):
    """Build the agg_rollup_10x elem population into fresh MetricLists:
    40% counters, 40% gauges, 20% timers at 10x counter_gauge_rollup's
    metric cardinality, with 10% of the gauges carrying a rollup-only
    pipeline into 1/40th-cardinality rollup ids consumed by a second
    aggregation stage (the multi_server_forwarding_pipeline_test.go
    forwarding shape; deliberately NO binary transform — see the in-loop
    comment). Returns (lists, elems, n_piped, n_rollup_ids) where elems
    is [(elem, kind, row_index)] for the staging pass."""
    from m3_tpu.metrics import aggregation as magg
    from m3_tpu.metrics.metric import MetricType
    from m3_tpu.metrics.pipeline import Op, Pipeline
    from m3_tpu.metrics.policy import StoragePolicy

    pol = StoragePolicy.parse("1m:40h")
    lists = lists_mod.MetricLists()
    lst = lists.for_resolution(60 * 1_000_000_000)
    n_counter = (n * 2) // 5
    n_gauge = (n * 2) // 5
    n_timer = n - n_counter - n_gauge
    n_piped = n_gauge // 10
    n_rollup_ids = max(1, n_piped // 40)
    elems = []
    for i in range(n_counter):
        key = elem_mod.ElemKey(b"bench.a10.c.%d" % i, pol)
        elems.append((lst.get_or_create(
            key, lambda k=key: elem_mod.Elem(k, MetricType.COUNTER)),
            "counter", i))
    sum_id = magg.AggID.compress([magg.AggType.SUM])
    for i in range(n_gauge):
        if i < n_piped:
            # Rollup-only pipeline: every window forwards its Last into
            # a 1/40th-cardinality second aggregation stage. (A binary
            # transform ahead of the rollup would thread prev-window
            # state across bench rounds and make the stage-2 window
            # count round-dependent; the property suite covers
            # transforms, the bench stays deterministic.)
            pipe = Pipeline((
                Op.roll(b"bench.a10.rollup.%d" % (i % n_rollup_ids),
                        (b"host",), sum_id),
            ))
            key = elem_mod.ElemKey(b"bench.a10.g.%d" % i, pol,
                                   magg.AggID.compress([magg.AggType.LAST]),
                                   pipe)
        else:
            key = elem_mod.ElemKey(b"bench.a10.g.%d" % i, pol)
        elems.append((lst.get_or_create(
            key, lambda k=key: elem_mod.Elem(k, MetricType.GAUGE)),
            "gauge", i))
    for i in range(n_timer):
        key = elem_mod.ElemKey(b"bench.a10.t.%d" % i, pol)
        elems.append((lst.get_or_create(
            key, lambda k=key: elem_mod.Elem(k, MetricType.TIMER)),
            "timer", i))
    return lists, elems, n_piped, n_rollup_ids


def bench_agg_rollup_10x():
    """10x-cardinality aggregator flush (ROADMAP item 4's bench config):
    500k metric ids (vs counter_gauge_rollup's 50k) in one 1m metric
    list — mixed counter/gauge/timer (default agg types, so timers run
    the full suffixed set incl. p50/p95/p99 quantiles) with 10% of the
    gauges on a rollup-only pipeline (Rollup(Sum) into shared ids, the
    forwarded partials consumed by a second flush). Measures the whole
    tier per round: collect + reduce + emit + pipeline forwarding +
    second-stage consume. The denominator counts primary staged values
    only (forwarded partials ride free), so rounds are comparable across
    implementations."""
    from m3_tpu.aggregator import elem as elem_mod
    from m3_tpu.aggregator import list as lists_mod

    n = int(os.environ.get("BENCH_AGG10X_SERIES", "500000"))
    iters = int(os.environ.get("BENCH_AGG10X_ITERS", "2"))
    s_ns = 1_000_000_000
    base_t = 1_700_000_000 * s_ns - (1_700_000_000 * s_ns) % (60 * s_ns)
    rng = np.random.default_rng(31)
    _phase("agg10x: building elems")
    lists, elems, n_piped, n_rollup_ids = _agg10x_build(
        n, lists_mod, elem_mod)
    lst = lists.for_resolution(60 * s_ns)
    # Two windows of 6 values at 10s cadence per metric (the PerSecond
    # transform needs window 1 to prime its previous-datapoint state).
    cvals = rng.poisson(5.0, (n, 12)).astype(np.float64)
    gvals = rng.standard_normal((n, 12))
    tvals = rng.lognormal(0.0, 1.0, (n, 12))
    planes = {"counter": cvals, "gauge": gvals, "timer": tvals}

    def stage():
        w0, w1 = base_t, base_t + 60 * s_ns
        for e, kind, i in elems:
            row = planes[kind][i]
            e.add_values(w0, row[:6])
            e.add_values(w1, row[6:])

    def forward_fn(new_id, t_nanos, value, meta, source_id):
        # Local loop-back of rollup partials into the same aggregation
        # ring (ForwardedWriter without routing): next-stage elems are
        # created on first delivery, exactly like Entry.add_forwarded.
        key = elem_mod.ElemKey(new_id, meta.storage_policy,
                               meta.aggregation_id, meta.pipeline,
                               meta.num_forwarded_times)
        from m3_tpu.metrics.metric import MetricType

        e = lst.get_or_create(key, lambda: elem_mod.Elem(
            key, MetricType.GAUGE))
        e.add_value(t_nanos, value)

    emitted = []
    flush_fn = lambda mid, t, v, pol: emitted.append(v)  # noqa: E731
    # the round's rollup forwards arrive batched (ForwardedWriter shape)
    forward_fn.forward_batch = lambda items: [forward_fn(*it)
                                              for it in items]
    t1 = base_t + 120 * s_ns   # closes both primary windows
    t2 = base_t + 180 * s_ns   # closes the forwarded stage-2 windows
    total_vals = n * 12

    _phase("agg10x: warmup flush")
    # warmup drives the per-datapoint compat sink path once
    stage()
    w_a = lst.flush(t1, flush_fn, forward_fn)
    w_b = lst.flush(t2, flush_fn, forward_fn)
    assert w_a == n * 2, w_a
    # Stage 2 consumed one window per rollup id per primary window (every
    # primary window forwards its Last; both land before t2).
    assert w_b == 2 * n_rollup_ids, (w_b, n_rollup_ids)
    _phase("agg10x: timing")
    col_fn = _ColumnarCapture(emitted)
    dts = []
    for _ in range(iters):
        stage()
        emitted.clear()
        t0 = time.perf_counter()
        w_a = lst.flush(t1, col_fn, forward_fn)
        w_b = lst.flush(t2, col_fn, forward_fn)
        dts.append(time.perf_counter() - t0)
        assert w_a == n * 2 and w_b == 2 * n_rollup_ids
    dt = min(dts)
    _phase("agg10x: oracle subset")
    extra = {
        "metrics": n, "mix": "40% counter / 40% gauge / 20% timer",
        "piped_gauges": n_piped, "rollup_ids": n_rollup_ids,
        "policies": ["1m:40h"], "input_cadence_s": 10,
        "windows_per_round": n * 2 + n_rollup_ids,
        "round_ms": round(dt * 1000, 1),
    }
    # Post-change builds retain the host flush as reduce_and_emit_ref;
    # assert the production path bit-identical to it on a subset mirror
    # (rounds 6-9 in-bench oracle protocol).
    if hasattr(lists_mod, "reduce_and_emit_ref"):
        sub_n = min(n, 20000)
        got, want = [], []
        for sink, ref in ((got, False), (want, True)):
            slists, selems, _, _ = _agg10x_build(
                sub_n, lists_mod, elem_mod)
            slst = slists.for_resolution(60 * s_ns)
            for e, kind, i in selems:
                row = planes[kind][i]
                e.add_values(base_t, row[:6])
                e.add_values(base_t + 60 * s_ns, row[6:])
            cap = lambda mid, t, v, pol, _s=sink: _s.append((mid, t, v))  # noqa: E731

            def fwd(new_id, t_nanos, value, meta, source_id,
                    _lst=slst, _sink=sink):
                key = elem_mod.ElemKey(new_id, meta.storage_policy,
                                       meta.aggregation_id, meta.pipeline,
                                       meta.num_forwarded_times)
                from m3_tpu.metrics.metric import MetricType

                e = _lst.get_or_create(key, lambda: elem_mod.Elem(
                    key, MetricType.GAUGE))
                e.add_value(t_nanos, value)

            if ref:
                jobs, _ = __import__(
                    "m3_tpu.aggregator.flush", fromlist=["plan_jobs"]
                ).plan_jobs(slists, t1, 0, cap, fwd)
                lists_mod.reduce_and_emit_ref(jobs)
                jobs2, _ = __import__(
                    "m3_tpu.aggregator.flush", fromlist=["plan_jobs"]
                ).plan_jobs(slists, t2, 0, cap, fwd)
                lists_mod.reduce_and_emit_ref(jobs2)
            else:
                slst.flush(t1, cap, fwd)
                slst.flush(t2, cap, fwd)
        assert sorted(got) == sorted(want), (
            "mesh flush diverged from the host oracle on the subset "
            f"mirror ({len(got)} vs {len(want)} rows)")
        assert all(g == w for g, w in zip(sorted(got), sorted(want)))
        extra["oracle"] = (f"reduce_and_emit_ref subset mirror "
                           f"({sub_n} metrics), bit-identical")
    return {
        "metric": "agg_rollup_10x",
        "value": round(total_vals / dt, 1),
        "unit": "datapoints/sec",
        "extra": extra,
    }


def bench_flush_merge():
    """BASELINE config #5: full-shard flush — merge two sealed half-blocks
    into one compacted block (dbnode fs merge semantics). Eligible series
    (timestamp-regular, one encoding epoch, continuous cadence — the
    scrape-aligned common case) merge by scan-free bit CONCATENATION
    (m3_tpu/ops/tsz_concat.py); the rest decode+re-encode. The partition is
    computed once at seal time; the loop times both device paths. Int-mode
    concat output is asserted bit-identical to directly encoding the full
    window; everything else must decode to the original points."""
    import jax
    import jax.numpy as jnp

    from m3_tpu.ops import bits64 as b64
    from m3_tpu.ops import tsz
    from m3_tpu.ops import tsz_concat
    from m3_tpu.parallel import ingest

    n = int(os.environ.get("BENCH_FLUSH_SERIES", "100000"))
    half = 60
    w = 2 * half
    iters = int(os.environ.get("BENCH_FLUSH_ITERS", "5"))
    rng = np.random.default_rng(17)
    raw_ts, raw_vals, npoints = ingest.make_example_raw(n, w, rng)
    full = ingest.make_batch_from_raw(raw_ts, raw_vals, npoints)
    mw_half = tsz.max_words_for(half)
    mw_full = tsz.max_words_for(w)

    def half_inputs(lo, hi):
        dt = np.asarray(full.dt[:, lo:hi]).copy()
        dt[:, 0] = 0
        t0hi, t0lo = b64.from_u64_np(raw_ts[:, lo].astype(np.int64))
        delta0 = dt[:, 1].copy()
        ts_regular = (dt[:, 1:] == delta0[:, None]).all(axis=1)
        return (dt, (t0hi, t0lo), np.asarray(full.vhi[:, lo:hi]),
                np.asarray(full.vlo[:, lo:hi]), np.asarray(full.int_mode),
                np.asarray(full.k), np.full(n, hi - lo, np.int32),
                ts_regular, delta0)

    enc_half = jax.jit(functools.partial(tsz.encode_batch, max_words=mw_half))
    w1, nb1 = enc_half(*half_inputs(0, half))
    w2, nb2 = enc_half(*half_inputs(half, w))
    w1n, w2n = np.asarray(w1), np.asarray(w2)
    nb1n, nb2n = np.asarray(nb1), np.asarray(nb2)
    npts_half = np.full(n, half, np.int32)
    boundary = (raw_ts[:, half] - raw_ts[:, half - 1]).astype(np.int32)

    # Seal-time boundary metadata for block1 — free at encode time, from
    # the already-prepped columns (the same helper the storage layer uses).
    imode_np = np.asarray(full.int_mode)
    half1 = half_inputs(0, half)
    bmeta = tsz.boundary_metadata({
        "dt": half1[0], "t0": half1[1], "vhi": half1[2], "vlo": half1[3],
        "int_mode": half1[4], "npoints": half1[6]})
    last_v = b64.from_u64_np(bmeta["last_v_bits"])
    last_vd = b64.from_u64_np(bmeta["last_vdelta_bits"])

    # Partition once (seal time); both sub-batches live on device. The
    # concat path's word-shift select chains win big on TPU but lose to a
    # straight recode on host CPU (same backend split as encode_batch's
    # pack= selection), so CPU sends everything down the recode path.
    use_concat = jax.default_backend() == "tpu"
    h1 = tsz_concat.parse_header(w1n)
    h2 = tsz_concat.parse_header(w2n)
    ok_all = np.asarray(tsz_concat.concat_eligible(
        h1, h2, npts_half, npts_half, boundary))
    ok = ok_all if use_concat else np.zeros_like(ok_all)
    fast = np.flatnonzero(ok)
    slow = np.flatnonzero(~ok)
    dp = jax.device_put
    fast_args = tuple(dp(a[fast]) for a in (w1n, nb1n, npts_half, w2n, nb2n,
                                            npts_half))
    fast_meta = (tuple(dp(a[fast]) for a in last_v),
                 tuple(dp(a[fast]) for a in last_vd))
    slow_args = tuple(dp(a[slow]) for a in (w1n, npts_half, w2n, npts_half,
                                            boundary))
    concat = functools.partial(tsz_concat.concat_regular_batch,
                               max_words=mw_full)
    recode = functools.partial(tsz_concat._merge_by_recode,
                               half_window=half, max_words=mw_full)

    def merge_all():
        fw, fnb = concat(*fast_args, *fast_meta)
        sw, snb = recode(*slow_args)
        return sw, snb, fw, fnb

    _phase(f"flush: compiling (eligible {fast.size}/{n})")
    sw, snb, fw, fnb = merge_all()

    # Correctness gates (outside the timing loop).
    ref_words, ref_nbits = tsz.encode_batch(
        full.dt, (full.t0_hi, full.t0_lo), full.vhi, full.vlo, full.int_mode,
        full.k, full.npoints, full.ts_regular, full.delta0,
        max_words=mw_full)
    ref_w_np, ref_nb_np = np.asarray(ref_words), np.asarray(ref_nbits)
    int_fast = imode_np[fast]
    assert np.array_equal(np.asarray(fnb)[int_fast], ref_nb_np[fast][int_fast])
    assert np.array_equal(np.asarray(fw)[int_fast], ref_w_np[fast][int_fast])
    merged_w = np.zeros((n, mw_full), np.uint32)
    merged_nb = np.zeros(n, np.int32)
    merged_w[fast], merged_nb[fast] = np.asarray(fw), np.asarray(fnb)
    merged_w[slow], merged_nb[slow] = np.asarray(sw), np.asarray(snb)
    dts, dv = tsz.decode(merged_w, np.full(n, w, np.int32), window=w)
    assert np.array_equal(dts, raw_ts) and np.array_equal(dv, raw_vals)
    # Forced-concat gate: whatever the timed partition routed, a sample
    # of eligible series runs the scan-free concat and is asserted
    # bit-exact (int mode) and decode-equal, so the artifact's merge_*
    # fields always quantify over a non-empty set.
    gate = np.flatnonzero(ok_all)[
        : int(os.environ.get("BENCH_CONCAT_GATE", "1000"))]
    assert gate.size, "no concat-eligible series for the correctness gate"
    gw, gnb = concat(
        *(dp(a[gate]) for a in (w1n, nb1n, npts_half, w2n, nb2n, npts_half)),
        tuple(dp(a[gate]) for a in last_v),
        tuple(dp(a[gate]) for a in last_vd))
    gw, gnb = np.asarray(gw), np.asarray(gnb)
    int_gate = imode_np[gate]
    assert np.array_equal(gnb[int_gate], ref_nb_np[gate][int_gate])
    assert np.array_equal(gw[int_gate], ref_w_np[gate][int_gate])
    gts, gv = tsz.decode(gw, np.full(gate.size, w, np.int32), window=w)
    assert np.array_equal(gts, raw_ts[gate])
    assert np.array_equal(gv, raw_vals[gate])
    _phase(f"flush: concat gate {gate.size} series "
           f"({int(int_gate.sum())} int-mode bit-exact) + full decode-equal; timing")
    dt = _timed(merge_all, iters=iters)
    _phase("flush: done")
    return {
        "metric": "shard_flush_merge",
        "value": round(n * w / dt, 1),
        "unit": "datapoints/sec",
        "extra": {"series": n, "points_merged": w,
                  "concat_eligible_frac": round(int(ok_all.sum()) / n, 4),
                  "concat_timed_frac": round(fast.size / n, 4),
                  # DISTINCT series asserted bit-exact through the concat
                  # path (the forced gate is a subset of the timed fast
                  # partition on TPU, so count the union, not the sum)
                  "merge_bit_exact_int_eligible": int(
                      imode_np[np.union1d(gate, fast)].sum()),
                  "merge_decode_equal_series": n,
                  "concat_gate_series": int(gate.size)},
    }


def bench_index_fetch_tagged():
    """Config #6: reverse-index fetch_tagged query mix (queries/sec).

    100k tagged documents in one sealed index block — the id-resolution
    path every promql selector and the node RPC's FetchTagged runs before
    any datapoint moves (db.query_ids -> NamespaceIndex.query -> segment
    execute). The mix mirrors selector traffic: exact terms, multi-term
    conjunctions with negation, literal-prefix regexps, a broad regexp,
    and a disjunction. Pure host work by design (the index is the one
    BASELINE surface that is pointer-chasing, not math), so the number is
    platform-independent; the regexp-heavy share dominates the pre-change
    pure-Python cost (pattern.fullmatch over every term in the field).

    Steady state runs the mix against a warm index (repeat queries hit
    the postings-list cache when present); extra.cold_qps records the
    first cache-cold pass separately so both populate the artifact."""
    from m3_tpu.index import query as iq
    from m3_tpu.index.namespace_index import NamespaceIndex
    from m3_tpu.utils import xtime

    n = int(os.environ.get("BENCH_INDEX_DOCS", "100000"))
    iters = int(os.environ.get("BENCH_INDEX_ITERS", "5"))
    rng = np.random.default_rng(31)
    t0 = 1_700_000_000 * 1_000_000_000

    n_hosts = max(n // 10, 1)
    names = [b"svc_%03d_latency" % i for i in range(100)]
    dcs = [b"dc_%d" % i for i in range(4)]
    roles = [b"role_%d" % i for i in range(8)]
    _phase(f"index: building {n} docs")
    items = []
    for i in range(n):
        sid = b"series-%07d" % i
        tags = {
            b"__name__": names[int(rng.integers(len(names)))],
            b"host": b"host-%05d" % int(rng.integers(n_hosts)),
            b"dc": dcs[int(rng.integers(len(dcs)))],
            b"role": roles[int(rng.integers(len(roles)))],
            b"pod": b"pod-%07d" % i,
        }
        items.append((sid, tags))
    nsi = NamespaceIndex(block_size_ns=4 * xtime.HOUR)
    nsi.insert_batch(items, t0)
    # Seal: queries run against the compacted immutable segment, the
    # shape the RPC serves once a block ages out of the write window.
    nsi.tick(t0 + 5 * xtime.HOUR, retention_ns=30 * xtime.DAY)
    _phase("index: sealed; building query mix")

    queries = []
    for i in range(8):  # exact terms
        queries.append(iq.new_term(b"host", b"host-%05d" % (i * 997 % n_hosts)))
    for i in range(6):  # conjunction + negation (the alert-rule shape)
        queries.append(iq.new_conjunction(
            iq.new_term(b"role", roles[i % len(roles)]),
            iq.new_term(b"dc", dcs[i % len(dcs)]),
            iq.new_negation(iq.new_term(b"__name__", names[i]))))
    for i in range(6):  # literal-prefix regexps (fst prefix-range idiom)
        queries.append(iq.new_regexp(b"host", b"host-00%02d.*" % i))
        queries.append(iq.new_regexp(b"__name__", b"svc_0[0-4]%d_.*" % i))
    queries.append(iq.new_regexp(b"pod", b".*-0000[0-9]{3}"))  # no prefix: full scan
    queries.append(iq.new_disjunction(
        iq.new_term(b"dc", dcs[0]), iq.new_term(b"dc", dcs[1])))
    queries.append(iq.new_conjunction(  # negation-only conjunction
        iq.new_negation(iq.new_term(b"dc", dcs[0])),
        iq.new_negation(iq.new_term(b"role", roles[0]))))

    def run_mix():
        total = 0
        for q in queries:
            total += len(nsi.query(q))
        return total

    _phase(f"index: cold pass ({len(queries)} queries)")
    t_cold0 = time.perf_counter()
    n_ids = run_mix()
    cold_s = time.perf_counter() - t_cold0
    assert n_ids > 0
    _phase(f"index: warm timing ({n_ids} ids/pass)")
    dts = []
    for _ in range(iters):
        t1 = time.perf_counter()
        got = run_mix()
        dts.append(time.perf_counter() - t1)
        assert got == n_ids
    dt = min(dts)
    _phase("index: done")
    extra = {
        "docs": n, "queries_per_pass": len(queries),
        "ids_per_pass": n_ids,
        "cold_qps": round(len(queries) / cold_s, 1),
        "mix": {"term": 8, "conjunction_negation": 6, "regexp_prefix": 12,
                "regexp_full_scan": 1, "disjunction": 1, "negation_only": 1},
    }
    stats_fn = getattr(nsi, "postings_cache_stats", None)
    if stats_fn is not None:
        extra["postings_cache"] = stats_fn()
    return {
        "metric": "index_fetch_tagged",
        "value": round(len(queries) / dt, 1),
        "unit": "queries/sec",
        "extra": extra,
    }


def bench_write_path_ingest():
    """Config #7: storage write path (datapoints/sec through
    database.write_batch), the host-plane path every ingest RPC pays
    before any device work: shard route -> series registry resolve ->
    reverse-index insert for first-seen series -> columnar buffer append.

    Two mixes, both against the exact Database wiring the node RPC
    serves (namespace index enabled, commitlog off so the measurement
    isolates the registry/index/buffer path):

      * new-series burst — every batch is ~80% first-seen series with
        full tag sets (deploy/topology-churn shape). Pre-change this
        pays a per-id synchronous registry + index insert under the
        shard write lock (the gap the reference covers with
        shard_insert_queue.go / index_insert_queue.go); the headline
        value measures that rebuild directly.
      * steady-state known series — the same ids re-written each pass
        with fresh timestamps, the scrape-interval hot path. Reported
        as extra.steady_dps and compared against the
        write_path_ingest_steady baseline key (the queue must not tax
        the known-series fast path).

    Pure host work by design (like index_fetch_tagged): the number is
    platform-independent."""
    from m3_tpu.parallel.sharding import ShardSet
    from m3_tpu.storage.database import Database
    from m3_tpu.utils import xtime

    n_series = int(os.environ.get("BENCH_WRITE_SERIES", "40000"))
    batch = int(os.environ.get("BENCH_WRITE_BATCH", "2000"))
    iters = int(os.environ.get("BENCH_WRITE_ITERS", "3"))
    steady_passes = int(os.environ.get("BENCH_WRITE_PASSES", "3"))
    rng = np.random.default_rng(47)
    t0 = 1_700_000_000 * 1_000_000_000
    now = {"t": t0}

    names = [b"svc_%03d_latency" % i for i in range(100)]
    dcs = [b"dc_%d" % i for i in range(4)]
    roles = [b"role_%d" % i for i in range(8)]

    def make_tags(i: int) -> dict:
        return {
            b"__name__": names[int(rng.integers(len(names)))],
            b"host": b"host-%05d" % int(rng.integers(n_series // 10 or 1)),
            b"dc": dcs[int(rng.integers(len(dcs)))],
            b"role": roles[int(rng.integers(len(roles)))],
            b"pod": b"pod-%07d" % i,
        }

    _phase(f"write: building {n_series} ids/tags")
    all_ids = [b"wseries-%07d" % i for i in range(n_series)]
    all_tags = [make_tags(i) for i in range(n_series)]

    # Burst batches: 80% new ids in first-seen order, 20% re-writes of
    # ids from earlier batches (the mixed new/known shape of a rollout).
    new_frac = 0.8
    burst_batches = []
    cursor = 0
    while cursor < n_series:
        n_new = min(int(batch * new_frac), n_series - cursor)
        sel = list(range(cursor, cursor + n_new))
        if cursor:
            sel += [int(x) for x in rng.integers(0, cursor, batch - n_new)]
        cursor += n_new
        burst_batches.append(
            ([all_ids[j] for j in sel], [all_tags[j] for j in sel]))
    burst_points = sum(len(ids) for ids, _ in burst_batches)

    def fresh_db() -> Database:
        db = Database(ShardSet(num_shards=16),
                      clock=lambda: now["t"])
        db.ensure_namespace(b"bench")
        return db

    def run_burst() -> Database:
        db = fresh_db()
        for ids, tags in burst_batches:
            ts = np.full(len(ids), now["t"], np.int64)
            db.write_batch(b"bench", ids, ts, np.ones(len(ids)), tags=tags)
        return db

    _phase(f"write: burst mix ({len(burst_batches)} batches, "
           f"{burst_points} points)")
    run_burst()  # warm allocator/caches outside the timing loop
    burst_dts = []
    for _ in range(iters):
        t1 = time.perf_counter()
        db = run_burst()
        burst_dts.append(time.perf_counter() - t1)
    burst_dps = burst_points / min(burst_dts)
    ns = db.namespace(b"bench")
    assert sum(s.num_series() for s in ns.shards.values()) == n_series

    # Steady state: same ids re-written against the LAST burst database
    # (registry and index fully warm), fresh timestamps per pass.
    steady_order = [all_ids[j]
                    for j in rng.permutation(n_series)]
    steady_batches = [steady_order[i:i + batch]
                      for i in range(0, n_series, batch)]

    def run_steady():
        for p in range(steady_passes):
            now["t"] = t0 + (p + 1) * xtime.SECOND
            for ids in steady_batches:
                ts = np.full(len(ids), now["t"], np.int64)
                db.write_batch(b"bench", ids, ts, np.ones(len(ids)))

    _phase(f"write: steady mix ({steady_passes} passes)")
    steady_points = n_series * steady_passes
    steady_dts = []
    for _ in range(iters):
        t1 = time.perf_counter()
        run_steady()
        steady_dts.append(time.perf_counter() - t1)
    steady_dps = steady_points / min(steady_dts)
    _phase("write: done")
    return {
        "metric": "write_path_ingest",
        "value": round(burst_dps, 1),
        "unit": "datapoints/sec",
        "extra": {
            "series": n_series, "batch": batch,
            "new_series_frac": new_frac,
            "steady_dps": round(steady_dps, 1),
            "steady_passes": steady_passes,
            "shards": 16,
        },
    }


def bench_hot_set_read():
    """Config #8: hot-set read serving (reads/sec through database.read
    against sealed blocks), the serving-path shape of millions-of-users
    dashboard traffic: a small hot set of series is re-read continuously
    while a long cold tail is touched occasionally.

    Build: 4-shard Database, two sealed 2h blocks per shard (tick-driven
    seal through the real encode path), index off and commitlog off so
    the measurement isolates the block read path (registry resolve ->
    sealed-block row decode -> clip/merge). The mix draws 90% of reads
    from a 5% hot set (the skew the HBM block-cache tier exists for) and
    every read spans both sealed blocks.

    Split: the COLD pass (first traversal, caches empty — post-change it
    additionally pays block-decode admissions) reports as extra.cold_qps;
    the headline value is the WARM pass (best of iters), the steady state
    a dashboard fleet actually sees. p99 per-read latency reports for
    both passes. The pre-change baseline is the same loop with no block
    cache (every warm read re-decodes its rows), so vs_baseline measures
    the device-block-cache tier directly.

    When the block cache is present, warm results are additionally
    checked bit-identical against a cache-bypassed re-read of a sample
    of the mix (the cached-decode correctness contract)."""
    from m3_tpu.parallel.sharding import ShardSet
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.namespace import NamespaceOptions
    from m3_tpu.utils import xtime

    try:
        from m3_tpu.storage import block_cache as _bc
    except ImportError:  # pre-change baseline run
        _bc = None

    n_series = int(os.environ.get("BENCH_HOT_SERIES", "4000"))
    ppb = int(os.environ.get("BENCH_HOT_POINTS", "120"))
    reads_per_pass = int(os.environ.get("BENCH_HOT_READS", "2000"))
    iters = int(os.environ.get("BENCH_HOT_ITERS", "3"))
    hot_frac, hot_weight = 0.05, 0.9
    n_blocks = 2
    rng = np.random.default_rng(53)
    block_ns = 2 * xtime.HOUR
    # Block starts must land on the block grid for the buffer's bucketing.
    t0 = (1_700_000_000 * 1_000_000_000 // block_ns) * block_ns
    step_ns = block_ns // ppb
    now = {"t": t0}
    db = Database(ShardSet(num_shards=4), clock=lambda: now["t"])
    db.ensure_namespace(b"bench", NamespaceOptions(
        index_enabled=False, snapshot_enabled=False,
        retention_ns=4 * xtime.DAY, writes_to_commitlog=False))
    ids = [b"hot-%06d" % i for i in range(n_series)]
    ones = np.ones(n_series)

    _phase(f"hot_set_read: writing {n_series} series x "
           f"{n_blocks * ppb} points")
    vals_by_step = rng.standard_normal((n_blocks * ppb,))
    for s in range(n_blocks * ppb):
        ts_i = t0 + s * step_ns
        now["t"] = ts_i
        db.write_batch(b"bench", ids, np.full(n_series, ts_i, np.int64),
                       ones * vals_by_step[s])
    # Seal both blocks: advance past the second window + buffer_past.
    now["t"] = t0 + n_blocks * block_ns + 11 * xtime.MINUTE
    stats = db.tick()
    assert stats["sealed"] >= n_blocks, stats

    # BENCH_HOT_VERIFY=1: arm the serve-time lazy integrity path on
    # every sealed block, as if each were paged in from a fileset —
    # expected per-row adler32s attached, memo dropped so the first
    # read actually pays the vectorized adler pass, then the per-read
    # flag checks. The obs-overhead guard A/Bs this knob to bound the
    # integrity tax on hot serving.
    if os.environ.get("BENCH_HOT_VERIFY"):
        for _sh in db.namespace(b"bench").shards.values():
            for _blk in _sh.blocks.values():
                _blk.expected_row_sums = _blk.row_checksums().copy()
                _blk._row_sums = None
                _blk._rows_verified = False

    n_hot = max(1, int(n_series * hot_frac))
    hot_ids = rng.permutation(n_series)[:n_hot]
    draws = rng.random(reads_per_pass)
    pick_hot = hot_ids[rng.integers(0, n_hot, reads_per_pass)]
    pick_cold = rng.integers(0, n_series, reads_per_pass)
    mix = np.where(draws < hot_weight, pick_hot, pick_cold)
    start, end = t0, t0 + n_blocks * block_ns

    def run_pass():
        durs = np.empty(reads_per_pass)
        total = 0
        for i, sidx in enumerate(mix):
            t1 = time.perf_counter()
            t, _v = db.read(b"bench", ids[int(sidx)], start, end)
            durs[i] = time.perf_counter() - t1
            total += len(t)
        return durs, total

    _phase(f"hot_set_read: cold pass ({reads_per_pass} reads)")
    cold_durs, n_points = run_pass()
    assert n_points == reads_per_pass * n_blocks * ppb, n_points
    _phase("hot_set_read: warm timing")
    best_durs, best_s = None, None
    for _ in range(iters):
        durs, got = run_pass()
        assert got == n_points
        if best_s is None or durs.sum() < best_s:
            best_durs, best_s = durs, durs.sum()
    extra = {
        "series": n_series, "blocks_per_shard": n_blocks, "shards": 4,
        "points_per_block": ppb, "reads_per_pass": reads_per_pass,
        "hot_frac": hot_frac, "hot_weight": hot_weight,
        "cold_qps": round(reads_per_pass / cold_durs.sum(), 1),
        "cold_p99_ms": round(float(np.quantile(cold_durs, 0.99)) * 1e3, 3),
        "warm_p99_ms": round(float(np.quantile(best_durs, 0.99)) * 1e3, 3),
    }
    if _bc is not None:
        extra["block_cache"] = _bc.get_cache().stats()
        # Correctness split: a sample of the warm mix re-read with the
        # cache bypassed must be bit-identical to the cached reads.
        sample = mix[rng.integers(0, reads_per_pass, 50)]
        cached = [db.read(b"bench", ids[int(s)], start, end)
                  for s in sample]
        with _bc.disabled():
            uncached = [db.read(b"bench", ids[int(s)], start, end)
                        for s in sample]
        for (ct, cv), (ut, uv) in zip(cached, uncached):
            assert np.array_equal(ct, ut) and np.array_equal(cv, uv), \
                "cached read diverged from uncached decode"
        extra["bit_identical_sample"] = len(sample)
    _phase("hot_set_read: done")
    return {
        "metric": "hot_set_read",
        "value": round(reads_per_pass / best_s, 1),
        "unit": "reads/sec",
        "extra": extra,
    }


def bench_peer_migration():
    """Config #9: peer-streaming shard migration (series/sec through
    PeersBootstrapper over a real node RPC session), the data plane of
    placement churn: a replacement node streams every sealed block of
    its shards from a donor replica and installs them locally.

    Build: one donor Database (8 shards, index off, commitlog off) holds
    N series x 4 points in one sealed 2h block, served by a real
    NodeServer; a fresh empty Database peer-bootstraps the whole shard
    space through a Session (metadata diff -> checksum-majority plan ->
    block fetch -> local apply). The measurement is the full migration
    wall time, series/sec — metadata paging, wire encode/decode, and
    the apply path all included, exactly what an operator waits on
    during replace-node.

    The pre-change baseline is the per-row path (per-series metadata
    dicts, per-series registry get_or_create, per-row np fills into the
    block tile), so vs_baseline measures the columnar-tile rebuild
    directly — same protocol as rounds 6-8. Post-change the bench
    additionally asserts the batched apply bit-identical to the
    retained per-row oracle on one shard's fetched tiles."""
    from m3_tpu.client.session import Session, SessionOptions
    from m3_tpu.cluster.placement import Instance, initial_placement
    from m3_tpu.cluster.topology import StaticTopology
    from m3_tpu.parallel.sharding import ShardSet
    from m3_tpu.rpc import NodeServer, NodeService
    from m3_tpu.storage import bootstrap as bs_mod
    from m3_tpu.storage.bootstrap import BootstrapContext, BootstrapProcess
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.namespace import NamespaceOptions
    from m3_tpu.utils import xtime

    n_series = int(os.environ.get("BENCH_PEER_SERIES", "100000"))
    ppb = int(os.environ.get("BENCH_PEER_POINTS", "4"))
    iters = int(os.environ.get("BENCH_PEER_ITERS", "2"))
    num_shards = 8
    ns_name = b"bench"
    block_ns = 2 * xtime.HOUR
    t0 = (1_700_000_000 * 1_000_000_000 // block_ns) * block_ns
    now = {"t": t0}
    ns_opts = NamespaceOptions(index_enabled=False, snapshot_enabled=False,
                               writes_to_commitlog=False)

    _phase(f"peer_migration: seeding donor ({n_series} series x {ppb} pts)")
    donor = Database(ShardSet(num_shards), clock=lambda: now["t"])
    donor.ensure_namespace(ns_name, ns_opts)
    ids = [b"mig-%07d" % i for i in range(n_series)]
    rng = np.random.default_rng(61)
    step_ns = block_ns // (ppb + 1)
    for s in range(ppb):
        ts_i = t0 + s * step_ns
        now["t"] = ts_i
        donor.write_batch(ns_name, ids, np.full(n_series, ts_i, np.int64),
                          rng.standard_normal(n_series))
    now["t"] = t0 + block_ns + 11 * xtime.MINUTE
    stats = donor.tick()
    assert stats["sealed"] >= num_shards, stats
    donor.mark_bootstrapped()

    srv = NodeServer(NodeService(donor)).start()
    placement = initial_placement(
        [Instance(id="donor", endpoint=srv.endpoint)], num_shards, 1)
    session = Session(StaticTopology(placement), SessionOptions(timeout_s=120))

    def fresh_db() -> Database:
        db = Database(ShardSet(num_shards), clock=lambda: now["t"])
        db.ensure_namespace(ns_name, ns_opts)
        return db

    def migrate() -> Database:
        db = fresh_db()
        proc = BootstrapProcess(
            chain=("peers",),
            ctx=BootstrapContext(session=session, placement=placement,
                                 host_id="joiner"))
        proc.run(db, now_ns=now["t"])
        return db

    _phase("peer_migration: warm pass")
    db = migrate()  # warm sockets/compile caches outside the timing loop
    got = sum(s.num_series() for s in db.namespace(ns_name).shards.values())
    assert got == n_series, f"migrated {got}/{n_series} series"
    sample = ids[n_series // 2]
    t_new, v_new = db.read(ns_name, sample, 0, now["t"])
    t_old, v_old = donor.read(ns_name, sample, 0, now["t"])
    assert np.array_equal(t_new, t_old) and np.array_equal(v_new, v_old), \
        "migrated series diverged from donor"

    _phase(f"peer_migration: timing ({iters} iters)")
    dts = []
    for _ in range(iters):
        t1 = time.perf_counter()
        migrate()
        dts.append(time.perf_counter() - t1)
    sps = n_series / min(dts)

    extra = {
        "series": n_series, "points_per_series": ppb,
        "shards": num_shards, "iters": iters,
        "migration_s": round(min(dts), 3),
    }
    # Oracle split (post-change only): the batched tile apply must be
    # state-identical to the retained per-row reference apply.
    if hasattr(bs_mod, "apply_peer_tiles_ref"):
        from m3_tpu.storage.shard import Shard
        tiles, tags, _failed = session.fetch_block_tiles_from_peers(
            ns_name, 0, t0, now["t"], exclude_host="joiner")
        opts = ns_opts.shard_options()
        sh_new, sh_ref = Shard(0, opts), Shard(0, opts)
        bs_mod.apply_peer_tiles(sh_new, tiles, tags)
        bs_mod.apply_peer_tiles_ref(sh_ref, tiles, tags)
        assert sorted(sh_new.blocks) == sorted(sh_ref.blocks)
        for bs_key, blk in sh_new.blocks.items():
            ref = sh_ref.blocks[bs_key]
            assert np.array_equal(blk.series_indices, ref.series_indices)
            assert np.array_equal(blk.words, ref.words)
            assert np.array_equal(blk.nbits, ref.nbits)
            assert np.array_equal(blk.npoints, ref.npoints)
        extra["oracle_blocks_checked"] = len(sh_new.blocks)
    session.close()
    srv.close()
    _phase("peer_migration: done")
    return {
        "metric": "peer_migration",
        "value": round(sps, 1),
        "unit": "series/sec",
        "extra": extra,
    }


def bench_bootstrap_replay():
    """Config #12: crash recovery to serving-ready (series/sec through
    BootstrapProcess over a kill -9 shaped data dir), the path a node
    takes back from death: complete flushed filesets for the old block
    (filesystem bootstrapper), the newest snapshot fileset for the warm
    block (commitlog bootstrapper's snapshot phase), and chunked WAL
    replay on top — exactly what run_dbnode replays after a hard kill.

    Build: one Database (8 shards, index off) writes N series into a
    flushed 2h block, then N series x a few points into the NEXT block
    which is snapshotted (Mediator.snapshot) and WAL-logged across
    several checksummed chunks, then the process state is ABANDONED
    without close() — on-disk state identical to SIGKILL (the commit
    log is flushed per wave, as WRITE_WAIT would have). The measurement
    is the full bootstrap wall time on a fresh db, series/sec to
    serving-ready — fileset decode, snapshot install, and WAL replay
    all included, exactly what an operator waits on after kill -9.

    The pre-change baseline is the per-entry path (one (ns, id, t,
    value) tuple per replayed WAL entry, per-row registry get_or_create
    + per-row buffer writes on the snapshot install), so vs_baseline
    measures the columnar recovery rebuild directly — same protocol as
    rounds 6-9. Post-change the bench additionally asserts the batched
    replay bit-identical to the retained per-entry oracle."""
    import shutil
    import tempfile

    from m3_tpu.parallel.sharding import ShardSet
    from m3_tpu.persist import commitlog as cl
    from m3_tpu.persist.fs import PersistManager
    from m3_tpu.storage import bootstrap as bs_mod
    from m3_tpu.storage.bootstrap import BootstrapContext, BootstrapProcess
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.mediator import Mediator
    from m3_tpu.storage.namespace import NamespaceOptions
    from m3_tpu.utils import xtime

    n_series = int(os.environ.get("BENCH_BOOT_SERIES", "100000"))
    wal_waves = int(os.environ.get("BENCH_BOOT_WAL_WAVES", "4"))
    iters = int(os.environ.get("BENCH_BOOT_ITERS", "2"))
    num_shards = 8
    ns_name = b"bench"
    block_ns = 2 * xtime.HOUR
    t0 = (1_700_000_000 * 1_000_000_000 // block_ns) * block_ns
    now = {"t": t0}
    root = tempfile.mkdtemp(prefix="bench_boot_")
    ns_opts = NamespaceOptions(index_enabled=False)

    try:
        _phase(f"bootstrap_replay: seeding dir ({n_series} series)")
        log = cl.CommitLog(os.path.join(root, "commitlog"))
        db = Database(ShardSet(num_shards), commitlog=log,
                      clock=lambda: now["t"])
        db.ensure_namespace(ns_name, ns_opts)
        pm = PersistManager(os.path.join(root, "data"))
        ids = [b"boot-%07d" % i for i in range(n_series)]
        rng = np.random.default_rng(83)
        # Old block: sealed + flushed (filesystem bootstrapper's input).
        now["t"] = t0 + xtime.MINUTE
        db.write_batch(ns_name, ids, np.full(n_series, t0, np.int64),
                       rng.standard_normal(n_series))
        now["t"] = t0 + block_ns + 11 * xtime.MINUTE
        db.tick()
        assert db.flush(pm) >= num_shards
        # Warm block: several WAL chunk waves + one snapshot of the lot.
        bs1 = t0 + block_ns
        step = block_ns // (wal_waves + 2)
        for wv in range(wal_waves):
            ts_w = bs1 + wv * step + 11 * xtime.MINUTE + 12 * xtime.MINUTE
            now["t"] = ts_w
            db.write_batch(ns_name, ids, np.full(n_series, ts_w, np.int64),
                           rng.standard_normal(n_series))
            log.flush()  # one checksummed chunk per wave (WRITE_WAIT shape)
        Mediator(db, pm).snapshot(now["t"])
        # Abandon without close(): on-disk state == SIGKILL.

        def recover() -> Database:
            fresh = Database(ShardSet(num_shards), clock=lambda: now["t"])
            fresh.ensure_namespace(ns_name, ns_opts)
            proc = BootstrapProcess(
                chain=("filesystem", "commitlog"),
                ctx=BootstrapContext(
                    persist=pm, commitlog_dir=os.path.join(root, "commitlog"),
                    shard_lookup=fresh.shard_set.lookup))
            proc.run(fresh, now_ns=now["t"])
            return fresh

        _phase("bootstrap_replay: warm pass")
        db2 = recover()
        got = sum(s.num_series()
                  for s in db2.namespace(ns_name).shards.values())
        assert got == n_series, f"recovered {got}/{n_series} series"
        sample = ids[n_series // 2]
        t_new, v_new = db2.read(ns_name, sample, 0, now["t"] + block_ns)
        t_old, v_old = db.read(ns_name, sample, 0, now["t"] + block_ns)
        assert np.array_equal(t_new, t_old) and np.array_equal(v_new, v_old), \
            "recovered series diverged from the pre-kill db"

        _phase(f"bootstrap_replay: timing ({iters} iters)")
        dts = []
        for _ in range(iters):
            t1 = time.perf_counter()
            recover()
            dts.append(time.perf_counter() - t1)
        sps = n_series / min(dts)

        extra = {
            "series": n_series, "wal_waves": wal_waves,
            "shards": num_shards, "iters": iters,
            "restart_s": round(min(dts), 3),
        }
        # Oracle split (post-change only): the batched chunk replay must
        # be bit-identical to the retained per-entry reference iterator.
        if hasattr(cl, "replay_ref"):
            ref = list(cl.replay_ref(os.path.join(root, "commitlog")))
            new = [(ns, sid, int(t), float(v))
                   for b in cl.replay_batches(os.path.join(root, "commitlog"))
                   for ns, sid, t, v in zip(b.namespaces, b.ids,
                                            b.t_ns, b.values)]
            assert new == ref, "batched replay diverged from per-entry oracle"
            extra["oracle_entries_checked"] = len(ref)
        if hasattr(bs_mod, "load_snapshots_ref"):
            extra["snapshot_install"] = "batched_tiles"
        _phase("bootstrap_replay: done")
        return {
            "metric": "bootstrap_replay",
            "value": round(sps, 1),
            "unit": "series/sec",
            "extra": extra,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_query_serve_e2e():
    """Round 16: the full serving stack, HTTP request in -> response bytes
    out (coordinator/http_api over the query engine), on a 10k-series
    dashboard mix — two fat-matrix shapes whose response is the whole
    [series x steps] plane, one grouped aggregation, and one instant
    vector. This measures the RESULT plane end to end: engine execution
    (compiled route), result materialization, and Prometheus JSON
    serialization, which pre-change is a per-series host loop (one python
    dict + one np.format_float_positional call per sample) downstream of
    a fully compiled query — bench r05 measured ~8.39 MB wire result per
    query pair with result materialization a tracked d2h choke point.

    The pre-change baseline is that per-series renderer, so vs_baseline
    measures the columnar result-frame rebuild directly — same protocol
    as rounds 6-13. Post-change the bench additionally asserts the
    columnar response bytes BYTE-IDENTICAL to the retained per-series
    oracle (`render_result_ref`) for every shape in the mix."""
    import urllib.request

    from m3_tpu.coordinator.http_api import HTTPApi
    from m3_tpu.query import Engine

    n = int(os.environ.get("BENCH_SERVE_SERIES", "10000"))
    hosts = int(os.environ.get("BENCH_SERVE_HOSTS", "200"))
    iters = int(os.environ.get("BENCH_SERVE_ITERS", "6"))
    s_ns = 1_000_000_000
    npts = 240  # 40min @ 10s
    rng = np.random.default_rng(61)
    t = (1_700_000_000 * s_ns + np.arange(npts, dtype=np.int64) * 10 * s_ns)
    vals = np.cumsum(rng.poisson(5.0, (n, npts)), axis=1).astype(np.float64)
    vals += 1e9 * (1 + np.arange(n)[:, None] % 4)  # counter magnitudes

    series = {}
    for i in range(n):
        host = b"host-%03d" % (i % hosts)
        series[b"bench_requests{i=%d}" % i] = {
            "tags": {b"__name__": b"bench_requests", b"host": host,
                     b"i": str(i).encode()},
            "t": t, "v": vals[i],
        }

    class _Storage:
        def fetch_raw(self, matchers, start_ns, end_ns):
            return series

    api = HTTPApi(Engine(_Storage())).serve()
    start_s = t[60] / s_ns
    end_s = t[-1] / s_ns
    from urllib.parse import urlencode

    def rq(params, path="/api/v1/query_range"):
        url = f"{api.endpoint}{path}?{urlencode(params)}"
        with urllib.request.urlopen(url) as resp:
            return resp.read()

    mix = [
        ("rate_matrix", dict(query="rate(bench_requests[5m])",
                             start=start_s, end=end_s, step="30")),
        ("max_over_time_matrix",
         dict(query="max_over_time(bench_requests[10m])",
              start=start_s, end=end_s, step="30")),
        ("sum_by_host", dict(query="sum by (host) (rate(bench_requests[5m]))",
                             start=start_s, end=end_s, step="30")),
        ("instant_vector", None),  # /api/v1/query below
    ]

    def one(name):
        for nm, params in mix:
            if nm != name:
                continue
            if params is None:
                return rq(dict(query="sum by (host) (bench_requests)",
                               time=end_s), path="/api/v1/query")
            return rq(params)

    try:
        _phase("query_serve_e2e: warmup (plan compiles)")
        sizes = {}
        for name, _ in mix:
            sizes[name] = len(one(name))

        # Post-change: the columnar frame must be byte-identical to the
        # retained per-series oracle for every shape in the mix.
        oracle = None
        try:
            from m3_tpu.query import render as qrender
            oracle = qrender
        except ImportError:
            pass
        if oracle is not None:
            eng = api.engine
            for name, params in mix:
                if params is None:
                    blk = eng.execute_instant(
                        "sum by (host) (bench_requests)", int(end_s * s_ns))
                    ref = oracle.render_result_ref(blk, instant=True)
                else:
                    blk = eng.execute_range(
                        params["query"], int(params["start"] * s_ns),
                        int(params["end"] * s_ns), 30 * s_ns)
                    ref = oracle.render_result_ref(blk)
                got = one(name)
                assert got == ref, (
                    f"{name}: columnar response diverged from "
                    f"render_result_ref ({len(got)} vs {len(ref)} bytes)")

        _phase(f"query_serve_e2e: steady state ({iters} rounds)")
        walls = {name: [] for name, _ in mix}
        t0 = time.perf_counter()
        for _ in range(iters):
            for name, _ in mix:
                t1 = time.perf_counter()
                one(name)
                walls[name].append(time.perf_counter() - t1)
        total = time.perf_counter() - t0
        _phase("query_serve_e2e: done")
        nreq = iters * len(mix)
        per_shape = {
            name: {"p50_ms": round(float(np.percentile(w, 50)) * 1000, 2),
                   "p99_ms": round(float(np.percentile(w, 99)) * 1000, 2),
                   "bytes": sizes[name]}
            for name, w in walls.items()
        }
        return {
            "metric": "query_serve_e2e",
            "value": round(nreq / total, 2),
            "unit": "responses/sec",
            "extra": {
                "series": n, "hosts": hosts, "points_per_series": npts,
                "mix": [name for name, _ in mix],
                "requests": nreq,
                "per_shape": per_shape,
                "wire_bytes_per_round": sum(sizes.values()),
                "oracle": ("render_result_ref byte-identity per shape"
                           if oracle is not None else None),
            },
        }
    finally:
        api.close()


def bench_codec_decode_fanout():
    """Decode fan-out: one sealed block serves its three decode consumers.

    Measures the serve-side codec floor that every read bottoms out in:
    a production SealedBlock (sealed through encode_block, realistic
    counter / fixed-decimal gauge / NaN-hole gauge / float-noise mix) is
    decoded per iteration by (1) the block-cache plane build
    (SealedBlock._decode_plane), (2) the client tile path
    (client.decode.decode_tile), and (3) the plan compiler's fetch
    staging downcast (padded f32 value plane, the `value` fetch kind).
    The device block cache is bypassed so every pass pays a real decode.

    Oracle: a row subsample is re-decoded through ops/ref_codec.py (the
    scalar bit-identity reference) and compared bit-for-bit (u64 views,
    NaN-safe) against the plane decode, every run."""
    from m3_tpu.client import decode as client_decode
    from m3_tpu.ops import ref_codec
    from m3_tpu.parallel import compile as plan_compile
    from m3_tpu.storage import block as storage_block
    from m3_tpu.storage import block_cache

    n = int(os.environ.get("BENCH_DECODE_SERIES", "4096"))
    w = int(os.environ.get("BENCH_DECODE_WINDOW", "120"))
    iters = int(os.environ.get("BENCH_DECODE_ITERS", "5"))
    s_ns = 1_000_000_000
    rng = np.random.default_rng(23)

    _phase("decode_fanout: building corpus")
    t0_ns = 1_700_000_000 * s_ns
    tdense = (t0_ns + np.arange(w, dtype=np.int64) * 10 * s_ns)[None, :]
    tdense = np.repeat(tdense, n, axis=0)
    # A quarter of the rows get second-aligned jitter so the ts stream
    # exercises the irregular delta-of-delta buckets, not just '0' bits.
    jrows = rng.random(n) < 0.25
    jit_s = rng.integers(-4, 5, size=(jrows.sum(), w)).astype(np.int64)
    tdense[jrows] += jit_s * s_ns
    tdense[jrows] = np.maximum.accumulate(tdense[jrows], axis=1)

    kind = rng.integers(0, 4, size=n)
    vdense = np.empty((n, w), np.float64)
    vdense[kind == 0] = np.cumsum(
        rng.poisson(5.0, (int((kind == 0).sum()), w)), axis=1)  # counters
    vdense[kind == 1] = np.round(
        rng.normal(250.0, 40.0, (int((kind == 1).sum()), w)), 2)  # 2dp gauge
    g = rng.normal(0.0, 10.0, (int((kind == 2).sum()), w))
    g[rng.random(g.shape) < 0.1] = np.nan  # sparse NaN holes (float mode)
    vdense[kind == 2] = g
    vdense[kind == 3] = rng.standard_normal(
        (int((kind == 3).sum()), w)) * 1e3  # float noise
    npoints = np.full(n, w, np.int32)
    short = rng.random(n) < 0.05
    npoints[short] = rng.integers(1, w, size=int(short.sum()))

    _phase("decode_fanout: sealing block (encode_block)")
    blk = storage_block.encode_block(
        t0_ns, np.arange(n, dtype=np.int32), tdense, vdense, npoints)
    unit = int(blk.time_unit)
    wb = blk.window  # encode_block pads the window to a power of two
    s_pad = 1 << (max(n, 1) - 1).bit_length()
    ext_pad = wb + 8

    def _stage_leg(vals):
        # The fetch-staging `value` kind: pad the grid, downcast to f32.
        # When compile.py grows a fused one-pass stager, pick it up so the
        # bench keeps measuring the canonical consumer path.
        fused = getattr(plan_compile, "stage_value_plane", None)
        if fused is not None:
            return fused(vals, s_pad, ext_pad)
        gp = plan_compile._pad_grid(vals, s_pad, ext_pad)
        return gp.astype(np.float32)

    def fanout():
        ts_p, vals_p = blk._decode_plane()
        ts_t, vals_t = client_decode.decode_tile(
            blk.words, blk.npoints, blk.window, unit)
        staged = _stage_leg(vals_p)
        return ts_p, vals_p, ts_t, vals_t, staged

    with block_cache.disabled():
        _phase("decode_fanout: warmup + compile")
        ts_p, vals_p, ts_t, vals_t, staged = fanout()

        # Oracle: scalar reference decode on a row subsample, bit-for-bit.
        sample = rng.choice(n, size=min(24, n), replace=False)
        for i in sample:
            i = int(i)
            npts = int(blk.npoints[i])
            rts, rvs = ref_codec.decode(ref_codec.EncodedBlock(
                words=np.asarray(blk.words[i], np.uint32),
                nbits=int(blk.nbits[i]), npoints=npts))
            assert np.array_equal(rts * blk.time_unit.nanos, ts_p[i, :npts]), (
                f"decode_fanout oracle: ts mismatch on row {i}")
            assert np.array_equal(
                np.asarray(rvs).view(np.uint64),
                np.ascontiguousarray(vals_p[i, :npts]).view(np.uint64)), (
                f"decode_fanout oracle: value bits mismatch on row {i}")
            assert np.array_equal(ts_p[i, :npts], ts_t[i, :npts])
            assert np.array_equal(
                np.ascontiguousarray(vals_p[i, :npts]).view(np.uint64),
                np.ascontiguousarray(vals_t[i, :npts]).view(np.uint64))
        assert np.array_equal(
            staged[:n, :wb][~np.isnan(vals_p)],
            vals_p.astype(np.float32)[~np.isnan(vals_p)]), (
            "decode_fanout: staged f32 plane diverged from numpy downcast")

        _phase("decode_fanout: timing")
        best = np.inf
        for _ in range(iters):
            t0 = time.perf_counter()
            fanout()
            best = min(best, time.perf_counter() - t0)
    points = int(npoints.sum())
    return {
        "metric": "codec_decode_fanout",
        "value": round(points / best, 1),
        "unit": "datapoints/sec",
        "extra": {
            "series": n, "window": w, "iters": iters,
            "consumers": ["block._decode_plane", "client.decode_tile",
                          "compile value-kind staging (pad + f32)"],
            "per_pass_ms": round(best * 1000, 2),
            "oracle": "ref_codec bit-identity on 24-row subsample",
            "note": ("value = datapoints decoded per second through the "
                     "full three-consumer fan-out of one sealed block"),
        },
    }


def _rules_corpus(n_metrics: int, n_mapping: int, n_rollup: int,
                  n_services: int = 500):
    """Seeded (rule set x metric batch) for the downsample_rules config.

    Rules: per-service mapping rules on literal-prefix name globs (some
    with an extra tag filter), a DROP_MUST class, and rollup rules whose
    first op is the rollup (new-id generation). Batch: mixed
    counter/gauge/timer samples whose names land every id on >=1 rule."""
    from m3_tpu.metrics.aggregation import AggID, AggType
    from m3_tpu.metrics.filters import TagsFilter
    from m3_tpu.metrics.metric import MetricType
    from m3_tpu.metrics.pipeline import Op, Pipeline
    from m3_tpu.metrics.policy import DropPolicy
    from m3_tpu.metrics.rules import (MappingRuleSnapshot,
                                      RollupRuleSnapshot, RollupTarget, Rule,
                                      RuleSet)
    from m3_tpu.metrics.policy import StoragePolicy

    pol_1m = (StoragePolicy.parse("1m:40h"),)
    pol_5m = (StoragePolicy.parse("5m:40h"),)
    mapping = []
    for k in range(n_mapping):
        svc = k % n_services
        filt = {"__name__": f"svc{svc:03d}_*"}
        if k % 7 == 0:
            filt["dc"] = "east" if k % 2 else "west"
        mapping.append(Rule([MappingRuleSnapshot(
            f"map-{k}", 0, TagsFilter(filt),
            storage_policies=pol_5m if k % 5 == 0 else pol_1m)]))
    # DROP_MUST class: ids named drop_* match ONLY this rule.
    mapping.append(Rule([MappingRuleSnapshot(
        "map-drop", 0, TagsFilter({"__name__": "drop_*"}),
        storage_policies=pol_1m, drop_policy=DropPolicy.DROP_MUST)]))
    rollup = []
    for k in range(n_rollup):
        svc = (k * 3) % n_services
        pipe = Pipeline((Op.roll(b"rollup_svc%03d" % svc, (b"dc",),
                                 AggID.compress([AggType.SUM])),))
        rollup.append(Rule([RollupRuleSnapshot(
            f"roll-{k}", 0, TagsFilter({"__name__": f"svc{svc:03d}_*"}),
            (RollupTarget(pipe, pol_1m),))]))
    rs = RuleSet(b"default", 1, mapping, rollup)

    types = (MetricType.GAUGE, MetricType.COUNTER, MetricType.TIMER)
    samples = []
    t0 = 1_700_000_000 * 1_000_000_000
    for i in range(n_metrics):
        if i % 50 == 49:  # 2%: the DROP_MUST class
            name = b"drop_%d" % i
        else:
            name = b"svc%03d_lat_%d" % (i % n_services, i)
        tags = {b"__name__": name, b"host": b"h%02d" % (i % 64),
                b"dc": b"east" if i % 2 else b"west",
                b"endpoint": b"e%02d" % (i % 16)}
        samples.append((tags, t0, float(i % 97) + 0.5, types[i % 3]))
    return rs, samples


def bench_downsample_rules():
    """Streaming rules-engine config (ROADMAP item 2's bench): one
    100k-metric mixed columnar batch matched + aggregated against a
    >=1k-rule set (mapping + rollup pipelines + a DROP_MUST class)
    through the embedded downsampler. The COLD pass is the headline —
    matching every distinct id against the whole rule set is the
    per-metric path's hot loop; the warm pass (match-memo steady state)
    rides along in extra. Post-change builds route through
    Downsampler.write_batch (batch matcher + grouped columnar adds) and
    must hold the retained per-metric path bit-identical on a subset
    mirror, in-bench."""
    from m3_tpu.cluster.kv import MemStore
    from m3_tpu.coordinator.downsample import Downsampler
    from m3_tpu.metrics.matcher import Matcher, RuleSetStore

    n = int(os.environ.get("BENCH_RULES_METRICS", "100000"))
    n_mapping = int(os.environ.get("BENCH_RULES_MAPPING", "800"))
    n_rollup = int(os.environ.get("BENCH_RULES_ROLLUP", "200"))
    _phase("downsample_rules: building rule set + batch")
    rs, samples = _rules_corpus(n, n_mapping, n_rollup)
    clock = lambda: samples[0][1]  # noqa: E731 - frozen bench clock

    def build():
        store = RuleSetStore(MemStore())
        store.publish(rs)
        matcher = Matcher(store, b"default", clock=clock)
        sink = []
        ds = Downsampler(
            matcher, lambda mid, tags, t, v, pol, _s=sink: _s.append(mid),
            clock=clock)
        return ds, sink

    batched = hasattr(Downsampler, "write_batch")

    def run_pass(ds):
        t0 = time.perf_counter()
        if batched:
            matched, dropped = ds.write_batch(samples)
            assert matched + dropped > 0
        else:
            for tags, t, v, mt in samples:
                ds.write(tags, t, v, mt)
        return time.perf_counter() - t0

    _phase(f"downsample_rules: warmup (subset, batched={batched})")
    ds_w, _ = build()
    if batched:
        ds_w.write_batch(samples[:2000])
    else:
        for tags, t, v, mt in samples[:2000]:
            ds_w.write(tags, t, v, mt)

    _phase("downsample_rules: cold pass")
    ds, sink = build()
    cold_dt = run_pass(ds)
    matched, dropped = ds.samples_matched, ds.samples_dropped
    assert matched > 0.9 * n and dropped > 0, (matched, dropped)
    _phase(f"downsample_rules: cold {cold_dt:.1f}s; warm pass")
    warm_dt = min(run_pass(ds) for _ in range(2))
    ds.flush(samples[0][1] + 10 * 60 * 1_000_000_000)
    assert sink, "flush produced no aggregated output"

    extra = {
        "metrics": n, "mapping_rules": n_mapping + 1,
        "rollup_rules": n_rollup, "mix": "gauge/counter/timer round-robin",
        "matched": matched, "dropped_drop_must": dropped,
        "cold_ms": round(cold_dt * 1000, 1),
        "warm_dps": round(n / warm_dt, 1),
        "flushed_rows": len(sink),
        "batched_path": batched,
    }
    if batched:
        # In-bench oracle: the retained per-metric path must produce the
        # SAME matches and the SAME aggregated flush rows on a subset
        # mirror (rounds 6-10 protocol).
        _phase("downsample_rules: per-metric oracle mirror")
        sub = samples[:4000]
        got_ds, got_sink = build()
        got_ds.write_batch(sub)
        ref_ds, ref_sink = build()
        for tags, t, v, mt in sub:
            ref_ds.write_ref(tags, t, v, mt)
        assert (got_ds.samples_matched, got_ds.samples_dropped) == \
            (ref_ds.samples_matched, ref_ds.samples_dropped)
        t_f = sub[0][1] + 10 * 60 * 1_000_000_000
        got_ds.flush(t_f)
        ref_ds.flush(t_f)
        assert sorted(got_sink) == sorted(ref_sink), (
            "batched downsample diverged from the per-metric oracle "
            f"({len(got_sink)} vs {len(ref_sink)} flushed rows)")
        extra["oracle"] = (f"write_ref per-metric mirror ({len(sub)} "
                           "samples), flush rows identical")
    return {
        "metric": "downsample_rules",
        "value": round(n / cold_dt, 1),
        "unit": "datapoints/sec",
        "extra": extra,
    }


_BENCHES = [
    ("m3tsz_encode_1m_rollup", bench_encode_rollup),
    ("counter_gauge_rollup", bench_counter_gauge),
    ("agg_rollup_10x", bench_agg_rollup_10x),
    ("promql_rate_sum_over_time_1h", bench_promql),
    ("promql_plan_agg", bench_promql_plan_agg),
    ("timer_quantile_rollup", bench_timer_quantiles),
    ("shard_flush_merge", bench_flush_merge),
    ("index_fetch_tagged", bench_index_fetch_tagged),
    ("write_path_ingest", bench_write_path_ingest),
    ("hot_set_read", bench_hot_set_read),
    ("peer_migration", bench_peer_migration),
    ("bootstrap_replay", bench_bootstrap_replay),
    ("query_serve_e2e", bench_query_serve_e2e),
    ("codec_decode_fanout", bench_codec_decode_fanout),
    ("downsample_rules", bench_downsample_rules),
]


def _child_main():
    _phase("child start")
    import jax

    from m3_tpu.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    _phase(f"backend init done: {dev.platform} ({dev.device_kind}, "
           f"{len(jax.devices())} device(s))")
    if dev.platform != "tpu":
        # No CPU fallback: a host timing under a device metric's name is
        # the mislabel this harness exists to prevent.
        raise SystemExit(
            f"bench child: jax.devices()[0].platform is {dev.platform!r}, "
            "not 'tpu' — refusing to measure")

    # Each result is printed the moment it is measured — benches may be
    # generators that stream a headline line before slower follow-up
    # segments — so a later bench (or segment) failing or hanging into the
    # parent's timeout cannot destroy metrics already measured. Repeated
    # yields under one metric name refine it (the parent keeps the last).
    import inspect

    failed = []
    for name, bench in _selected_benches():
        emitted = 0
        try:
            rs = bench()
            for r in rs if inspect.isgenerator(rs) else (rs,):
                r["metric"] = name
                r["platform"] = dev.platform
                r["device_kind"] = dev.device_kind
                r["device_count"] = len(jax.devices())
                print(json.dumps(r), flush=True)
                emitted += 1
        except Exception as e:  # noqa: BLE001 - isolate per-bench failures
            _phase(f"{name} FAILED after {emitted} result(s): {e!r}")
            # Even with a headline already streamed, a raising segment is a
            # FAILURE: the nonzero exit makes the parent record the error
            # next to whatever partial it keeps — a partial must never
            # masquerade as a clean run.
            failed.append(name)
            continue
    _phase("child done" + (f" ({len(failed)} failed: {failed})" if failed else ""))
    if failed:
        raise SystemExit(1)


def _spawn_child(only):
    """One config in its own process (this parent never imports JAX, so
    the child is the only process that holds the chip)."""
    env = dict(os.environ)
    env["BENCH_ONLY"] = ",".join(only)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=env,
            capture_output=True,
            text=True,
            timeout=_CHILD_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired as e:
        stderr = ((e.stderr or b"").decode() if isinstance(e.stderr, bytes)
                  else (e.stderr or ""))
        stdout = ((e.stdout or b"").decode() if isinstance(e.stdout, bytes)
                  else (e.stdout or ""))
        for line in stderr.splitlines():
            if line.startswith("bench-phase"):
                print(line, file=sys.stderr)
        # Benches stream results as they complete: keep whatever finished
        # before the hang.
        results = _parse_results(stdout)
        return (results or None), f"timeout after {_CHILD_TIMEOUT_S}s"
    for line in (proc.stderr or "").splitlines():
        if line.startswith("bench-phase"):
            print(line, file=sys.stderr)
    results = _parse_results(proc.stdout or "")
    if proc.returncode != 0:
        lines = (proc.stderr or proc.stdout or "").strip().splitlines()
        # Prefer the bench's own phase/failure stamps over backend log spew
        # (XLA warnings can be thousands of chars a line) so the recorded
        # error stays readable in the artifact.
        marked = [ln for ln in lines if "bench-phase" in ln or "FAILED" in ln]
        # Keep the raw last lines too: a failure outside the per-bench try
        # (import error, no TPU, serialization) never prints a FAILED
        # stamp and its message would otherwise be dropped.
        tail = marked[-5:] + [ln for ln in lines[-3:] if ln not in marked]
        return (results or None), f"rc={proc.returncode}: " + " | ".join(tail)
    if not results:
        return None, "no JSON lines in child output"
    return results, None


def _parse_results(stdout: str):
    results = []
    for line in stdout.strip().splitlines():
        try:
            results.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    return results


def _load_baselines():
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_baseline.json")) as f:
            base = json.load(f)
    except Exception as e:
        print(f"warning: no usable bench_baseline.json ({e})", file=sys.stderr)
        return {}
    out = dict(base.get("metrics", {}))
    if "cpu_dps" in base:
        out.setdefault("m3tsz_encode_1m_rollup", base["cpu_dps"])
    return out


def _selected_benches():
    """(metric, fn) pairs matching BENCH_ONLY (comma-separated substrings of
    the metric or function name); an empty match is a config error raised
    before any backend init."""
    only = [s for s in os.environ.get("BENCH_ONLY", "").split(",") if s]
    selected = [
        (name, fn) for name, fn in _BENCHES
        if not only or any(s in name or s in fn.__name__ for s in only)
    ]
    if not selected:
        names = ", ".join(name for name, _ in _BENCHES)
        raise SystemExit(f"no bench matched BENCH_ONLY={only!r} (have: {names})")
    return selected


def main():
    if "--child" in sys.argv:
        _child_main()
        return 0
    selected = [name for name, _ in _selected_benches()]

    errors = {}
    got = {}
    for name in selected:
        results, err = _spawn_child(only=[name])
        for r in results or []:
            got[r["metric"]] = r
        if err:
            errors[name] = err
            print(f"warning: bench[{name}] {err}", file=sys.stderr)

    baselines = _load_baselines()
    for name in selected:
        r = got.get(name)
        if r is None:
            print(json.dumps({
                "metric": name,
                "value": None,
                "unit": "datapoints/sec",
                "vs_baseline": None,
                "error": errors.get(name, "bench produced no result"),
            }))
            continue
        base = baselines.get(name)
        extra = r.setdefault("extra", {})
        for key in ("platform", "device_kind", "device_count"):
            extra[key] = r.pop(key, None)
        extra["cpu_baseline_dps"] = base
        # End-to-end ratio for the ingest config: device step INCLUDING
        # per-block host prep vs the same path on CPU (the north star
        # covers the whole shard ingest, not just the device launch).
        e2e = extra.get("e2e_dps_with_host_prep")
        e2e_base = baselines.get("m3tsz_encode_e2e")
        if e2e and e2e_base:
            extra["cpu_e2e_baseline_dps"] = e2e_base
            extra["e2e_vs_cpu_e2e"] = round(e2e / e2e_base, 3)
        # Steady-state companion ratio for the write-path config: the
        # new-series burst is the headline, but the known-series fast
        # path must not regress (>=0.95x is the acceptance bar).
        steady = extra.get("steady_dps")
        steady_base = baselines.get("write_path_ingest_steady")
        if steady and steady_base:
            extra["steady_baseline_dps"] = steady_base
            extra["steady_vs_baseline"] = round(steady / steady_base, 3)
        if name in errors:  # a partial: headline kept, a later segment failed
            extra["error"] = errors[name]
        vs = (r["value"] / base) if (base and r["value"]) else None
        print(json.dumps({
            "metric": name,
            "value": r["value"],
            "unit": r["unit"],
            "vs_baseline": round(vs, 3) if vs is not None else None,
            "extra": extra,
        }))
    # A missing result is a failed run, never an exit 0.
    return 1 if any(name not in got for name in selected) else 0


if __name__ == "__main__":
    sys.exit(main())
