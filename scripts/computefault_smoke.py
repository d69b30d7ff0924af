#!/usr/bin/env python
"""Seeded compute-fault smoke: the check_all tier for the compute-fault
plane (testing/faultcomp + parallel/guard). ONE seeded pass arms the
dispatch seam over the real guarded routes and asserts the whole loop:

  1. oracle equality under chaos: the compiled plan route (Engine vs
     the retained interpreter), the mesh agg-flush quantile kernel (vs
     the single-device twin), and the Pallas codec kernels (vs
     ref_codec) all keep serving correct answers while every guarded
     dispatch raises/OOMs/corrupts under the seeded plan;
  2. typed degradation, not silence: the plan fallback is recorded as
     FallbackReason.DEVICE_FAULT scope=runtime, the faulted shape
     bucket lands in the executable quarantine (no recompile
     crash-loop), and telemetry.compute.* fallback/fault/quarantine
     counters all move;
  3. breaker lifecycle: a crash-looping route trips OPEN within
     min_samples dispatches, reads as compute-degraded (0.8 — degraded,
     never shedding) on the health probe, and recovers to CLOSED
     through the half-open probe once the faults clear;
  4. replayability: the seam's decision log equals the pure
     (seed, route, index) schedule.

The full matrix (five fault kinds x every guarded route, OOM
evict-then-retry, quarantine TTL, flush all-or-nothing, churn
composition) lives in tests/test_compute_faults.py; the per-kernel
kill-switch matrix is tests/test_codec_pallas.py.

Usage: python scripts/computefault_smoke.py [--seed N]
Wall budget: COMPUTEFAULT_SMOKE_BUDGET_S (default 10 seconds).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# A fault-injection drill, not a device run: pin the CPU backend and take
# the Pallas codec route (interpret mode).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("M3_TPU_PALLAS", "1")
os.environ.setdefault("M3_TPU_MESH_AGG_MIN_CELLS", "0")

S = 1_000_000_000


class MemStorage:
    def __init__(self, n=8):
        import numpy as np

        rng = np.random.default_rng(5)
        t0 = 1_700_000_000 * S
        self.t = t0 + np.arange(120, dtype=np.int64) * 10 * S
        self.series = []
        for i in range(n):
            tags = {b"__name__": b"m", b"host": b"h%d" % (i % 3),
                    b"i": str(i).encode()}
            v = 1e9 * (1 + i) + np.cumsum(
                rng.poisson(5.0, 120)).astype(np.float64)
            self.series.append((tags, self.t, v))

    def fetch_raw(self, matchers, start_ns, end_ns):
        out = {}
        for tags, t, v in self.series:
            if all(m.matches(tags.get(m.name, b"")) for m in matchers):
                keep = (t >= start_ns) & (t < end_ns)
                sid = b",".join(k + b"=" + x
                                for k, x in sorted(tags.items()))
                out[sid] = {"tags": tags, "t": t[keep], "v": v[keep]}
        return out


def _assert_blocks_match(got, ref):
    import numpy as np

    gtags = [bytes(t.id()) for t in got.series_tags]
    rtags = [bytes(t.id()) for t in ref.series_tags]
    assert set(gtags) == set(rtags), "route changed the series set"
    order = {t: i for i, t in enumerate(rtags)}
    g = np.asarray(got.values)
    r = np.asarray(ref.values)[[order[t] for t in gtags]]
    np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-9, equal_nan=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="seeded compute-fault smoke")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    budget_s = float(os.environ.get("COMPUTEFAULT_SMOKE_BUDGET_S", "10.0"))
    t_start = time.monotonic()

    from m3_tpu.utils import compile_cache

    compile_cache.configure()

    import numpy as np

    from m3_tpu.ops import ref_codec, tsz
    from m3_tpu.parallel import agg_flush, guard
    from m3_tpu.parallel import ingest as pingest
    from m3_tpu.query import Engine
    from m3_tpu.query import plan as qplan
    from m3_tpu.testing import faultcomp
    from m3_tpu.utils import hashing
    from m3_tpu.utils.instrument import ROOT
    from m3_tpu.utils.retry import Breaker, BreakerOptions

    guard.reset()
    rng = np.random.default_rng(1000 + args.seed)

    # -- leg 1: plan route under chaos -> interpreter oracle + typed
    #    DEVICE_FAULT + quarantine + recovery after the faults clear.
    floor = qplan.PLAN_MIN_CELLS
    qplan.PLAN_MIN_CELLS = 1
    try:
        st = MemStorage()
        eng = Engine(st)
        query = "sum by (host) (rate(m[5m]))"
        start, end, step = int(st.t[30]), int(st.t[-1]), 30 * S
        ref = eng.execute_range_ref(query, start, end, step)
        got = eng.execute_range(query, start, end, step)
        assert eng.last_route()["route"] == "compiled", \
            "compiled route never engaged clean"
        _assert_blocks_match(got, ref)

        before = ROOT.snapshot()
        plan = faultcomp.ComputeFaultPlan(
            seed=args.seed, route_filter="plan", dispatch_raise=1.0)
        with faultcomp.injected(plan) as seam:
            for _ in range(3):
                _assert_blocks_match(
                    eng.execute_range(query, start, end, step), ref)
        route = eng.last_route()
        assert route["route"] == "interpreter"
        assert route["fallback_reason"] == \
            qplan.FallbackReason.DEVICE_FAULT.value
        assert guard.quarantined_keys("plan"), "shape bucket not quarantined"
        assert len(seam.decisions["plan"]) == 1, \
            "quarantine did not stop the recompile loop"
        assert seam.decisions["plan"] == plan.schedule("plan", 1), \
            "decision log diverged from the seeded schedule"
        after = ROOT.snapshot()
        for key in ("telemetry.compute.fallback{route=plan}",
                    "telemetry.compute.quarantined{route=plan}",
                    "telemetry.plan_fallback.count"
                    "{reason=device-fault,scope=runtime}"):
            assert after.get(key, 0) > before.get(key, 0), f"{key} flat"

        guard.reset()  # operator clears the incident
        _assert_blocks_match(eng.execute_range(query, start, end, step), ref)
        assert eng.last_route()["route"] == "compiled", \
            "compiled route did not recover"
    finally:
        qplan.PLAN_MIN_CELLS = floor

    # -- leg 2: agg-flush quantile kernel under chaos vs the
    #    single-device twin (bit-identical: same kernel, unpadded rows).
    counts = rng.integers(0, 40, 12).astype(np.int64)
    counts[0] = 0
    buckets = [np.sort(rng.normal(100, 20, int(c))) for c in counts]
    qs = (0.5, 0.99)
    mesh = pingest.make_mesh(1)
    orig_mesh = agg_flush.flush_mesh
    agg_flush.flush_mesh = lambda: mesh
    try:
        oracle = agg_flush.exact_quantile_values(buckets, counts, qs)
        plan = faultcomp.ComputeFaultPlan(
            seed=args.seed, route_filter="agg_flush",
            dispatch_raise=0.4, corrupt=0.4)
        with faultcomp.injected(plan) as seam:
            for _ in range(3):
                np.testing.assert_array_equal(
                    agg_flush.exact_quantile_values(buckets, counts, qs),
                    oracle)
        agg_faults = sum(1 for d in seam.decisions.get("agg_flush", [])
                         if d != faultcomp.NO_FAULT)
    finally:
        agg_flush.flush_mesh = orig_mesh
    assert agg_faults > 0, "agg-flush chaos never fired"

    # -- leg 3: codec kernels (encode/decode/hash) under chaos vs
    #    ref_codec / murmur3 oracles, bit-identical.
    w = 16
    base = np.int64(1_700_000_000)
    ts = base + np.arange(w, dtype=np.int64)[None, :] * 10 \
        + rng.integers(0, 2, (16, w))
    ts = np.sort(ts, axis=1)
    vals = np.round(rng.normal(100, 10, (16, w)), 2)
    npoints = rng.integers(1, w + 1, 16).astype(np.int32)
    inp = tsz.prepare_encode_inputs(ts, vals, npoints)
    kw = dict(dt=inp["dt"], t0=inp["t0"], vhi=inp["vhi"], vlo=inp["vlo"],
              int_mode=inp["int_mode"], k=inp["k"], npoints=inp["npoints"],
              ts_regular=inp["ts_regular"], delta0=inp["delta0"])
    mw = tsz.max_words_for(w)
    ow, onb = tsz.encode_batch(**kw, max_words=mw, pack="scatter")
    ow, onb = np.asarray(ow), np.asarray(onb)
    ids = [bytes(rng.integers(0, 256, ln, dtype=np.uint8))
           for ln in rng.integers(1, 33, 64)]
    href = np.array([hashing.murmur3_32(i) for i in ids], np.uint32)
    plan = faultcomp.ComputeFaultPlan(
        seed=args.seed, route_filter="codec.",
        dispatch_raise=0.3, corrupt=0.3, oom=0.2)
    with faultcomp.injected(plan) as seam:
        for _ in range(3):
            w2, nb2 = tsz.encode_batch(**kw, max_words=mw)
            np.testing.assert_array_equal(np.asarray(w2), ow)
            np.testing.assert_array_equal(np.asarray(nb2), onb)
            tsp, _vsp = tsz.decode_plane(ow, npoints, window=w,
                                         unit_nanos=1)
            for r in range(4):
                n = int(npoints[r])
                t_ref, _ = ref_codec.decode(ref_codec.EncodedBlock(
                    words=ow[r], nbits=0, npoints=n))
                np.testing.assert_array_equal(t_ref,
                                              np.asarray(tsp[r, :n]))
            np.testing.assert_array_equal(hashing.hash_batch(ids), href)
        codec_faults = sum(
            1 for decs in seam.decisions.values()
            for d in decs if d != faultcomp.NO_FAULT)
    assert codec_faults > 0, "codec chaos never fired"

    # -- leg 4: breaker lifecycle + health posture + recovery.
    guard.reset()  # the codec/agg campaigns may have tripped routes

    class Clock:
        t = 1000.0

        def __call__(self):
            return self.t

    clock = Clock()
    guard.configure("smoke.trip", clock=clock, opts=BreakerOptions(
        window=8, failure_ratio=0.5, min_samples=2, cooldown_s=5.0))
    with faultcomp.injected(faultcomp.ComputeFaultPlan(
            seed=args.seed, dispatch_raise=1.0)):
        for _ in range(4):
            guard.dispatch("smoke.trip", lambda: 1, lambda _e: 0)
    assert guard.debug_snapshot()["smoke.trip"]["state"] == Breaker.OPEN
    sat = guard._degradation()
    assert 0.7 <= sat < 0.95, f"compute degradation {sat} not degraded-only"
    trips = ROOT.snapshot().get("telemetry.compute.trips", 0)
    assert trips >= 1, "breaker trip never counted"
    clock.t += 6.0  # past cooldown; faults cleared -> half-open probe
    assert guard.dispatch("smoke.trip", lambda: 1, lambda _e: 0) == 1
    assert guard.debug_snapshot()["smoke.trip"]["state"] == Breaker.CLOSED
    assert guard._degradation() == 0.0, "recovery left the probe degraded"
    guard.reset()

    print(f"computefault smoke: seed={args.seed} "
          f"plan_quarantine=1 agg_faults={agg_faults} "
          f"codec_faults={codec_faults} trips={trips} "
          f"degraded_sat={sat} recovered=True")

    elapsed = time.monotonic() - t_start
    assert elapsed <= budget_s, (
        f"computefault smoke took {elapsed:.1f}s > budget {budget_s}s "
        f"(COMPUTEFAULT_SMOKE_BUDGET_S to override)")
    print(f"COMPUTEFAULT SMOKE PASS ({elapsed:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
