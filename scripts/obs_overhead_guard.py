#!/usr/bin/env python
"""Instrumentation-overhead bench guard (PERF.md round 10): tracing at
default sampling must cost <3% on the two host-plane benches the spans
ride — `write_path_ingest` (storage.write_batch child span per batch)
and `index_fetch_tagged` (index.query child span per query).

Protocol:
  * each bench runs at its FULL default config (so the absolute floors
    against bench_baseline.json stay meaningful), alternating modes
    OFF, ON, OFF, ON (`OBS_GUARD_REPS` pairs, default 2), best value
    per mode — interleaving cancels allocator/cache warmup drift, and
    the benches' internal best-of-N damps per-run noise further;
  * OFF = tracing's idle state: no active span, every child_span is the
    shared NOOP (one thread-local read per call site);
  * ON = a sampled root span active around the whole bench at default
    sampling (M3_TPU_TRACE_SAMPLE=1), so EVERY child span on the path
    is real — strictly harsher than production, where only sampled
    requests pay;
  * asserts ON >= (1 - OBS_GUARD_MAX_REGRESSION) * OFF per metric
    (default 3%), and ON >= the recorded bench_baseline.json floor
    (the acceptance criterion's "vs recorded baselines").

VERIFY section: serve-time lazy row verification
(storage/block._verify_rows) must cost <3% on `hot_set_read`'s warm
reads/sec — the bench's BENCH_HOT_VERIFY=1 knob arms every sealed block
with expected per-row adler32s (as paged-in filesets carry), so ON pays
one adler pass per block cold plus the per-read verified-flag check
warm. Bound via VERIFY_GUARD_MAX_REGRESSION.

ANALYZE section (PERF.md round 15): the query observatory's ANALYZE
hooks (query/explain.py — bind stage, device dispatch, result
materialization, grid-cache events) must be free when disabled.
Interleaves BYPASS (hooks monkeypatched out — the no-hook comparator)
vs OFF (shipped dormant hooks) vs ON (active context) on
promql_plan_agg and index_fetch_tagged: dormant within
ANALYZE_GUARD_MAX_REGRESSION (default 1%) of no-hook, active within
ANALYZE_GUARD_ON_MAX_REGRESSION (default 10%) as a pathology backstop,
and ANALYZE-off above the recorded floors.

GUARD section: the compute-fault guard seam (parallel/guard.dispatch —
breaker check, seam indirection, telemetry counters) rides every
accelerated dispatch, so faults-OFF it must cost <3% on the two benches
whose steady state crosses it most: promql_plan_agg (the compiled plan
route + per-invocation temporal guarded builders) and
counter_gauge_rollup (the aggregator flush tier — the no-accidental-
coupling control). Interleaves BYPASS
(guard.dispatch monkeypatched to a direct primary call — the pre-guard
code to within one function call) vs OFF (the shipped seam, no fault
plan installed). Bound via GUARD_SEAM_MAX_REGRESSION.

Usage: python scripts/obs_overhead_guard.py
Env: OBS_GUARD_REPS, OBS_GUARD_MAX_REGRESSION, VERIFY_GUARD_MAX_REGRESSION,
ANALYZE_GUARD_REPS, ANALYZE_GUARD_MAX_REGRESSION,
ANALYZE_GUARD_ON_MAX_REGRESSION, GUARD_SEAM_REPS,
GUARD_SEAM_MAX_REGRESSION, GUARD_SEAM_CONTROL_MAX_REGRESSION,
the benches' own
BENCH_WRITE_*/BENCH_INDEX_*/BENCH_HOT_*/BENCH_PLAN_* knobs.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("M3_TPU_TRACE_SAMPLE", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    reps = int(os.environ.get("OBS_GUARD_REPS", "2"))
    max_reg = float(os.environ.get("OBS_GUARD_MAX_REGRESSION", "0.03"))

    import bench
    from m3_tpu.utils import tracing

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench_baseline.json")) as f:
        baselines = json.load(f)["metrics"]

    def run(fn, traced: bool) -> dict:
        if not traced:
            return fn()
        with tracing.TRACER.span("bench.obs_guard"):
            return fn()

    def series(fn, extract):
        """Alternate OFF/ON reps; return (best_off, best_on) dicts of
        metric -> value (max across reps per mode)."""
        best = ({}, {})
        for _ in range(reps):
            for mode in (0, 1):
                vals = extract(run(fn, traced=bool(mode)))
                for k, v in vals.items():
                    best[mode][k] = max(best[mode].get(k, 0.0), v)
        return best

    failures = []

    def check(name, ok, detail=""):
        print(f"  {name:58s} {'ok' if ok else 'FAIL'}"
              f"{('  ' + detail) if detail else ''}")
        if not ok:
            failures.append(name)

    def guard(label, off, on, floor_key):
        for metric, off_v in off.items():
            on_v = on[metric]
            ratio = on_v / off_v if off_v else 1.0
            check(f"{label}.{metric} traced within {max_reg:.0%} of untraced",
                  ratio >= 1.0 - max_reg,
                  f"off={off_v:.1f} on={on_v:.1f} ratio={ratio:.3f}")
        floor = baselines.get(floor_key)
        head = next(iter(on.values()))
        if floor:
            check(f"{label} traced beats recorded baseline",
                  head >= floor, f"on={head:.1f} floor={floor:.1f}")

    print("== index_fetch_tagged (traced vs untraced) ==")
    off, on = series(
        bench.bench_index_fetch_tagged,
        lambda r: {"warm_qps": float(r["value"]),
                   "cold_qps": float(r["extra"]["cold_qps"])})
    guard("index_fetch_tagged", off, on, "index_fetch_tagged")

    print("== write_path_ingest (traced vs untraced) ==")
    off_w, on_w = series(
        bench.bench_write_path_ingest,
        lambda r: {"burst_dps": float(r["value"]),
                   "steady_dps": float(r["extra"]["steady_dps"])})
    guard("write_path_ingest",
          {"burst_dps": off_w["burst_dps"]},
          {"burst_dps": on_w["burst_dps"]}, "write_path_ingest")
    guard("write_path_ingest",
          {"steady_dps": off_w["steady_dps"]},
          {"steady_dps": on_w["steady_dps"]}, "write_path_ingest_steady")

    # ---- Serve-time lazy verification (storage/block._verify_rows):
    # the integrity tax on hot serving. A/B the BENCH_HOT_VERIFY knob
    # on hot_set_read — ON arms every sealed block with its expected
    # per-row adler32s as if paged in from a fileset, so the cold pass
    # pays one vectorized adler pass per block and every warm read pays
    # the two-getattr verified-flag check. Warm reads/sec (the headline,
    # the dashboard steady state) must stay within
    # VERIFY_GUARD_MAX_REGRESSION (default 3%) of the unverified run,
    # and the VERIFIED run must still beat the recorded baseline floor.
    # cold_qps reports unguarded: the one-time adler pass is the
    # designed detection cost, bounded by the flag's laziness, not by
    # this guard.
    v_max = float(os.environ.get("VERIFY_GUARD_MAX_REGRESSION", "0.03"))

    def verify_series(fn, extract):
        best = ({}, {})
        for _ in range(reps):
            for mode in (0, 1):
                if mode:
                    os.environ["BENCH_HOT_VERIFY"] = "1"
                try:
                    vals = extract(fn())
                finally:
                    os.environ.pop("BENCH_HOT_VERIFY", None)
                for k, v in vals.items():
                    best[mode][k] = max(best[mode].get(k, 0.0), v)
        return best

    print("== hot_set_read (lazy row verification on vs off) ==")
    v_off, v_on = verify_series(
        bench.bench_hot_set_read,
        lambda r: {"warm_qps": float(r["value"]),
                   "cold_qps": float(r["extra"]["cold_qps"])})
    ratio = (v_on["warm_qps"] / v_off["warm_qps"]
             if v_off["warm_qps"] else 1.0)
    check(f"hot_set_read.warm_qps verified within {v_max:.0%} of unverified",
          ratio >= 1.0 - v_max,
          f"off={v_off['warm_qps']:.1f} on={v_on['warm_qps']:.1f} "
          f"ratio={ratio:.3f}")
    floor = baselines.get("hot_set_read")
    if floor:
        check("hot_set_read verified beats recorded baseline",
              v_on["warm_qps"] >= floor,
              f"on={v_on['warm_qps']:.1f} floor={floor:.1f}")
    print(f"  cold_qps (unguarded): off={v_off['cold_qps']:.1f} "
          f"on={v_on['cold_qps']:.1f}")

    # ---- ANALYZE instrumentation (query/explain.py): the hooks on the
    # query path (bind stage, device dispatch, result materialization,
    # grid-cache events) must be FREE when no ANALYZE context is active.
    # Methodology: interleave BYPASS (qexplain.current monkeypatched to
    # a constant None — the pre-change no-hook code, to within one
    # C-level call) against OFF (the shipped dormant hooks, production
    # default), per-metric best; dormant must stay within
    # ANALYZE_GUARD_MAX_REGRESSION (default 1%) of bypassed on BOTH
    # promql_plan_agg (hooks live here) and index_fetch_tagged (no hooks
    # on that path — proves no accidental coupling). An ACTIVE context
    # additionally runs at a loose bound (default 10%) as a pathology
    # backstop, with its stage table printed.
    from m3_tpu.query import explain as qexplain

    areps = int(os.environ.get("ANALYZE_GUARD_REPS", "2"))
    a_max = float(os.environ.get("ANALYZE_GUARD_MAX_REGRESSION", "0.01"))
    a_on_max = float(
        os.environ.get("ANALYZE_GUARD_ON_MAX_REGRESSION", "0.10"))

    def analyze_series(fn, extract):
        """(best_bypass, best_off, best_on, last_on_stages): best dicts
        of metric -> value per mode, plus the last ON rep's recorded
        stage table (printed so a failing ON bound is localizable).
        One unmeasured warmup run first (the first invocation pays
        one-time compiles — without it, whichever mode runs first eats
        the skew); then interleaved reps, best per mode."""
        best = ({}, {}, {})
        on_stages = {}
        real, real_phase = qexplain.current, tracing.phase
        fn()  # warmup: compiles + allocator steady state
        for _ in range(areps):
            for mode in (0, 1, 2):
                if mode == 0:
                    # the timed sites (bind, interpreter_eval,
                    # result_materialize) are tracing.phase hooks
                    qexplain.current = lambda: None
                    tracing.phase = lambda *a, **k: tracing._NOOP_PHASE
                try:
                    if mode == 2:
                        with qexplain.analyzing() as actx:
                            vals = extract(fn())
                        on_stages = actx.to_dict()
                    else:
                        vals = extract(fn())
                finally:
                    qexplain.current, tracing.phase = real, real_phase
                for k, v in vals.items():
                    best[mode][k] = max(best[mode].get(k, 0.0), v)
        return best, on_stages

    def analyze_guard(label, bypass, off, on, floor_key):
        for metric, byp_v in bypass.items():
            off_v, on_v = off[metric], on[metric]
            ratio = off_v / byp_v if byp_v else 1.0
            check(f"{label}.{metric} ANALYZE-off within {a_max:.0%} of "
                  "no-hook", ratio >= 1.0 - a_max,
                  f"bypass={byp_v:.1f} off={off_v:.1f} ratio={ratio:.3f}")
            on_ratio = on_v / byp_v if byp_v else 1.0
            check(f"{label}.{metric} ANALYZE-on within {a_on_max:.0%}",
                  on_ratio >= 1.0 - a_on_max,
                  f"on={on_v:.1f} ratio={on_ratio:.3f}")
        floor = baselines.get(floor_key)
        head = next(iter(off.values()))
        if floor:
            check(f"{label} ANALYZE-off beats recorded baseline",
                  head >= floor, f"off={head:.1f} floor={floor:.1f}")

    print("== promql_plan_agg (ANALYZE off vs no-hook vs on) ==")
    (p_bypass, p_off, p_on), p_stages = analyze_series(
        bench.bench_promql_plan_agg,
        lambda r: {"dps": float(r["value"])})
    analyze_guard("promql_plan_agg", p_bypass, p_off, p_on,
                  "promql_plan_agg")
    print(f"  ON-mode stage table: {json.dumps(p_stages)}")

    print("== index_fetch_tagged (ANALYZE off vs no-hook vs on) ==")
    (i_bypass, i_off, i_on), _ = analyze_series(
        bench.bench_index_fetch_tagged,
        lambda r: {"warm_qps": float(r["value"])})
    analyze_guard("index_fetch_tagged", i_bypass, i_off, i_on,
                  "index_fetch_tagged")

    # ---- Compute-fault guard seam (parallel/guard.dispatch): the
    # breaker-gated dispatch indirection on every accelerated route.
    # Faults-off, a dispatch is: one registry lookup, one allow() under
    # the breaker lock, the seam call, record_success, two cached
    # Counter.incs. BYPASS monkeypatches guard.dispatch to call the
    # primary directly — the pre-guard code path to within one function
    # call — so OFF/BYPASS isolates exactly the seam tax. Bounded at
    # GUARD_SEAM_MAX_REGRESSION (default 3%, the acceptance criterion)
    # on promql_plan_agg (compiled plan dispatch + temporal guarded
    # builders per invocation) and counter_gauge_rollup (the aggregator
    # flush tier — host-exact moments cross NO guarded dispatch on the
    # single-device steady state, so this one is the no-accidental-
    # coupling control, same role as index_fetch_tagged in the ANALYZE
    # section), plus the recorded baseline floors.
    from m3_tpu.parallel import guard as pguard

    # 3 reps, not the section default of 2: the seam tax being measured
    # is ~one dispatch per query, far below this bench's run-to-run
    # noise, so best-of needs one more draw per mode to damp it.
    greps = int(os.environ.get("GUARD_SEAM_REPS", "3"))
    g_max = float(os.environ.get("GUARD_SEAM_MAX_REGRESSION", "0.03"))
    # The coupling control runs IDENTICAL code in both modes (zero
    # guarded dispatches on its path), so its bound is a pathology
    # backstop against accidental coupling, not a seam-tax measurement
    # — same split as the ANALYZE section's loose ON bound. A 3% gate
    # on a pure-noise comparison would flap (counter_gauge_rollup shows
    # >10% rep-to-rep spread on busy containers).
    g_ctl_max = float(
        os.environ.get("GUARD_SEAM_CONTROL_MAX_REGRESSION", "0.10"))

    def guard_series(fn, extract):
        best = ({}, {})
        real = pguard.dispatch

        def direct(route, primary, fallback, **kw):
            return primary()

        fn()  # warmup: compiles + allocator steady state
        for _ in range(greps):
            for mode in (0, 1):
                if mode == 0:
                    pguard.dispatch = direct
                try:
                    vals = extract(fn())
                finally:
                    pguard.dispatch = real
                for k, v in vals.items():
                    best[mode][k] = max(best[mode].get(k, 0.0), v)
        return best

    def guard_seam_guard(label, bypass, off, floor_key, bound=None):
        bnd = g_max if bound is None else bound
        for metric, byp_v in bypass.items():
            off_v = off[metric]
            ratio = off_v / byp_v if byp_v else 1.0
            check(f"{label}.{metric} guard seam within {bnd:.0%} of "
                  "direct dispatch", ratio >= 1.0 - bnd,
                  f"bypass={byp_v:.1f} off={off_v:.1f} ratio={ratio:.3f}")
        floor = baselines.get(floor_key)
        head = next(iter(off.values()))
        if floor:
            check(f"{label} guarded beats recorded baseline",
                  head >= floor, f"off={head:.1f} floor={floor:.1f}")

    print("== promql_plan_agg (guard seam vs direct dispatch) ==")
    g_bypass_p, g_off_p = guard_series(
        bench.bench_promql_plan_agg,
        lambda r: {"dps": float(r["value"])})
    guard_seam_guard("promql_plan_agg", g_bypass_p, g_off_p,
                     "promql_plan_agg")

    print("== counter_gauge_rollup (guard seam vs direct dispatch) ==")
    g_bypass_c, g_off_c = guard_series(
        bench.bench_counter_gauge,
        lambda r: {"dps": float(r["value"])})
    guard_seam_guard("counter_gauge_rollup", g_bypass_c, g_off_c,
                     "counter_gauge_rollup", bound=g_ctl_max)

    out = {
        "index_fetch_tagged": {"off": off, "on": on},
        "write_path_ingest": {"off": off_w, "on": on_w},
        "verify_hot_set_read": {"off": v_off, "on": v_on},
        "analyze_promql_plan_agg": {
            "bypass": p_bypass, "off": p_off, "on": p_on},
        "analyze_index_fetch_tagged": {
            "bypass": i_bypass, "off": i_off, "on": i_on},
        "guard_promql_plan_agg": {"bypass": g_bypass_p, "off": g_off_p},
        "guard_counter_gauge_rollup": {
            "bypass": g_bypass_c, "off": g_off_c},
    }
    print(json.dumps(out, indent=1))
    print(f"obs overhead guard: {len(failures)} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
