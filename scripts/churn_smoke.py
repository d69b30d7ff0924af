#!/usr/bin/env python
"""Seeded SLO-under-churn smoke: the check_all tier for the macro
scenario (testing/scenario.py). ONE seeded run composes every
production ingredient at once — an RF=3 cluster behind seeded faultnet
proxies, seeded open-loop mixed-priority load, and concurrent placement
churn (add-node -> peer-bootstrap, remove-node, replace-down-node, a
jittered repair sweep) — and asserts the hard SLOs:

  1. zero lost acked writes (full-coverage verification of the write
     ledger against quorum reads after convergence);
  2. zero shed CRITICAL traffic at any point;
  3. bounded p99 read/write latency for served requests;
  4. bounded RPC-gate and insert-queue depths;
  5. clean convergence: all placement shards AVAILABLE and every sealed
     block's row checksums replica-consistent after the final repair.

The full matrix (per-op scenarios, oracle properties, peer-death
re-plan, deadline-bounded bootstrap) lives in
tests/test_dtest_scenarios.py and tests/test_bootstrap_repair.py.

Usage: python scripts/churn_smoke.py [--seed N]
Wall budget: CHURN_SMOKE_BUDGET_S (default 60 seconds; the first run on
a cold machine pays one-time XLA kernel compiles, persisted to the JAX
compilation cache for subsequent runs).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="seeded SLO-under-churn smoke")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    budget_s = float(os.environ.get("CHURN_SMOKE_BUDGET_S", "60.0"))
    t_start = time.monotonic()

    # Persist kernel compiles across runs: the scenario's SLOs measure
    # serving, not XLA compilation.
    from m3_tpu.utils import compile_cache

    compile_cache.configure()

    from m3_tpu.testing.scenario import ChurnScenario, ChurnScenarioOptions

    sc = ChurnScenario(ChurnScenarioOptions(
        seed=args.seed, duration_s=2.5, base_rate=50))
    try:
        result = sc.verify(sc.run())
    finally:
        sc.close()

    rep = result.report
    total = len(rep.records)
    ok = len(rep.select(outcome="ok"))
    print(f"churn ops:        {result.churn_log}")
    print(f"requests served:  {ok}/{total} "
          f"(outcomes {result.outcome_counts()})")
    print(f"critical:         {result.outcome_counts('critical')} "
          "(zero shed asserted)")
    print(f"p99 write/read:   "
          f"{rep.quantile_latency(0.99, kind='write') * 1e3:.1f}ms / "
          f"{rep.quantile_latency(0.99, kind='read') * 1e3:.1f}ms")
    print(f"acked verified:   {result.verified_points} datapoints, zero lost")
    print(f"replica blocks:   {result.checksum_blocks_checked} "
          "checksum-consistent")
    print(f"gate depth:       {result.max_gate_depth}/{result.gate_capacity}"
          f"  insert-queue {result.max_queue_pending}/"
          f"{result.queue_capacity}")

    elapsed = time.monotonic() - t_start
    print(f"churn smoke OK in {elapsed:.1f}s (budget {budget_s:.0f}s)")
    if elapsed > budget_s:
        print(f"FAIL: smoke exceeded wall budget ({elapsed:.1f}s > "
              f"{budget_s:.0f}s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
