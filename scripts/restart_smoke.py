#!/usr/bin/env python
"""Seeded kill -9 restart smoke: the check_all tier for crash-safe
columnar recovery (testing/scenario.py KillRestartScenario). ONE seeded
drill runs a REAL dbnode child process (WRITE_WAIT commit log,
background mediator flushing + snapshotting, bootstrap chain on
startup) under seeded open-loop write load, SIGKILLs it at a seeded
point mid-window (the mediator runs every 100ms, so the kill lands
mid-flush/mid-snapshot/mid-commitlog-stream), injects deterministic
crash residue (a torn half-chunk on the WAL tail + a checkpoint-less
fileset), restarts over the same data dir, and asserts:

  1. zero lost acked writes: every write the client saw acked is served
     after restart + bootstrap, value-exact;
  2. zero fabrication: everything the node serves is a write the drill
     attempted (torn/corrupt bytes never surface as data);
  3. bounded restart: child-reported bootstrap time AND full
     exec-to-listening wall stay under the budget.

The full matrix (4+ seeds, namespace-migration and out-of-order
backfill variants riding the same-start merge, batched-vs-_ref replay
bit-identity, corruption fuzz subsets) lives in tests/test_durability.py;
the open-ended campaign is scripts/fuzz_durability.py.

Usage: python scripts/restart_smoke.py [--seed N]
Wall budget: RESTART_SMOKE_BUDGET_S (default 10 seconds).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The drill's parent side is pure host work and its children are
# separate processes (a chip belongs to one process): all on the CPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="seeded kill -9 restart smoke")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    budget_s = float(os.environ.get("RESTART_SMOKE_BUDGET_S", "10.0"))
    t_start = time.monotonic()

    from m3_tpu.testing.scenario import (KillRestartOptions,
                                         KillRestartScenario)

    sc = KillRestartScenario(KillRestartOptions(
        seed=args.seed, restart_budget_s=budget_s))
    try:
        res = sc.verify(sc.run())
    finally:
        sc.close()

    assert res.acked_points > 0, "drill acked nothing"
    assert res.verified_points == res.acked_points
    assert res.torn_tail_bytes > 0, "torn-tail injection never happened"
    restart_wall = res.restart_walls_s[-1]
    bootstrap_s = res.bootstrap_s[-1]
    print(f"restart smoke: seed={args.seed} acked={res.acked_points} "
          f"verified={res.verified_points} "
          f"recovered_series={res.recovered_series[-1]} "
          f"restart_wall={restart_wall:.2f}s bootstrap={bootstrap_s:.3f}s "
          f"torn_tail_bytes={res.torn_tail_bytes}")

    elapsed = time.monotonic() - t_start
    assert elapsed <= budget_s, (
        f"restart smoke took {elapsed:.1f}s > budget {budget_s}s "
        f"(RESTART_SMOKE_BUDGET_S to override)")
    print(f"RESTART SMOKE PASS ({elapsed:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
