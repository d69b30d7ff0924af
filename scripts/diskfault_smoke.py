#!/usr/bin/env python
"""Seeded disk-fault smoke: the check_all tier for the disk-fault plane
(testing/scenario.py DiskFaultScenario). ONE seeded drill runs an RF=3
in-process cluster where the victim node's persist tier sits behind a
seeded `testing.faultfs` plan, and asserts the whole loop:

  1. corruption detected at serve time: seeded bit-flips/short reads on
     the victim's cold filesets trip the row-checksum verification,
     the rotten filesets are quarantined (sidecar + counters), and
     replica coverage hides the damage (zero acked-write loss);
  2. scrub repairs: a DatabaseScrubber sweep with a ShardRepairer
     re-fetches quarantined blocks from the healthy peers,
     un-quarantines them, and the rewrite leaves the victim clean;
  3. full-disk degradation: an ENOSPC plan trips DiskHealth into the
     read-only posture (NORMAL writes shed typed Backpressure, CRITICAL
     and reads keep flowing) and the node auto-recovers once the fault
     clears;
  4. zero fabrication: every point any replica serves is a write the
     drill attempted — torn/corrupt bytes never surface as data.

The full matrix (injector determinism, quarantine round-trip, scrubber
scheduling, WAL typed ACK failures, 4+ seeds) lives in
tests/test_diskfault.py; the region-targeted bit-flip corpus is
scripts/fuzz_durability.py.

Usage: python scripts/diskfault_smoke.py [--seed N]
Wall budget: DISKFAULT_SMOKE_BUDGET_S (default 10 seconds).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The drill is pure host work: pin the CPU backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="seeded disk-fault smoke")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    budget_s = float(os.environ.get("DISKFAULT_SMOKE_BUDGET_S", "10.0"))
    t_start = time.monotonic()

    # Persist kernel compiles across runs: the drill's SLOs measure
    # serving under faults, not XLA compilation.
    from m3_tpu.utils import compile_cache

    compile_cache.configure()

    from m3_tpu.testing.scenario import (DiskFaultScenario,
                                         DiskFaultScenarioOptions)

    # duration_s trimmed from the 1.5s test default: the corruption is
    # caught by the deterministic cold-read sweeps, not the open-loop
    # window, so a shorter window buys budget without losing coverage.
    sc = DiskFaultScenario(DiskFaultScenarioOptions(
        seed=args.seed, duration_s=1.0))
    try:
        res = sc.verify(sc.run())
    finally:
        sc.close()

    assert res.verified_points > 0, "drill verified nothing"
    assert res.quarantined_after_faults >= 1, "corruption never quarantined"
    assert res.quarantined_after_scrub == 0, "scrub left quarantine behind"
    assert res.scrub_stats is not None and res.scrub_stats.blocks_repaired >= 1
    assert res.health_tripped and res.normal_shed and res.critical_served
    assert res.recovered, "node never recovered from the disk-full posture"
    print(f"diskfault smoke: seed={args.seed} "
          f"acked={len(res.ledger.acked())} "
          f"verified_points={res.verified_points} "
          f"filesets_verified={res.filesets_verified} "
          f"quarantined={res.quarantined_after_faults} "
          f"repaired={res.scrub_stats.blocks_repaired} "
          f"health_tripped={res.health_tripped} recovered={res.recovered}")

    elapsed = time.monotonic() - t_start
    assert elapsed <= budget_s, (
        f"diskfault smoke took {elapsed:.1f}s > budget {budget_s}s "
        f"(DISKFAULT_SMOKE_BUDGET_S to override)")
    print(f"DISKFAULT SMOKE PASS ({elapsed:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
