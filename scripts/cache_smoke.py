"""Device-block-cache smoke: the <5s check_all tier for the HBM-resident
read-serving hot tier (m3_tpu/storage/block_cache.py). Asserts, not just
times:

  1. warm hit-rate: a skewed hot-set read mix against sealed blocks must
     serve its warm passes from the cache (hit-rate floor) with results
     bit-identical to the cache-bypassed decode, and the seal must have
     RETAINED its encoded device buffers (forced on via
     M3_TPU_BLOCK_CACHE_RETAIN=1 so the adopt path runs on CPU hosts);
  2. eviction: under a tiny HBM budget (the in-process analog of
     M3_TPU_HBM_BUDGET_BYTES) reclaim actually evicts, stays bounded,
     and never changes read results;
  3. zero residency: namespace close drops every cached byte.

Usage: python scripts/cache_smoke.py   (CPU; wall budget overridable via
CACHE_SMOKE_BUDGET_S)
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Exercise the seal-time device-buffer retention path even on CPU hosts.
os.environ.setdefault("M3_TPU_BLOCK_CACHE_RETAIN", "1")

from m3_tpu.parallel import scope as dscope  # noqa: E402
from m3_tpu.parallel.sharding import ShardSet  # noqa: E402
from m3_tpu.storage import block_cache  # noqa: E402
from m3_tpu.storage.block_cache import DeviceBlockCache  # noqa: E402
from m3_tpu.storage.database import Database  # noqa: E402
from m3_tpu.storage.namespace import NamespaceOptions  # noqa: E402
from m3_tpu.utils import xtime  # noqa: E402
from m3_tpu.utils.hbm import HBMBudget  # noqa: E402

BLOCK = 2 * xtime.HOUR
T0 = (1_700_000_000 * 1_000_000_000 // BLOCK) * BLOCK


def build_db(n_series: int, n_blocks: int, ppb: int):
    now = {"t": T0}
    db = Database(ShardSet(num_shards=2), clock=lambda: now["t"])
    db.ensure_namespace(b"smoke", NamespaceOptions(
        index_enabled=False, snapshot_enabled=False,
        writes_to_commitlog=False))
    ids = [b"cs-%04d" % i for i in range(n_series)]
    step = BLOCK // ppb
    for s in range(n_blocks * ppb):
        t = T0 + s * step
        now["t"] = t
        db.write_batch(b"smoke", ids, np.full(n_series, t, np.int64),
                       np.full(n_series, float(s % 17)))
    now["t"] = T0 + n_blocks * BLOCK + 11 * xtime.MINUTE
    stats = db.tick()
    assert stats["sealed"] >= n_blocks, stats
    return db, ids


def main() -> int:
    t_start = time.perf_counter()
    rng = np.random.default_rng(71)

    # --- 1. warm hit-rate + bit-identity + seal retention -----------------
    cache = DeviceBlockCache(budget=HBMBudget(256 * 1024 * 1024),
                             admit_after=2)
    dscope.DEFAULT.put("block_cache", cache)
    db, ids = build_db(n_series=200, n_blocks=2, ppb=48)
    assert cache.stats()["retained"] >= 2, \
        f"seal did not retain encoded device buffers: {cache.stats()}"
    n_hot = 10
    hot = rng.permutation(len(ids))[:n_hot]
    mix = [int(hot[i % n_hot]) if rng.random() < 0.9
           else int(rng.integers(len(ids))) for i in range(300)]
    span = (T0, T0 + 2 * BLOCK)

    def run_mix():
        return [db.read(b"smoke", ids[i], *span) for i in mix]

    run_mix()  # cold pass: touches + admissions
    s0 = cache.stats()
    t_warm0 = time.perf_counter()
    warm = run_mix()
    warm_s = time.perf_counter() - t_warm0
    s1 = cache.stats()
    hits = s1["hits"] - s0["hits"]
    misses = s1["misses"] - s0["misses"]
    hit_rate = hits / max(hits + misses, 1)
    floor = float(os.environ.get("CACHE_SMOKE_HIT_RATE", "0.95"))
    assert hit_rate >= floor, \
        f"warm hit-rate {hit_rate:.2%} below floor {floor:.0%} ({s1})"
    sample = rng.integers(0, len(mix), 40)
    with block_cache.disabled():
        for j in sample:
            ut, uv = db.read(b"smoke", ids[mix[j]], *span)
            assert np.array_equal(ut, warm[j][0]) and \
                np.array_equal(uv, warm[j][1]), \
                "cached read diverged from uncached decode"

    # --- 2. eviction under a tiny budget ---------------------------------
    # Dedicated knob (NOT M3_TPU_HBM_BUDGET_BYTES): an environment sizing
    # the real budget must not defuse the smoke's eviction scenario.
    tiny_bytes = int(os.environ.get("CACHE_SMOKE_TINY_BYTES", "16384"))
    tiny = DeviceBlockCache(budget=HBMBudget(tiny_bytes), admit_after=1)
    dscope.DEFAULT.put("block_cache", tiny)
    for j in range(60):
        got = db.read(b"smoke", ids[mix[j]], *span)
        with block_cache.disabled():
            want = db.read(b"smoke", ids[mix[j]], *span)
        assert np.array_equal(want[0], got[0]) and \
            np.array_equal(want[1], got[1])
    ts = tiny.stats()
    assert ts["evictions"] >= 1, f"tiny budget never evicted: {ts}"
    assert tiny.resident_bytes() <= 64 * tiny_bytes, ts

    # --- 3. zero residency after namespace close -------------------------
    dscope.DEFAULT.put("block_cache", cache)
    run_mix()  # re-warm the main cache
    assert cache.stats()["bytes"] > 0
    db.close()
    cs = cache.stats()
    assert cs["bytes"] == 0 and cs["entries"] == 0, \
        f"residency survived namespace close: {cs}"

    total_s = time.perf_counter() - t_start
    print(f"CACHE SMOKE PASS: warm hit-rate {hit_rate:.0%} ({hits} hits), "
          f"retained {s1['retained']} seal buffers, "
          f"{ts['evictions']} evictions under a {tiny_bytes}B budget, "
          f"zero residency after close, warm pass {warm_s * 1e3:.1f}ms, "
          f"total {total_s:.1f}s")
    budget_s = float(os.environ.get("CACHE_SMOKE_BUDGET_S", "30"))
    assert total_s < budget_s, (
        f"smoke tier took {total_s:.1f}s (> {budget_s:.0f}s budget)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
