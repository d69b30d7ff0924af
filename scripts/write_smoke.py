"""Write-path smoke: the <5s check_all tier for the insert-queue write
path and the mesh-routed flush encode. Asserts, not just times:

  1. queue drain on shutdown — async-mode writes enqueued but never
     ticked are fully visible (registry + index + buffer) after close();
  2. zero lost writes under a seeded burst — concurrent mixed
     new/known-series writers racing a ticking clock across a seal
     boundary, every accepted datapoint readable afterwards and the
     reverse index holding exactly the written series;
  3. mesh-vs-single-device encode_block bit-equality on the virtual
     mesh — the serving flush's shard x time mesh path produces
     bit-identical words/nbits (and decode-equal points) vs the
     single-device encode, and the instrument counter proves the mesh
     path actually ran.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python scripts/write_smoke.py
(The mesh leg degrades to a skip note on a true single-device platform.)
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"

# Persistent compile cache: the seal/mesh encode shapes compile once per
# checkout, keeping warm runs inside the budget.
from m3_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from m3_tpu.index import query as iq  # noqa: E402
from m3_tpu.index.namespace_index import NamespaceIndex  # noqa: E402
from m3_tpu.parallel import ingest as par_ingest  # noqa: E402
from m3_tpu.parallel import scope as dscope  # noqa: E402
from m3_tpu.parallel.sharding import ShardSet  # noqa: E402
from m3_tpu.storage import block as storage_block  # noqa: E402
from m3_tpu.storage.database import Database  # noqa: E402
from m3_tpu.storage.namespace import NamespaceOptions  # noqa: E402
from m3_tpu.utils import xtime  # noqa: E402

S = 1_000_000_000
T0 = 1_700_000_000 * S
BLOCK = 2 * xtime.HOUR


def make_db(clock, **opts):
    db = Database(ShardSet(8), clock=clock)
    db.create_namespace(b"default", NamespaceOptions(**opts),
                        index=NamespaceIndex(clock=clock))
    return db


def check_shutdown_drain() -> str:
    db = make_db(lambda: T0, write_new_series_async=True)
    ids = [b"shutdown-%03d" % i for i in range(64)]
    db.write_batch(b"default", ids, np.full(64, T0, np.int64),
                   np.arange(64.0), tags=[{b"app": b"shutdown"}] * 64)
    ns = db.namespace(b"default")
    pending = sum(s.insert_queue.pending() for s in ns.shards.values())
    assert pending == 64, f"async writes should be queued, pending={pending}"
    db.close()
    left = sum(s.insert_queue.pending() for s in ns.shards.values())
    assert left == 0, f"close() left {left} queued inserts"
    for i in (0, 31, 63):
        t, v = db.read(b"default", ids[i], T0 - 1, T0 + 1)
        assert list(v) == [float(i)], f"{ids[i]} lost by shutdown drain"
    got = sorted(db.query_ids(b"default", iq.new_term(b"app", b"shutdown")))
    assert got == sorted(ids), "index missing shutdown-drained series"
    return f"shutdown drain: {len(ids)} queued inserts visible after close()"


def check_seeded_burst() -> str:
    rng = np.random.default_rng(int(os.environ.get("WRITE_SMOKE_SEED", "7")))
    now = {"t": T0}
    db = make_db(lambda: now["t"])
    pool = [b"burst-%04d" % i for i in range(200)]
    written = []
    wlock = threading.Lock()
    errs = []

    def writer(seed):
        trng = np.random.default_rng(seed)
        try:
            for _ in range(15):
                sel = trng.integers(0, len(pool), 16)
                ids = [pool[j] for j in sel]
                t_now = now["t"]
                ts = t_now - trng.integers(0, 500, 16) * S
                vals = ts.astype(np.float64) % 977
                try:
                    db.write_batch(b"default", ids,
                                   np.asarray(ts, np.int64), vals,
                                   tags=[{b"app": b"burst"}] * 16)
                except ValueError:
                    continue  # clock raced past the window: whole batch refused
                with wlock:
                    written.append((ids, ts, vals))
        except Exception as e:  # noqa: BLE001 — reported below
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(int(s),))
               for s in rng.integers(0, 2**31, 4)]
    for t in threads:
        t.start()
    # March the clock across one seal boundary while ticking, so drains
    # race tick/seal without compiling a fresh encode shape per step.
    for now_t in (T0, T0 + BLOCK // 3, T0 + 2 * (BLOCK // 3),
                  T0 + BLOCK + 11 * xtime.MINUTE):
        now["t"] = now_t
        db.tick()
    for t in threads:
        t.join()
    db.close()
    db.tick(now["t"])
    assert not errs, f"writer errors: {errs[:3]}"
    assert written, "no writes landed"
    # Oracle: last-wins per (id, t); values are t-derived so equal anyway.
    want = {}
    for ids, ts, vals in written:
        for sid, t, v in zip(ids, ts, vals):
            want.setdefault(sid, {})[int(t)] = float(v)
    # Materialize the database's full state batched: ONE read_all per
    # sealed block + raw buffer columns (a read() per series would pay a
    # one-row decode dispatch each — the smoke's budget is 5s).
    got = {}
    ns = db.namespace(b"default")
    for sh in ns.shards.values():
        for blk in sh.blocks.values():
            t_all, v_all, npts = blk.read_all()
            for row, sidx in enumerate(blk.series_indices.tolist()):
                d = got.setdefault(sh.registry.id_of(sidx), {})
                n = int(npts[row])
                d.update(zip(t_all[row, :n].tolist(),
                             v_all[row, :n].tolist()))
        for bucket in sh.buffer.buckets.values():
            sidx, ts_b, vs_b = bucket.cols.view()
            for si, tt, vv in zip(sidx.tolist(), ts_b.tolist(),
                                  vs_b.tolist()):
                got.setdefault(sh.registry.id_of(si), {})[tt] = vv
    lost = sum(1 for sid, points in want.items()
               for tt, vv in points.items()
               if got.get(sid, {}).get(tt) != vv)
    assert lost == 0, f"{lost} accepted datapoints lost under burst"
    got_ids = sorted(db.query_ids(b"default", iq.new_term(b"app", b"burst")))
    assert got_ids == sorted(want), "index series set != written series set"
    npoints = sum(len(p) for p in want.values())
    return (f"seeded burst: {len(written)} batches, {npoints} distinct "
            f"points across {len(want)} series, 0 lost, index exact")


def check_mesh_bit_equality(rng) -> str:
    if par_ingest.flush_mesh() is None:
        return "mesh encode: SKIPPED (single-device platform)"
    s, w = 32, 64
    ts = T0 + np.arange(w, dtype=np.int64)[None, :] * 10 * S + \
        np.zeros((s, 1), np.int64)
    vals = np.floor(rng.standard_normal((s, w)) * 100)
    series = np.arange(s, dtype=np.int32)
    npts = np.full(s, w, np.int32)
    counter = storage_block._FLUSH_METRICS.counter("mesh_encode")
    before = counter.value()
    mesh_blk = storage_block.encode_block(T0, series, ts, vals, npts)
    assert counter.value() == before + 1, "flush encode did not route mesh"
    os.environ["M3_TPU_MESH_FLUSH"] = "0"
    dscope.DEFAULT.clear("flush_mesh")
    try:
        single_blk = storage_block.encode_block(T0, series, ts, vals, npts)
    finally:
        del os.environ["M3_TPU_MESH_FLUSH"]
        dscope.DEFAULT.clear("flush_mesh")
    assert np.array_equal(mesh_blk.words, single_blk.words), \
        "mesh words != single-device words"
    assert np.array_equal(mesh_blk.nbits, single_blk.nbits), \
        "mesh nbits != single-device nbits"
    dt, dv, _ = mesh_blk.read_all()
    assert np.array_equal(dt, ts) and np.array_equal(dv, vals), \
        "mesh-encoded block does not decode to the written points"
    ndev = par_ingest.flush_mesh().devices.size
    return (f"mesh encode: bit-identical words/nbits across {ndev} devices "
            f"({s}x{w} tile), decode-equal")


def main() -> int:
    t_start = time.perf_counter()
    lines = [
        check_shutdown_drain(),
        check_seeded_burst(),
        check_mesh_bit_equality(np.random.default_rng(11)),
    ]
    total_s = time.perf_counter() - t_start
    for ln in lines:
        print("  " + ln)
    print(f"WRITE SMOKE PASS: total {total_s:.1f}s")
    # Nominal runtime is ~5s, dominated by XLA compiles of the mesh
    # encode + seal shapes (the storage work itself is <1s); the
    # generous overridable ceiling catches a real regression without
    # turning host contention into a flaky tier failure.
    budget_s = float(os.environ.get("WRITE_SMOKE_BUDGET_S", "60"))
    assert total_s < budget_s, (
        f"smoke tier took {total_s:.1f}s (> {budget_s:.0f}s budget)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
