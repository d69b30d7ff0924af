"""Write-path insert queue + mesh-routed flush encode.

Covers the shard/index insert-queue rebuild (reference:
src/dbnode/storage/shard_insert_queue.go, storage/index/
index_insert_queue.go): sync read-your-write, async visible-after-one-
drain, shutdown drains, bounded-depth shedding via Backpressure, writes
racing tick/seal losing nothing, a 16-thread mixed new/known-series
hammer against the synchronous oracle, and the serving flush's
shard x time mesh encode being bit-identical to the single-device path
(parallel.ingest.flush_encode_prepared on the 8-device virtual mesh)."""

import threading

import numpy as np
import pytest

from m3_tpu.index import query as iq
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.parallel import ingest as par_ingest
from m3_tpu.parallel import scope as dscope
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.storage import block as storage_block
from m3_tpu.storage.block import encode_block, merge_same_start
from m3_tpu.storage.database import Database
from m3_tpu.storage.insert_queue import InsertGroup, InsertQueue
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.storage.shard import Shard, ShardOptions
from m3_tpu.utils import xtime
from m3_tpu.utils.health import Priority
from m3_tpu.utils.limits import Backpressure

S = 1_000_000_000
T0 = 1_700_000_000 * S
BLOCK = 2 * xtime.HOUR


def make_db(num_shards=8, clock=None, **ns_opts):
    clock = clock or (lambda: T0)
    db = Database(ShardSet(num_shards), clock=clock)
    db.create_namespace(b"default", NamespaceOptions(**ns_opts),
                        index=NamespaceIndex(clock=clock))
    return db


def total_points(db, ids, start=T0 - xtime.DAY, end=T0 + xtime.DAY):
    return sum(len(db.read(b"default", sid, start, end)[0]) for sid in ids)


class TestQueueLifecycle:
    def test_sync_read_your_write(self):
        """Default mode: write_batch returns only after the queue drain —
        buffer, registry AND reverse index are all visible."""
        db = make_db()
        ids = [b"ryw-%d" % i for i in range(20)]
        tags = [{b"app": b"ryw", b"n": b"%d" % i} for i in range(20)]
        db.write_batch(b"default", ids, np.full(20, T0, np.int64),
                       np.arange(20.0), tags=tags)
        for i in (0, 7, 19):
            t, v = db.read(b"default", ids[i], T0 - 1, T0 + 1)
            np.testing.assert_array_equal(v, [float(i)])
        assert sorted(db.query_ids(b"default", iq.new_term(b"app", b"ryw"))) \
            == sorted(ids)

    def test_async_visible_after_one_drain(self):
        db = make_db(write_new_series_async=True)
        ids = [b"async-%d" % i for i in range(10)]
        db.write_batch(b"default", ids, np.full(10, T0, np.int64),
                       np.ones(10), tags=[{b"app": b"async"}] * 10)
        # Not yet drained: reads miss, the queue holds the entries.
        assert total_points(db, ids) == 0
        ns = db.namespace(b"default")
        assert sum(s.insert_queue.pending() for s in ns.shards.values()) == 10
        assert db.query_ids(b"default", iq.new_term(b"app", b"async")) == []
        db.tick()  # tick drains before sealing
        assert total_points(db, ids) == 10
        assert sorted(db.query_ids(b"default", iq.new_term(b"app", b"async"))) \
            == sorted(ids)

    def test_shutdown_drains_queue(self):
        db = make_db(write_new_series_async=True)
        ids = [b"shut-%d" % i for i in range(8)]
        db.write_batch(b"default", ids, np.full(8, T0, np.int64),
                       np.ones(8), tags=[{b"app": b"shut"}] * 8)
        assert total_points(db, ids) == 0
        db.close()  # stop() drains even without a background thread
        assert total_points(db, ids) == 8
        assert sorted(db.query_ids(b"default", iq.new_term(b"app", b"shut"))) \
            == sorted(ids)

    def test_background_drainer(self):
        """start() opts into the reference's dedicated-drainer shape:
        async inserts become visible without any tick."""
        db = make_db(write_new_series_async=True)
        ns = db.namespace(b"default")
        sid = b"bg-series"
        shard = ns.shard_for(db.shard_set.lookup(sid))
        shard.insert_queue.start()
        try:
            db.write(b"default", sid, T0, 5.0, tags={b"app": b"bg"})
            deadline = threading.Event()
            for _ in range(200):
                if len(db.read(b"default", sid, T0 - 1, T0 + 1)[0]):
                    break
                deadline.wait(0.01)
            t, v = db.read(b"default", sid, T0 - 1, T0 + 1)
            np.testing.assert_array_equal(v, [5.0])
        finally:
            shard.insert_queue.stop()

    def test_rate_limited_drains_coalesce(self):
        """interval_ns bounds the drain rate: many inserts inside one
        interval coalesce into few batches, and nothing is lost."""
        applied = []
        q = InsertQueue(lambda groups: applied.extend(groups),
                        interval_ns=int(0.05 * 1e9))
        q.start()
        try:
            for i in range(20):
                q.insert(InsertGroup([b"rl-%d" % i], None), sync=False)
            q.stop()
        finally:
            q.stop()
        assert sum(len(g) for g in applied) == 20
        assert q.drains < 20  # coalesced, not one drain per insert

    def test_drain_error_propagates_to_sync_waiter(self):
        def boom(groups):
            raise RuntimeError("drain failed")

        q = InsertQueue(boom)
        with pytest.raises(RuntimeError, match="drain failed"):
            q.insert(InsertGroup([b"x"], None), sync=True)
        # The gate budget was still released — the queue is reusable.
        assert q.gate.depth() == 0

    def test_single_write_sync_and_known_fast_path(self):
        db = make_db()
        assert db.write(b"default", b"one", T0, 1.0, tags={b"a": b"b"}) is None
        t, v = db.read(b"default", b"one", T0 - 1, T0 + 1)
        np.testing.assert_array_equal(v, [1.0])
        # Second write takes the known-series fast path (no queue).
        ns = db.namespace(b"default")
        shard = ns.shard_for(db.shard_set.lookup(b"one"))
        drains_before = shard.insert_queue.drains
        db.write(b"default", b"one", T0 + S, 2.0)
        assert shard.insert_queue.drains == drains_before
        t, v = db.read(b"default", b"one", T0 - 1, T0 + 2 * S)
        np.testing.assert_array_equal(v, [1.0, 2.0])


class TestBackpressure:
    def opts(self, **kw):
        return ShardOptions(write_new_series_async=True,
                            insert_max_pending=10,
                            insert_high_watermark=0.75, **kw)

    def write_new(self, shard, tag, n, priority):
        ids = [b"%s-%d" % (tag, i) for i in range(n)]
        shard.write_batch(ids, np.full(n, T0, np.int64), np.ones(n), T0,
                          priority=priority)

    def test_bounded_depth_sheds_by_priority(self):
        """Seeded overload: BULK sheds at the high watermark, NORMAL at
        capacity, CRITICAL never — and a shed leaves depth untouched."""
        shard = Shard(0, self.opts())
        self.write_new(shard, b"a", 5, Priority.BULK)       # depth 5
        with pytest.raises(Backpressure):
            self.write_new(shard, b"b", 3, Priority.BULK)   # 8 > high 7.5
        assert shard.insert_queue.pending() == 5
        self.write_new(shard, b"c", 4, Priority.NORMAL)     # 9 <= 10
        with pytest.raises(Backpressure):
            self.write_new(shard, b"d", 2, Priority.NORMAL)  # 11 > 10
        self.write_new(shard, b"e", 2, Priority.CRITICAL)   # always admitted
        assert shard.insert_queue.pending() == 11
        assert shard.insert_queue.gate.shed == {"critical": 0, "normal": 2,
                                                "bulk": 3}
        shard.insert_queue.drain()
        assert shard.num_series() == 11
        assert shard.insert_queue.gate.depth() == 0

    def test_shed_batch_is_all_or_nothing(self):
        """A shed write_batch must not partially apply: the known-series
        rows of the rejected batch are NOT written either."""
        shard = Shard(0, self.opts())
        shard.write_batch([b"known"], np.array([T0]), np.array([1.0]), T0)
        shard.insert_queue.drain()
        before = len(shard.read(b"known", T0 - S, T0 + xtime.DAY)[0])
        self.write_new(shard, b"fill", 9, Priority.NORMAL)  # depth 9
        ids = [b"known", b"fresh-0", b"fresh-1"]
        with pytest.raises(Backpressure):
            shard.write_batch(ids, np.full(3, T0 + S, np.int64),
                              np.ones(3), T0, priority=Priority.NORMAL)
        assert len(shard.read(b"known", T0 - S, T0 + xtime.DAY)[0]) == before


class TestRacingTickSeal:
    def test_writes_racing_tick_lose_nothing(self):
        """Writers race a ticking clock across a seal boundary; every
        accepted sync write is readable afterwards."""
        now = {"t": T0}
        db = make_db(num_shards=4, clock=lambda: now["t"])
        written = []
        errs = []
        stop = threading.Event()

        def writer(k):
            i = 0
            try:
                while not stop.is_set():
                    sid = b"race-%d-%d" % (k, i)
                    t = now["t"]
                    try:
                        db.write_batch(b"default", [sid],
                                       np.array([t], np.int64),
                                       np.array([1.0]),
                                       tags=[{b"app": b"race"}])
                    except ValueError:
                        # The clock marched past the acceptance window
                        # between sampling and validating — a legitimate
                        # whole-batch rejection, nothing applied.
                        continue
                    written.append(sid)
                    i += 1
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        # March the clock over two seal boundaries while ticking.
        for step in range(20):
            now["t"] = T0 + step * (BLOCK // 4)
            db.tick()
        stop.set()
        for t in threads:
            t.join()
        db.close()
        db.tick(now["t"])
        assert not errs
        assert written
        # Every accepted write is readable (buffer or sealed block).
        missing = [sid for sid in written
                   if not len(db.read(b"default", sid,
                                      T0 - xtime.DAY, now["t"] + xtime.DAY)[0])]
        assert missing == []

    def test_same_start_reseal_merges(self):
        """A drain landing after its bucket sealed must MERGE into the
        existing block on the next tick, not overwrite it."""
        shard = Shard(0, ShardOptions())
        bs = (T0 // BLOCK) * BLOCK
        t1, t2 = bs + xtime.MINUTE, bs + 2 * xtime.MINUTE
        shard.write_batch([b"early"], np.array([t1], np.int64),
                          np.array([1.0]), t1)
        seal_at = bs + BLOCK + 11 * xtime.MINUTE
        shard.tick(seal_at)
        assert bs in shard.blocks and shard.blocks[bs].num_series == 1
        # Simulate the late drain: the write was accepted before the
        # boundary but its bucket re-materializes after the seal.
        idx, _ = shard.registry.get_or_create(b"late")
        shard.buffer.write_batch(np.array([idx], np.int32),
                                 np.array([t2], np.int64), np.array([2.0]))
        shard.tick(seal_at + xtime.MINUTE)
        blk = shard.blocks[bs]
        assert blk.num_series == 2  # merged, not overwritten
        t, v = shard.read(b"early", bs, bs + BLOCK)
        np.testing.assert_array_equal(v, [1.0])
        t, v = shard.read(b"late", bs, bs + BLOCK)
        np.testing.assert_array_equal(v, [2.0])

    def test_merge_same_start_last_wins(self, rng):
        """Direct merge contract: union of series; duplicate timestamps
        resolve to the later block's value."""
        w = 16
        ts = T0 + np.arange(w, dtype=np.int64)[None, :] * xtime.SECOND
        v1 = rng.standard_normal((1, w))
        v2 = rng.standard_normal((1, w))
        b1 = encode_block(T0, np.array([0], np.int32), ts, v1,
                          np.array([w], np.int32))
        b2 = encode_block(T0, np.array([0, 1], np.int32),
                          np.concatenate([ts, ts]),
                          np.concatenate([v2, v1 + 7.0]),
                          np.array([w, w], np.int32))
        merged = merge_same_start(b1, b2)
        np.testing.assert_array_equal(merged.series_indices, [0, 1])
        got_t, got_v = merged.read(0)
        np.testing.assert_array_equal(got_t, ts[0])
        np.testing.assert_allclose(got_v, v2[0])  # b2 wins duplicates
        got_t, got_v = merged.read(1)
        np.testing.assert_allclose(got_v, v1[0] + 7.0)


class TestHammerVsOracle:
    @pytest.mark.parametrize("async_mode", [False, True])
    def test_16_thread_hammer_matches_synchronous_oracle(self, async_mode):
        """16 threads hammer mixed new/known-series write_batches through
        the queue-enabled path; the final registry + index + buffer state
        must equal a single-threaded synchronous replay of the same
        logical writes. (id, t) pairs map to one deterministic value, so
        arrival order cannot change the converged state."""
        n_threads, ops = 16, 30
        pool = [b"hammer-%03d" % i for i in range(120)]
        tags = {sid: {b"app": b"hammer", b"mod": b"%d" % (i % 5)}
                for i, sid in enumerate(pool)}
        db = make_db(num_shards=4, write_new_series_async=async_mode)

        def value_of(sid, t):
            return float((hash((sid, t)) % 1000))

        all_writes = []
        lock = threading.Lock()
        errs = []

        def worker(k):
            rng = np.random.default_rng(1000 + k)
            try:
                for op in range(ops):
                    sel = rng.integers(0, len(pool), 20)
                    ids = [pool[j] for j in sel]
                    ts = np.asarray(
                        T0 - (rng.integers(0, 500, 20)) * S, np.int64)
                    vals = np.asarray([value_of(s, int(t))
                                       for s, t in zip(ids, ts)])
                    db.write_batch(b"default", ids, ts, vals,
                                   tags=[tags[s] for s in ids])
                    with lock:
                        all_writes.append((ids, ts, vals))
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        db.close()  # async mode: converge via the shutdown drain

        oracle = make_db(num_shards=4)
        for ids, ts, vals in all_writes:
            oracle.write_batch(b"default", ids, ts, vals,
                               tags=[tags[s] for s in ids])

        ns, ons = db.namespace(b"default"), oracle.namespace(b"default")
        # Registry state: same ids per shard.
        for sid_ in ns.shards:
            assert sorted(ns.shards[sid_].registry.all_ids()) == \
                sorted(ons.shards[sid_].registry.all_ids())
        # Index state: every tag query returns the oracle's id set.
        for mod in range(5):
            q = iq.new_conjunction(iq.new_term(b"app", b"hammer"),
                                   iq.new_term(b"mod", b"%d" % mod))
            assert db.query_ids(b"default", q) == \
                oracle.query_ids(b"default", q)
        # Buffer state: identical merged reads per series.
        touched = {s for ids, _, _ in all_writes for s in ids}
        for sid in sorted(touched):
            t_a, v_a = db.read(b"default", sid, T0 - xtime.DAY,
                               T0 + xtime.DAY)
            t_b, v_b = oracle.read(b"default", sid, T0 - xtime.DAY,
                                   T0 + xtime.DAY)
            np.testing.assert_array_equal(t_a, t_b)
            np.testing.assert_array_equal(v_a, v_b)


class TestMeshFlushEncode:
    def _dense(self, rng, s=32, w=64):
        ts = T0 + np.arange(w, dtype=np.int64)[None, :] * 10 * S \
            + np.zeros((s, 1), np.int64)
        vals = np.floor(rng.standard_normal((s, w)) * 100)
        return (np.arange(s, dtype=np.int32), ts, vals,
                np.full(s, w, np.int32))

    def test_mesh_encode_bit_identical_to_single_device(self, rng,
                                                        monkeypatch):
        """The serving flush's mesh-routed encode produces bit-identical
        words/nbits vs the single-device path, and the instrument counter
        proves the mesh path actually ran."""
        series, ts, vals, npts = self._dense(rng)
        counter = storage_block._FLUSH_METRICS.counter("mesh_encode")
        before = counter.value()
        assert par_ingest.flush_mesh() is not None  # 8-device virtual mesh
        mesh_blk = encode_block(T0, series, ts, vals, npts)
        assert counter.value() == before + 1
        # Single-device reference path.
        monkeypatch.setenv("M3_TPU_MESH_FLUSH", "0")
        dscope.DEFAULT.clear("flush_mesh")
        try:
            single_blk = encode_block(T0, series, ts, vals, npts)
            assert counter.value() == before + 1  # did NOT route
        finally:
            monkeypatch.undo()
            dscope.DEFAULT.clear("flush_mesh")
        np.testing.assert_array_equal(mesh_blk.words, single_blk.words)
        np.testing.assert_array_equal(mesh_blk.nbits, single_blk.nbits)
        np.testing.assert_array_equal(mesh_blk.npoints, single_blk.npoints)
        # And both decode to the original points.
        dt, dv, dn = mesh_blk.read_all()
        np.testing.assert_array_equal(dt, ts)
        np.testing.assert_array_equal(dv, vals)

    def test_tick_seal_routes_through_mesh(self, rng):
        """Shard._tick_locked's seal encode takes the mesh path when the
        padded tile divides the device count and clears the dispatch
        floor (32 series x 64 points = 2048 cells)."""
        shard = Shard(0, ShardOptions())
        bs = (T0 // BLOCK) * BLOCK
        ids = [b"mesh-%02d" % i for i in range(32)]
        base = bs + xtime.MINUTE
        for p in range(64):
            t = base + p * xtime.SECOND
            shard.write_batch(ids, np.full(32, t, np.int64),
                              np.arange(32.0) + p, t)
        counter = storage_block._FLUSH_METRICS.counter("mesh_encode")
        before = counter.value()
        shard.tick(bs + BLOCK + 11 * xtime.MINUTE)
        assert counter.value() == before + 1
        t_r, v_r = shard.read(ids[5], bs, bs + BLOCK)
        np.testing.assert_array_equal(v_r, np.arange(64.0) + 5.0)
