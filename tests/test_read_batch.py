"""The embedded read path's batched cold read (storage/read_batch.py):
its answers are Shard.read's bit for bit, its cold rows cost a dispatch a
geometry, and a store larger than the block cache's budget evicts,
re-admits and still answers exactly."""

import threading

import numpy as np
import pytest

from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.ops import decode_rows as rows_mod
from m3_tpu.parallel import scope as dscope
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.query.model import Matcher, MatchType
from m3_tpu.query.storage import LocalStorage
from m3_tpu.storage import block as block_mod
from m3_tpu.storage import block_cache
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.storage.read_batch import read_many
from m3_tpu.utils import tracing, xtime
from m3_tpu.utils.hbm import HBMBudget
from m3_tpu.utils.instrument import ROOT

NS = b"deep"
BLOCK = 10 * xtime.MINUTE
STEP = 30 * xtime.SECOND
T0 = 1_600_000_000 * 10**9
T0 -= T0 % BLOCK
SEALED = 5


class Store:
    """`SEALED` sealed blocks and an open buffer over 4 shards. `late-*`
    series start in block 2; `gap-*` skip block 1; the last sealed
    block's last scrape is written AGAIN after the seal with another
    value, so a sealed block and the buffer hold the same timestamps."""

    def __init__(self, n_series=40, seed=5):
        self.now = T0
        self.db = Database(ShardSet(4), clock=lambda: self.now)
        self.db.mark_bootstrapped()
        opts = NamespaceOptions(block_size_ns=BLOCK,
                                buffer_past_ns=BLOCK + xtime.MINUTE,
                                writes_to_commitlog=False)
        self.db.create_namespace(NS, opts, index=NamespaceIndex(
            opts.index_block_size_ns, clock=lambda: self.now))
        self.ids = [b"s-%02d" % i for i in range(n_series)] \
            + [b"late-%d" % i for i in range(6)] \
            + [b"gap-%d" % i for i in range(6)]
        self.tags = {sid: {b"__name__": b"m", b"id": sid} for sid in self.ids}
        rng = np.random.default_rng(seed)
        per = BLOCK // STEP
        for k in range(SEALED * per + 4):
            t = T0 + k * STEP
            self.now = t
            rows = [sid for sid in self.ids
                    if not (sid.startswith(b"late") and t < T0 + 2 * BLOCK)
                    and not (sid.startswith(b"gap")
                             and T0 + BLOCK <= t < T0 + 2 * BLOCK)]
            self.write(rows, t, rng)
        # seal every full block, keeping the buffer's newest
        self.db.tick(T0 + (SEALED + 1) * BLOCK + 2 * xtime.MINUTE)
        self.now = T0 + SEALED * BLOCK + 4 * STEP
        # rewrite a sealed timestamp: the buffer's value must win
        self.dup_t = T0 + SEALED * BLOCK - STEP
        self.write(self.ids[:10], self.dup_t, rng)
        self.end = self.now + STEP
        self.ns = self.db.namespace(NS)

    def write(self, rows, t, rng):
        self.db.write_batch(
            NS, rows, np.full(len(rows), t, np.int64),
            rng.integers(0, 1000, len(rows)).astype(np.float64),
            [self.tags[sid] for sid in rows])

    def per_row(self, sid, start, end):
        shard = self.ns.shards[self.db.shard_set.lookup(sid)]
        return shard.read(sid, start, end)

    def batched(self, ids, start, end, acc=None):
        return read_many(self.ns, self.db.shard_set, ids, start, end, acc)


@pytest.fixture()
def cache(monkeypatch):
    """A block cache of the test's own over a budget of its own."""
    def make(limit_bytes, admit_after=2):
        c = block_cache.DeviceBlockCache(
            budget=HBMBudget(limit_bytes), admit_after=admit_after,
            scope=ROOT.sub_scope("test.read_batch.cache"))
        monkeypatch.setitem(dscope.DEFAULT._owned, "block_cache", c)
        return c
    return make


@pytest.fixture(scope="module")
def store():
    return Store()


RANGES = {
    "all": (0, (SEALED + 1) * BLOCK),
    "first-block-only": (0, BLOCK),
    "inside-two-blocks": (BLOCK + 3 * STEP, 3 * BLOCK - 2 * STEP),
    "buffer-only": (SEALED * BLOCK, (SEALED + 1) * BLOCK),
    "before-the-data": (-3 * BLOCK, -BLOCK),
}


def assert_same(store, ids, start, end, acc=None):
    got = store.batched(ids, start, end, acc)
    assert len(got) == len(ids)
    for sid, (tags, t, v) in zip(ids, got):
        wt, wv = store.per_row(sid, start, end)
        assert t.dtype == wt.dtype and v.dtype == wv.dtype
        np.testing.assert_array_equal(t, wt)
        np.testing.assert_array_equal(v, wv)
        assert tags == store.tags.get(sid)
    return got


@pytest.mark.parametrize("name", sorted(RANGES))
def test_batched_read_equals_the_per_row_read(store, cache, name):
    """Misses only (nothing admitted: admit_after is out of reach)."""
    cache(1 << 30, admit_after=10**9)
    lo, hi = RANGES[name]
    assert_same(store, store.ids, T0 + lo, T0 + hi)


def test_hits_and_misses_mix_in_one_fetch(store, cache):
    c = cache(1 << 30, admit_after=2)
    ids = store.ids
    start, end = T0, store.end
    assert_same(store, ids[:2], start, T0 + BLOCK)     # touches of block 0
    assert_same(store, ids[:2], start, T0 + BLOCK)
    assert c.wait_filled() and c.stats()["admitted"] > 0
    hits0, misses0 = c.stats()["hits"], c.stats()["misses"]
    # block 0 of those shards is resident, no other block has been
    # touched: hits and misses in one fetch
    got = store.batched(ids, start, end)
    assert c.stats()["hits"] > hits0 and c.stats()["misses"] > misses0
    for sid, (_tags, t, v) in zip(ids, got):
        wt, wv = store.per_row(sid, start, end)
        np.testing.assert_array_equal(t, wt)
        np.testing.assert_array_equal(v, wv)


def test_a_lone_miss_is_one_dispatch_and_never_a_one_row_program(
        store, cache, monkeypatch):
    cache(1 << 30, admit_after=10**9)
    shapes = []
    real = rows_mod._call

    def spy(words, *args):
        shapes.append(np.shape(words))
        return real(words, *args)

    monkeypatch.setattr(rows_mod, "_call", spy)
    sid = store.ids[3]
    assert_same(store, [sid], T0 + BLOCK, T0 + 2 * BLOCK)
    # the batched read's one dispatch, then the per-row read's
    assert [s[0] for s in shapes] == [block_mod.ROW_BUCKETS[0]] * 2


def test_cold_rows_cost_a_dispatch_a_geometry_not_a_pair(store, cache,
                                                         monkeypatch):
    cache(1 << 30, admit_after=10**9)
    tracer = tracing.Tracer(sample_rate=1.0)
    monkeypatch.setattr(tracing, "TRACER", tracer)  # decode_plane's phases
    with tracer.background_span("query.fetch") as sp:
        assert sp.detailed
        store.batched(store.ids, T0, store.end, sp)
    costs = sp.to_dict()["costs"]
    pairs = costs["block_n"]
    assert pairs > 4 * len(store.ids)           # several blocks a series
    assert costs["cold_rows_n"] == pairs
    # the store's blocks share at most a few geometries (window x width)
    geometries = {(b.window, np.shape(b.words)[-1])
                  for sh in store.ns.shards.values()
                  for b in sh.blocks.values()}
    assert 1 <= costs["cold_dispatch_n"] <= len(geometries)
    # each dispatch is one decode_plane call, its stretches on the span,
    # and a call is one upload and one fetch
    calls = costs["cold_dispatch_n"]
    assert all(costs[k + "_n"] == calls for k in ANATOMY)
    assert costs["fetch_n"] == costs["upload_n"] == calls
    assert 0 < costs["device_wait_ns"] + costs["d2h_ns"] + costs["layout_ns"] \
        <= costs["cold_decode_ns"]


ANATOMY = ("h2d", "launch", "device_wait", "d2h", "layout")


def test_a_cold_fetch_gathers_a_tile_in_four_array_operations(store, cache):
    """Nothing admitted: every (series, block) row is gathered cold. The
    points are the per-row read's bit for bit, and the gathers are four
    a tile however many (shard, block) pieces a tile holds."""
    cache(1 << 30, admit_after=10**9)
    tracer = tracing.Tracer(sample_rate=1.0)
    before = {k: ROOT.counter("storage.tiles." + k).value()
              for k in ("gathers", "rows")}
    with tracer.background_span("query.fetch") as sp:
        got = assert_same(store, store.ids, T0, store.end, sp)
    for sid, (_, t, v) in zip(store.ids, got):
        wt, wv = store.per_row(sid, T0, store.end)
        assert t.tobytes() == wt.tobytes() and v.tobytes() == wv.tobytes()
    costs = sp.to_dict()["costs"]
    keys = {(bs, b.window, int(b.time_unit), np.shape(b.words)[-1])
            for sh in store.ns.shards.values() for bs, b in sh.blocks.items()}
    pieces = sum(len(sh.blocks) for sh in store.ns.shards.values())
    assert len(keys) < pieces and costs["cold_rows_n"] > pieces
    assert costs["tile_gathers_n"] == 4 * len(keys)
    assert ROOT.counter("storage.tiles.gathers").value() \
        - before["gathers"] == costs["tile_gathers_n"]
    assert ROOT.counter("storage.tiles.rows").value() - before["rows"] \
        == costs["cold_rows_n"]


def test_an_admission_is_one_call_with_all_five_stretches(store, cache,
                                                          monkeypatch):
    """A full cache admits one block a fetch on the fetch's own thread:
    the whole-block decode is a decode_plane call like the cold rows',
    with the same five stretches, one upload and one fetch, and the
    planes the cache keeps of it are read-only and C-contiguous."""
    blocks = [b for sh in store.ns.shards.values() for b in sh.blocks.values()]
    one = max(block_cache.plane_bytes(b) for b in blocks)
    c = cache(2 * one + one // 2, admit_after=1)      # room for two
    store.batched(store.ids, T0, store.end)           # fills it
    assert c.wait_filled()
    tracer = tracing.Tracer(sample_rate=1.0)
    monkeypatch.setattr(tracing, "TRACER", tracer)
    admitted0 = c.stats()["admitted"]
    with tracer.background_span("query.fetch") as sp:
        store.batched(store.ids, T0, store.end, sp)
    assert c.stats()["admitted"] == admitted0 + 1     # inline, by this fetch
    costs = sp.to_dict()["costs"]
    calls = costs["cold_dispatch_n"] + 1
    assert all(costs[k + "_n"] == calls and costs[k + "_ns"] > 0
               for k in ANATOMY)
    assert costs["fetch_n"] == costs["upload_n"] == calls
    with c._lock:
        kept = [e.decoded for e in c._entries.values()
                if e.decoded is not None]
    assert kept
    for ts, vals in kept:
        for plane in (ts, vals):
            assert plane.flags.c_contiguous and not plane.flags.writeable
        assert ts.dtype == np.int64 and vals.dtype == np.float64


def test_unknown_and_unheld_ids(store, cache):
    cache(1 << 30)
    got = store.batched([b"nobody", store.ids[0]], T0, store.end)
    tags, t, v = got[0]
    assert tags is None and len(t) == 0 and len(v) == 0
    assert len(got[1][1])
    # a shard this namespace does not hold leaves no row
    shard_id = store.db.shard_set.lookup(store.ids[1])
    shards = dict(store.ns.shards)
    del shards[shard_id]

    class Held:
        opts = store.ns.opts

    Held.shards = shards
    assert read_many(Held, store.db.shard_set, [store.ids[1]], T0,
                     store.end) == [None]


def test_duplicates_across_a_sealed_block_and_the_buffer(store, cache):
    cache(1 << 30)
    ids = store.ids[:10]
    got = assert_same(store, ids, T0, store.end)
    for (_tags, t, v), sid in zip(got, ids):
        at = np.flatnonzero(t == store.dup_t)
        assert len(at) == 1                      # one point a timestamp
        shard = store.ns.shards[store.db.shard_set.lookup(sid)]
        bt, bv = shard.buffer.read(shard.registry.get(sid), T0, store.end)
        assert v[at[0]] == bv[bt == store.dup_t][0]   # the buffer's wins


def test_local_storage_fetch_goes_through_it(store, cache):
    cache(1 << 30)
    ls = LocalStorage(store.db, NS)
    got = ls.fetch_raw([Matcher(MatchType.EQUAL, b"__name__", b"m")],
                       T0, store.end)
    assert set(got) == set(store.ids)
    for sid, entry in got.items():
        wt, wv = store.per_row(sid, T0, store.end)
        np.testing.assert_array_equal(entry["t"], wt)
        np.testing.assert_array_equal(entry["v"], wv)
        assert entry["tags"] == store.tags[sid]


def test_a_store_larger_than_the_budget_evicts_readmits_and_answers(
        store, cache):
    """More generations than the budget holds: planes are admitted while
    there is room (by the fill thread), then one a fetch, least recently
    used first out; every answer stays exact, and a block that was
    evicted comes back."""
    blocks = [b for sh in store.ns.shards.values() for b in sh.blocks.values()]
    one = max(block_cache.plane_bytes(b) for b in blocks)
    c = cache(3 * one + one // 2, admit_after=2)      # room for three
    ids = store.ids
    rng = np.random.default_rng(11)
    seen_resident = set()
    for _ in range(30):
        pick = [ids[i] for i in rng.choice(len(ids), 6, replace=False)]
        assert_same(store, pick, T0, store.end)
        assert c.wait_filled()
        with c._lock:
            resident = {g for g, e in c._entries.items()
                        if e.decoded is not None}
        assert c.resident_bytes() <= c.budget.limit
        seen_resident |= resident
    st = c.stats()
    assert st["evictions"] > 0
    assert st["admitted"] - st["evictions"] == len(resident) > 0
    assert len(seen_resident) > 3       # the cache turned over
    assert st["hits"] > 0 and st["misses"] > 0
    # a full sweep over everything still answers exactly
    assert_same(store, ids, T0, store.end)


def test_while_there_is_room_the_fill_thread_admits_not_the_fetch(
        store, cache, monkeypatch):
    """A restarted node's first fetches: the blocks they touch twice are
    decoded whole by the cache's own thread; the fetch's thread decodes
    the rows it wants in one dispatch and nothing else."""
    c = cache(1 << 30, admit_after=2)
    whole = []
    real = block_mod.SealedBlock._decode_plane

    def spy(self, encoded=None):
        whole.append(threading.current_thread().name)
        return real(self, encoded)

    monkeypatch.setattr(block_mod.SealedBlock, "_decode_plane", spy)
    assert_same(store, store.ids, T0, store.end)    # rows >= 2 a block
    assert c.wait_filled()
    assert whole and set(whole) == {block_cache.FILL_THREAD_NAME}
    st = c.stats()
    assert st["admitted"] == len(whole) and st["evictions"] == 0
    hits0 = st["hits"]
    assert_same(store, store.ids, T0, store.end)    # now from the planes
    assert c.stats()["hits"] > hits0
    assert c.stats()["admitted"] == len(whole)


def test_no_more_is_queued_than_fits_and_a_full_cache_admits_one_a_fetch(
        store, cache, monkeypatch):
    blocks = [b for sh in store.ns.shards.values() for b in sh.blocks.values()]
    one = max(block_cache.plane_bytes(b) for b in blocks)
    c = cache(2 * one + one // 2, admit_after=1)      # room for two
    started = threading.Event()
    release = threading.Event()
    real = block_mod.SealedBlock._decode_plane

    def held(self, encoded=None):
        if threading.current_thread().name == block_cache.FILL_THREAD_NAME:
            started.set()
            assert release.wait(30)
        return real(self, encoded)

    monkeypatch.setattr(block_mod.SealedBlock, "_decode_plane", held)
    store.batched(store.ids, T0, store.end)     # touches every block
    assert started.wait(30)
    with c._lock:
        queued = len(c._fill) + 1
        assert 2 <= queued < len(blocks)
        assert c._fill_bytes <= c.budget.limit < c._fill_bytes + one
    # what the queue has promised is room no more: this fetch admits the
    # one hottest itself, on its own thread
    admitted0 = c.stats()["admitted"]
    store.batched(store.ids, T0, store.end)
    assert c.stats()["admitted"] == admitted0 + 1
    release.set()
    assert c.wait_filled()
    assert c.resident_bytes() <= c.budget.limit and c._fill_bytes == 0
    assert c.stats()["admitted"] == queued + 1
    assert_same(store, store.ids, T0, store.end)


def test_a_block_dropped_while_it_waits_for_the_fill_thread_is_not_pinned(
        store, cache, monkeypatch):
    c = cache(1 << 30, admit_after=1)
    release = threading.Event()
    real = block_mod.SealedBlock._decode_plane

    def held(self, encoded=None):
        assert release.wait(30)
        return real(self, encoded)

    monkeypatch.setattr(block_mod.SealedBlock, "_decode_plane", held)
    store.batched(store.ids[:4], T0, T0 + BLOCK)
    with c._lock:
        waiting = [b.gen for b in c._fill] + sorted(c._decoding)
    assert waiting
    for gen in waiting:
        c.invalidate(gen)
    release.set()
    assert c.wait_filled()
    assert len(c) == 0 and c.resident_bytes() == 0
    assert c.stats()["admitted"] == 0


def test_the_fill_thread_stands_back_while_a_request_is_served(
        store, cache, monkeypatch):
    from m3_tpu.utils import foreground

    monkeypatch.setattr(block_cache, "FILL_STANDS_BACK_S", 30.0)
    c = cache(1 << 30, admit_after=1)
    with foreground.serving:
        assert not foreground.wait_quiet(0.01)
        store.batched(store.ids, T0, store.end)
        with c._lock:
            assert c._fill and c._filler is not None
        assert not c.wait_filled(0.2) and c.stats()["admitted"] == 0
    assert foreground.wait_quiet(0.01)
    assert c.wait_filled() and c.stats()["admitted"] > 0
    # and never for long: a server that is never quiet still fills
    monkeypatch.setattr(block_cache, "FILL_STANDS_BACK_S", 0.01)
    c = cache(1 << 30, admit_after=1)
    with foreground.serving:
        store.batched(store.ids, T0, store.end)
        assert c.wait_filled() and c.stats()["admitted"] > 0
