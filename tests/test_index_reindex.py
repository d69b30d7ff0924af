"""A series is indexed in every index block it is written in (the
reference's entry.IndexedForBlockStart; ROADMAP B-m11): a query whose
range overlaps only a later index block than a series' first sighting
finds it, before a restart and after one, where the index segments the
flush persisted hold every series written in their block and give the
bootstrapped series their tags and their marks back."""

import numpy as np
import pytest

from m3_tpu.index import persist as idx_persist
from m3_tpu.index import query as iq
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.persist.fs import PersistManager
from m3_tpu.storage.bootstrap import BootstrapContext, BootstrapProcess
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.storage.series import NEVER_INDEXED
from m3_tpu.utils import xtime
from m3_tpu.utils.instrument import ROOT

NS = b"reindex"
INDEX_BLOCK = 4 * xtime.HOUR
BLOCK = 20 * xtime.MINUTE
T0 = 1_600_000_000 * 10**9
T0 -= T0 % INDEX_BLOCK
EARLY = T0 + INDEX_BLOCK - 30 * xtime.MINUTE      # in index block 0
LATE = T0 + INDEX_BLOCK + 10 * xtime.MINUTE       # in index block 1
IDS = [b"both-%d" % i for i in range(12)] + [b"early-only", b"late-only"]
TAGS = {sid: {b"__name__": b"m", b"id": sid, b"kind": sid.split(b"-")[0]}
        for sid in IDS}


def make_db(now, persist=None):
    db = Database(ShardSet(4), clock=lambda: now["t"])
    opts = NamespaceOptions(block_size_ns=BLOCK, retention_ns=2 * xtime.DAY,
                            buffer_past_ns=5 * xtime.MINUTE,
                            writes_to_commitlog=False)
    db.create_namespace(NS, opts, index=NamespaceIndex(
        opts.index_block_size_ns, clock=lambda: now["t"]))
    return db


def write(db, now, rows, t, tagged=True):
    now["t"] = t
    db.write_batch(NS, rows, np.full(len(rows), t, np.int64),
                   np.arange(len(rows), dtype=np.float64),
                   [TAGS[sid] for sid in rows] if tagged else None)


def found(db, start, end, q=None):
    return set(db.query_ids(NS, q or iq.AllQuery(), start, end))


@pytest.fixture()
def written():
    now = {"t": T0}
    db = make_db(now)
    db.mark_bootstrapped()
    write(db, now, [s for s in IDS if s != b"late-only"], EARLY)
    write(db, now, [s for s in IDS if s != b"early-only"], LATE, tagged=True)
    return db, now


def test_a_query_over_the_later_block_alone_finds_the_series(written):
    db, _now = written
    both = {s for s in IDS if s.startswith(b"both")}
    # a range inside index block 1 only
    got = found(db, LATE - xtime.MINUTE, LATE + xtime.MINUTE)
    assert got == both | {b"late-only"}
    # and inside index block 0 only
    assert found(db, EARLY - xtime.MINUTE, EARLY + xtime.MINUTE) \
        == both | {b"early-only"}
    # a matcher still matches in the later block: the document went whole
    assert found(db, LATE - xtime.MINUTE, LATE + xtime.MINUTE,
                 iq.new_term(b"kind", b"both")) == both


def test_a_boundary_costs_one_insert_a_series_and_then_one_integer_test(
        written):
    db, now = written
    ns = db.namespace(NS)
    reindexed = ROOT.counter("index.insert.reindexed")
    docs = len(ns.index.blocks[T0 + INDEX_BLOCK].mutable)
    before = reindexed.value()
    for k in range(1, 4):       # more scrapes in the same index block
        write(db, now, [s for s in IDS if s != b"early-only"],
              LATE + k * 10 * xtime.SECOND, tagged=False)
    assert reindexed.value() == before
    assert len(ns.index.blocks[T0 + INDEX_BLOCK].mutable) == docs == 13
    for shard in ns.shards.values():
        reg = shard.registry
        marks = reg._indexed[:len(reg)]
        for idx, mark in enumerate(marks.tolist()):
            want = T0 if reg.id_of(idx) == b"early-only" \
                else T0 + INDEX_BLOCK
            assert mark == want
        # nobody of a shard whose series were all written late is behind
        if b"early-only" not in reg.all_ids() and len(reg):
            assert reg.index_floor == T0 + INDEX_BLOCK


def test_a_series_without_tags_waits_for_them(written):
    db, now = written
    write(db, now, [b"both-0"], LATE + xtime.MINUTE, tagged=False)
    now["t"] = LATE + 2 * xtime.MINUTE
    db.write_batch(NS, [b"nameless"], np.array([now["t"]], np.int64),
                   np.array([1.0]), None)
    shard = db.namespace(NS).shards[db.shard_set.lookup(b"nameless")]
    idx = shard.registry.get(b"nameless")
    assert shard.registry._indexed[idx] == NEVER_INDEXED
    assert b"nameless" not in found(db, T0, LATE + xtime.HOUR)
    # its tags arrive with a later write: indexed where that write falls
    now["t"] = LATE + 3 * xtime.MINUTE
    db.write_batch(NS, [b"nameless"], np.array([now["t"]], np.int64),
                   np.array([2.0]), [{b"__name__": b"m", b"id": b"nameless"}])
    assert b"nameless" in found(db, LATE, LATE + xtime.HOUR)


def test_after_a_restart_the_later_block_still_finds_them(written, tmp_path):
    db, now = written
    persist = PersistManager(str(tmp_path / "data"))
    # both index blocks go cold, every data block seals and flushes
    now["t"] = T0 + 2 * INDEX_BLOCK + xtime.HOUR
    db.tick(now["t"])
    assert db.flush(persist, now["t"]) > 0
    assert idx_persist.list_segments(persist.root, NS) == [
        T0, T0 + INDEX_BLOCK]
    # the later block's segment holds every series written in it, whole
    seg = idx_persist.read_segment(persist.root, NS, T0 + INDEX_BLOCK)
    assert {d.id for d in seg._docs} == {s for s in IDS if s != b"early-only"}
    db.close()

    db2 = make_db(now)
    BootstrapProcess(
        chain=("filesystem", "uninitialized_topology"),
        ctx=BootstrapContext(persist=persist,
                             shard_lookup=db2.shard_set.lookup)).run(db2)
    both = {s for s in IDS if s.startswith(b"both")}
    assert found(db2, LATE - xtime.MINUTE, LATE + xtime.MINUTE) \
        == both | {b"late-only"}
    assert found(db2, EARLY - xtime.MINUTE, EARLY + xtime.MINUTE) \
        == both | {b"early-only"}
    ns2 = db2.namespace(NS)
    for shard in ns2.shards.values():
        reg = shard.registry
        assert reg.untagged == 0        # filesets carry no tags: the index's
        for idx in range(len(reg)):
            sid = reg.id_of(idx)
            assert reg.tags_of(idx) == TAGS[sid]
            want = T0 if sid == b"early-only" else T0 + INDEX_BLOCK
            assert reg._indexed[idx] == want
    # the data came back too
    t, v = db2.read(NS, b"both-3", T0, now["t"])
    assert t.tolist() == [EARLY, LATE]
    # a live write in a THIRD index block indexes the series there, once
    reindexed = ROOT.counter("index.insert.reindexed")
    before = reindexed.value()
    third = T0 + 2 * INDEX_BLOCK + xtime.HOUR
    write(db2, now, sorted(both), third, tagged=False)
    assert reindexed.value() - before == len(both)
    assert found(db2, third - xtime.MINUTE, third + xtime.MINUTE) == both
    write(db2, now, sorted(both), third + 10 * xtime.SECOND, tagged=False)
    assert reindexed.value() - before == len(both)
    db2.close()
