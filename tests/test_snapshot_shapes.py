"""A snapshot compiles nothing for a bucket that holds only part of its
shard's series yet. The first snapshot after a block boundary meets a
bucket that the scrape in flight has only begun to fill; a row shape of
its own there is a compile of seconds inside the tick, for the encode
and, on the chip, for the bloom's device hash (PERF.md section 6, PR
34). The encode takes the shard's row shape; the bloom of a block of
another size than its shard hashes on the host."""

import os

import numpy as np
import pytest

from m3_tpu.ops import tsz
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.persist.fs import FilesetReader, PersistManager
from m3_tpu.storage import block as block_mod
from m3_tpu.storage.database import Database
from m3_tpu.storage.mediator import Mediator
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.utils import bloom as bloom_mod
from m3_tpu.utils import hashing, xtime

NS = b"default"
BLOCK = 2 * xtime.HOUR
T0 = 1_600_000_000 * xtime.SECOND - (1_600_000_000 * xtime.SECOND) % BLOCK
SERIES = 40


@pytest.fixture
def node(tmp_path, monkeypatch):
    now = {"t": T0 + xtime.MINUTE}
    db = Database(ShardSet(1), clock=lambda: now["t"])
    db.create_namespace(NS, NamespaceOptions(index_enabled=False))
    pm = PersistManager(os.path.join(str(tmp_path), "data"))
    shapes = []
    real = tsz.prepare_encode_inputs

    def spy(ticks, vals, npoints):
        shapes.append(np.shape(ticks))
        return real(ticks, vals, npoints)

    monkeypatch.setattr(block_mod.tsz, "prepare_encode_inputs", spy)
    device_hashes = []

    def hash_spy(items, seed=0):
        device_hashes.append(len(items))
        return hashing.hash_batch(items, seed)

    monkeypatch.setattr(bloom_mod, "hash_batch", hash_spy)
    ids = [b"snap-%04d" % i for i in range(SERIES)]
    return db, pm, ids, now, shapes, device_hashes


@pytest.mark.parametrize("arrived", [1, 3, 17, 33, SERIES])
def test_a_partly_filled_bucket_snapshots_at_its_shards_row_shape(
        node, rng, arrived):
    db, pm, ids, now, shapes, device_hashes = node
    db.write_batch(NS, ids, np.full(SERIES, T0, np.int64),
                   rng.standard_normal(SERIES))
    med = Mediator(db, pm)
    med.snapshot(now["t"])
    full = set(shapes)
    assert full == {(64, 8)} and device_hashes == [SERIES, SERIES]
    # the scrape that crosses the block boundary has reached `arrived`
    # of the shard's series when the next snapshot runs
    b1 = T0 + BLOCK
    now["t"] = b1 + xtime.SECOND
    vals = rng.standard_normal(arrived)
    db.write_batch(NS, ids[:arrived], np.full(arrived, b1, np.int64), vals)
    del shapes[:], device_hashes[:]
    # (the first block's bucket took no row since its snapshot: it is
    # not written again)
    assert med.snapshot(now["t"]) == 1
    assert len(shapes) == 1 and set(shapes) == full
    # only a block of all its shard's series hashes by the program that
    # is keyed by the row count
    assert set(device_hashes) <= {SERIES}
    assert len(device_hashes) == (2 if arrived == SERIES else 0)
    # and the rows it wrote are the rows a bucket-shaped encode gives
    (_bs, _v, path), = [s for s in pm.list_snapshots(NS, 0) if s[0] == b1]
    reader = FilesetReader(path)
    reader.verify_rows()        # the bloom holds every id of the index
    got, got_ids = reader.to_block()
    shard = db.namespace(NS).shard_for(0)
    series, td, vd, npoints = shard.buffer.snapshot(b1)
    want = block_mod.encode_block(b1, series, td, vd, npoints)
    assert got_ids == ids[:arrived]
    np.testing.assert_array_equal(got.words, want.words)
    np.testing.assert_array_equal(got.nbits, want.nbits)
    for row in range(arrived):
        ts, vs = got.read(row)
        assert ts.tolist() == [b1] and vs.tolist() == [vals[row]]
