"""Crash-safe columnar recovery (reference test model: the commitlog
reader/iterator tests, dbnode/digest validation, and the dtest
kill-restart destructive scenarios).

Tier-1 promotion of scripts/fuzz_durability.py's invariants — seeded
SUBSETS run here on every pass, the open-ended campaign stays in the
script — plus the columnar-recovery bit-identity contracts (batched
replay and bootstrap vs the retained `_ref` per-entry oracles) and the
kill -9 disaster drill (KillRestartScenario: a REAL dbnode child under
seeded open-loop load, SIGKILLed, restarted, zero acked-write loss)."""

import os
import shutil
import tempfile
import zlib

import numpy as np
import pytest

from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.persist import commitlog as cl
from m3_tpu.persist import fs as pfs
from m3_tpu.persist.diskio import CorruptionError
from m3_tpu.persist.fs import FilesetReader, PersistManager, fileset_complete
from m3_tpu.storage import bootstrap as bs
from m3_tpu.storage.block import encode_block
from m3_tpu.storage.database import Database
from m3_tpu.storage.mediator import Mediator
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.storage.series import SeriesRegistry
from m3_tpu.testing.scenario import (KillRestartOptions, KillRestartScenario)
from m3_tpu.utils import xtime
from m3_tpu.utils.checksum import adler32_rows
from m3_tpu.utils.instrument import ROOT

NS = b"default"
BLOCK = 2 * xtime.HOUR
T0 = 1_600_000_000 * xtime.SECOND - (1_600_000_000 * xtime.SECOND) % BLOCK


# ---------------------------------------------------------------------------
# vectorized adler32
# ---------------------------------------------------------------------------


class TestAdler32Rows:
    def test_bit_identical_to_zlib(self, rng):
        for s, n, dtype in [(1, 1, np.uint8), (7, 33, np.uint8),
                            (5, 16, np.uint32), (3, 0, np.uint8),
                            (12, 129, np.uint32), (4, 7, np.int64)]:
            if dtype == np.uint8:
                mat = rng.integers(0, 256, (s, max(n, 1)),
                                   dtype=np.uint8)[:, :n]
            else:
                mat = rng.integers(0, 2**31 - 1, (s, n)).astype(dtype)
            got = adler32_rows(mat)
            want = [zlib.adler32(np.ascontiguousarray(mat)[i].tobytes())
                    for i in range(s)]
            assert got.tolist() == want

    def test_non_contiguous_rows(self, rng):
        mat = rng.integers(0, 256, (6, 40), dtype=np.uint8)[::2, 1::3]
        got = adler32_rows(mat)
        want = [zlib.adler32(np.ascontiguousarray(mat)[i].tobytes())
                for i in range(mat.shape[0])]
        assert got.tolist() == want

    def test_rejects_non_matrix(self):
        with pytest.raises(ValueError):
            adler32_rows(np.zeros(8, np.uint8))


# ---------------------------------------------------------------------------
# commitlog: torn tails, corruption isolation, batched-vs-ref identity
# ---------------------------------------------------------------------------


def _write_log(tmp, rng, n_entries=120, rotate_p=0.12):
    """Unique-entry stream across rotated files -> (dir, per_file)."""
    d = str(tmp)
    log = cl.CommitLog(d, strategy=cl.Strategy.WRITE_WAIT)
    per_file = [[]]
    for seq in range(n_entries):
        entry = (b"ns%d" % rng.integers(3), b"s%d" % rng.integers(8),
                 int(seq), float(seq))
        log.write(*entry[:2], entry[2], entry[3])
        per_file[-1].append(entry)
        if rng.random() < rotate_p:
            log.rotate()
            per_file.append([])
    log.close()
    return d, per_file


def _run_iter(gen):
    """(entries, exception-name-or-None): corrupt streams must fail the
    SAME way in the batched decoder as in the per-entry oracle."""
    out = []
    try:
        for e in gen:
            out.append(e)
        return out, None
    except Exception as e:  # noqa: BLE001 — equality of failure is the point
        return out, type(e).__name__


def _corrupt(path, rng):
    data = bytearray(open(path, "rb").read())
    kind = ["truncate", "flip", "insert", "delete"][int(rng.integers(4))]
    if not data:
        kind = "insert"
    if kind == "truncate":
        data = data[: int(rng.integers(0, len(data)))]
    elif kind == "flip":
        for _ in range(int(rng.integers(1, 5))):
            i = int(rng.integers(0, len(data)))
            data[i] ^= int(rng.integers(1, 256))
    elif kind == "insert":
        i = int(rng.integers(0, len(data) + 1))
        junk = bytes(rng.integers(0, 256, int(rng.integers(1, 17)),
                                  dtype=np.uint8))
        data = bytes(data[:i]) + junk + bytes(data[i:])
    else:
        i = int(rng.integers(0, len(data)))
        j = int(rng.integers(i + 1, min(len(data), i + 64) + 1))
        data = data[:i] + data[j:]
    with open(path, "wb") as f:
        f.write(bytes(data))
    return kind


class TestCommitlogReplay:
    def test_torn_tail_chunk_dropped(self, tmp_path, rng):
        d, per_file = _write_log(tmp_path, rng, rotate_p=0.0)
        want = per_file[0]
        fname = sorted(os.listdir(d))[-1]
        # A half-written chunk: header promises 512 bytes, 24 arrive.
        with open(os.path.join(d, fname), "ab") as f:
            f.write(cl._CHUNK_HEADER.pack(512, 0xBAD) + b"x" * 24)
        assert list(cl.replay(d)) == want
        assert list(cl.replay_ref(d)) == want
        flat = [(ns, sid, int(t), float(v))
                for b in cl.replay_batches(d)
                for ns, sid, t, v in zip(b.namespaces, b.ids, b.t_ns,
                                         b.values)]
        assert flat == want

    def test_mid_file_truncation_keeps_prefix(self, tmp_path, rng):
        d, per_file = _write_log(tmp_path, rng, n_entries=40, rotate_p=0.0)
        fname = sorted(os.listdir(d))[-1]
        path = os.path.join(d, fname)
        size = os.path.getsize(path)
        with open(path, "rb+") as f:
            f.truncate(size - 11)  # tear inside the final chunk
        got = list(cl.replay(d))
        assert got == per_file[0][: len(got)]  # an exact PREFIX, nothing made up
        assert len(got) < len(per_file[0])

    @pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
    def test_corruption_batched_vs_ref_bit_identity(self, tmp_path, seed):
        """Seeded fuzz subset: one corrupted file per round — the
        batched decoder must yield the SAME entries AND fail the same
        way as the per-entry oracle, and damage must stay inside the
        corrupted file."""
        rng = np.random.default_rng(seed)
        d, per_file = _write_log(tmp_path, rng)
        files = sorted(f for f in os.listdir(d) if f.startswith("commitlog-"))
        assert len(files) == len(per_file)
        k = int(rng.integers(len(files)))
        _corrupt(os.path.join(d, files[k]), rng)
        ref, ref_err = _run_iter(cl.replay_ref(d))
        new, new_err = _run_iter(cl.replay(d))
        assert (new, new_err) == (ref, ref_err)
        # Cross-file isolation: files before/after the damaged one
        # replay exactly (ref semantics proven by the fuzz campaign;
        # here we assert the batched path preserves them).
        flat_expect = [e for i, f in enumerate(per_file) if i != k for e in f]
        surviving = [e for e in new if e not in per_file[k]]
        assert surviving == [e for e in flat_expect if e in surviving]
        pre = [e for i, f in enumerate(per_file) if i < k for e in f]
        assert new[: len(pre)] == pre or ref_err is not None

    def test_str_tags_never_abort_the_append(self, tmp_path):
        """The JSON ingest surfaces hand over str-keyed tag dicts; the
        WAL append must normalize them (or degrade to untagged), never
        raise — the shard buffer was already written, so an abort here
        silently diverges served data from the WAL."""
        d = str(tmp_path)
        log = cl.CommitLog(d, strategy=cl.Strategy.WRITE_WAIT)
        log.write(b"ns", b"s1", 1, 1.0, tags={"host": "a"})     # str/str
        log.write(b"ns", b"s2", 2, 2.0, tags={b"k": object()})  # hopeless
        log.close()
        batches = list(cl.replay_batches(d))
        entries = [(sid, t.item()) for b in batches
                   for sid, t in zip(b.ids, b.t_ns)]
        assert entries == [(b"s1", 1), (b"s2", 2)]
        tags = {sid: tg for b in batches
                for sid, tg in zip(b.ids, b.tags)}
        assert tags[b"s1"] == {b"host": b"a"}  # normalized to bytes
        assert tags[b"s2"] is None             # degraded, not dropped

    def test_tagged_write_after_untagged_first_sighting(self, tmp_path):
        """A series whose FIRST write in a file is untagged must still
        get its tags into the WAL when a later tagged write arrives
        (a fresh tagged meta is emitted), or recovery cannot rebuild
        its index document."""
        d = str(tmp_path)
        log = cl.CommitLog(d, strategy=cl.Strategy.WRITE_WAIT)
        log.write(b"ns", b"s1", 1, 1.0)
        log.write(b"ns", b"s1", 2, 2.0, tags={b"k": b"v"})
        log.write(b"ns", b"s1", 3, 3.0)  # cached tagged ref reused
        log.close()
        assert list(cl.replay(d)) == [(b"ns", b"s1", 1, 1.0),
                                      (b"ns", b"s1", 2, 2.0),
                                      (b"ns", b"s1", 3, 3.0)]
        per_entry = [tg for b in cl.replay_batches(d) for tg in b.tags]
        assert per_entry[0] is None
        assert per_entry[1] == {b"k": b"v"}
        assert per_entry[2] == {b"k": b"v"}

    def test_unrecognized_format_file_skipped_not_misparsed(self, tmp_path,
                                                            rng):
        """A commitlog file without this format's header (older layout,
        foreign bytes) is SKIPPED with a warning — misparsing would
        fabricate (ns, id) pairs into shard buffers."""
        d, per_file = _write_log(tmp_path, rng, n_entries=20, rotate_p=0.0)
        # A v1-era file: chunked entries but no file header.
        legacy = os.path.join(d, "commitlog-00000099.bin")
        body = cl._DATA_ENTRY.pack(1, 0, 5, 5.0)
        with open(legacy, "wb") as f:
            f.write(cl._CHUNK_HEADER.pack(len(body), zlib.adler32(body)))
            f.write(body)
        assert list(cl.replay(d)) == per_file[0]
        assert list(cl.replay_ref(d)) == per_file[0]

    def test_streaming_positions_and_wrapper_types(self, tmp_path, rng):
        d, per_file = _write_log(tmp_path, rng, n_entries=30, rotate_p=0.3)
        batches = list(cl.replay_batches(d))
        # chunk positions are per-file monotonic and chunk-aligned
        by_file = {}
        for b in batches:
            assert b.end_offset > by_file.get(b.file_num, 0)
            by_file[b.file_num] = b.end_offset
        for b in batches:
            assert b.before((b.file_num, b.end_offset))
            assert not b.before((b.file_num, b.end_offset - 1))
            assert b.before((b.file_num + 1, 0))
        for ns, sid, t, v in cl.replay(d):
            assert type(t) is int and type(v) is float
            break


# ---------------------------------------------------------------------------
# fileset verification
# ---------------------------------------------------------------------------


def _mk_fileset(root, rng, n=12, w=9):
    reg = SeriesRegistry()
    ids = [b"fz.%d" % i for i in range(n)]
    for sid in ids:
        reg.get_or_create(sid)
    ts = (T0 + np.arange(w, dtype=np.int64)[None, :] * 10 * xtime.SECOND
          + np.zeros((n, 1), np.int64))
    vals = rng.integers(0, 50, size=(n, w)).astype(np.float64)
    blk = encode_block(T0, np.arange(n, dtype=np.int32), ts, vals,
                       np.full(n, w, np.int32))
    pm = PersistManager(root)
    return pm.write_block(NS, 1, blk, reg)


class TestFilesetVerification:
    @pytest.mark.parametrize("seed", [11, 12, 13, 14])
    def test_one_byte_corruption_detected(self, tmp_path, seed):
        """Seeded fuzz subset: one flipped byte in one component file
        must be DETECTED — incomplete fileset, raising verified reader,
        or raising row verification. A clean read of corrupt bytes is
        the failure this exists to catch."""
        rng = np.random.default_rng(seed)
        path = _mk_fileset(str(tmp_path), rng)
        assert fileset_complete(path)
        names = sorted(os.listdir(path))
        fname = names[int(rng.integers(len(names)))]
        fpath = os.path.join(path, fname)
        data = bytearray(open(fpath, "rb").read())
        if not data:
            pytest.skip("empty component")
        i = int(rng.integers(0, len(data)))
        data[i] ^= int(rng.integers(1, 256))
        with open(fpath, "wb") as f:
            f.write(bytes(data))
        if not fileset_complete(path):
            return  # checkpoint/digest chain flagged it
        with pytest.raises((ValueError, KeyError, OSError, IndexError)):
            reader = FilesetReader(path, verify=True)
            reader.verify_rows()
            reader.to_block()

    def test_row_checksums_vectorized_match_entries(self, tmp_path, rng):
        path = _mk_fileset(str(tmp_path), rng)
        reader = FilesetReader(path)
        reader.verify_rows()  # must pass clean
        sums = reader.row_checksums()
        by_row = {e.row: e.checksum for e in reader.entries}
        assert all(int(sums[r]) == c for r, c in by_row.items())

    def test_row_mismatch_detected_past_digests(self, tmp_path, rng):
        """Cross-wire the index against the data (digests recomputed so
        the file-level chain passes): only row verification catches it."""
        import json

        path = _mk_fileset(str(tmp_path), rng)
        reader = FilesetReader(path)
        e0 = reader.entries[0]
        idx_path = os.path.join(path, "index.bin")
        data = bytearray(open(idx_path, "rb").read())
        # flip a checksum byte of the first entry (offset 16..19 of the
        # fixed header) then recompute the digest chain around it
        data[16] ^= 0xFF
        with open(idx_path, "wb") as f:
            f.write(bytes(data))
        from m3_tpu.persist.fs import _adler
        digests = json.load(open(os.path.join(path, "digest.json")))
        digests["index.bin"] = _adler(idx_path)
        with open(os.path.join(path, "digest.json"), "w") as f:
            json.dump(digests, f)
        with open(os.path.join(path, "checkpoint.json"), "w") as f:
            json.dump({"digest": _adler(os.path.join(path, "digest.json"))},
                      f)
        assert fileset_complete(path)
        reader2 = FilesetReader(path, verify=True)  # digests all pass
        with pytest.raises(IOError, match="row checksum mismatch"):
            reader2.verify_rows()
        assert reader2.entries[0].id == e0.id

    def test_tmp_fileset_residue_ignored_and_cleaned(self, tmp_path, rng):
        """A SIGKILL between the checkpoint write and os.replace leaves
        a complete-looking '<kind>-<bs>.tmp' dir: listings must skip it
        (a crash must never wedge the next restart on int('...tmp')),
        and the mediator's cleanup removes it."""
        root = str(tmp_path)
        path = _mk_fileset(root, rng)  # ns shard-00001 fileset
        shard_dir = os.path.dirname(path)
        shutil.copytree(path, path + ".tmp")  # full chain inside .tmp
        pm = PersistManager(root)
        listed = pm.list_filesets(NS, 1)
        assert [p for _bs, p in listed] == [path]
        assert pm.list_snapshots(NS, 1) == []
        # cleanup sweeps the residue
        db = Database(ShardSet(2), clock=lambda: T0)
        db.create_namespace(NS, NamespaceOptions(index_enabled=False))
        Mediator(db, pm).cleanup(T0)
        assert not os.path.exists(path + ".tmp")
        assert os.path.exists(path)
        assert [p for _bs, p in pm.list_filesets(NS, 1)] == [path]

    def test_bloom_divergence_detected(self, tmp_path, rng):
        import json

        path = _mk_fileset(str(tmp_path), rng)
        bloom_path = os.path.join(path, "bloom.bin")
        data = bytearray(open(bloom_path, "rb").read())
        data[0] ^= 0x01
        with open(bloom_path, "wb") as f:
            f.write(bytes(data))
        from m3_tpu.persist.fs import _adler
        digests = json.load(open(os.path.join(path, "digest.json")))
        digests["bloom.bin"] = _adler(bloom_path)
        with open(os.path.join(path, "digest.json"), "w") as f:
            json.dump(digests, f)
        with open(os.path.join(path, "checkpoint.json"), "w") as f:
            json.dump({"digest": _adler(os.path.join(path, "digest.json"))},
                      f)
        with pytest.raises(IOError, match="bloom"):
            FilesetReader(path).verify_rows()


# ---------------------------------------------------------------------------
# region-targeted bit-flip corpus over the LAZY serve path
# ---------------------------------------------------------------------------


class TestRegionBitflipCorpus:
    """Seeded subset of the fuzzer's region corpus
    (scripts/fuzz_durability.py region_round): one flipped byte in one
    NAMED fileset region, read back through the lazy serve path
    (verify=False reader -> SealedBlock row verification, and the
    Seeker point-lookup route). The invariant is detect-or-serve-
    correct: every read either raises typed or returns bit-identical
    data — a clean read of WRONG bytes is the only failure."""

    REGIONS = {
        "index": pfs.INDEX_FILE, "data": pfs.DATA_FILE,
        "bloom": pfs.BLOOM_FILE, "checkpoint": pfs.CHECKPOINT_FILE,
    }
    _TYPED = (CorruptionError, ValueError, KeyError, OSError, IndexError)

    @pytest.mark.parametrize("region", sorted(REGIONS))
    @pytest.mark.parametrize("seed", [21, 22, 23])
    def test_detect_or_serve_correct(self, tmp_path, region, seed):
        rng = np.random.default_rng(seed)
        path = _mk_fileset(str(tmp_path), rng)
        clean_blk, clean_ids = FilesetReader(path, verify=True).to_block()
        truth_ts, truth_vs, truth_np = clean_blk.read_all()
        sk0 = pfs.Seeker(path)
        truth_rows = {sid: sk0.seek(sid) for sid in clean_ids}
        fpath = os.path.join(path, self.REGIONS[region])
        data = bytearray(open(fpath, "rb").read())
        assert data, f"{region} region unexpectedly empty"
        i = int(rng.integers(0, len(data)))
        data[i] ^= int(rng.integers(1, 256))
        with open(fpath, "wb") as f:
            f.write(bytes(data))
        if not fileset_complete(path):
            return  # detected: checkpoint chain flagged it
        # Serve path 1: lazy block materialization + row verify.
        try:
            blk, ids = FilesetReader(path, verify=False).to_block()
            ts, vs, npts = blk.read_all()
        except self._TYPED:
            pass  # detected, typed
        else:
            assert list(ids) == list(clean_ids)
            assert np.array_equal(truth_ts, ts)
            assert np.array_equal(truth_vs, vs, equal_nan=True)
            assert np.array_equal(truth_np, npts)
        # Serve path 2: Seeker point lookups (bloom + index + row adler
        # route — distinct bytes from to_block's matrix route). seek
        # returns the packed (words row, nbits, npoints) triple.
        try:
            sk = pfs.Seeker(path)
            for sid in clean_ids:
                got = sk.seek(sid)
                assert got is not None, \
                    f"{region} flip at {i} dropped {sid!r} from seek"
                want = truth_rows[sid]
                assert np.array_equal(want[0], got[0])
                assert want[1:] == got[1:]
        except self._TYPED:
            pass  # detected, typed

    def test_clean_fileset_serves_both_routes(self, tmp_path, rng):
        """The corpus's negative: no flip -> both serve routes return
        the written data (guards against detection-by-default)."""
        path = _mk_fileset(str(tmp_path), rng)
        blk, ids = FilesetReader(path, verify=False).to_block()
        _ts, _vs, npts = blk.read_all()  # row verification passes
        sk = pfs.Seeker(path)
        for r, sid in enumerate(ids):
            got = sk.seek(sid)
            assert got is not None
            assert got[2] == int(npts[r])


# ---------------------------------------------------------------------------
# bootstrap: batched recovery vs retained per-entry oracles
# ---------------------------------------------------------------------------


def _seed_recovery_dir(root, rng, n_series=60, num_shards=4):
    """Kill -9 shaped dir: flushed old block + snapshotted warm block +
    WAL tail past the snapshot (incl. an overwrite of a snapshotted
    point)."""
    now = {"t": T0 + xtime.MINUTE}
    log = cl.CommitLog(os.path.join(root, "cl"))
    db = Database(ShardSet(num_shards), commitlog=log, clock=lambda: now["t"])
    db.create_namespace(NS, NamespaceOptions(index_enabled=False))
    pm = PersistManager(os.path.join(root, "data"))
    ids = [b"rec-%04d" % i for i in range(n_series)]
    db.write_batch(NS, ids, np.full(n_series, T0, np.int64),
                   rng.standard_normal(n_series))
    now["t"] = T0 + BLOCK + 11 * xtime.MINUTE
    db.tick()
    db.flush(pm)
    b1 = T0 + BLOCK
    for w in range(3):
        tsw = b1 + (12 + w) * xtime.MINUTE
        now["t"] = tsw
        db.write_batch(NS, ids, np.full(n_series, tsw, np.int64),
                       rng.standard_normal(n_series))
        log.flush()
    Mediator(db, pm).snapshot(now["t"])
    tsw = b1 + 20 * xtime.MINUTE
    now["t"] = tsw
    db.write_batch(NS, ids[: n_series // 2],
                   np.full(n_series // 2, tsw, np.int64),
                   rng.standard_normal(n_series // 2))
    db.write_batch(NS, ids[:5], np.full(5, b1 + 12 * xtime.MINUTE, np.int64),
                   np.full(5, 424242.0))  # overwrite a snapshotted point
    log.flush()
    # Abandoned WITHOUT close(): on-disk state == SIGKILL.
    return db, pm, ids, now


def _recover(root, pm, now, num_shards, path):
    """path='new' -> batched tiles + columnar WAL; 'ref' -> retained
    per-entry oracles; 'chain' -> the real BootstrapProcess."""
    db2 = Database(ShardSet(num_shards), clock=lambda: now["t"])
    db2.create_namespace(NS, NamespaceOptions(index_enabled=False))
    ns = db2.namespace(NS)
    ctx = bs.BootstrapContext(persist=pm, commitlog_dir=os.path.join(root, "cl"),
                              shard_lookup=db2.shard_set.lookup)
    proc = bs.BootstrapProcess(
        chain=("filesystem", "commitlog", "uninitialized_topology"), ctx=ctx)
    if path == "chain":
        proc.run(db2, now_ns=now["t"])
        return db2
    req = proc.target_ranges(ns, now["t"])
    claimed = proc.bootstrappers[0].bootstrap(ns, req, ctx)
    rem = req.subtract(claimed)
    if path == "new":
        positions = bs.load_snapshots(ns, rem, ctx)
        assert bs.replay_wal(ns, rem, ctx, positions)
    else:
        bs.load_snapshots_ref(ns, rem, ctx)
        assert bs.replay_wal_ref(ns, rem, ctx)
    db2.mark_bootstrapped()
    return db2


class TestBootstrapOracle:
    @pytest.mark.parametrize("seed", [21, 22, 23])
    def test_recovery_read_identical_to_ref_and_origin(self, tmp_path, seed):
        rng = np.random.default_rng(seed)
        root = str(tmp_path)
        db, pm, ids, now = _seed_recovery_dir(root, rng)
        dn = _recover(root, pm, now, 4, "new")
        dr = _recover(root, pm, now, 4, "ref")
        dc = _recover(root, pm, now, 4, "chain")
        end = now["t"] + xtime.HOUR
        for sid in ids:
            tn, vn = dn.read(NS, sid, 0, end)
            for other in (dr, dc, db):
                t2, v2 = other.read(NS, sid, 0, end)
                np.testing.assert_array_equal(tn, t2)
                np.testing.assert_array_equal(vn, v2)
        for s in range(4):
            assert (dn.namespace(NS).shards[s].registry.all_ids()
                    == dr.namespace(NS).shards[s].registry.all_ids())
        # Seal both (the recovered-buffer drain rides merge_same_start
        # over the snapshot tile on the new path) and re-compare.
        now["t"] = T0 + 2 * BLOCK + 21 * xtime.MINUTE
        dn.tick()
        dr.tick()
        for sid in ids:
            tn, vn = dn.read(NS, sid, 0, end)
            tr2, vr2 = dr.read(NS, sid, 0, end)
            np.testing.assert_array_equal(tn, tr2)
            np.testing.assert_array_equal(vn, vr2)

    def test_wal_only_buffer_bit_identity(self, tmp_path, rng):
        """Pure-WAL recovery (no snapshots/filesets): the batched path
        must leave buffer COLUMNS and registries bit-identical to the
        per-entry oracle — same entries, same order, same dtypes."""
        root = str(tmp_path)
        now = {"t": T0 + xtime.MINUTE}
        log = cl.CommitLog(os.path.join(root, "cl"))
        db = Database(ShardSet(4), commitlog=log, clock=lambda: now["t"])
        db.create_namespace(NS, NamespaceOptions(index_enabled=False))
        ids = [b"wal-%03d" % i for i in range(40)]
        for w in range(4):
            tsw = T0 + w * xtime.MINUTE
            now["t"] = tsw + xtime.MINUTE
            db.write_batch(NS, ids, np.full(len(ids), tsw, np.int64),
                           rng.standard_normal(len(ids)))
            log.flush()
        dbs = {}
        for path in ("new", "ref"):
            db2 = Database(ShardSet(4), clock=lambda: now["t"])
            db2.create_namespace(NS, NamespaceOptions(index_enabled=False))
            ns = db2.namespace(NS)
            ctx = bs.BootstrapContext(commitlog_dir=os.path.join(root, "cl"),
                                      shard_lookup=db2.shard_set.lookup)
            req = bs.BootstrapProcess(ctx=ctx).target_ranges(ns, now["t"])
            fn = bs.replay_wal if path == "new" else bs.replay_wal_ref
            assert fn(ns, req, ctx) is True
            dbs[path] = db2
        for s in range(4):
            shn = dbs["new"].namespace(NS).shards[s]
            shr = dbs["ref"].namespace(NS).shards[s]
            assert shn.registry.all_ids() == shr.registry.all_ids()
            assert sorted(shn.buffer.buckets) == sorted(shr.buffer.buckets)
            for bstart, bucket in shn.buffer.buckets.items():
                a, b = bucket.cols.view(), shr.buffer.buckets[bstart].cols.view()
                for x, y in zip(a, b):
                    np.testing.assert_array_equal(x, y)
                    assert x.dtype == y.dtype

    def test_recovery_rebuilds_reverse_index_from_wal_tags(self, tmp_path):
        """Tagged series must be QUERYABLE after recovery, not merely
        readable by id: the WAL meta entries carry encoded tags (the
        reference commitlog's EncodedTags) and replay re-indexes series
        whose index blocks were never flushed — including series whose
        DATA the snapshot position-skip drops. The recovered node must
        answer the same index query with the same ids, and serve the
        same PromQL range, as the pre-kill node."""
        from m3_tpu.index.query import TermQuery

        root = str(tmp_path)
        now = {"t": T0 + 2 * xtime.HOUR}
        log = cl.CommitLog(os.path.join(root, "cl"),
                           strategy=cl.Strategy.WRITE_WAIT)
        db = Database(ShardSet(4), commitlog=log, clock=lambda: now["t"])
        db.ensure_namespace(NS, NamespaceOptions())  # index ON
        pm = PersistManager(os.path.join(root, "data"))
        med = Mediator(db, pm)
        base = now["t"]
        for i in range(1, 9):
            sid = b"idx_cpu;host=h%d" % (i % 3)
            db.write(NS, sid, base - 60 * xtime.SECOND + i * xtime.SECOND,
                     100.0 + i,
                     tags={b"__name__": b"idx_cpu", b"host": b"h%d" % (i % 3)})
            # Mediator cadence between writes: snapshots cover the lot,
            # so WAL data chunks are position-skipped on recovery — the
            # index docs must STILL come back.
            med.run_once(now["t"])
        # Abandoned WITHOUT close(): on-disk state == SIGKILL.
        db2 = Database(ShardSet(4), clock=lambda: now["t"])
        db2.ensure_namespace(NS, NamespaceOptions())
        proc = bs.BootstrapProcess(
            chain=("filesystem", "commitlog", "uninitialized_topology"),
            ctx=bs.BootstrapContext(
                persist=pm, commitlog_dir=os.path.join(root, "cl"),
                shard_lookup=db2.shard_set.lookup))
        proc.run(db2, now_ns=now["t"])
        q = TermQuery(b"__name__", b"idx_cpu")
        want_ids = sorted(db.query_ids(NS, q))
        got_ids = sorted(db2.query_ids(NS, q))
        assert want_ids == got_ids and len(got_ids) == 3
        for sid in got_ids:
            t1, v1 = db.read(NS, sid, 0, base + 1)
            t2, v2 = db2.read(NS, sid, 0, base + 1)
            np.testing.assert_array_equal(t1, t2)
            np.testing.assert_array_equal(v1, v2)
        # registry tags recovered too (CompleteTags / aggregate paths)
        for sid in got_ids:
            shard = db2.namespace(NS).shards[db2.shard_set.lookup(sid)]
            tags = shard.registry.tags_of(shard.registry.get(sid))
            assert tags is not None and tags[b"__name__"] == b"idx_cpu"

    def test_warm_snapshot_tile_not_flushed_before_seal(self, tmp_path, rng):
        """A snapshot tile recovered for a STILL-WRITABLE window must
        not flush: a tile-only fileset would make the NEXT restart's
        filesystem bootstrapper claim the whole block range and
        range-filter the WAL tail out of replay — acked writes lost on
        the second kill. The tile flushes only once the window is cold
        (post-seal, merged with the replayed tail)."""
        root = str(tmp_path)
        now = {"t": T0 + 30 * xtime.MINUTE}
        log = cl.CommitLog(os.path.join(root, "cl"),
                           strategy=cl.Strategy.WRITE_WAIT)
        db = Database(ShardSet(2), commitlog=log, clock=lambda: now["t"])
        db.create_namespace(NS, NamespaceOptions(index_enabled=False))
        pm = PersistManager(os.path.join(root, "data"))
        ids = [b"warm-%02d" % i for i in range(12)]
        db.write_batch(NS, ids, np.full(len(ids), now["t"], np.int64),
                       rng.standard_normal(len(ids)))
        Mediator(db, pm).snapshot(now["t"])
        post_t = now["t"] + xtime.MINUTE
        now["t"] = post_t
        db.write_batch(NS, ids[:6], np.full(6, post_t, np.int64),
                       rng.standard_normal(6))  # WAL tail past the snapshot
        # kill #1: restart while the block is STILL warm
        db2 = Database(ShardSet(2), clock=lambda: now["t"])
        db2.create_namespace(NS, NamespaceOptions(index_enabled=False))
        proc = bs.BootstrapProcess(
            chain=("filesystem", "commitlog", "uninitialized_topology"),
            ctx=bs.BootstrapContext(
                persist=pm, commitlog_dir=os.path.join(root, "cl"),
                shard_lookup=db2.shard_set.lookup))
        proc.run(db2, now_ns=now["t"])
        med2 = Mediator(db2, pm)
        med2.run_once(now["t"])  # tick + flush + snapshot + cleanup, warm
        for sh in (0, 1):
            assert pm.list_filesets(NS, sh) == [], \
                "warm snapshot tile flushed before seal"
        # kill #2, still warm: recovery must serve EVERYTHING
        db3 = Database(ShardSet(2), clock=lambda: now["t"])
        db3.create_namespace(NS, NamespaceOptions(index_enabled=False))
        bs.BootstrapProcess(
            chain=("filesystem", "commitlog", "uninitialized_topology"),
            ctx=bs.BootstrapContext(
                persist=pm, commitlog_dir=os.path.join(root, "cl"),
                shard_lookup=db3.shard_set.lookup)).run(db3, now_ns=now["t"])
        for sid in ids:
            t1, v1 = db.read(NS, sid, 0, now["t"] + xtime.HOUR)
            t3, v3 = db3.read(NS, sid, 0, now["t"] + xtime.HOUR)
            np.testing.assert_array_equal(t1, t3)
            np.testing.assert_array_equal(v1, v3)
        # ... and once the window is COLD, the merged block flushes.
        now["t"] = T0 + BLOCK + 11 * xtime.MINUTE
        med2.run_once(now["t"])
        assert any(pm.list_filesets(NS, sh) for sh in (0, 1))

    def test_same_chunk_untagged_then_tagged_series_indexed(self, tmp_path):
        """A series created untagged whose tagged entry lands in the
        SAME WAL chunk (one write_batch) must still get its reverse-
        index document on recovery — the hook reads the registry's
        backfilled tags, not the first-occurrence position."""
        from m3_tpu.index.query import TermQuery

        root = str(tmp_path)
        now = {"t": T0 + 30 * xtime.MINUTE}
        log = cl.CommitLog(os.path.join(root, "cl"))
        db = Database(ShardSet(2), commitlog=log, clock=lambda: now["t"])
        db.ensure_namespace(NS, NamespaceOptions())  # index ON
        tg = {b"__name__": b"mix", b"host": b"a"}
        db.write_batch(NS, [b"mix;host=a", b"mix;host=a"],
                       np.full(2, now["t"], np.int64), np.array([1.0, 2.0]),
                       tags=[None, tg])  # untagged THEN tagged, one chunk
        log.flush()
        db2 = Database(ShardSet(2), clock=lambda: now["t"])
        db2.ensure_namespace(NS, NamespaceOptions())
        bs.BootstrapProcess(
            chain=("commitlog", "uninitialized_topology"),
            ctx=bs.BootstrapContext(
                commitlog_dir=os.path.join(root, "cl"),
                shard_lookup=db2.shard_set.lookup)).run(db2, now_ns=now["t"])
        got = db2.query_ids(NS, TermQuery(b"__name__", b"mix"))
        assert sorted(got) == [b"mix;host=a"]

    def test_async_insert_queue_never_loses_to_snapshot_position(
            self, tmp_path, rng):
        """write_new_series_async: an acked write can sit in the insert
        queue with its WAL append already durable. A snapshot cut at
        that moment records a position COVERING the entry's chunk — the
        snapshot must therefore contain the entry (queues drain between
        position and buffer read), else position-filtered replay drops
        it on restart: silent acked-data loss."""
        root = str(tmp_path)
        now = {"t": T0 + xtime.MINUTE}
        log = cl.CommitLog(os.path.join(root, "cl"))
        db = Database(ShardSet(2), commitlog=log, clock=lambda: now["t"])
        db.create_namespace(NS, NamespaceOptions(
            index_enabled=False, write_new_series_async=True))
        pm = PersistManager(os.path.join(root, "data"))
        db.write_batch(NS, [b"async-1", b"async-2"],
                       np.full(2, T0, np.int64), np.array([7.0, 8.0]))
        # The writes are acked (WAL durable via the snapshot's flush)
        # but still queued: no tick, no drain yet.
        assert any(sh.insert_queue.pending()
                   for sh in db.namespace(NS).shards.values())
        Mediator(db, pm).snapshot(now["t"])
        db2 = Database(ShardSet(2), clock=lambda: now["t"])
        db2.create_namespace(NS, NamespaceOptions(index_enabled=False))
        proc = bs.BootstrapProcess(
            chain=("commitlog",),
            ctx=bs.BootstrapContext(
                persist=pm, commitlog_dir=os.path.join(root, "cl"),
                shard_lookup=db2.shard_set.lookup))
        proc.run(db2, now_ns=now["t"])
        for sid, want in ((b"async-1", 7.0), (b"async-2", 8.0)):
            t, v = db2.read(NS, sid, 0, now["t"] + 1)
            assert v.tolist() == [want], f"acked async write lost: {sid!r}"

    def test_unchanged_bucket_keeps_its_snapshot_and_recovers(
            self, tmp_path, rng):
        """A tick snapshots only the buckets that took a row since their
        last snapshot: the others keep the one on disk (an older version
        and WAL position, through cleanup), and a kill -9 after it loses
        nothing of either kind."""
        root = str(tmp_path)
        now = {"t": T0 + xtime.MINUTE}
        log = cl.CommitLog(os.path.join(root, "cl"))
        db = Database(ShardSet(4), commitlog=log, clock=lambda: now["t"])
        db.create_namespace(NS, NamespaceOptions(index_enabled=False))
        pm = PersistManager(os.path.join(root, "data"))
        ids = [b"keep-%03d" % i for i in range(40)]
        vals = rng.standard_normal(40)
        db.write_batch(NS, ids, np.full(40, T0, np.int64), vals)
        log.flush()
        mediator = Mediator(db, pm)
        buckets = mediator.snapshot(now["t"])
        assert buckets == 4
        assert mediator.snapshot(now["t"]) == 0     # nothing appended
        db.write_batch(NS, ids[:1], np.full(1, T0 + xtime.SECOND, np.int64),
                       np.array([5.5]))
        log.flush()
        assert mediator.snapshot(now["t"]) == 1     # that row's bucket
        mediator.cleanup(now["t"])
        ns = db.namespace(NS)
        versions = sorted(v for sid in ns.shards
                          for _bs, v, _p in pm.list_snapshots(NS, sid))
        assert versions == [1, 1, 1, 3]     # one a bucket, the newest
        # abandoned without close(): on-disk state == SIGKILL
        db2 = _recover(root, pm, now, 4, "chain")
        for sid, want in zip(ids, vals):
            t, v = db2.read(NS, sid, 0, now["t"] + xtime.HOUR)
            assert v.tolist() == ([want, 5.5] if sid == ids[0] else [want])

    def test_cleanup_reads_a_digest_chain_only_where_it_decides(
            self, tmp_path, rng, monkeypatch):
        """One listing a shard; a snapshot volume seen complete is not
        read again at the next tick; a newer volume without its
        checkpoint supersedes nothing and is left alone."""
        from m3_tpu.storage import mediator as mediator_mod

        root = str(tmp_path)
        now = {"t": T0 + xtime.MINUTE}
        log = cl.CommitLog(os.path.join(root, "cl"))
        db = Database(ShardSet(4), commitlog=log, clock=lambda: now["t"])
        db.create_namespace(NS, NamespaceOptions(index_enabled=False))
        pm = PersistManager(os.path.join(root, "data"))
        ids = [b"keep-%03d" % i for i in range(40)]
        mediator = Mediator(db, pm)
        for k in range(2):
            db.write_batch(NS, ids, np.full(40, T0 + k * xtime.SECOND,
                                            np.int64),
                           rng.standard_normal(40))
            log.flush()
            assert mediator.snapshot(now["t"]) == 4
        ns = db.namespace(NS)
        torn = pm.list_snapshots(NS, 2)[-1][2]      # shard 2's version 2
        os.remove(os.path.join(torn, pfs.CHECKPOINT_FILE))
        checked = []
        real = mediator_mod.fileset_complete
        monkeypatch.setattr(mediator_mod, "fileset_complete",
                            lambda d: checked.append(d) or real(d))
        assert mediator.cleanup(now["t"]) == 3      # three version 1s
        assert len(checked) == 8
        versions = {sid: [v for _bs, v, _p in pm.list_snapshots(NS, sid)]
                    for sid in ns.shards}
        assert versions == {0: [2], 1: [2], 2: [1], 3: [2]}
        assert os.path.isdir(torn)
        del checked[:]
        assert mediator.cleanup(now["t"]) == 0
        assert checked == [torn]        # the others were seen complete

    def test_skipped_replay_is_surfaced(self, tmp_path, rng):
        """Satellite: no shard_lookup + a partial shard set must COUNT
        the skip, and surface it on the BootstrapResult notes."""
        root = str(tmp_path)
        now = {"t": T0 + xtime.MINUTE}
        log = cl.CommitLog(os.path.join(root, "cl"))
        db = Database(ShardSet(4), commitlog=log, clock=lambda: now["t"])
        db.create_namespace(NS, NamespaceOptions(index_enabled=False))
        db.write_batch(NS, [b"skip-1"], np.array([T0], np.int64),
                       np.array([1.0]))
        log.close()
        # A node owning a PARTIAL shard set: murmur%N would misroute.
        db2 = Database(ShardSet(4), clock=lambda: now["t"])
        db2.create_namespace(NS, NamespaceOptions(index_enabled=False))
        ns2 = db2.namespace(NS)
        for sid in (1, 3):
            ns2.shards.pop(sid)
        before = ROOT.sub_scope("bootstrap.commitlog") \
                     .counter("replay_skipped").value()
        proc = bs.BootstrapProcess(
            chain=("commitlog",),
            ctx=bs.BootstrapContext(commitlog_dir=os.path.join(root, "cl")))
        results = proc.run(db2, now_ns=now["t"])
        after = ROOT.sub_scope("bootstrap.commitlog") \
                    .counter("replay_skipped").value()
        assert after == before + 1
        assert any("SKIPPED" in n for n in results[NS].notes)
        # With a proper lookup the same shape replays fine: no note.
        db3 = Database(ShardSet(4), clock=lambda: now["t"])
        db3.create_namespace(NS, NamespaceOptions(index_enabled=False))
        proc3 = bs.BootstrapProcess(
            chain=("commitlog",),
            ctx=bs.BootstrapContext(commitlog_dir=os.path.join(root, "cl"),
                                    shard_lookup=db3.shard_set.lookup))
        results3 = proc3.run(db3, now_ns=now["t"])
        assert results3[NS].notes == []
        t, v = db3.read(NS, b"skip-1", 0, now["t"] + 1)
        assert v.tolist() == [1.0]


# ---------------------------------------------------------------------------
# the kill -9 disaster drill
# ---------------------------------------------------------------------------


def _drill(opts):
    sc = KillRestartScenario(opts)
    try:
        return sc.verify(sc.run())
    finally:
        sc.close()


class TestKillRestartDrill:
    @pytest.mark.parametrize("seed", [7, 19])
    def test_base_drill_zero_acked_loss(self, seed):
        res = _drill(KillRestartOptions(seed=seed))
        assert res.verified_points == res.acked_points > 0
        assert res.torn_tail_bytes > 0  # torn tail was present AND dropped

    def test_namespace_migration_variant(self):
        res = _drill(KillRestartOptions(seed=11, variant="migration"))
        assert res.verified_points == res.acked_points > 0

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", [3, 23])
    def test_more_seeds(self, seed):
        res = _drill(KillRestartOptions(seed=seed))
        assert res.verified_points == res.acked_points > 0

    @pytest.mark.slow
    def test_backfill_variant_rides_same_start_merge(self):
        res = _drill(KillRestartOptions(seed=5, variant="backfill"))
        assert res.backfill_points > 0
        assert res.verified_points == res.acked_points > 0
        # three generations: initial + restart + post-backfill restart
        assert len(res.restart_walls_s) == 3
