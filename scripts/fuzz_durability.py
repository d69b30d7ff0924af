"""Randomized durability campaign: corrupted bytes must NEVER surface as
data. Two phases per round:

COMMITLOG (m3_tpu/persist/commitlog.py): write a unique-entry stream
across several rotated files, then corrupt ONE file (truncate at a
random offset / xor-flip random bytes / insert garbage / delete a middle
slice) and replay. Invariants:
  * replay never raises — corruption is a clean stop, not a crash;
  * every replayed record is bit-identical to a written one (entries are
    globally unique, so any fabricated/corrupt record is caught);
  * every file OTHER than the corrupted one replays in full, and the
    corrupted file yields at most an in-order SUBSEQUENCE of its
    records (usually a truncated tail; a delete of exactly
    chunk-aligned bytes legitimately realigns the stream and leaves a
    mid-file gap) — damage never leaks across files.

FILESET (m3_tpu/persist/fs.py): write a complete fileset, xor-flip one
random byte in one random file. Invariant: the corruption is DETECTED —
either the checkpoint/digest chain marks the fileset incomplete, or
FilesetReader(verify=True) raises; a silent clean read of corrupt bytes
is the failure this campaign exists to catch (reference:
src/dbnode/digest + persist/fs read.go validation).

Usage: python scripts/fuzz_durability.py --rounds 200
(pure numpy/stdlib — no jax backend is touched)
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Durability fuzzing has no device work of its own: pin the CPU backend
# before any m3_tpu import (encode_block's seal path initializes jax).
os.environ["JAX_PLATFORMS"] = "cpu"

from m3_tpu.persist import commitlog as cl  # noqa: E402
from m3_tpu.persist import fs as pfs  # noqa: E402
from m3_tpu.persist.diskio import CorruptionError  # noqa: E402
from m3_tpu.persist.fs import (FilesetReader, PersistManager,  # noqa: E402
                               fileset_complete)
from m3_tpu.storage.block import encode_block  # noqa: E402
from m3_tpu.storage.series import SeriesRegistry  # noqa: E402
from m3_tpu.utils import xtime  # noqa: E402


def _corrupt(path: str, rng) -> str:
    """Apply one random mutation to the file; returns its kind."""
    data = bytearray(open(path, "rb").read())
    kind = ["truncate", "flip", "insert", "delete"][rng.integers(4)]
    if not data:
        kind = "insert"
    if kind == "truncate":
        data = data[: rng.integers(0, len(data))]
    elif kind == "flip":
        for _ in range(int(rng.integers(1, 5))):
            i = int(rng.integers(0, len(data)))
            data[i] ^= int(rng.integers(1, 256))
    elif kind == "insert":
        i = int(rng.integers(0, len(data) + 1))
        junk = bytes(rng.integers(0, 256, int(rng.integers(1, 17)),
                                  dtype=np.uint8))
        data = data[:i] + junk + data[i:]
    else:  # delete a middle slice (always at least one byte)
        i = int(rng.integers(0, len(data)))
        j = int(rng.integers(i + 1, min(len(data), i + 64) + 1))
        data = data[:i] + data[j:]
    with open(path, "wb") as f:
        f.write(bytes(data))
    return kind


def commitlog_round(rng, seq_start: int) -> int:
    d = tempfile.mkdtemp(prefix="fuzz_cl_")
    try:
        log = cl.CommitLog(d, strategy=cl.Strategy.WRITE_WAIT)
        per_file = [[]]
        seq = seq_start
        for _ in range(int(rng.integers(5, 60))):
            ns = b"ns%d" % rng.integers(3)
            sid = b"s%d" % rng.integers(8)
            entry = (ns, sid, int(seq), float(seq))  # globally unique
            log.write(*entry[:2], entry[2], entry[3])
            per_file[-1].append(entry)
            seq += 1
            if rng.random() < 0.15:
                log.rotate()
                per_file.append([])
        log.close()
        files = sorted(f for f in os.listdir(d) if f.startswith("commitlog-"))
        # files with zero entries still exist; align by order
        assert len(files) == len(per_file), (files, len(per_file))
        k = int(rng.integers(len(files)))
        kind = _corrupt(os.path.join(d, files[k]), rng)
        replayed = list(cl.replay(d))  # must not raise
        # Undamaged files must replay EXACTLY; the corrupted file may
        # yield any (in-order) SUBSEQUENCE of its records — a delete of
        # exactly chunk-aligned bytes legitimately realigns the stream
        # and produces a mid-file gap, not just a truncated tail.
        pos = 0
        for i, expected in enumerate(per_file):
            if i != k:
                seg = replayed[pos: pos + len(expected)]
                assert seg == expected, (
                    f"undamaged file {i} diverged after {kind} of "
                    f"file {k}")
                pos += len(expected)
            else:
                want = iter(expected)
                while (pos < len(replayed)
                       and replayed[pos] in per_file[k]):
                    e = replayed[pos]
                    # in-order: e must appear in the remaining expected
                    for x in want:
                        if x == e:
                            break
                    else:
                        raise AssertionError(
                            f"corrupted file {k} replayed out of order "
                            f"after {kind}: {e}")
                    pos += 1
        assert pos == len(replayed), (
            f"replay fabricated records after {kind}: "
            f"{replayed[pos:][:3]}")
        return seq
    finally:
        shutil.rmtree(d, ignore_errors=True)


BLOCK = 2 * xtime.HOUR
T0 = 1_600_000_000 * xtime.SECOND - (1_600_000_000 * xtime.SECOND) % BLOCK


def fileset_round(rng) -> None:
    root = tempfile.mkdtemp(prefix="fuzz_fs_")
    try:
        n, w = int(rng.integers(2, 20)), int(rng.integers(4, 40))
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
        path = pm.write_block(b"ns", 1, blk, reg)
        assert fileset_complete(path)
        fname = sorted(os.listdir(path))[int(rng.integers(
            len(os.listdir(path))))]
        fpath = os.path.join(path, fname)
        data = bytearray(open(fpath, "rb").read())
        if not data:
            return  # empty component; nothing to corrupt
        i = int(rng.integers(0, len(data)))
        data[i] ^= int(rng.integers(1, 256))
        with open(fpath, "wb") as f:
            f.write(bytes(data))
        # Detection: incomplete fileset OR a raising verified reader
        # (fileset_complete already folds unparseable metadata into
        # False, so no exception path exists there).
        if not fileset_complete(path):
            return  # checkpoint/digest chain flagged it
        try:
            FilesetReader(path, verify=True).to_block()
        except (ValueError, KeyError, OSError, IndexError):
            return  # digest/parse rejected the corrupt bytes
        raise AssertionError(
            f"one-byte corruption of {fname} at {i} read back cleanly")
    finally:
        shutil.rmtree(root, ignore_errors=True)


# Region-targeted serve-path corpus: one flipped byte in one NAMED
# fileset region, then read through the LAZY serve path (verify=False
# reader -> SealedBlock row verification, and the Seeker point-lookup
# path) instead of the up-front verify=True scan above. The invariant
# is detect-or-serve-correct: every read either raises typed
# (CorruptionError / parse rejection) or returns bit-identical data —
# a clean read of wrong bytes is the only failure.
REGIONS = ("index", "data", "bloom", "checkpoint", "summaries")
_REGION_FILES = {
    "index": pfs.INDEX_FILE, "data": pfs.DATA_FILE, "bloom": pfs.BLOOM_FILE,
    "checkpoint": pfs.CHECKPOINT_FILE, "summaries": pfs.SUMMARIES_FILE,
}


def region_round(rng, region: str) -> str:
    """Returns the outcome: 'detected' or 'served-correct'."""
    root = tempfile.mkdtemp(prefix="fuzz_region_")
    try:
        n, w = int(rng.integers(2, 20)), int(rng.integers(4, 40))
        reg = SeriesRegistry()
        ids = [b"rz.%d" % i for i in range(n)]
        for sid in ids:
            reg.get_or_create(sid)
        ts = (T0 + np.arange(w, dtype=np.int64)[None, :] * 10 * xtime.SECOND
              + np.zeros((n, 1), np.int64))
        vals = rng.integers(0, 50, size=(n, w)).astype(np.float64)
        blk = encode_block(T0, np.arange(n, dtype=np.int32), ts, vals,
                           np.full(n, w, np.int32))
        pm = PersistManager(root)
        path = pm.write_block(b"ns", 1, blk, reg)
        clean_blk, clean_ids = FilesetReader(path, verify=True).to_block()
        truth = clean_blk.read_all()
        sk0 = pfs.Seeker(path)
        truth_rows = {sid: sk0.seek(sid) for sid in clean_ids}
        fpath = os.path.join(path, _REGION_FILES[region])
        data = bytearray(open(fpath, "rb").read())
        if not data:
            return "detected"  # empty region; nothing to corrupt
        i = int(rng.integers(0, len(data)))
        data[i] ^= int(rng.integers(1, 256))
        with open(fpath, "wb") as f:
            f.write(bytes(data))
        if not fileset_complete(path):
            return "detected"  # checkpoint chain flagged it
        # Serve path 1: lazy block materialization + row verification.
        try:
            got_blk, got_ids = FilesetReader(path, verify=False).to_block()
            ts_g, vs_g, np_g = got_blk.read_all()
        except (CorruptionError, ValueError, KeyError, OSError, IndexError):
            return "detected"
        assert list(got_ids) == list(clean_ids), (
            f"{region} flip at {i} served a different id set")
        for want, got, label in ((truth[0], ts_g, "timestamps"),
                                 (truth[1], vs_g, "values"),
                                 (truth[2], np_g, "npoints")):
            assert np.array_equal(want, got, equal_nan=True), (
                f"{region} flip at {i} served wrong {label}")
        # Serve path 2: the Seeker point lookups (bloom + index + row
        # adler route — distinct bytes from to_block's matrix route).
        # seek returns the packed (words row, nbits, npoints) triple.
        try:
            sk = pfs.Seeker(path)
            for sid in clean_ids:
                got = sk.seek(sid)
                if got is None:
                    raise AssertionError(
                        f"{region} flip at {i} dropped {sid!r} from seek")
                want = truth_rows[sid]
                assert np.array_equal(want[0], got[0]) and \
                    want[1:] == got[1:], (
                    f"{region} flip at {i} served wrong row for {sid!r}")
        except (CorruptionError, ValueError, KeyError, OSError, IndexError):
            return "detected"
        return "served-correct"
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    seq = 0
    outcomes = {"detected": 0, "served-correct": 0}
    for r in range(args.rounds):
        seq = commitlog_round(rng, seq)
        fileset_round(rng)
        outcomes[region_round(rng, REGIONS[r % len(REGIONS)])] += 1
        if (r + 1) % 25 == 0:
            print(f"  round {r + 1}/{args.rounds} "
                  f"({seq} wal records, {time.time() - t0:.0f}s)", flush=True)
    print(f"DURABILITY FUZZ PASS: {args.rounds} rounds, {seq} wal records, "
          f"region corpus {outcomes['detected']} detected / "
          f"{outcomes['served-correct']} served-correct, "
          f"seed {args.seed}, {time.time() - t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
