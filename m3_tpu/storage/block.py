"""Immutable sealed blocks + block LRU (reference: src/dbnode/storage/block:
DatabaseBlock holding one compressed segment per series per block window, and
wired_list.go's global LRU of blocks paged in from disk).

A sealed block here is batch-first: ONE object holds the compressed streams
of every series in a (shard, block-start) — words [S, MW] u32 — because
that is the unit the device encodes/decodes in a single launch, and the unit
filesets persist. Per-series access slices a row."""

from __future__ import annotations

import dataclasses
import itertools
import zlib
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

from ..ops import tsz
from ..ops.decode_rows import ROW_BUCKETS, decode_rows
from ..parallel import ingest as par_ingest
from ..parallel import scope as dscope
from ..utils import tracing, xtime
from ..utils.checksum import adler32_rows
from ..utils.instrument import ROOT
from . import block_cache

# Process-unique block generations (device-block-cache keys): every
# SealedBlock CONSTRUCTION gets a fresh one — merge/re-seal/bootstrap
# replacement produces a new generation by construction, so stale cache
# entries are unreachable even before eager invalidation lands.
# dataclasses.replace() builds a new object and therefore a new gen too
# (two blocks must never share a generation: load_block permutes rows
# in place after replace()).
_GEN = itertools.count(1)

# Fires once per block encoded through the shard x time mesh — the
# dryrun/tests assert the serving flush actually took the mesh path.
_FLUSH_METRICS = ROOT.sub_scope("storage.flush")

# Serve-time integrity counters (shared scope with persist/fs and the
# retriever's quarantine path).
_CORRUPTION = ROOT.sub_scope("storage.corruption")


def choose_time_unit(ts: np.ndarray) -> xtime.Unit:
    """Coarsest unit that represents every timestamp losslessly (the codec
    works in scaled integer ticks; the reference keys its DoD bucket scheme
    by time unit, m3tsz/scheme.go:41-52)."""
    for u in (xtime.Unit.MINUTE, xtime.Unit.SECOND, xtime.Unit.MILLISECOND,
              xtime.Unit.MICROSECOND):
        if (ts % u.nanos == 0).all():
            return u
    return xtime.Unit.NANOSECOND


@dataclasses.dataclass
class SealedBlock:
    """Compressed block for all series written in one (shard, block_start)."""

    block_start: int
    window: int                    # static decode window (max points/series)
    series_indices: np.ndarray     # int32 [S] registry indices, sorted
    words: np.ndarray              # uint32 [S, MW] packed streams
    nbits: np.ndarray              # int32 [S]
    npoints: np.ndarray            # int32 [S]
    time_unit: xtime.Unit = xtime.Unit.NANOSECOND  # tick scale of the streams
    checksum: int = 0
    # Seal-time boundary metadata (tsz.boundary_metadata): lets a later
    # adjacent block be appended by scan-free bit concat without decoding
    # this one. None for blocks paged in from disk — those merge via the
    # decode fallback.
    boundary: Optional[dict] = None

    def __post_init__(self):
        self.gen = next(_GEN)
        if self.checksum == 0:
            self.checksum = zlib.adler32(np.ascontiguousarray(self.words).tobytes())

    @property
    def num_series(self) -> int:
        return len(self.series_indices)

    def row_checksums(self) -> np.ndarray:
        """adler32 of every series' packed stream, int64 [S] — the ONE
        definition of the per-row checksum convention that repair local
        compare, the peer metadata tiles RPC, and `row_checksum` all
        share (divergent re-implementations would silently report
        permanent replica divergence). Memoized: blocks are immutable
        once published, and repair sweeps + metadata pages re-read it
        every cycle."""
        sums = getattr(self, "_row_sums", None)
        if sums is None:
            sums = adler32_rows(self.words) if len(self.words) \
                else np.zeros(0, np.int64)
            sums.setflags(write=False)
            self._row_sums = sums
        return sums

    def row_checksum(self, row: int) -> int:
        """adler32 of one series' packed stream (the unit of repair/peer
        metadata comparison, persist/fs write.go per-entry checksum)."""
        return int(self.row_checksums()[row])

    def _verify_rows(self) -> None:
        """Lazy serve-time integrity. Blocks paged in from a fileset
        carry the index's recorded per-row adler32s (`expected_row_sums`,
        attached by FilesetReader.to_block); the FIRST read through this
        block object compares them against checksums computed from the
        bytes actually mapped. Verified once per generation — the flag
        rides the block object, so the hot path pays one vectorized
        adler pass per paged-in block, then two getattr lookups per
        read. Divergence raises typed CorruptionError naming the rotten
        rows so the serving layer can quarantine the fileset; nothing
        bit-flipped is ever returned."""
        expected = getattr(self, "expected_row_sums", None)
        if expected is None or getattr(self, "_rows_verified", False):
            return
        expected = np.asarray(expected)
        actual = self.row_checksums()
        if actual.shape == expected.shape and bool((actual == expected).all()):
            self._rows_verified = True
            _CORRUPTION.counter("serve_verified").inc()
            return
        from ..persist.diskio import CorruptionError

        if actual.shape == expected.shape:
            bad = [int(b) for b in np.flatnonzero(actual != expected)]
        else:
            bad = list(range(self.num_series))
        ids = getattr(self, "expected_row_ids", None) or []
        _CORRUPTION.counter("serve_verify_failed").inc()
        raise CorruptionError(
            f"row checksum mismatch on read: {len(bad)} row(s) in block "
            f"{self.block_start}",
            path=getattr(self, "source_path", None), rows=bad,
            ids=[ids[b] for b in bad if b < len(ids)])

    def row_of(self, series_idx: int) -> Optional[int]:
        i = int(np.searchsorted(self.series_indices, series_idx))
        if i < len(self.series_indices) and self.series_indices[i] == series_idx:
            return i
        return None

    def rows_of(self, idxs, top: int
                ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """The rows that hold registry indices `idxs` (an array or a
        list of ints whose largest is `top`), resolved in one step:
        (rows, present), where `present` masks the `idxs` this block
        holds and is None where it holds them all. A block whose sorted
        indices end at their own length holds every index below it, each
        in its own row: `idxs` itself is handed back, no array made."""
        si = self.series_indices
        held = len(si)
        if held and si[-1] == held - 1 and top < held:
            return idxs, None
        if not held:
            return idxs[:0], np.zeros(len(idxs), bool)
        at = np.minimum(si.searchsorted(idxs), held - 1)
        present = si[at] == idxs
        return at[present], present

    def take(self, rows: np.ndarray, series_indices: np.ndarray
             ) -> "SealedBlock":
        """The block of these rows alone, under `series_indices` (sorted):
        what one encode over many shards' series is cut into, a block a
        shard. Seal-time boundary metadata goes with its rows."""
        boundary = self.boundary
        if boundary is not None:
            boundary = {k: v[rows] for k, v in boundary.items()}
        return SealedBlock(
            block_start=self.block_start, window=self.window,
            series_indices=np.asarray(series_indices, np.int32),
            words=self.words[rows], nbits=self.nbits[rows],
            npoints=self.npoints[rows], time_unit=self.time_unit,
            boundary=boundary)

    def read(self, series_idx: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Decode one series' datapoints (the per-row read: a miss is a
        decode of this row alone, padded to the smallest rung).

        Consults the device block cache first: a hot block's decoded
        planes are resident (admission after repeated touches), turning
        the per-series read into a row slice with no decode launch.

        Returned arrays are READ-ONLY on every path (cache hits hand out
        views of shared planes; the miss path freezes to keep the
        contract observable cold — the query layer already treats fetch
        results as immutable throughout)."""
        self._verify_rows()
        row = self.row_of(series_idx)
        if row is None:
            return None
        cache = block_cache.active()
        if cache is not None:
            dec = cache.decoded(self, row_read=True)
            if dec is not None:
                n = int(self.npoints[row])
                return dec[0][row, :n], dec[1][row, :n]
        ts, vals, calls = decode_rows(
            self.words[row : row + 1], self.npoints[row : row + 1],
            self.window, self.time_unit.nanos)
        count_cold(1, calls)
        n = int(self.npoints[row])
        t_out, v_out = ts[0, :n], vals[0, :n]
        t_out.setflags(write=False)
        v_out.setflags(write=False)
        return t_out, v_out

    def read_all(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Decode every series in one batched launch: (ts [S, W], vals, npoints).

        Hot blocks serve from the device block cache; cold blocks decode
        via _decode_plane. The planes are READ-ONLY
        on every path (cache hits share them across readers — the
        fetch-result immutability contract the query layer already
        relies on; the cold path freezes so the contract is observable
        before a block turns hot)."""
        self._verify_rows()
        cache = block_cache.active()
        if cache is not None:
            dec = cache.decoded(self)
            if dec is not None:
                return dec[0], dec[1], self.npoints
        ts, vals = self._decode_plane()
        return ts, vals, self.npoints

    def _decode_plane(self, encoded: Optional[tuple] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Whole-block decode to (ts_ns [S, W], vals [S, W]) through
        `decode_rows`: the programs of a geometry's cold reads serve its
        blocks too, and merge/repair paths decode blocks of arbitrary
        series counts without per-count recompiles.

        `encoded` is the cache's retained device (words, padded npoints)
        from the seal-time encode: decoding from it skips the H2D
        re-upload of the stream words entirely (decode is
        row-independent, so rows [:S] are bit-identical either way). It
        is used where the encode's row padding is a rung of ROW_BUCKETS
        (a block of 5 to 1,024 rows); the block's own words are uploaded
        otherwise, so a geometry's decodes stay the rungs' programs.
        Planes come back read-only: they may be cache-shared across
        readers."""
        s = len(self.series_indices)
        words, npoints = self.words, self.npoints
        if encoded is not None and np.shape(encoded[0])[0] in ROW_BUCKETS:
            words, npoints = encoded
        ts, vals, _calls = decode_rows(words, npoints, self.window,
                                       self.time_unit.nanos)
        ts, vals = ts[:s], vals[:s]
        ts.setflags(write=False)
        vals.setflags(write=False)
        return ts, vals

    def nbytes(self) -> int:
        return int(self.words.nbytes)


_COLD_ROWS = ROOT.counter("storage.read.cold_rows")
_COLD_DISPATCHES = ROOT.counter("storage.read.cold_dispatches")


def count_cold(rows: int, calls: int, acc=None):
    """A node's rows that no cache held and the decode calls they took
    (the row read above, storage/read_batch.py's sweep; a session's
    rows are not a node's cold rows). `acc` (a detailed span) receives
    `cold_rows_n` and `cold_dispatch_n`."""
    _COLD_ROWS.inc(rows)
    _COLD_DISPATCHES.inc(calls)
    if acc is not None:
        acc.add_cost("cold_rows_n", rows)
        acc.add_cost("cold_dispatch_n", calls)


def _next_pow2(n: int, floor: int = 8) -> int:
    n = max(n, floor)
    return 1 << (n - 1).bit_length()


def encode_block(block_start: int, series_indices, tdense, vdense, npoints,
                 max_words: Optional[int] = None, min_rows: int = 0
                 ) -> SealedBlock:
    """Batch-encode dense tiles (from ShardBuffer.drain) into a SealedBlock.

    Tiles are padded to power-of-two (series, window) geometry so XLA
    re-uses one compiled kernel across shards/blocks instead of compiling
    per exact shape (shape bucketing; padding columns replicate the last
    point, padding rows are npoints=1 dummies sliced away afterwards).
    `min_rows`: rows are padded as if there were at least so many — a
    snapshot gives its shard's series count, so a bucket that a scrape
    has only begun to fill (the first after every block boundary) takes
    the program of a full one and not a row shape of its own, compiled
    inside the tick; the encoded rows are the same bits either way.

    On a multi-device platform the encode routes through the shard x time
    mesh (parallel.ingest.flush_encode_prepared): rows shard across every
    attached device and the output bitstreams are bit-identical to the
    single-device encode — this is the serving flush path's use of the
    mesh (Shard._tick_locked seals, mediator snapshots), closing the gap
    where make_sharded_ingest was exercised only by dryrun/bench."""
    s, w = tdense.shape
    # One span per encoded block when somebody is tracing (the
    # mediator's tick, a traced request); its phases are costs.
    with tracing.child_span("encode.block", series=s, window=w) as sp:
        if sp.sampled:
            sp.set_tag("device", dscope.device_tag())
        return _encode_block(block_start, series_indices, tdense, vdense,
                             npoints, max_words, min_rows)


def _encode_block(block_start: int, series_indices, tdense, vdense, npoints,
                  max_words: Optional[int], min_rows: int = 0) -> SealedBlock:
    s, w = tdense.shape
    wp = _next_pow2(w)
    sp = _next_pow2(max(s, min_rows), floor=1)
    with tracing.phase("pad"):
        if wp != w:
            padc_t = np.repeat(tdense[:, -1:], wp - w, axis=1)
            padc_v = np.repeat(vdense[:, -1:], wp - w, axis=1)
            tdense = np.concatenate([tdense, padc_t], axis=1)
            vdense = np.concatenate([vdense, padc_v], axis=1)
        npoints = np.asarray(npoints, np.int32)
        if sp != s:
            tdense = np.concatenate(
                [tdense, np.repeat(tdense[:1], sp - s, axis=0)])
            vdense = np.concatenate(
                [vdense, np.repeat(vdense[:1], sp - s, axis=0)])
            npoints = np.concatenate([npoints, np.ones(sp - s, np.int32)])
    window = wp
    with tracing.phase("prepare"):
        unit = choose_time_unit(tdense)
        mw = max_words if max_words is not None else tsz.max_words_for(window)
        inp = tsz.prepare_encode_inputs(tdense // unit.nanos, vdense, npoints)
    # the encode's enqueue is `dispatch_ns` (parallel/guard.py)
    got = par_ingest.flush_encode_prepared(inp, max_words=mw)
    if got is not None:
        words, nbits = got
        _FLUSH_METRICS.counter("mesh_encode").inc()
    else:
        words, nbits = tsz.encode_prepared(inp, max_words=mw)
    with tracing.phase("prepare"):
        boundary = tsz.boundary_metadata(inp)
    # Keep the just-encoded DEVICE buffers (padded [sp, mw] words + padded
    # npoints — exactly what a later whole-block decode consumes) for the
    # device block cache: the seal hook (Shard._tick_locked) adopts them
    # via retain_encoded, so warm reads decode without re-uploading what
    # this encode just produced on the mesh. Transient blocks (snapshots,
    # merge intermediates) that nobody retains drop the handle with the
    # block object.
    encoded_dev = None
    if block_cache.wants_encoded():
        encoded_dev = (words, np.asarray(npoints, np.int32))
    with tracing.phase("device_wait"):     # the device finishes, then D2H
        words = np.asarray(words)[:s]
        nbits = np.asarray(nbits)[:s]
    tracing.count_cost("d2h_bytes", words.nbytes + nbits.nbytes)
    # Every pack backend silently drops bits past max_words; an undersized
    # caller-supplied bound would seal truncated, undecodable streams.
    tsz.check_cursor(nbits, mw)
    npoints = npoints[:s]
    boundary = {k: v[:s] for k, v in boundary.items()}
    blk = SealedBlock(
        block_start=block_start,
        window=window,
        series_indices=np.asarray(series_indices, np.int32),
        words=np.asarray(words),
        nbits=np.asarray(nbits),
        npoints=np.asarray(npoints, np.int32),
        time_unit=unit,
        boundary=boundary,
    )
    if encoded_dev is not None:
        blk._encoded_dev = encoded_dev
    return blk


def merge_sealed_blocks(b1: SealedBlock, b2: SealedBlock) -> SealedBlock:
    """Compact two time-adjacent sealed blocks into one (block compaction;
    the reference's fs merge re-encodes point streams — here series present
    in both blocks ride the scan-free concat fast path when eligible, see
    m3_tpu/ops/tsz_concat.py). b2 must start at or after b1's window end.

    Series in only one input copy through untouched. Requires b1's
    seal-time boundary metadata and a shared time unit; otherwise both
    blocks are decoded and re-encoded wholesale."""
    from ..ops import bits64 as b64
    from ..ops import tsz_concat

    if b1.block_start >= b2.block_start:
        raise ValueError("merge_sealed_blocks: blocks must be time-ordered")
    if b1.boundary is None or b1.time_unit != b2.time_unit:
        return _merge_by_full_recode(b1, b2)

    window = b1.window + b2.window
    max_words = tsz.max_words_for(window)
    union = np.union1d(b1.series_indices, b2.series_indices)
    r1 = np.searchsorted(b1.series_indices, union)
    r2 = np.searchsorted(b2.series_indices, union)
    in1 = (r1 < len(b1.series_indices)) & \
        (b1.series_indices[np.minimum(r1, len(b1.series_indices) - 1)] == union)
    in2 = (r2 < len(b2.series_indices)) & \
        (b2.series_indices[np.minimum(r2, len(b2.series_indices) - 1)] == union)

    words = np.zeros((len(union), max_words), np.uint32)
    nbits = np.zeros(len(union), np.int32)
    npoints = np.zeros(len(union), np.int32)

    only1 = in1 & ~in2
    only2 = ~in1 & in2
    for only, blk, rows in ((only1, b1, r1), (only2, b2, r2)):
        src = rows[only]
        w = blk.words[src]
        words[only, :w.shape[1]] = w[:, :max_words]
        nbits[only] = blk.nbits[src]
        npoints[only] = blk.npoints[src]

    both = in1 & in2
    same_epoch = np.ones(len(union), bool)
    if both.any():
        i1, i2 = r1[both], r2[both]
        h1 = tsz_concat.parse_header(b1.words[i1])
        h2 = tsz_concat.parse_header(b2.words[i2])
        t0_2 = np.asarray(b64.to_u64_np(*(np.asarray(a) for a in h2["t0"]))
                          ).astype(np.int64)
        gap = t0_2 - b1.boundary["last_ticks"][i1]
        if (np.abs(gap) >= 2**31).any():
            # The DoD payload is 32-bit: a gap this wide cannot be encoded
            # in one stream at this time unit (prepare_encode_inputs raises
            # the same way on the ingest path).
            raise ValueError(
                "merge_sealed_blocks: inter-block gap exceeds int32 ticks")
        boundary_dt = gap.astype(np.int32)
        stale = ~b1.boundary.get(
            "valid", np.ones(len(b1.series_indices), bool))[i1]
        mw, mnb = tsz_concat.merge_adjacent(
            b1.words[i1], b1.nbits[i1], b1.npoints[i1],
            b2.words[i2], b2.nbits[i2], b2.npoints[i2], boundary_dt,
            b64.from_u64_np(b1.boundary["last_v_bits"][i1]),
            b64.from_u64_np(b1.boundary["last_vdelta_bits"][i1]),
            half_window=max(b1.window, b2.window), max_words=max_words,
            force_recode=stale)
        words[both] = mw
        nbits[both] = mnb
        npoints[both] = b1.npoints[i1] + b2.npoints[i2]
        same_epoch[both] = np.asarray(
            (h1["int_mode"] == h2["int_mode"]) & (h1["k"] == h2["k"]))
        # When b2 contributed exactly ONE point, b2's sealed
        # last_vdelta_bits is 0 (there is no intra-b2 value delta), but the
        # MERGED stream's final value-delta is m2[0] - m1[last] — the
        # boundary delta the merge just encoded. Copying b2's 0 verbatim
        # would make a later concat of the compacted block encode the next
        # double-delta against 0 while the decoder's prev_vdelta register
        # (ref_codec int-mode codes are stateful double-deltas) holds the
        # true delta, silently corrupting values. Recompute it from b1's
        # seal metadata where trustworthy; rows with stale b1 metadata are
        # pushed onto the recode path of the NEXT merge instead.
        single2 = b2.npoints[i2] < 2
        m0_2 = b64.to_u64_np(*(np.asarray(a) for a in h2["v0"]))
        fixed_vdelta = np.where(
            np.asarray(h2["int_mode"]),
            (m0_2.astype(np.int64)
             - b1.boundary["last_v_bits"][i1].astype(np.int64)
             ).view(np.uint64),
            np.uint64(0))
        vdelta_trusted = ~stale & single2

    boundary2 = None
    if b2.boundary is not None:
        boundary2 = {}
        for key in ("last_ticks", "last_v_bits", "last_vdelta_bits"):
            col = np.zeros(len(union), b2.boundary[key].dtype)
            col[in2] = b2.boundary[key][r2[in2]]
            if b1.boundary is not None:
                col[only1] = b1.boundary[key][r1[only1]]
            boundary2[key] = col
        valid = np.zeros(len(union), bool)
        valid[in2] = b2.boundary.get(
            "valid", np.ones(len(b2.series_indices), bool))[r2[in2]]
        if b1.boundary is not None:
            valid[only1] = b1.boundary.get(
                "valid", np.ones(len(b1.series_indices), bool))[r1[only1]]
        # Epoch-mismatched rows were re-encoded with fresh mode detection:
        # b2's stream-space metadata no longer describes the merged stream.
        valid &= same_epoch
        if both.any():
            u_both = np.flatnonzero(both)
            boundary2["last_vdelta_bits"][u_both[vdelta_trusted]] = \
                fixed_vdelta[vdelta_trusted]
            valid[u_both[single2 & stale]] = False
        boundary2["valid"] = valid

    return SealedBlock(
        block_start=b1.block_start, window=window,
        series_indices=union.astype(np.int32), words=words, nbits=nbits,
        npoints=npoints, time_unit=b1.time_unit, boundary=boundary2)


def _merge_by_full_recode(b1: SealedBlock, b2: SealedBlock) -> SealedBlock:
    """General fallback: decode both blocks and re-encode the union."""
    t1, v1, n1 = b1.read_all()
    t2, v2, n2 = b2.read_all()
    union = np.union1d(b1.series_indices, b2.series_indices)
    w = b1.window + b2.window
    ts = np.zeros((len(union), w), np.int64)
    vs = np.zeros((len(union), w), np.float64)
    npts = np.zeros(len(union), np.int32)
    for i, sid in enumerate(union):
        t_parts, v_parts = [], []
        for blk, t, v, n in ((b1, t1, v1, n1), (b2, t2, v2, n2)):
            row = blk.row_of(int(sid))
            if row is not None:
                t_parts.append(t[row, : n[row]])
                v_parts.append(v[row, : n[row]])
        tt = np.concatenate(t_parts)
        vv = np.concatenate(v_parts)
        npts[i] = tt.size
        ts[i, : tt.size] = tt
        vs[i, : tt.size] = vv
        if tt.size < w:
            ts[i, tt.size:] = tt[-1]
            vs[i, tt.size:] = vv[-1]
    return encode_block(b1.block_start, union.astype(np.int32), ts, vs, npts)


def merge_same_start(b1: SealedBlock, b2: SealedBlock) -> SealedBlock:
    """Merge two sealed blocks covering the SAME block start into one
    (an insert-queue drain racing tick can land late writes for a block
    start that already sealed; the re-seal must union, not overwrite).

    b2 is the later arrival: on duplicate (series, timestamp) pairs its
    value wins, matching the buffer's last-arrival-wins drain dedup."""
    if b1.block_start != b2.block_start:
        raise ValueError("merge_same_start: blocks must share a block start")
    t1, v1, n1 = b1.read_all()
    t2, v2, n2 = b2.read_all()
    union = np.union1d(b1.series_indices, b2.series_indices)
    parts_t: List[np.ndarray] = []
    parts_v: List[np.ndarray] = []
    npts = np.zeros(len(union), np.int32)
    for i, sid in enumerate(union):
        tt_parts, vv_parts = [], []
        for blk, t, v, n in ((b1, t1, v1, n1), (b2, t2, v2, n2)):
            row = blk.row_of(int(sid))
            if row is not None:
                tt_parts.append(t[row, : n[row]])
                vv_parts.append(v[row, : n[row]])
        tt = np.concatenate(tt_parts)
        vv = np.concatenate(vv_parts)
        # Stable sort by time keeps b1-then-b2 arrival order within a
        # duplicate timestamp; keep the LAST arrival per timestamp.
        order = np.argsort(tt, kind="stable")
        tt, vv = tt[order], vv[order]
        if len(tt) > 1:
            keep = np.concatenate([tt[:-1] != tt[1:], [True]])
            tt, vv = tt[keep], vv[keep]
        npts[i] = tt.size
        parts_t.append(tt)
        parts_v.append(vv)
    w = int(npts.max(initial=1))
    ts = np.zeros((len(union), w), np.int64)
    vs = np.zeros((len(union), w), np.float64)
    for i, (tt, vv) in enumerate(zip(parts_t, parts_v)):
        ts[i, : tt.size] = tt
        vs[i, : tt.size] = vv
        if tt.size < w:  # pad with the last real point (codec contract)
            ts[i, tt.size:] = tt[-1]
            vs[i, tt.size:] = vv[-1]
    return encode_block(b1.block_start, union.astype(np.int32), ts, vs, npts)


class WiredList:
    """Capacity-bounded LRU over blocks paged in from disk
    (block/wired_list.go:77): evicts least-recently-read whole blocks.
    Thread-safe — serving threads share one list."""

    def __init__(self, max_bytes: int = 1 << 30):
        import threading

        self.max_bytes = max_bytes
        self._items: "OrderedDict[Tuple, SealedBlock]" = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()

    def get(self, key) -> Optional[SealedBlock]:
        with self._lock:
            blk = self._items.get(key)
            if blk is not None:
                self._items.move_to_end(key)
            return blk

    def put(self, key, blk: SealedBlock):
        # Invalidation goes through get_cache(), not active(): dropping
        # residency must happen even while a thread is inside a
        # block_cache.disabled() bypass.
        cache = block_cache.get_cache()
        with self._lock:
            if key in self._items:
                self._items.move_to_end(key)
                return
            self._items[key] = blk
            self._bytes += blk.nbytes()
            while self._bytes > self.max_bytes and len(self._items) > 1:
                _, old = self._items.popitem(last=False)
                self._bytes -= old.nbytes()
                # An unwired block can never be read again (the next
                # retrieve builds a NEW block/generation): drop its
                # decoded residency too.
                cache.invalidate_block(old)

    def drop(self, pred) -> int:
        """Remove entries whose key matches `pred` (fileset invalidation)."""
        cache = block_cache.get_cache()
        with self._lock:
            doomed = [k for k in self._items if pred(k)]
            for k in doomed:
                old = self._items.pop(k)
                self._bytes -= old.nbytes()
                cache.invalidate_block(old)
            return len(doomed)

    def __len__(self):
        with self._lock:
            return len(self._items)
