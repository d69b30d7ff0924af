"""Mesh-sharded aggregator flush reduce: the aggregation tier's device
program (ROADMAP item 4; the same shard_map pattern PR 5 proved for
seal-time flush encode and PR 9 for plan fan-in).

One flush round batches the staged closed windows of EVERY aggregation
shard (Aggregator.flush gathers across shards and resolutions) into one
padded (rows x width) f32 tile, and the O(W log W) work — exact
sort-based timer quantile ordering (ops/aggregation.quantile_rank_select)
— runs as ONE shard_map'd program with the rows partitioned over every
attached device (both mesh axes, the make_flush_encoder layout). Rows
are independent, so no collectives are needed and the mesh result is
bit-identical to the single-device jit by construction; the host then
lands the exact float64 quantile values with one columnar gather by the
returned indices (aggregator/list.py emit_batch).

Moments stay in the host-exact f64 columnar pass (np.reduceat in
aggregator/list.py): the bit-exactness oracle contract — every emitted
moment equals the reference's float64 accumulator output — cannot be
met by f32 device reductions, and PR 9's residual/baseline
decomposition is exact only for integer-valued counters, not the
arbitrary f64 gauges/timers this tier aggregates. The ordering work the
device IS exact at (ranks, not sums) is what ships here; measured, the
moments pass is a single-digit percentage of flush cost while the sort
dominates the timer path.
"""

from __future__ import annotations

import functools
import os

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from . import guard as pguard
from . import telemetry
from .ingest import flush_mesh
from ..ops import aggregation as agg
from ..utils import numwatch

# Pad the value axis to lane multiples to limit recompiles. MUST match
# aggregator/list.py's _LANE: the oracle's single-device tile and the
# mesh tile quantize width identically, so a NaN-bearing row (whose
# in-row inf-padding count is order-visible to the stable argsort)
# selects the same element on both routes.
LANE = 128


@telemetry.jit_builder("agg_flush_reducer")
@functools.lru_cache(maxsize=64)
def make_mesh_rank_selector(mesh, width: int, qs: tuple):
    """Quantile rank selection as a shard_map program over the
    shard x time mesh: tile rows (one staged window each) are
    data-parallel, so they shard across BOTH mesh axes — every attached
    device orders its slice of the flush with the same kernel the
    single-device path runs (ops/aggregation.quantile_rank_select), and
    the indices are bit-identical by construction (row-independent, no
    collectives)."""
    rows = P(("shard", "time"))
    rowc = P(("shard", "time"), None)

    def local_select(values, counts):
        return agg.quantile_rank_select(values, counts, qs)

    fn = jax.shard_map(local_select, mesh=mesh, in_specs=(rowc, rows),
                       out_specs=rowc, check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _single_rank_selector(width: int, qs: tuple):
    return jax.jit(
        lambda values, counts: agg.quantile_rank_select(values, counts, qs))


def quantile_rank_rows(tile: np.ndarray, counts: np.ndarray,
                       qs: tuple) -> np.ndarray:
    """Dispatch the flush's quantile ordering: the shard x time mesh when
    one is attached, the tile divides it (rows pad with count-0 windows)
    and the tile is above the dispatch floor (M3_TPU_MESH_AGG_MIN_CELLS,
    default 2048 — a tiny flush costs more in multi-device dispatch than
    the parallel sort saves); otherwise the single-device jit. Returns
    [B, len(qs)] i32 in-row indices, identical on every route."""
    n, width = tile.shape
    mesh = flush_mesh()
    min_cells = int(os.environ.get("M3_TPU_MESH_AGG_MIN_CELLS", "2048"))
    if mesh is not None and n * width >= min_cells:
        orig_tile, orig_counts = tile, counts
        ndev = mesh.devices.size
        pad = (-n) % ndev
        if pad:
            tile = np.concatenate(
                [tile, np.zeros((pad, width), tile.dtype)])
            counts = np.concatenate([counts, np.zeros(pad, counts.dtype)])

        def _mesh_select():
            telemetry.mesh_dispatch("agg_flush", cells=int(tile.size))
            sel = make_mesh_rank_selector(mesh, width, qs)
            return np.asarray(sel(tile, counts))[:n]

        def _single_select(_err):
            # The single-device jit is bit-identical by construction
            # (row-independent, same kernel) — the proven fallback when
            # the mesh program faults or its breaker is open. Runs on the
            # UNpadded tile; nothing was partially applied (the flush
            # consumes only this function's return value).
            return np.asarray(
                _single_rank_selector(width, qs)(orig_tile, orig_counts))

        return pguard.dispatch("agg_flush", _mesh_select, _single_select)
    return np.asarray(_single_rank_selector(width, qs)(tile, counts))


def build_quantile_tile(buckets, counts: np.ndarray):
    """Pad a ragged bucket list into the [B, width] f32 tile the rank
    selector consumes, width quantized to LANE multiples of the max
    bucket length (the same rule as the oracle's _quantile_rows_for).
    One vectorized scatter fills the tile — no per-row Python assignment
    — from the same concatenation the exact-value gather reuses.
    Returns (tile f32, cat f64, starts i64): cat/starts locate each
    row's exact f64 values for the post-ordering host gather."""
    max_n = max(1, int(counts.max()))
    width = ((max_n + LANE - 1) // LANE) * LANE
    sizes = np.maximum(counts, 1)
    starts = np.zeros(len(buckets), dtype=np.int64)
    starts[1:] = np.cumsum(sizes)[:-1]
    safe = [b if b.size else np.zeros(1) for b in buckets]
    cat = np.concatenate(safe)
    tile = np.zeros((len(buckets), width), dtype=np.float32)
    total = int(sizes.sum())
    within = np.arange(total, dtype=np.int64) - np.repeat(starts, sizes)
    rows = np.repeat(np.arange(len(buckets), dtype=np.int64), sizes)
    flat = rows * width + within
    tile.ravel()[flat] = cat.astype(np.float32)
    # zero-size rows scattered a placeholder 0 into column 0; their
    # count is 0 so the selector never reads it, and the gather below
    # guards count==0 explicitly.
    return tile, cat, starts


def exact_quantile_values(buckets, counts: np.ndarray, qs: tuple):
    """Timer quantile ordering end-to-end: build the tile, order on
    device (mesh-sharded when attached), then ONE columnar host gather
    of the exact f64 values by index. Returns [B, len(qs)] f64, rows
    with count 0 all-zero (stream.go:145-146 empty convention)."""
    tile, cat, starts = build_quantile_tile(buckets, counts)
    idx = quantile_rank_rows(tile, counts.astype(np.int32), qs)
    safe_idx = np.minimum(idx.astype(np.int64),
                          np.maximum(counts - 1, 0)[:, None])
    vals = cat[starts[:, None] + safe_idx]
    vals[counts == 0] = 0.0
    if numwatch.installed():
        # Numerics witness: live rows (count > 0) carry the gathered
        # exact values; count-0 rows must be exactly zero (the
        # stream.go:145-146 empty convention) — a non-zero there means
        # a padding row's ordering index leaked into the gather.
        numwatch.observe_rows("agg_flush", vals, counts > 0)
    return vals
