"""Sharded ingest pipeline: the framework's flagship device program.

One "step" is the per-shard hot path of the reference's write+flush loop
(src/dbnode/storage/series/buffer.go:178 Write -> m3tsz encoder, and
src/aggregator/aggregator/generic_elem.go:264 Consume) executed as a single
XLA program over a whole shard of series at once:

  (N series x W points) -> M3TSZ-compressed bitstreams
                         + 1m rollup moments + block-level moments + quantiles

Multi-chip layout (SPMD via shard_map over a Mesh):
  axis "shard": data-parallel over series — the TPU expression of the
      reference's murmur3 virtual-shard partitioning
      (src/dbnode/sharding/shardset.go:76). No cross-series communication.
  axis "time": sequence-parallel over block windows — the TPU expression of
      the reference's time-partitioned blocks (series/buffer.go:51 rotating
      block buckets). Each device encodes its own block (blocks are
      independent bitstreams by design, exactly like the reference's sealed
      blocks), while block-spanning aggregates are merged with ICI
      collectives: psum for moments, pmin/pmax for extremes, ppermute-free
      `last` resolution by taking the final time chunk's value.

This is why the design is TPU-first rather than a port: the reference
serialises per-series encoder state behind mutexes; here the only sequential
state (the Gorilla leading/meaningful window) lives in a lax.scan carry while
series ride vector lanes and shards/blocks ride the mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import guard as pguard
from . import scope as dscope
from . import telemetry
from ..ops import aggregation as agg
from ..ops import bits64 as b64
from ..ops import tsz


class IngestBatch(NamedTuple):
    """Device inputs for one shard x block-window ingest step.

    Leading dims: [T, N, W] = (time chunks, series, points-per-chunk) for the
    sharded path; [N, W] single-chip. Produced by `make_example_batch` /
    m3_tpu.ops.tsz.prepare_encode_inputs.
    """

    dt: jax.Array        # int32 [..., W] timestamp deltas, first col 0
    t0_hi: jax.Array     # u32 [...] first-timestamp high word
    t0_lo: jax.Array     # u32 [...]
    vhi: jax.Array       # u32 [..., W] value bits (f64 or int64 m)
    vlo: jax.Array       # u32 [..., W]
    int_mode: jax.Array  # bool [...]
    k: jax.Array         # int32 [...] decimal exponent
    npoints: jax.Array   # int32 [...] valid points
    ts_regular: jax.Array  # bool [...] all deltas equal delta0
    delta0: jax.Array    # int32 [...] common scrape interval (ticks)
    values: jax.Array    # f32 [..., W] raw values for aggregation


def ingest_step(batch: IngestBatch, *, rollup_factor: int, max_words: int, quantile_qs=(0.5, 0.99)):
    """Single-chip ingest: encode one block + rollup/aggregate its window.

    Returns (words u32 [N, max_words], nbits i32 [N], rollup stats dict
    [N, W//factor], block stats dict [N], quantiles [N, W//factor, Q]).
    """
    words, nbits = tsz.encode_batch(
        batch.dt,
        (batch.t0_hi, batch.t0_lo),
        batch.vhi,
        batch.vlo,
        batch.int_mode,
        batch.k,
        batch.npoints,
        batch.ts_regular,
        batch.delta0,
        max_words=max_words,
    )
    w = batch.values.shape[-1]
    mask = jnp.arange(w, dtype=jnp.int32) < batch.npoints[..., None]
    roll = agg.rollup_stats(batch.values, mask, rollup_factor)
    blk = agg.window_stats(batch.values, mask)
    qs = agg.rollup_quantiles(batch.values, mask, rollup_factor, quantile_qs)
    return words, nbits, roll, blk, qs


class RawIngestBatch(NamedTuple):
    """Raw device inputs for the fused prep+encode ingest step:
    INTERLEAVED u32-pair views of the int64 timestamps / f64 value bits —
    the exact memory the host already holds. Host cost to build one: two
    zero-copy views (make_raw_batch, ~0ms); the hi/lo split is a strided
    slice fused into the encode program and the f32 aggregation values
    are derived on device by exact RNE bit conversion
    (bits64.f64_bits_to_f32), so no host pass touches the data at all
    (was ~440ms of splits + cast per 100k x 120 block) and the f32 plane
    never crosses H2D."""

    ts_pairs: jax.Array  # u32 [N, W, 2] raw int64 bytes, native order
    v_pairs: jax.Array   # u32 [N, W, 2] raw f64 bytes, native order
    npoints: jax.Array   # int32 [N]


# THE endianness decision lives in bits64 (shared with from_u64_np).
_HI = b64.PAIR_HI


def make_raw_batch(ts: np.ndarray, values: np.ndarray,
                   npoints: np.ndarray) -> RawIngestBatch:
    """Zero-cost host prep for ingest_step_raw: two zero-copy pair views —
    the hi/lo split, the f32 value derivation, and all delta/int-mode/
    mantissa work happens on device."""
    return RawIngestBatch(
        b64.pair_view_np(np.asarray(ts, np.int64)),
        b64.pair_view_np(np.asarray(values, np.float64)),
        np.asarray(npoints, np.int32))


def ingest_step_raw(raw: RawIngestBatch, *, rollup_factor: int,
                    max_words: int, quantile_qs=(0.5, 0.99)):
    """Fused prep+encode+aggregate from raw inputs: ONE XLA program covers
    what prepare_encode_inputs did on the host plus ingest_step's device
    work. Returns ingest_step's outputs plus a range_ok bool scalar (the
    device twin of the host prep's int32 delta/DoD ValueErrors — callers
    must check it once per block)."""
    lo = 1 - _HI
    vhi_raw, vlo_raw = raw.v_pairs[..., _HI], raw.v_pairs[..., lo]
    prep, range_ok = tsz.prepare_on_device_math(
        raw.ts_pairs[..., _HI], raw.ts_pairs[..., lo],
        vhi_raw, vlo_raw, raw.npoints)
    # f32 aggregation values from the ORIGINAL f64 bits (prep rewrites
    # vhi/vlo to extracted mantissas for int-mode series).
    values32 = b64.f64_bits_to_f32(vhi_raw, vlo_raw)
    batch = IngestBatch(
        dt=prep["dt"], t0_hi=prep["t0"][0], t0_lo=prep["t0"][1],
        vhi=prep["vhi"], vlo=prep["vlo"], int_mode=prep["int_mode"],
        k=prep["k"], npoints=prep["npoints"],
        ts_regular=prep["ts_regular"], delta0=prep["delta0"],
        values=values32)
    return (*ingest_step(batch, rollup_factor=rollup_factor,
                         max_words=max_words, quantile_qs=quantile_qs),
            range_ok)


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """Build the ("shard", "time") device mesh.

    Time-axis size 2 when the device count allows (>=4 and even), exercising
    sequence parallelism; otherwise all devices go to the shard axis.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    devices = np.asarray(devices[:n_devices])
    t = 2 if n_devices >= 4 and n_devices % 2 == 0 else 1
    return Mesh(devices.reshape(n_devices // t, t), ("shard", "time"))


def _make_flush_mesh(sc) -> Mesh | None:
    import os

    if os.environ.get("M3_TPU_MESH_FLUSH", "1") == "0":
        return None
    devices = sc.devices
    if len(devices) <= 1:
        return None
    return make_mesh(devices=list(devices))


def flush_mesh() -> Mesh | None:
    """The serving flush's shard x time mesh: make_mesh() over every
    device of the calling thread's scope (parallel/scope.py: every
    attached device, for a service that was given none) when >1 is
    present, else None (single-device platforms, and a service that
    owns one chip, keep the plain jit path). M3_TPU_MESH_FLUSH=0
    disables mesh routing for A/B comparison (write_smoke uses it to
    prove bit-equality against the single-device encode, and forgets
    the mesh it saw before: `scope.DEFAULT.clear("flush_mesh")`)."""
    return dscope.current().owned("flush_mesh", _make_flush_mesh)


@telemetry.jit_builder("flush_encoder")
@functools.lru_cache(maxsize=32)
def make_flush_encoder(mesh: Mesh, max_words: int):
    """The serving-flush encode as a shard_map program over the
    shard x time mesh: sealed-block rows (series) are data-parallel, so
    they shard across BOTH mesh axes — every attached device encodes its
    slice of the block with the same kernel the single-device path runs,
    and the results are bit-identical by construction (encode_batch is
    row-independent; no collectives are needed). This is
    make_sharded_ingest's mesh carrying the REAL flush path
    (storage/block.py encode_block -> Shard._tick_locked /
    mediator.snapshot), not just the dryrun/bench ingest program."""
    rows = P(("shard", "time"))
    rowc = P(("shard", "time"), None)

    def local_encode(dt, t0_hi, t0_lo, vhi, vlo, int_mode, k, npoints,
                     ts_regular, delta0):
        from ..ops import tsz

        return tsz.encode_batch(
            dt, (t0_hi, t0_lo), vhi, vlo, int_mode, k, npoints,
            ts_regular, delta0, max_words=max_words)

    fn = jax.shard_map(
        local_encode, mesh=mesh,
        in_specs=(rowc, rows, rows, rowc, rowc, rows, rows, rows, rows,
                  rows),
        out_specs=(rowc, rows), check_vma=False)
    return jax.jit(fn)


def flush_encode_prepared(inp: dict, max_words: int):
    """Route prepared encode inputs (ops.tsz.prepare_encode_inputs)
    through the shard x time mesh. Returns (words, nbits) — bit-identical
    to the single-device encode — or None when no mesh is attached, the
    padded row count does not divide it (caller falls back to the plain
    path; encode_block's power-of-two row padding makes most real blocks
    divisible), or the tile is below the dispatch floor
    (M3_TPU_MESH_FLUSH_MIN_CELLS, default 2048): a tiny seal costs more
    in multi-device dispatch than the parallel encode saves."""
    import os

    mesh = flush_mesh()
    if mesh is None:
        return None
    shape = np.asarray(inp["dt"]).shape
    n = shape[0]
    ndev = mesh.devices.size
    if n < ndev or n % ndev:
        return None
    min_cells = int(os.environ.get("M3_TPU_MESH_FLUSH_MIN_CELLS", "2048"))
    if n * shape[1] < min_cells:
        return None
    def _mesh_encode():
        enc = make_flush_encoder(mesh, max_words)
        telemetry.mesh_dispatch("flush_encode", cells=int(n * shape[1]))
        return enc(inp["dt"], inp["t0"][0], inp["t0"][1], inp["vhi"],
                   inp["vlo"], inp["int_mode"], inp["k"], inp["npoints"],
                   inp["ts_regular"], inp["delta0"])

    # Guarded dispatch: a device fault here degrades to the plain
    # single-device encode by returning None — the caller consumes ONLY
    # this function's return value, so a mid-dispatch fault leaves
    # nothing partially applied (the PR 5 all-or-nothing seal contract
    # holds under injected faults; acked writes still seal via the
    # fallback path).
    return pguard.dispatch("flush_encode", _mesh_encode, lambda _err: None)


def make_sharded_ingest(mesh: Mesh, *, rollup_factor: int, max_words: int, quantile_qs=(0.5, 0.99)):
    """Build the jitted multi-chip ingest step over `mesh`.

    Inputs carry a leading time-chunk axis T == mesh "time" size: dt/vhi/vlo/
    values are [T, N, W_chunk], per-series headers [T, N]. Outputs: compressed
    words stay sharded in place ([T, N, MW], one block per time chunk, exactly
    the reference's per-blockstart fileset layout persist/fs/write.go:53);
    whole-window stats are merged across the time axis with collectives and
    replicated over it.
    """
    chunk = P("time", "shard", None)
    per_series = P("time", "shard")
    merged = P("shard")

    def local_step(dt, t0_hi, t0_lo, vhi, vlo, int_mode, k, npoints,
                   ts_regular, delta0, values):
        # Each device sees [1, N_local, W_chunk]: its own block of its shard.
        squeeze = lambda a: a.reshape(a.shape[1:])
        batch = IngestBatch(*(squeeze(a) for a in (
            dt, t0_hi, t0_lo, vhi, vlo, int_mode, k, npoints, ts_regular,
            delta0, values)))
        words, nbits, roll, blk, qtl = ingest_step(
            batch, rollup_factor=rollup_factor, max_words=max_words, quantile_qs=quantile_qs
        )

        # Cross-block merge over the sequence axis (ICI collectives).
        whole = {
            "sum": jax.lax.psum(blk["sum"], "time"),
            "sumsq": jax.lax.psum(blk["sumsq"], "time"),
            "count": jax.lax.psum(blk["count"], "time"),
            "min": jax.lax.pmin(blk["min"], "time"),
            "max": jax.lax.pmax(blk["max"], "time"),
        }
        # Centered second moment across chunks (generalized Chan merge):
        # m2_tot = sum_i m2_i + sum_i n_i*(mean_i - mean_tot)^2.
        mean_tot = jnp.where(whole["count"] > 0, whole["sum"] / jnp.maximum(whole["count"], 1), 0.0)
        dmu = jnp.where(blk["count"] > 0, agg.mean(blk) - mean_tot, 0.0)
        whole["m2"] = jax.lax.psum(blk["m2"] + blk["count"] * dmu * dmu, "time")
        # `last` comes from the latest chunk holding data; gather per-chunk
        # lasts and counts along the time axis and select the last non-empty.
        lasts = jax.lax.all_gather(blk["last"], "time")          # [T, N_local]
        counts = jax.lax.all_gather(blk["count"], "time")
        t_idx = jnp.arange(lasts.shape[0])[:, None]
        last_t = jnp.where(counts > 0, t_idx, -1).max(axis=0)
        whole["last"] = jnp.take_along_axis(lasts, jnp.maximum(last_t, 0)[None, :], axis=0)[0]
        firsts = jax.lax.all_gather(blk["first"], "time")
        first_t = jnp.where(counts > 0, t_idx, lasts.shape[0]).min(axis=0)
        whole["first"] = jnp.take_along_axis(
            firsts, jnp.minimum(first_t, lasts.shape[0] - 1)[None, :], axis=0
        )[0]

        # Global compressed-bits total (for bytes/datapoint accounting):
        # psum over both mesh axes, replicated scalar out.
        total_bits = jax.lax.psum(jax.lax.psum(nbits.sum(), "time"), "shard")

        expand = lambda a: a.reshape((1,) + a.shape)
        return (
            expand(words),
            expand(nbits),
            jax.tree.map(expand, roll),
            jax.tree.map(expand, qtl),
            whole,
            total_bits,
        )

    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(chunk, per_series, per_series, chunk, chunk, per_series,
                  per_series, per_series, per_series, per_series, chunk),
        out_specs=(chunk, per_series, chunk, chunk, merged, P()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_example_raw(n: int, tw: int, rng: np.random.Generator,
                     start=1_600_000_000):
    """Synthetic raw shard data shaped like production metrics: regular 10s
    timestamps, mixed int-optimizable gauges/counters and true floats.
    Returns (timestamps int64 [n, tw], values f64 [n, tw], npoints [n])."""
    # Timestamps: scrape-style regular 10s interval; ~5% of series see
    # per-point jitter (mirrors the production workload behind the
    # reference's 1.45 bytes/datapoint figure, where delta-of-delta is
    # overwhelmingly zero — docs/m3db/architecture/engine.md:20-24).
    jittered = rng.random((n, 1)) < 0.05
    jitter = np.where(jittered, rng.integers(0, 3, size=(n, tw)), 0)
    ts = np.int64(start) + np.arange(tw, dtype=np.int64)[None, :] * 10 + jitter
    ts = np.maximum.accumulate(ts, axis=1)
    # Values: 40% counters (steady rates, occasional step), 40% integer
    # gauges (slow random walk, frequently flat), 20% float gauges.
    kind = rng.integers(0, 5, size=(n, 1))
    base = rng.integers(0, 1000, size=(n, 1)).astype(np.float64)
    rate = rng.integers(1, 20, size=(n, 1)).astype(np.float64)
    steps = rate + np.where(rng.random((n, tw)) < 0.05, rng.integers(-3, 4, size=(n, tw)), 0)
    counters = base + np.cumsum(steps, axis=1)
    moves = np.where(rng.random((n, tw)) < 0.2, rng.integers(-2, 3, size=(n, tw)), 0)
    gauges = base + np.cumsum(moves, axis=1).astype(np.float64)
    floats = base + np.cumsum(moves, axis=1) * 0.1 + rng.standard_normal((n, tw)) * 1e-3
    values = np.where(kind <= 1, counters, np.where(kind <= 3, gauges, floats))
    return ts, values, np.full(n, tw, np.int32)


def make_batch_from_raw(ts2: np.ndarray, v2: np.ndarray,
                        npoints: np.ndarray) -> IngestBatch:
    """Host prep: raw (timestamps, values) -> device-ready IngestBatch."""
    inp = tsz.prepare_encode_inputs(ts2, v2, npoints)
    return IngestBatch(
        dt=inp["dt"],
        t0_hi=inp["t0"][0],
        t0_lo=inp["t0"][1],
        vhi=inp["vhi"],
        vlo=inp["vlo"],
        int_mode=inp["int_mode"],
        k=inp["k"],
        npoints=inp["npoints"],
        ts_regular=inp["ts_regular"],
        delta0=inp["delta0"],
        values=v2.astype(np.float32),
    )


def make_example_batch(n: int, w: int, rng: np.random.Generator, *, chunks: int | None = None, start=1_600_000_000):
    """Synthetic shard batch: make_example_raw + host prep, optionally split
    into `chunks` leading time chunks for the sharded [T, N, W] layout."""
    t_chunks = chunks or 1
    ts, values, _ = make_example_raw(n, t_chunks * w, rng, start=start)

    def prep(ts2, v2):
        return make_batch_from_raw(
            ts2, v2, np.full(ts2.shape[0], ts2.shape[1], np.int32))

    if chunks is None:
        return prep(ts, values)
    parts = [prep(ts[:, i * w : (i + 1) * w], values[:, i * w : (i + 1) * w]) for i in range(t_chunks)]
    return IngestBatch(*(np.stack(cols) for cols in zip(*parts)))


def shard_batch(batch: IngestBatch, mesh: Mesh) -> IngestBatch:
    """Place an example [T, N, ...] batch onto the mesh with ingest shardings."""
    chunk = NamedSharding(mesh, P("time", "shard", None))
    per_series = NamedSharding(mesh, P("time", "shard"))
    specs = IngestBatch(
        dt=chunk, t0_hi=per_series, t0_lo=per_series, vhi=chunk, vlo=chunk,
        int_mode=per_series, k=per_series, npoints=per_series,
        ts_regular=per_series, delta0=per_series, values=chunk,
    )
    # DELIBERATE raw put (mesh staging for the dryrun/bench ingest step):
    # the placed batch is the program input the caller immediately
    # consumes; per-example staging is not resident-cache memory.
    return IngestBatch(*(jax.device_put(a, s) for a, s in zip(batch, specs)))  # m3lint: disable=unbudgeted-device-put
