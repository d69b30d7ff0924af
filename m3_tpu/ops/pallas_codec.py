"""Pallas TPU kernels for the codec floor (reference: the bit-twiddling
inner loops of src/dbnode/encoding/m3tsz/encoder.go and the
stack-allocated murmur3 fork under src/dbnode/sharding).

Three kernels, one dispatch gate:

  pack_chunks   the m3tsz bit-packing inner loop: per-slot <=96-bit code
                chunks concatenate into packed rows in ONE pass with a
                running bit cursor per lane. Series ride the 128 vector
                lanes; each tile's chunk words and the packed output stay
                in VMEM for the whole slot loop (no HBM round-trip per
                merge level, unlike the XLA tree's log2(S) materialized
                stages). Bit-identical to _pack_scatter/_pack_segments:
                the same four shifted words per chunk, OR'd at the same
                cursor, with past-the-end words dropped by the dense
                word-window mask instead of scatter mode="drop".

  decode_core   the decode point scan with the stream words VMEM-resident
                per lane tile. Reuses tsz._decode_header/_decode_step
                verbatim — the wire format has ONE definition — swapping
                only the bit readers for a dense masked select over the
                word column (Mosaic's sublane gather serves one vreg).
                Emits the same dt/tick/value planes as tsz._decode_core
                so the fused decode consumers are route-agnostic.

  hash_words    batched murmur3-32 over the hash_batch buffer layout
                (zero-padded little-endian u32 rows), lane-parallel with
                per-lane active masks; bit-identical to hashing.murmur3_32.

Shape of every kernel here: an lru_cached `_build(..., interpret)` seam,
BlockSpec lane tiles, interpret-mode parity on CPU. The codec loops walk
a data-dependent bit cursor, so nothing is unrolled over a static
stride. Rows are read from the Refs
at a dynamic sublane (`ref[pl.ds(j, 1), :]`) and per-lane addressing is
a masked select, never `lax.dynamic_slice`/`take_along_axis` on loaded
values: the installed Pallas TPU lowering has no rule for the former and
Mosaic refuses the latter past one vreg.

Dispatch: `enabled()` gates every call site (M3_TPU_PALLAS=1 opt-in
off-TPU where kernels run in interpret mode; on-by-default on a TPU
backend; =0 is the kill switch — the XLA paths remain complete). What
proves the kernels, at which depth:
  tests/test_codec_pallas.py   interpret-mode bit-identity vs the XLA
                               route and ops/ref_codec.py (the algebra)
  tests/test_pallas_lowering.py  they build for a v5e from the CPU
                               (lowering rules + Mosaic legalization)
  chip_smoke.py                compiled on the chip: bit-identity vs the
                               XLA twins and ref_codec at served shapes
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import bits64 as b64
from .bits64 import U32

I32 = jnp.int32

# Interpret-mode parity against the XLA path and ref_codec lives in
# (tests/test_pallas_lowering.py builds the same kernels for TPU):
_PALLAS_ORACLE = "tests/test_codec_pallas.py"

_LANES = 128  # series per grid tile, riding the vector lanes
# hash_words bound: beyond this many padded u32 columns per ID the VMEM
# tile stops paying for itself and hash_batch keeps its numpy path.
HASH_MAX_COLS = 512

_C1 = 0xCC9E2D51
_C2 = 0x1B873593


def enabled() -> bool:
    """Dispatch gate for the Pallas codec kernels.

    M3_TPU_PALLAS=1 forces them on (interpret mode off-TPU — the parity
    /CI configuration), =0 is the kill switch, unset enables them only
    when the default backend is a real TPU."""
    v = os.environ.get("M3_TPU_PALLAS")
    if v:
        return v == "1"
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def route(kernel: str, pallas: bool) -> None:
    """Record one codec dispatch (telemetry.codec.{pallas,xla}_<kernel>).
    Lazy import keeps this module a pure ops leaf at import time."""
    from ..parallel import telemetry

    telemetry.codec_route(kernel, pallas)


def compile_recorded(kernel: str, seconds: float) -> None:
    from ..parallel import telemetry

    telemetry.codec_compile_recorded(kernel, seconds)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _tiles_for(n: int) -> int:
    return _ceil_to(max(n, 1), _LANES) // _LANES


# ---------------------------------------------------------------------------
# encode: one-pass bit packing with a running cursor per lane
# ---------------------------------------------------------------------------


def _pack_kernel(c0_ref, c1_ref, c2_ref, nb_ref, out_ref, *, n_slots, mwp):
    """OR each slot's four cursor-shifted words into the packed rows.

    Per slot j and lane cursor `cur`, the chunk words c0..c2 (left-aligned
    <=96 bits) shift right by cur%32 into four candidate words s0..s3 and
    land at word cur//32 + 0..3 — exactly _pack_scatter's splice with the
    implicit fourth chunk word zero. The scatter becomes a dense masked OR
    over the word window (rel == j), which vectorizes on the VPU instead
    of serializing; words past the padded bound simply never match."""
    wiota = jax.lax.broadcasted_iota(I32, (mwp, _LANES), 0)

    def body(j, state):
        cur, acc = state
        row = pl.ds(j, 1)
        a0 = c0_ref[row, :]
        a1 = c1_ref[row, :]
        a2 = c2_ref[row, :]
        nb = nb_ref[row, :]
        cb = (cur & 31).astype(U32)
        inv = U32(32) - cb
        s0 = b64._shr32(a0, cb)
        s1 = b64._shr32(a1, cb) | b64._shl32(a0, inv)
        s2 = b64._shr32(a2, cb) | b64._shl32(a1, inv)
        s3 = b64._shl32(a2, inv)
        rel = wiota - (cur >> 5)
        z = jnp.zeros_like(s0)
        add = (jnp.where(rel == 0, s0, z) | jnp.where(rel == 1, s1, z)
               | jnp.where(rel == 2, s2, z) | jnp.where(rel == 3, s3, z))
        return cur + nb, acc | add

    cur0 = jnp.zeros((1, _LANES), I32)
    acc0 = jnp.zeros((mwp, _LANES), jnp.uint32)
    _, acc = jax.lax.fori_loop(0, n_slots, body, (cur0, acc0))
    out_ref[...] = acc


@functools.lru_cache(maxsize=64)
def _build_pack(sp, mwp, tiles, interpret):
    return pl.pallas_call(
        functools.partial(_pack_kernel, n_slots=sp, mwp=mwp),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((sp, _LANES), lambda i: (0, i))] * 4,
        out_specs=pl.BlockSpec((mwp, _LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((mwp, tiles * _LANES), jnp.uint32),
        interpret=interpret,
    )


def pack_chunks(sc, snb, max_words):
    """Pallas drop-in for _pack_scatter/_pack_segments (traceable; runs
    inside the jitted encode program). sc: 3-list u32 [N, S] left-aligned
    chunks, snb: int32 [N, S] bit lengths -> u32 [N, max_words]."""
    n, s = snb.shape
    sp = _ceil_to(s, 8)
    mwp = _ceil_to(max_words, 8)
    tiles = _tiles_for(n)
    npad = tiles * _LANES - n
    c = [jnp.pad(x.T, ((0, sp - s), (0, npad))) for x in sc]
    nb = jnp.pad(snb.T.astype(I32), ((0, sp - s), (0, npad)))
    out = _build_pack(sp, mwp, tiles, _interpret())(c[0], c[1], c[2], nb)
    return out[:max_words, :n].T


# ---------------------------------------------------------------------------
# decode: the point scan with VMEM-resident stream words
# ---------------------------------------------------------------------------


def _decode_kernel(words_ref, npts_ref, dt_ref, tshi_ref, tslo_ref,
                   vhi_ref, vlo_ref, *, window, mw):
    """Header parse + point loop, storing one output row per point.

    The bit readers clamp word indices to the UNPADDED stream width `mw`
    (matching tsz._take_word exactly, so speculative reads past the
    stream end see the same words on both routes); the lazy tsz import
    runs at trace time and avoids a module-level cycle."""
    from . import tsz as _tsz

    # i32 view: the Pallas TPU lowering has no unsigned reductions.
    words = jax.lax.bitcast_convert_type(words_ref[...], I32)
    npts = npts_ref[...]
    wiota = jax.lax.broadcasted_iota(I32, words.shape, 0)

    def take(wi):
        # Per-lane word pick as a dense masked select + sublane reduce:
        # Mosaic's dynamic_gather serves one source vreg along the gather
        # axis, and the word column spans mwp/8 of them. Exactly one row
        # matches the clamped index, so the (wrapping) sum is the gather.
        hit = wiota == jnp.clip(wi, 0, mw - 1)
        picked = jnp.sum(jnp.where(hit, words, 0), axis=0, keepdims=True)
        return jax.lax.bitcast_convert_type(picked, jnp.uint32)

    def read32(pos):
        wi = pos >> 5
        bi = (pos & 31).astype(U32)
        return b64._shl32(take(wi), bi) | b64._shr32(take(wi + 1),
                                                     U32(32) - bi)

    def read64(pos):
        return read32(pos), read32(pos + 32)

    def read96(pos):
        wi = pos >> 5
        bi = (pos & 31).astype(U32)
        inv = U32(32) - bi
        w0, w1 = take(wi), take(wi + 1)
        w2, w3 = take(wi + 2), take(wi + 3)
        return (b64._shl32(w0, bi) | b64._shr32(w1, inv),
                b64._shl32(w1, bi) | b64._shr32(w2, inv),
                b64._shl32(w2, bi) | b64._shr32(w3, inv))

    zero = jnp.zeros((1, _LANES), I32)
    hdr = _tsz._decode_header(read32, read64, zero)
    t0, v0 = hdr["t0"], hdr["v0"]
    int_mode, ts_regular = hdr["int_mode"], hdr["ts_regular"]
    dt_ref[0:1, :] = zero
    tshi_ref[0:1, :] = t0[0]
    tslo_ref[0:1, :] = t0[1]
    vhi_ref[0:1, :] = v0[0]
    vlo_ref[0:1, :] = v0[1]
    zu = jnp.zeros((1, _LANES), U32)
    neg1 = jnp.full((1, _LANES), -1, I32)
    init = (hdr["pos0"], jnp.where(ts_regular, hdr["delta0"], zero),
            zu, zu, v0[0], v0[1], neg1, neg1, neg1, neg1, t0[0], t0[1])

    def body(i, carry):
        carry2, (d, th, tl, vh, vl) = _tsz._decode_step(
            read32, read64, read96, npts, int_mode, ts_regular, carry, i)
        dt_ref[pl.ds(i, 1), :] = d
        tshi_ref[pl.ds(i, 1), :] = th
        tslo_ref[pl.ds(i, 1), :] = tl
        vhi_ref[pl.ds(i, 1), :] = vh
        vlo_ref[pl.ds(i, 1), :] = vl
        return carry2

    jax.lax.fori_loop(1, window, body, init)


@functools.lru_cache(maxsize=64)
def _build_decode(mwp, mw, wp, window, tiles, interpret):
    ospec = pl.BlockSpec((wp, _LANES), lambda i: (0, i))
    dts = (jnp.int32, jnp.uint32, jnp.uint32, jnp.uint32, jnp.uint32)
    return pl.pallas_call(
        functools.partial(_decode_kernel, window=window, mw=mw),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((mwp, _LANES), lambda i: (0, i)),
                  pl.BlockSpec((1, _LANES), lambda i: (0, i))],
        out_specs=[ospec] * 5,
        out_shape=[jax.ShapeDtypeStruct((wp, tiles * _LANES), d)
                   for d in dts],
        interpret=interpret,
    )


def decode_core(words, npoints, *, window):
    """Pallas twin of tsz._decode_core (traceable; runs inside the fused
    decode program). Same return dict: dt [N, W] i32, ts/vhi/vlo u32
    planes, int_mode/k/t0 per series."""
    from . import tsz as _tsz

    n, mw = words.shape
    mwp = _ceil_to(mw, 8)
    wp = _ceil_to(window, 8)
    tiles = _tiles_for(n)
    npad = tiles * _LANES - n
    wt = jnp.pad(words.T, ((0, mwp - mw), (0, npad)))
    npts = jnp.pad(npoints.astype(I32)[None, :], ((0, 0), (0, npad)))
    fn = _build_decode(mwp, mw, wp, window, tiles, _interpret())
    dt, tshi, tslo, vhi, vlo = (a[:window, :n].T for a in fn(wt, npts))
    # Header-derived scalars re-parse on the XLA side: three clamped
    # gathers per series, vs threading five more outputs through the grid.
    zero = jnp.zeros((n,), I32)
    hdr = _tsz._decode_header(functools.partial(_tsz._read32, words),
                              functools.partial(_tsz._read64, words), zero)
    return {"dt": dt, "ts": (tshi, tslo), "vhi": vhi, "vlo": vlo,
            "int_mode": hdr["int_mode"], "k": hdr["k"], "t0": hdr["t0"]}


# ---------------------------------------------------------------------------
# hash: lane-parallel murmur3-32 over padded ID rows
# ---------------------------------------------------------------------------


def _rotl(x, r: int):
    return (x << U32(r)) | (x >> U32(32 - r))


def _hash_kernel(w_ref, len_ref, out_ref, *, cols, seed):
    """Columnwise murmur3 block mix with per-lane active masks, then the
    tail/finalizer — the hash_batch numpy loop verbatim, words on
    sublanes and IDs on lanes. Tail bytes come from the word at index
    nblocks: the buffer is zero past each row's length by construction,
    and every tail byte is additionally gated on tail_len."""
    lens = len_ref[...]
    nblocks = lens >> 2
    # Tail word = the word at index nblocks (clamped like the numpy
    # twin's index bound); picked up inside the block loop by a per-lane
    # select instead of a sublane gather Mosaic cannot lower.
    tail_at = jnp.minimum(nblocks, cols - 1)
    h0 = jnp.full((1, _LANES), np.uint32(seed), jnp.uint32)

    def body(j, state):
        h, tw = state
        kw0 = w_ref[pl.ds(j, 1), :]
        tw = jnp.where(tail_at == j, kw0, tw)
        kw = _rotl(kw0 * U32(_C1), 15) * U32(_C2)
        h2 = _rotl(h ^ kw, 13) * U32(5) + U32(0xE6546B64)
        return jnp.where(nblocks > j, h2, h), tw

    h, tw = jax.lax.fori_loop(0, cols, body, (h0, jnp.zeros_like(h0)))
    tl = lens & 3
    z = jnp.zeros_like(h)
    k = jnp.where(tl >= 3, ((tw >> U32(16)) & U32(0xFF)) << U32(16), z)
    k = jnp.where(tl >= 2, k ^ (((tw >> U32(8)) & U32(0xFF)) << U32(8)), k)
    has = tl >= 1
    k = jnp.where(has, k ^ (tw & U32(0xFF)), k)
    k = _rotl(k * U32(_C1), 15) * U32(_C2)
    h = jnp.where(has, h ^ k, h)
    h = h ^ lens.astype(jnp.uint32)
    h = h ^ (h >> U32(16))
    h = h * U32(0x85EBCA6B)
    h = h ^ (h >> U32(13))
    h = h * U32(0xC2B2AE35)
    out_ref[...] = h ^ (h >> U32(16))


@functools.lru_cache(maxsize=64)
def _build_hash(cp, tiles, seed, interpret):
    return jax.jit(pl.pallas_call(
        functools.partial(_hash_kernel, cols=cp, seed=seed),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((cp, _LANES), lambda i: (0, i)),
                  pl.BlockSpec((1, _LANES), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, _LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, tiles * _LANES), jnp.uint32),
        interpret=interpret,
    ))


_HASH_TIMED: set = set()


def hash_words(words: np.ndarray, lens: np.ndarray, seed: int = 0) -> np.ndarray:
    """Murmur3-32 over hash_batch's padded buffer: words u32 [N, C]
    little-endian rows zero-padded past each length, lens [N] byte
    lengths. Returns np.uint32 [N], bit-identical to murmur3_32. Owns
    its jit boundary (unlike pack/decode, which trace inside the codec
    programs), so first-call compile time is recorded here."""
    n, c = words.shape
    cp = _ceil_to(max(c, 1), 8)
    tiles = _tiles_for(n)
    wt = np.zeros((cp, tiles * _LANES), np.uint32)
    wt[:c, :n] = words.T
    lp = np.zeros((1, tiles * _LANES), np.int32)
    lp[0, :n] = lens
    interp = _interpret()
    key = (cp, tiles, int(seed), interp)
    t0 = time.perf_counter() if key not in _HASH_TIMED else None
    out = np.asarray(_build_hash(cp, tiles, int(seed), interp)(wt, lp))
    if t0 is not None:
        _HASH_TIMED.add(key)
        compile_recorded("hash", time.perf_counter() - t0)
    return out[0, :n]
