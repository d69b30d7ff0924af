"""Batched TTSZ codec: N series encode/decode as single XLA programs on TPU.

This is the north-star kernel replacing the reference's per-datapoint scalar
hot loop (src/dbnode/encoding/m3tsz/encoder.go:113 Encode,
iterator.go:78 Next) with data-parallel device code. Wire format is defined by
m3_tpu/ops/ref_codec.py (the scalar oracle); these kernels are bit-exact
against it.

Encode strategy (no sequential bit cursor):
  1. All per-point code words ("chunks", <= 96 bits, left-aligned in 3 u32
     words) are computed vectorized over the (series, point) grid. The only
     sequential state — the Gorilla leading/meaningful-bits window
     (encoder.go:38-39 trackNewSig analog) — runs as one lax.scan over the
     window axis with all series in vector lanes.
  2. Chunks are concatenated by recursive doubling: log2(2W) dense merge
     levels, each OR-ing pairs of left-aligned bit segments after a dynamic
     right shift (bit part via carry shifts, word part via binary-decomposed
     selects). A scatter into the packed rows would serialize on TPU
     (measured ~1% of VPU throughput); the merge tree is pure vector ALU
     with the series axis riding the 128 lanes.

Decode runs a lax.scan over points with a per-series bit cursor in the carry;
all series advance in lockstep lanes with clamped dynamic gathers into their
word rows. Control flow is branchless where-selection, never Python branching,
so the whole thing jits to one XLA program.

All 64-bit math is on (hi, lo) u32 pairs — see m3_tpu/ops/bits64.py.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import bits64 as b64
from .bits64 import U32
from .ref_codec import REWRITE_THRESHOLD
from ..utils import instrument, tracing

I32 = jnp.int32

# v2 header worst case: 8 flag bits + t0 (64) in slot 0; delta0 (32) + v0
# (64) in slot 1 (see ref_codec module docstring for the layout).
HEADER_MAX_BITS = (8 + 64) + (32 + 64)
# Worst case per point: ts '1111111'+32 = 39 bits, float rewrite 3+6+6+64 = 79.
MAX_POINT_BITS = 39 + 79


class CursorOverflowError(ValueError):
    """A packed bit cursor exceeded the block's max_words bound.

    Every pack backend (scatter-OR, merge tree, Pallas) silently DROPS
    bits past max_words — scatter via mode="drop", the tree via the final
    slice, the Pallas kernel via its dense word-window mask — so an
    undersized bound would truncate streams into undecodable garbage.
    check_cursor turns that into this typed error at encode time."""


@functools.lru_cache(maxsize=None)
def max_words_for(window: int) -> int:
    """Conservative packed-words bound for a block of `window` points.

    Memoized: the per-window constants are pure arithmetic but every
    encode/merge/bench call site recomputed them; one table keeps the
    bound definitionally identical everywhere (and check_cursor asserts
    the packed cursors actually stayed under it)."""
    bits = HEADER_MAX_BITS + max(window - 1, 0) * MAX_POINT_BITS
    return (bits + 31) // 32 + 1


def check_cursor(nbits, max_words: int) -> None:
    """Assert no packed stream's final bit cursor exceeds max_words.

    Called at encode time on HOST-materialized nbits (the seal path
    fetches them anyway); raises CursorOverflowError naming the worst
    row instead of letting any pack backend truncate silently."""
    nb = np.asarray(nbits)
    if nb.size == 0:
        return
    worst = int(nb.max())
    if worst > 32 * int(max_words):
        row = int(nb.argmax())
        raise CursorOverflowError(
            f"packed cursor overflow: row {row} needs {worst} bits but "
            f"max_words={int(max_words)} holds {32 * int(max_words)}")


# ---------------------------------------------------------------------------
# chunk96: <=96-bit left-aligned code words under construction
# ---------------------------------------------------------------------------


_shl32 = b64._shl32
_shr32 = b64._shr32


def _shl96(v0, v1, v2, s):
    """Left shift a 96-bit (3xu32, big-endian) value by dynamic s in [0, 95]."""
    s = jnp.asarray(s, U32)
    r = s & U32(31)
    q = s >> U32(5)
    t0 = _shl32(v0, r) | _shr32(v1, U32(32) - r)
    t1 = _shl32(v1, r) | _shr32(v2, U32(32) - r)
    t2 = _shl32(v2, r)
    z = jnp.zeros_like(v0)
    o0 = jnp.where(q == 0, t0, jnp.where(q == 1, t1, t2))
    o1 = jnp.where(q == 0, t1, jnp.where(q == 1, t2, z))
    o2 = jnp.where(q == 0, t2, z)
    return o0, o1, o2


def chunk_empty(shape):
    z = jnp.zeros(shape, U32)
    return (z, z, z), jnp.zeros(shape, I32)


def chunk_append(chunk, cn, value_pair, vbits):
    """Append the low `vbits` (dynamic, 0..64) of value_pair to each chunk."""
    c0, c1, c2 = chunk
    vbits = jnp.asarray(vbits, I32)
    # Mask value to its low vbits (vbits==0 -> zero).
    sh = jnp.asarray(64 - vbits, U32)
    vm = b64.shr64(b64.shl64(value_pair, sh), sh)
    s = (96 - cn - vbits).astype(U32)
    p0, p1, p2 = _shl96(jnp.zeros_like(c0), vm[0], vm[1], s)
    return (c0 | p0, c1 | p1, c2 | p2), cn + vbits


def _append_u32(chunk, cn, value, vbits):
    return chunk_append(chunk, cn, (jnp.zeros_like(jnp.asarray(value, U32)), jnp.asarray(value, U32)), vbits)


# ---------------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------------


def _ts_chunks(dod, valid):
    """Timestamp DoD chunks for columns >= 1. dod, valid: [N, W].

    v2 buckets: '0' | '10'+4 | '110'+7 | '1110'+9 | '11110'+12 |
    '111110'+16 | '1111110'+20 | '1111111'+32 (two's complement payloads).
    """
    z = dod == 0
    f4 = (dod >= -8) & (dod < 8)
    f7 = (dod >= -64) & (dod < 64)
    f9 = (dod >= -256) & (dod < 256)
    f12 = (dod >= -2048) & (dod < 2048)
    f16 = (dod >= -(1 << 15)) & (dod < (1 << 15))
    f20 = (dod >= -(1 << 19)) & (dod < (1 << 19))
    sel = lambda vals: jnp.where(z, vals[0], jnp.where(f4, vals[1], jnp.where(
        f7, vals[2], jnp.where(f9, vals[3], jnp.where(f12, vals[4], jnp.where(
            f16, vals[5], jnp.where(f20, vals[6], vals[7])))))))
    ctrl = sel((0, 0b10, 0b110, 0b1110, 0b11110, 0b111110, 0b1111110, 0b1111111))
    ctrl_len = sel((1, 2, 3, 4, 5, 6, 7, 7))
    pay_len = sel((0, 4, 7, 9, 12, 16, 20, 32))
    vmask = valid.astype(I32)
    chunk, cn = chunk_empty(dod.shape)
    chunk, cn = _append_u32(chunk, cn, ctrl.astype(U32), ctrl_len * vmask)
    chunk, cn = _append_u32(chunk, cn, dod.astype(U32), pay_len * vmask)
    return chunk, cn


def _int_value_chunks(zz, valid):
    """Int-mode zigzag(vdod) chunks. zz: u32 pair [N, W].

    v2 buckets: '0' | '10'+4 | '110'+7 | '1110'+12 | '11110'+20 |
    '111110'+32 | '111111'+64.
    """
    blen = b64.bitlen64(zz)
    z = blen == 0
    f4 = blen <= 4
    f7 = blen <= 7
    f12 = blen <= 12
    f20 = blen <= 20
    f32 = blen <= 32
    sel = lambda vals: jnp.where(z, vals[0], jnp.where(f4, vals[1], jnp.where(
        f7, vals[2], jnp.where(f12, vals[3], jnp.where(f20, vals[4], jnp.where(
            f32, vals[5], vals[6]))))))
    ctrl = sel((0, 0b10, 0b110, 0b1110, 0b11110, 0b111110, 0b111111))
    ctrl_len = sel((1, 2, 3, 4, 5, 6, 6))
    pay_len = sel((0, 4, 7, 12, 20, 32, 64))
    vmask = valid.astype(I32)
    chunk, cn = chunk_empty(blen.shape)
    chunk, cn = _append_u32(chunk, cn, ctrl.astype(U32), ctrl_len * vmask)
    chunk, cn = chunk_append(chunk, cn, zz, pay_len * vmask)
    return chunk, cn


def _float_window_scan(xor_hi, xor_lo, valid):
    """Sequential two-window state over the point axis (window A = latest
    rewrite, window B = the one before; see ref_codec float-mode docs).

    Inputs [N, W] (column 0 ignored). Returns per-column (use_a, use_b,
    rewrite, lead_used, mlen_used, trail_shift) with windows threaded.
    """
    lz = b64.clz64((xor_hi, xor_lo))
    tz = b64.ctz64((xor_hi, xor_lo))
    xor0 = (xor_hi | xor_lo) == 0
    inf = I32(1 << 20)

    def step(carry, xs):
        la, ma, lb, mb = carry
        lz_i, tz_i, xor0_i, valid_i = xs
        tight = 64 - lz_i - tz_i
        fits_a = (la >= 0) & (lz_i >= la) & (tz_i >= 64 - la - ma)
        fits_b = (lb >= 0) & (lz_i >= lb) & (tz_i >= 64 - lb - mb)
        cost_a = jnp.where(fits_a, 2 + ma, inf)
        cost_b = jnp.where(fits_b, 3 + mb, inf)
        reuse_cost = jnp.minimum(cost_a, cost_b)
        live = ~xor0_i & valid_i
        # Policy must match ref_codec exactly: rewrite when nothing fits or
        # the cheapest window wastes > REWRITE_THRESHOLD bits vs tight.
        rewrite = live & (
            (reuse_cost >= inf)
            | (reuse_cost - (2 + tight) > REWRITE_THRESHOLD))
        use_a = live & ~rewrite & (cost_a <= cost_b)
        use_b = live & ~rewrite & ~use_a
        lead_used = jnp.where(rewrite, lz_i, jnp.where(use_a, la, lb))
        mlen_used = jnp.where(rewrite, tight, jnp.where(use_a, ma, mb))
        shift = 64 - lead_used - mlen_used
        la2 = jnp.where(rewrite, lz_i, la)
        ma2 = jnp.where(rewrite, tight, ma)
        lb2 = jnp.where(rewrite, la, lb)
        mb2 = jnp.where(rewrite, ma, mb)
        return (la2, ma2, lb2, mb2), (use_a, use_b, rewrite, lead_used, mlen_used, shift)

    n = xor_hi.shape[0]
    neg = jnp.full((n,), -1, I32)
    init = (neg, neg, neg, neg)
    xs = (lz.T, tz.T, xor0.T, valid.T)
    _, outs = jax.lax.scan(step, init, xs)
    use_a, use_b, rewrite, lead_used, mlen_used, shift = (o.T for o in outs)
    return use_a, use_b, rewrite, xor0, lead_used, mlen_used, shift


def _float_value_chunks(vhi, vlo, valid):
    """Float-mode XOR chunks for columns >= 1. vhi/vlo: raw f64 bits [N, W].

    v2 ctrl: '0' zero-xor | '10' reuse A | '110' reuse B | '111' rewrite.
    """
    xhi = vhi ^ jnp.roll(vhi, 1, axis=1)
    xlo = vlo ^ jnp.roll(vlo, 1, axis=1)
    use_a, use_b, rewrite, xor0, lead_u, mlen_u, shift = _float_window_scan(
        xhi, xlo, valid)
    vmask = valid.astype(I32)
    emit0 = xor0 & valid  # '0' control bit
    ctrl = jnp.where(emit0, 0, jnp.where(use_a, 0b10, jnp.where(use_b, 0b110, 0b111)))
    ctrl_len = jnp.where(emit0, 1, jnp.where(use_a, 2, 3)) * vmask
    payload = b64.shr64((xhi, xlo), shift.astype(U32))
    chunk, cn = chunk_empty(vhi.shape)
    chunk, cn = _append_u32(chunk, cn, ctrl.astype(U32), ctrl_len)
    chunk, cn = _append_u32(chunk, cn, lead_u.astype(U32), jnp.where(rewrite, 6, 0))
    chunk, cn = _append_u32(chunk, cn, (mlen_u - 1).astype(U32), jnp.where(rewrite, 6, 0))
    chunk, cn = chunk_append(chunk, cn, payload, jnp.where(xor0, 0, mlen_u) * vmask)
    return chunk, cn


def _default_pack() -> str:
    """Pack backend when the caller passes pack=None: the Pallas one-pass
    kernel when the codec kernels are enabled, else the XLA backend the
    platform favors (tree on TPU where scatters serialize, scatter-OR on
    host CPU). Resolved OUTSIDE the jitted program so M3_TPU_PALLAS flips
    take effect per call, not per trace cache."""
    from . import pallas_codec
    from ..parallel import guard

    if pallas_codec.enabled() and guard.available("codec.encode"):
        return "pallas"
    return "tree" if jax.default_backend() == "tpu" else "scatter"


_ENCODE_TIMED: set = set()


def encode_batch(dt, t0, vhi, vlo, int_mode, k, npoints, ts_regular=None,
                 delta0=None, *, max_words, pack=None):
    """Encode a batch of series blocks (wire format v2, see ref_codec).

    Args:
      dt: int32 [N, W] timestamp deltas, dt[:, 0] == 0.
      t0: (hi, lo) u32 [N] first timestamps.
      vhi, vlo: u32 [N, W] values — raw f64 bits (float mode) or two's
        complement int64 of m = rint(v * 10^k) (int mode).
      int_mode: bool [N]; k: int32 [N] decimal exponent.
      npoints: int32 [N] valid points per series (>= 1).
      ts_regular: bool [N] — every valid delta equals delta0, so per-point
        timestamp codes are omitted (None -> computed here).
      delta0: int32 [N] — dt[:, 1] where npoints > 1 else 0 (None -> computed).
      max_words: static output row width in u32 words.
      pack: "tree" (recursive-doubling concat, the XLA TPU path — scatters
        serialize there), "scatter" (cumsum + scatter-OR, faster on host
        CPU where scatters are cheap), or "pallas" (the one-pass VMEM
        bit-cursor kernel, ops/pallas_codec). None selects by dispatch
        gate + backend; all three are bit-identical.

    Returns: (words u32 [N, max_words], nbits int32 [N]).

    This host-level dispatcher resolves the route, counts it, and calls
    the jitted program with `pack` static. Under an enclosing trace
    (e.g. the fuzz harness jits this whole function) the telemetry fires
    once per trace rather than per call — routes still prove dispatch.
    """
    if pack is None:
        pack = _default_pack()
    from ..parallel import telemetry

    telemetry.codec_route("encode", pack == "pallas")
    traced = isinstance(dt, jax.core.Tracer)
    # isinstance() is a host-side type test — it never concretizes the
    # tracer; the branch exists precisely to SKIP host timing under an
    # enclosing trace.
    if pack == "pallas" and not traced:  # m3lint: disable=jax-traced-branch
        from ..parallel import guard

        def _pallas_encode():
            key = (tuple(dt.shape), int(max_words))
            timed = key not in _ENCODE_TIMED
            if timed:
                _ENCODE_TIMED.add(key)
                t_start = time.perf_counter()
            out = _encode_batch(dt, t0, vhi, vlo, int_mode, k, npoints,
                                ts_regular, delta0, max_words=max_words,
                                pack=pack)
            if timed:
                jax.block_until_ready(out)
                telemetry.codec_compile_recorded(
                    "encode", time.perf_counter() - t_start)
            return out

        def _xla_encode(_err):
            # The XLA twin is bit-identical by contract (the property
            # corpus proves all three packs equal) — the proven fallback
            # when the Pallas kernel faults or its breaker is open.
            xla_pack = ("tree" if jax.default_backend() == "tpu"
                        else "scatter")
            return _encode_batch(dt, t0, vhi, vlo, int_mode, k, npoints,
                                 ts_regular, delta0, max_words=max_words,
                                 pack=xla_pack)

        return guard.dispatch("codec.encode", _pallas_encode, _xla_encode)
    return _encode_batch(dt, t0, vhi, vlo, int_mode, k, npoints,
                         ts_regular, delta0, max_words=max_words, pack=pack)


@functools.partial(jax.jit, static_argnames=("max_words", "pack"))
def _encode_batch(dt, t0, vhi, vlo, int_mode, k, npoints, ts_regular=None,
                  delta0=None, *, max_words, pack):
    n, w = dt.shape
    cols = jnp.arange(w, dtype=I32)[None, :]
    valid = (cols < npoints[:, None]) & (cols >= 1)

    if delta0 is None:
        delta0 = jnp.where(npoints > 1, dt[:, 1] if w > 1 else 0, 0).astype(I32)
    if ts_regular is None:
        ts_regular = jnp.where(valid, dt == delta0[:, None], True).all(axis=1)

    # Timestamp chunks (suppressed entirely for regular series).
    dod = dt - jnp.roll(dt, 1, axis=1)
    ts_chunk, ts_bits = _ts_chunks(dod, valid & ~ts_regular[:, None])

    # Int-mode value chunks: vdod of m.
    m = (vhi, vlo)
    mprev = (jnp.roll(vhi, 1, axis=1), jnp.roll(vlo, 1, axis=1))
    vdelta = b64.sub64(m, mprev)
    col0 = cols == 0
    vdelta = (jnp.where(col0, 0, vdelta[0]), jnp.where(col0, 0, vdelta[1]))
    vdelta_prev = (jnp.roll(vdelta[0], 1, axis=1), jnp.roll(vdelta[1], 1, axis=1))
    vdelta_prev = (jnp.where(col0, 0, vdelta_prev[0]), jnp.where(col0, 0, vdelta_prev[1]))
    zz = b64.zigzag64(b64.sub64(vdelta, vdelta_prev))
    int_chunk, int_bits = _int_value_chunks(zz, valid)

    # Float-mode value chunks.
    flt_chunk, flt_bits = _float_value_chunks(vhi, vlo, valid)

    im = int_mode[:, None]
    val_chunk = tuple(jnp.where(im, ic, fc) for ic, fc in zip(int_chunk, flt_chunk))
    val_bits = jnp.where(im, int_bits, flt_bits)

    # Header chunks in slots 0 (ts stream) and 1 (value stream) of column 0:
    # slot 0 = 8 flag bits + t0, slot 1 = [delta0] + v0 (ref_codec layout).
    ones = jnp.ones((n,), I32)
    t0zz = b64.zigzag64(t0)
    t0c = (t0zz[0] != 0).astype(I32)
    dzz = b64.zigzag64(b64.i32_to_pair(delta0))
    dc = (ts_regular & (dzz[1] >= 256)).astype(I32)
    m0zz = b64.zigzag64((vhi[:, 0], vlo[:, 0]))
    vc = (int_mode & (m0zz[0] != 0)).astype(I32)
    imode = int_mode.astype(U32)
    flags = (
        (imode << 7) | (k.astype(U32) << 4) | (ts_regular.astype(U32) << 3)
        | (t0c.astype(U32) << 2) | (vc.astype(U32) << 1) | dc.astype(U32)
    )
    hdr0, hn0 = chunk_empty((n,))
    hdr0, hn0 = _append_u32(hdr0, hn0, flags, 8 * ones)
    hdr0, hn0 = chunk_append(hdr0, hn0, t0zz, 32 + 32 * t0c)
    hdr1, hn1 = chunk_empty((n,))
    hdr1, hn1 = chunk_append(
        hdr1, hn1, dzz, ts_regular.astype(I32) * (8 + 24 * dc))
    v0pair = tuple(jnp.where(int_mode, a, b)
                   for a, b in zip(m0zz, (vhi[:, 0], vlo[:, 0])))
    v0bits = jnp.where(int_mode, 32 + 32 * vc, 64)
    hdr1, hn1 = chunk_append(hdr1, hn1, v0pair, v0bits)

    # Interleave into slot arrays [N, 2W]: slot 2i = ts chunk of point i,
    # slot 2i+1 = value chunk (point 0 slots carry the header).
    def interleave(a, b):
        return jnp.stack([a, b], axis=2).reshape(n, 2 * w)

    sc = []
    for j in range(3):
        ts_j = ts_chunk[j].at[:, 0].set(hdr0[j])
        val_j = val_chunk[j].at[:, 0].set(hdr1[j])
        sc.append(interleave(ts_j, val_j))
    snb = interleave(ts_bits.at[:, 0].set(hn0), val_bits.at[:, 0].set(hn1))

    total = jnp.sum(snb, axis=1)
    if pack == "pallas":
        from . import pallas_codec

        out = pallas_codec.pack_chunks(sc, snb, max_words)
    elif pack == "tree":
        out = _pack_segments(sc, snb, max_words)
    else:
        out = _pack_scatter(sc, snb, max_words)
    return out, total


def _pack_scatter(sc, snb, max_words):
    """Cumsum bit offsets + scatter-OR each shifted chunk into place.

    The natural formulation on backends with fast scatters (host CPU);
    on TPU scatters serialize — use _pack_segments there.
    """
    n = snb.shape[0]
    offs = jnp.cumsum(snb, axis=1) - snb
    bofs = (offs & 31).astype(U32)
    wofs = offs >> 5
    c = sc + [jnp.zeros_like(sc[0])]
    out = jnp.zeros((n, max_words), U32)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], offs.shape)
    for j in range(4):
        prev = c[j - 1] if j > 0 else jnp.zeros_like(c[0])
        sh = _shr32(c[j], bofs) | _shl32(prev, U32(32) - bofs)
        out = out.at[rows, wofs + j].add(sh, mode="drop")
    return out


def _pack_segments(sc, snb, max_words):
    """Concatenate per-slot variable-length bit segments into packed rows.

    sc: 3-list of u32 [N, S] (left-aligned <=96-bit chunks), snb: int32
    [N, S] bit lengths. Returns u32 [N, max_words].

    Recursive-doubling concatenation: pairs of adjacent segments merge at
    each of log2(S) levels, b shifted right by len(a) bits and OR'd in.
    Per-level capacity follows the worst-case bits a merged segment can
    hold (header slots + covered points), so early levels stay narrow.
    All arrays keep the series axis minor so it rides the vector lanes;
    the word axis lives in sublanes where static shifts are cheap.
    """
    n, S = snb.shape
    G = 1 << (S - 1).bit_length()
    B = jnp.stack([c.T for c in sc], axis=1)            # [S, 3, N]
    B = jnp.pad(B, ((0, G - S), (0, 0), (0, 0)))
    L = jnp.pad(snb.T.astype(I32), ((0, G - S), (0, 0)))  # [G, N]
    C = 3
    level = 0
    while B.shape[0] > 1:
        level += 1
        # Worst-case merged-segment bits: the first segment carries both
        # header slots plus 2^(level-1) - 1 full points.
        maxbits = HEADER_MAX_BITS + max(2 ** (level - 1) - 1, 0) * MAX_POINT_BITS
        C2 = max(min((maxbits + 31) // 32, max_words), C)
        a, b = B[0::2], B[1::2]
        La, Lb = L[0::2], L[1::2]
        a = jnp.pad(a, ((0, 0), (0, C2 - C), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, C2 - C), (0, 0)))
        # Shift b right by La bits: sub-word part with carry-in from the
        # previous word, then whole words via binary-decomposed selects.
        r = (La & 31).astype(U32)[:, None, :]
        bprev = jnp.pad(b, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        bs = _shr32(b, r) | _shl32(bprev, U32(32) - r)
        k = (La >> 5)[:, None, :]
        p = 1
        while p <= C:  # word shift is bounded by the pre-merge capacity
            shifted = jnp.pad(bs, ((0, 0), (p, 0), (0, 0)))[:, :C2]
            bs = jnp.where((k & p) != 0, shifted, bs)
            p <<= 1
        B = a | bs
        L = La + Lb
        C = C2
    out = B[0]                                          # [C, N]
    if C < max_words:
        out = jnp.pad(out, ((0, max_words - C), (0, 0)))
    else:
        out = out[:max_words]
    return out.T


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _take_word(words, idx):
    """words [N, MW], idx [N] -> u32 [N], clamped gather."""
    idx = jnp.clip(idx, 0, words.shape[1] - 1)
    return jnp.take_along_axis(words, idx[:, None], axis=1)[:, 0]


def _read32(words, pos):
    """32-bit window starting at bit pos [N]."""
    wi = pos >> 5
    bi = (pos & 31).astype(U32)
    a = _take_word(words, wi)
    b = _take_word(words, wi + 1)
    return _shl32(a, bi) | _shr32(b, U32(32) - bi)


def _read64(words, pos):
    """64-bit window at bit pos: three gathers (not two chained read32s,
    which would fetch the middle word twice)."""
    wi = pos >> 5
    bi = (pos & 31).astype(U32)
    inv = U32(32) - bi
    w0 = _take_word(words, wi)
    w1 = _take_word(words, wi + 1)
    w2 = _take_word(words, wi + 2)
    return (_shl32(w0, bi) | _shr32(w1, inv),
            _shl32(w1, bi) | _shr32(w2, inv))


def _read96(words, pos):
    """96-bit window starting at bit pos [N]: four clamped gathers serve
    EVERY value-path read of a decode step (ctrl bits + all speculative
    payloads live within [pos, pos+96)), replacing the step's per-payload
    read32/read64 gathers with static shifts of one shared window — the
    gather count is what bounds the scan on host CPU."""
    wi = pos >> 5
    bi = (pos & 31).astype(U32)
    inv = U32(32) - bi
    w0 = _take_word(words, wi)
    w1 = _take_word(words, wi + 1)
    w2 = _take_word(words, wi + 2)
    w3 = _take_word(words, wi + 3)
    return (_shl32(w0, bi) | _shr32(w1, inv),
            _shl32(w1, bi) | _shr32(w2, inv),
            _shl32(w2, bi) | _shr32(w3, inv))


def _sext(value_u, nbits):
    """Sign-extend the low nbits of value_u (nbits >= 1, dynamic)."""
    v = value_u.astype(I32)
    sb = _shl32(jnp.ones_like(value_u), (nbits - 1).astype(U32)).astype(I32)
    return (v ^ sb) - sb


def _decode_header(read32, read64, zero):
    """Parse the v2 stream header (flags + t0 [+ delta0] + v0).

    Parameterized by the bit readers so the XLA scan (clamped gathers
    into [N, MW] rows) and the Pallas kernel (VMEM-resident word tile)
    share ONE definition of the wire format. `zero` is an i32 zeros
    array whose shape sets the batch axis ([N] or a lane tile)."""
    b0 = read32(zero)
    int_mode = (b0 >> 31) == 1
    kexp = ((b0 >> 28) & 7).astype(I32)
    ts_regular = ((b0 >> 27) & 1) == 1
    t0c = ((b0 >> 26) & 1).astype(I32)
    vc = ((b0 >> 25) & 1).astype(I32)
    dc = ((b0 >> 24) & 1).astype(I32)
    nt0 = 32 + 32 * t0c
    t0 = b64.unzigzag64(
        b64.shr64(read64(zero + 8), (64 - nt0).astype(U32)))
    pos = zero + 8 + nt0
    nd = jnp.where(ts_regular, 8 + 24 * dc, 0)
    dzz = b64.shr64(read64(pos), (64 - nd).astype(U32))
    delta0 = jnp.where(ts_regular, b64.pair_to_i32(b64.unzigzag64(dzz)), 0)
    pos = pos + nd
    nv = jnp.where(int_mode, 32 + 32 * vc, 64)
    vraw = b64.shr64(read64(pos), (64 - nv).astype(U32))
    v0un = b64.unzigzag64(vraw)
    v0 = tuple(jnp.where(int_mode, a, b) for a, b in zip(v0un, vraw))
    return dict(int_mode=int_mode, k=kexp, ts_regular=ts_regular, t0=t0,
                delta0=delta0, v0=v0, pos0=pos + nv)


def _lut(idx, table):
    """Tiny lookup by where-chain over scalar literals instead of a
    gather into a constant array: Pallas kernels may not capture
    constant arrays, and both decode routes must share one step
    definition — scalars inline as immediates on either route."""
    out = jnp.full_like(idx, table[-1])
    for j in range(len(table) - 2, -1, -1):
        out = jnp.where(idx == j, table[j], out)
    return out


def _decode_step(read32, read64, read96, npoints, int_mode, ts_regular,
                 carry, i):
    """One decode step for point column i (>= 1), shared by the XLA scan
    and the Pallas kernel's fori_loop. All arrays ride the batch axis.

    Carry: (pos, prev_delta, pvd_hi, pvd_lo, pv_hi, pv_lo, la, ma, lb,
    mb, ts_hi, ts_lo) — the trailing tick pair accumulates t0 + sum(dt)
    in-scan so the fused decode emits final timestamps with no host
    cumsum pass. Emits (delta, ts_hi, ts_lo, vhi, vlo); consumers that
    ignore the tick pair (decode_batch's dict contract) let XLA DCE the
    accumulation away."""
    (pos, prev_delta, pvd_hi, pvd_lo, pv_hi, pv_lo,
     la, ma, lb, mb, ts_hi, ts_lo) = carry
    ts_payload = (0, 4, 7, 9, 12, 16, 20, 32)
    int_payload = (0, 4, 7, 12, 20, 32, 64)

    # --- timestamp: leading-ones prefix selects the payload width ---
    # One 64-bit window covers ctrl + payload (prefix <= 7 bits, payload
    # <= 32: everything ends within pos+39), so the payload read is a
    # dynamic shift of the same window instead of a second gather.
    t64_hi, t64_lo = read64(pos)
    cw = t64_hi
    ones_t = jnp.minimum(b64.clz32(~cw), 7)
    is0 = ones_t == 0
    plen = jnp.where(is0, 1, jnp.where(ones_t <= 5, ones_t + 1, 7))
    nbits = _lut(ones_t, ts_payload)
    pr = plen.astype(U32)
    pw = _shl32(t64_hi, pr) | _shr32(t64_lo, U32(32) - pr)
    pay = _shr32(pw, (U32(32) - nbits.astype(U32)))
    dod = jnp.where(is0 | ts_regular, 0, _sext(pay, jnp.maximum(nbits, 1)))
    delta = prev_delta + dod
    pos1 = pos + jnp.where(ts_regular, 0, jnp.where(is0, 1, plen + nbits))

    # ONE 96-bit window at pos1 serves every value read below: the float
    # ctrl + both reuse payloads + the rewrite header/payload end within
    # pos1+79, the int prefix + payload within pos1+70. Static shifts of
    # the shared window replace per-payload gathers (4 per step vs 18).
    a96_0, a96_1, a96_2 = read96(pos1)

    def w64(s: int):
        """64-bit pair at static bit offset s (1 <= s <= 31) in the window."""
        return (_shl32(a96_0, U32(s)) | _shr32(a96_1, U32(32 - s)),
                _shl32(a96_1, U32(s)) | _shr32(a96_2, U32(32 - s)))

    # --- value: float path ('0' | '10' A | '110' B | '111' rewrite) ---
    cf = a96_0
    fxor0 = (cf >> 31) == 0
    fa = (cf >> 30) == 0b10
    fb = (cf >> 29) == 0b110
    frw = ~fxor0 & ~fa & ~fb
    # reuse A: payload mlenA bits at pos1+2; reuse B: mlenB at pos1+3.
    xor_a = b64.shl64(
        b64.shr64(w64(2), (64 - ma).astype(U32)), (64 - la - ma).astype(U32))
    xor_b = b64.shl64(
        b64.shr64(w64(3), (64 - mb).astype(U32)), (64 - lb - mb).astype(U32))
    # rewrite: lead(6) mlen-1(6) payload at pos1+15
    lead_n = ((cf >> 23) & 63).astype(I32)
    mlen_n = (((cf >> 17) & 63) + 1).astype(I32)
    xor_w = b64.shl64(
        b64.shr64(w64(15), (64 - mlen_n).astype(U32)), (64 - lead_n - mlen_n).astype(U32)
    )
    xor = tuple(
        jnp.where(fxor0, 0, jnp.where(fa, a, jnp.where(fb, b_, w_)))
        for a, b_, w_ in zip(xor_a, xor_b, xor_w)
    )
    fval = b64.xor64((pv_hi, pv_lo), xor)
    fconsumed = jnp.where(
        fxor0, 1, jnp.where(fa, 2 + ma, jnp.where(fb, 3 + mb, 15 + mlen_n)))
    la2 = jnp.where(frw, lead_n, la)
    ma2 = jnp.where(frw, mlen_n, ma)
    lb2 = jnp.where(frw, la, lb)
    mb2 = jnp.where(frw, ma, mb)

    # --- value: int path (leading-ones prefix, v2 buckets) ---
    ci = a96_0
    ones_i = jnp.minimum(b64.clz32(~ci), 6)
    iz = ones_i == 0
    iplen = jnp.where(iz, 1, jnp.where(ones_i <= 4, ones_i + 1, 6))
    inb = _lut(ones_i, int_payload)
    # dynamic offset iplen in [1, 6]: the same window, shifted in-vector
    ir = iplen.astype(U32)
    iinv = U32(32) - ir
    p64i = (_shl32(a96_0, ir) | _shr32(a96_1, iinv),
            _shl32(a96_1, ir) | _shr32(a96_2, iinv))
    zz = b64.shr64(p64i, (64 - inb).astype(U32))
    vdod = b64.unzigzag64(zz)
    vdod = tuple(jnp.where(iz, 0, x) for x in vdod)
    nvd = b64.add64((pvd_hi, pvd_lo), vdod)
    ival = b64.add64((pv_hi, pv_lo), nvd)
    iconsumed = jnp.where(iz, 1, iplen + inb)

    # --- select by per-series mode ---
    val = tuple(jnp.where(int_mode, a, b) for a, b in zip(ival, fval))
    pos2 = pos1 + jnp.where(int_mode, iconsumed, fconsumed)
    active = i < npoints
    pos2 = jnp.where(active, pos2, pos)
    delta_o = jnp.where(active, delta, 0)
    val = tuple(jnp.where(active, v, p) for v, p in zip(val, (pv_hi, pv_lo)))
    prev_delta2 = jnp.where(active, delta, prev_delta)
    nvd = tuple(jnp.where(active & int_mode, x, p) for x, p in zip(nvd, (pvd_hi, pvd_lo)))
    la2 = jnp.where(active, la2, la)
    ma2 = jnp.where(active, ma2, ma)
    lb2 = jnp.where(active, lb2, lb)
    mb2 = jnp.where(active, mb2, mb)
    ts2 = b64.add64((ts_hi, ts_lo), b64.i32_to_pair(delta_o))

    carry2 = (pos2, prev_delta2, nvd[0], nvd[1], val[0], val[1],
              la2, ma2, lb2, mb2, ts2[0], ts2[1])
    return carry2, (delta_o, ts2[0], ts2[1], val[0], val[1])


def _decode_core(words, npoints, *, window):
    """Header parse + point scan over [N, MW] streams (the XLA route).

    Returns dict with dt [N, W] i32, ts (hi, lo) u32 [N, W] tick pairs
    (t0 + running delta sum), vhi/vlo [N, W] u32, int_mode, k, t0."""
    n = words.shape[0]
    zero = jnp.zeros((n,), I32)
    read32 = functools.partial(_read32, words)
    read64 = functools.partial(_read64, words)
    read96 = functools.partial(_read96, words)
    hdr = _decode_header(read32, read64, zero)
    int_mode, ts_regular = hdr["int_mode"], hdr["ts_regular"]
    t0, v0 = hdr["t0"], hdr["v0"]

    def step(carry, i):
        return _decode_step(read32, read64, read96, npoints, int_mode,
                            ts_regular, carry, i)

    init = (
        hdr["pos0"],
        jnp.where(ts_regular, hdr["delta0"], zero),
        jnp.zeros((n,), U32),
        jnp.zeros((n,), U32),
        v0[0],
        v0[1],
        jnp.full((n,), -1, I32),
        jnp.full((n,), -1, I32),
        jnp.full((n,), -1, I32),
        jnp.full((n,), -1, I32),
        t0[0],
        t0[1],
    )
    _, (deltas, tshis, tslos, vhis, vlos) = jax.lax.scan(
        step, init, jnp.arange(1, window, dtype=I32))
    dt = jnp.concatenate([jnp.zeros((n, 1), I32), deltas.T], axis=1)
    ts = (jnp.concatenate([t0[0][:, None], tshis.T], axis=1),
          jnp.concatenate([t0[1][:, None], tslos.T], axis=1))
    vhi = jnp.concatenate([v0[0][:, None], vhis.T], axis=1)
    vlo = jnp.concatenate([v0[1][:, None], vlos.T], axis=1)
    return {"dt": dt, "ts": ts, "vhi": vhi, "vlo": vlo,
            "int_mode": int_mode, "k": hdr["k"], "t0": t0}


@functools.partial(jax.jit, static_argnames=("window",))
def decode_batch(words, npoints, *, window):
    """Decode batched TTSZ streams.

    Args:
      words: u32 [N, MW] packed streams (>= 2 words of zero padding after the
        stream end is guaranteed by encode_batch's conservative max_words).
      npoints: int32 [N]; window: static max points W.

    Returns dict with dt [N, W] int32, vhi/vlo [N, W] u32 (f64 bits or int64
    m per mode), int_mode bool [N], k int32 [N], t0 (hi, lo) u32 [N].
    """
    out = _decode_core(words, npoints, window=window)
    return {key: out[key]
            for key in ("dt", "vhi", "vlo", "int_mode", "k", "t0")}


def prepare_on_device_math(ts_hi, ts_lo, vhi, vlo, npoints):
    """Traceable encode prep from RAW inputs — the device-side twin of
    prepare_encode_inputs, so the whole ingest hot path (prep + encode +
    rollup) is ONE XLA program and the host's per-block work shrinks to
    u32-pair view splits.

    ts_*: u32 pairs of int64 timestamps (ticks) [N, W]; v*: u32 pairs of
    raw f64 bits [N, W]; npoints int32 [N].

    Int-mode detection happens by f64 BIT inspection (no f64 arithmetic
    exists on TPU): value v with biased exponent e and 52-bit mantissa is
    an integer with |v| < 2^53 iff it is +/-0, or 1023 <= e <= 1075 with
    the low (1075 - e) mantissa bits zero; its exact int64 value is
    +/-((2^52 | mantissa) >> (1075 - e)). DIVERGENCE from the host prep:
    only k=0 (plain integer) rows take the int path — decimal series
    (host k in 1..6, needs exact f64 multiplies) encode as floats, which
    costs bytes on decimal-heavy shards but changes no values
    (DIVERGENCES.md). Returns (prep dict, range_ok bool scalar) —
    range_ok mirrors the host's int32 delta/DoD ValueErrors."""
    n, w = ts_hi.shape
    ts = (ts_hi, ts_lo)
    valid = jnp.arange(w, dtype=I32)[None, :] < npoints[:, None]
    prev = tuple(jnp.concatenate([a[:, :1], a[:, :-1]], axis=1) for a in ts)
    dt64 = b64.sub64(ts, prev)
    zero = (jnp.zeros_like(ts_hi), jnp.zeros_like(ts_hi))
    dt64 = tuple(jnp.where(valid, a, z) for a, z in zip(dt64, zero))

    def fits_i32(p):
        hi, lo = p
        return ((hi == 0) & (lo < U32(1 << 31))) | (
            (hi == U32(0xFFFFFFFF)) & (lo >= U32(1 << 31)))

    prev_dt = tuple(jnp.concatenate([z[:, :1], a[:, :-1]], axis=1)
                    for a, z in zip(dt64, zero))
    dod64 = b64.sub64(dt64, prev_dt)
    range_ok = jnp.where(
        valid, fits_i32(dt64) & fits_i32(dod64), True).all()
    dt = b64.pair_to_i32(dt64)

    # f64 bit classification (see docstring).
    e = ((vhi >> U32(20)) & U32(0x7FF)).astype(I32)
    sign = vhi >> U32(31)
    mhi = vhi & U32(0xFFFFF)
    is_zero = (e == 0) & (mhi == 0) & (vlo == 0)
    neg_zero = is_zero & (sign == 1)
    frac = jnp.clip(1075 - e, 0, 63).astype(jnp.uint32)
    mask_lo = jnp.where(
        frac >= 32, U32(0xFFFFFFFF),
        (U32(1) << jnp.minimum(frac, jnp.uint32(31))) - U32(1))
    mask_hi = jnp.where(
        frac <= 32, U32(0),
        (U32(1) << jnp.minimum(frac - 32, jnp.uint32(31))) - U32(1))
    low_zero = ((vlo & mask_lo) == 0) & ((mhi & mask_hi) == 0)
    col_int = is_zero | ((e >= 1023) & (e <= 1075) & low_zero)
    mag = b64.shr64((mhi | U32(0x100000), vlo), frac)
    m = tuple(jnp.where(sign == 1, a, b)
              for a, b in zip(b64.neg64(mag), mag))
    m = tuple(jnp.where(is_zero | ~valid, z, a) for a, z in zip(m, zero))
    live_int = jnp.where(valid, col_int, True).all(axis=1)
    row_int = live_int & ~(neg_zero & valid).any(axis=1)
    vhi_out = jnp.where(row_int[:, None], m[0], vhi)
    vlo_out = jnp.where(row_int[:, None], m[1], vlo)

    delta0 = (dt[:, 1] if w > 1 else jnp.zeros(n, I32)) * (npoints > 1)
    cols1 = jnp.arange(w, dtype=I32)[None, :] >= 1
    ts_regular = jnp.where(
        valid & cols1, dt == delta0[:, None], True).all(axis=1)
    prep = dict(
        dt=dt,
        t0=(ts_hi[:, 0], ts_lo[:, 0]),
        vhi=vhi_out,
        vlo=vlo_out,
        int_mode=row_int,
        k=jnp.zeros(n, I32),
        npoints=npoints,
        ts_regular=ts_regular,
        delta0=delta0,
    )
    return prep, range_ok


# ---------------------------------------------------------------------------
# host wrappers: f64/int64 <-> u32-pair prep (vectorized numpy)
# ---------------------------------------------------------------------------

MAX_DECIMAL_EXP = 6


def detect_int_mode_batch(values: np.ndarray, npoints: np.ndarray):
    """Vectorized per-series int-mode detection (ref_codec.detect_int_mode):
    smallest k in [0, MAX_DECIMAL_EXP] with round(v*10^k)/10^k == v for
    every live point. k ascends over a shrinking candidate set — in metric
    workloads most series are plain integers, so the k=0 pass resolves
    ~everything and the k>=1 passes touch only the float-ish remainder."""
    v = np.asarray(values, dtype=np.float64)
    n, w = v.shape
    cols = np.arange(w)[None, :] < np.asarray(npoints)[:, None]
    dead = ~cols
    with np.errstate(invalid="ignore"):
        eligible = (np.isfinite(v) | dead).all(axis=1)
        # -0.0 only survives the float/XOR path (int path canonicalizes it
        # to +0.0), so its presence forces float mode (detect_int_mode).
        eligible &= ~(((v == 0.0) & np.signbit(v) & cols).any(axis=1))
    best_k = np.full(n, -1, dtype=np.int32)
    rows = np.flatnonzero(eligible)
    for k in range(0, MAX_DECIMAL_EXP + 1):
        if rows.size == 0:
            break
        vr = v[rows]
        # over: huge magnitudes overflow vr*scale to inf, which correctly
        # fails the < 2^53 bound — an expected classification signal.
        with np.errstate(invalid="ignore", over="ignore"):
            if k == 0:
                m = np.rint(vr)
                ok = (np.abs(m) < 2.0**53) & (m == vr)
            else:
                scale = np.float64(10.0**k)
                m = np.rint(vr * scale)
                ok = (np.abs(m) < 2.0**53) & ((m / scale) == vr)
        ok = (ok | dead[rows]).all(axis=1)
        best_k[rows[ok]] = k
        rows = rows[~ok]
    return best_k >= 0, np.maximum(best_k, 0)


def _prepare_slice(ts, v, npts, out, lo):
    """Row-slice worker for prepare_encode_inputs: writes [lo:lo+rows) of
    every output array. All passes are per-row, so slices are independent."""
    hi = lo + ts.shape[0]
    dt64 = np.diff(ts, axis=1, prepend=ts[:, :1])
    valid = np.arange(ts.shape[1])[None, :] < npts[:, None]
    dt_checked = np.where(valid, dt64, 0)
    if np.abs(dt_checked).max(initial=0) >= 2**31:
        raise ValueError("timestamp deltas must fit in int32 ticks")
    dod = np.diff(dt_checked, axis=1, prepend=np.zeros_like(ts[:, :1]))
    if np.abs(np.where(valid, dod, 0)).max(initial=0) >= 2**31:
        raise ValueError("timestamp delta-of-deltas must fit in 32-bit signed")
    dt = dt_checked.astype(np.int32)
    int_mode, k = detect_int_mode_batch(v, npts)
    # Float rows keep raw IEEE bits; int rows get scaled-mantissa bits.
    # Only the int subset pays the rint/astype passes (it is finite on all
    # live columns by construction; dead columns are zeroed defensively).
    bits = np.ascontiguousarray(v).view(np.uint64).copy()
    rows_i = np.flatnonzero(int_mode)
    if rows_i.size:
        vi = v[rows_i]
        ki = k[rows_i]
        if ki.any():
            vi = vi * np.power(10.0, ki.astype(np.float64))[:, None]
        with np.errstate(invalid="ignore", over="ignore"):
            vi = np.where(np.isfinite(vi), vi, 0.0)
        bits[rows_i] = np.rint(vi).astype(np.int64).view(np.uint64)
    vhi, vlo = b64.from_u64_np(bits)
    t0hi, t0lo = b64.from_u64_np(ts[:, 0])
    w = ts.shape[1]
    delta0 = (dt[:, 1] if w > 1 else np.zeros(len(dt), np.int32)) * (npts > 1)
    cols1 = np.arange(w)[None, :] >= 1
    ts_regular = np.where(valid & cols1, dt == delta0[:, None], True).all(axis=1)
    out["dt"][lo:hi] = dt
    out["t0"][0][lo:hi] = t0hi
    out["t0"][1][lo:hi] = t0lo
    out["vhi"][lo:hi] = vhi
    out["vlo"][lo:hi] = vlo
    out["int_mode"][lo:hi] = int_mode
    out["k"][lo:hi] = k
    out["ts_regular"][lo:hi] = ts_regular
    out["delta0"][lo:hi] = delta0


# Persistent worker pool for the ingest prep path: every pass is a big
# per-row numpy ufunc that releases the GIL, so row-chunking across threads
# scales near-linearly — this is the host half of the sealed-block encode,
# and it must keep up with the device step when the two are pipelined.
_PREP_POOL = None
_PREP_WORKERS = max(1, min(8, (os.cpu_count() or 2) - 1))
_PREP_MIN_ROWS_PER_WORKER = 4096


def _prep_pool():
    global _PREP_POOL
    if _PREP_POOL is None:
        import concurrent.futures

        _PREP_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=_PREP_WORKERS, thread_name_prefix="tsz-prep")
    return _PREP_POOL


def prepare_encode_inputs(timestamps: np.ndarray, values: np.ndarray, npoints: np.ndarray):
    """Host prep: int64/f64 arrays -> u32-pair device inputs. Large batches
    fan out row-chunks across the prep pool; small ones stay inline."""
    ts = np.asarray(timestamps, dtype=np.int64)
    v = np.asarray(values, dtype=np.float64)
    npts = np.asarray(npoints, dtype=np.int32)
    n, w = ts.shape
    out = dict(
        dt=np.empty((n, w), np.int32),
        t0=(np.empty(n, np.uint32), np.empty(n, np.uint32)),
        vhi=np.empty((n, w), np.uint32),
        vlo=np.empty((n, w), np.uint32),
        int_mode=np.empty(n, bool),
        k=np.empty(n, np.int32),
        npoints=npts,
        ts_regular=np.empty(n, bool),
        delta0=np.empty(n, np.int32),
    )
    workers = min(_PREP_WORKERS, max(1, n // _PREP_MIN_ROWS_PER_WORKER))
    if workers <= 1:
        _prepare_slice(ts, v, npts, out, 0)
        return out
    bounds = np.linspace(0, n, workers + 1, dtype=np.int64)
    futs = [
        _prep_pool().submit(_prepare_slice, ts[b0:b1], v[b0:b1],
                            npts[b0:b1], out, int(b0))
        for b0, b1 in zip(bounds[:-1], bounds[1:])
    ]
    for f in futs:
        f.result()  # re-raises range-check ValueErrors from any slice
    return out


def encode(timestamps: np.ndarray, values: np.ndarray, npoints=None, max_words: int | None = None):
    """Encode [N, W] int64 timestamps + f64 values -> (words, nbits) on device."""
    ts = np.asarray(timestamps)
    if npoints is None:
        npoints = np.full(ts.shape[0], ts.shape[1], dtype=np.int32)
    if max_words is None:
        max_words = max_words_for(ts.shape[1])
    inp = prepare_encode_inputs(ts, values, npoints)
    words, nbits = encode_batch(
        inp["dt"],
        inp["t0"],
        inp["vhi"],
        inp["vlo"],
        inp["int_mode"],
        inp["k"],
        inp["npoints"],
        inp["ts_regular"],
        inp["delta0"],
        max_words=max_words,
    )
    if max_words < max_words_for(ts.shape[1]):
        check_cursor(nbits, max_words)
    return words, nbits


def boundary_metadata(inp: dict) -> dict:
    """Seal-time boundary metadata from prepared encode inputs: everything
    the scan-free concat merge (tsz_concat) needs to append a later block
    without decoding this one. Free at encode time — it reads the prepared
    columns the encoder already holds."""
    npts = np.asarray(inp["npoints"])
    rows = np.arange(npts.shape[0])
    last_col = np.maximum(npts - 1, 0)
    prev_col = np.maximum(npts - 2, 0)
    vhi = np.asarray(inp["vhi"])
    vlo = np.asarray(inp["vlo"])
    last_bits = b64.to_u64_np(vhi[rows, last_col], vlo[rows, last_col])
    prev_bits = b64.to_u64_np(vhi[rows, prev_col], vlo[rows, prev_col])
    int_mode = np.asarray(inp["int_mode"])
    last_vdelta = np.where(
        int_mode & (npts >= 2),
        last_bits.astype(np.int64) - prev_bits.astype(np.int64), 0
    ).view(np.uint64)
    dt = np.asarray(inp["dt"])
    t0 = b64.to_u64_np(*(np.asarray(a) for a in inp["t0"])).astype(np.int64)
    last_ticks = t0 + np.cumsum(dt, axis=1)[rows, last_col]
    return {"last_ticks": last_ticks, "last_v_bits": last_bits,
            "last_vdelta_bits": last_vdelta,
            # valid=False marks rows whose metadata no longer describes the
            # stream's epoch (set by merges that re-detected int mode).
            "valid": np.ones(npts.shape[0], bool)}


def encode_prepared(inp: dict, max_words: int):
    """encode_batch from prepared inputs (seal path), on the default
    device. Multi-device seals go through the shard_map flush encoder
    (parallel.ingest.flush_encode_prepared) BEFORE reaching here; what
    arrives is below its dispatch floor or does not divide the mesh.
    Sharding such a tile with GSPMD would not split the Pallas pack
    kernel anyway: the partitioner cannot see inside a pallas_call, so it
    all-gathers the chunk planes and runs the whole kernel on every
    device (cross-compiled for a 2x2 v5e: 20 all-gathers, replicated
    output)."""
    return encode_batch(
        inp["dt"], inp["t0"], inp["vhi"], inp["vlo"], inp["int_mode"],
        inp["k"], inp["npoints"], inp["ts_regular"], inp["delta0"],
        max_words=max_words)


def encode_with_boundary(timestamps, values, npoints=None,
                         max_words: int | None = None):
    """encode() that also returns the boundary metadata dict (seal path)."""
    ts = np.asarray(timestamps)
    if npoints is None:
        npoints = np.full(ts.shape[0], ts.shape[1], dtype=np.int32)
    if max_words is None:
        max_words = max_words_for(ts.shape[1])
    inp = prepare_encode_inputs(ts, values, npoints)
    words, nbits = encode_prepared(inp, max_words)
    return words, nbits, boundary_metadata(inp)


_DECODE_TIMED: set = set()


def _decode_route():
    """Decode scan route: "pallas" when the Pallas codec kernels are
    enabled (interpret-mode on CPU), else the XLA lax.scan."""
    from . import pallas_codec
    from ..parallel import guard

    return ("pallas" if pallas_codec.enabled()
            and guard.available("codec.decode") else "xla")


def _row_mesh(words):
    """(mesh, row axes) when `words` is a device array whose ROWS are
    partitioned over more than one device (the block cache's retained
    mesh-flush output), else None."""
    sh = getattr(words, "sharding", None)
    if not isinstance(sh, jax.sharding.NamedSharding) or sh.mesh.size <= 1:
        return None
    spec = tuple(sh.spec)
    if not spec or spec[0] is None or any(a is not None for a in spec[1:]):
        return None
    return sh.mesh, spec[0]


_DECODE_CALLS = instrument.ROOT.counter("codec.decode.calls")
_DECODE_FETCHES = instrument.ROOT.counter("codec.decode.fetches")
_DECODE_UPLOADS = instrument.ROOT.counter("codec.decode.uploads")


@functools.lru_cache(maxsize=None)
def _decode_fused_jit(window: int, unit_nanos: int, with_f32: bool,
                      route: str, rows=None):
    """Jitted fused decode program for one static (window, unit, route):
    stream scan + tick cumsum + unit-nanos multiply (mul64_const — minute
    units exceed u32 range) + exact on-device int->f64 bit conversion for
    k=0 int rows, and ONE u32 result of [2N + E, 2W]: rows [0, N) are the
    timestamps and rows [N, 2N) the values, each row the 2W words of its
    W 64-bit cells in native order (PAIR_HI), so the fetched buffer is
    C-ordered and the host views the two planes as int64 / float64 where
    they lie; the E = ceil(N / 2W) trailing rows hold one word a
    decoded row, its decimal exponent k where the row is int-mode with
    k > 0 (fixed-decimal gauges: the values plane keeps their raw
    mantissas for the host's exact /10^k) and 0 elsewhere. `with_f32`
    adds the float32 plane as a second result.

    `rows` = _row_mesh(words): row-partitioned input decodes as an
    explicit shard_map over those rows (decode is row-independent), each
    device scanning its own slice, and the planes are gathered into the
    one result on the mesh. Left to GSPMD, the Pallas route's
    pallas_call is opaque to the partitioner, which all-gathers the
    streams and runs the full kernel on every device."""
    hi = b64.PAIR_HI

    def weave(pair):
        parts = [None, None]
        parts[hi] = pair[0]
        parts[1 - hi] = pair[1]
        return jnp.stack(parts, axis=-1).reshape(-1, 2 * window)

    def scan_rows(words, npoints):
        if route == "pallas":
            from . import pallas_codec

            out = pallas_codec.decode_core(words, npoints, window=window)
        else:
            out = _decode_core(words, npoints, window=window)
        ts_ns = b64.mul64_const(out["ts"], unit_nanos)
        k0 = out["int_mode"] & (out["k"] == 0)
        fb = b64.i64_pair_to_f64_bits((out["vhi"], out["vlo"]))
        vhi = jnp.where(k0[:, None], fb[0], out["vhi"])
        vlo = jnp.where(k0[:, None], fb[1], out["vlo"])
        fix_k = jnp.where(out["int_mode"] & (out["k"] > 0), out["k"], 0)
        res = (weave(ts_ns), weave((vhi, vlo)), fix_k.astype(U32))
        if with_f32:
            res += (b64.f64_bits_to_f32(vhi, vlo),)
        return res

    scan, placed = scan_rows, None
    if rows is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axes = rows
        # one copy of the result on every device of the mesh: the fetch
        # reads one of them
        placed = NamedSharding(mesh, P())
        out_specs = (P(axes, None), P(axes, None), P(axes))
        if with_f32:
            out_specs += (P(axes, None),)
        scan = jax.shard_map(scan_rows, mesh=mesh,
                             in_specs=(P(axes, None), P(axes)),
                             out_specs=out_specs, check_vma=False)

    # `run` by name: the device trace calls the Pallas kernel after the
    # jitted function it sits in (benchmark/layer_metrics/decode_roofline.py)
    def run(words, npoints):
        ts, vals, fix_k, *f32 = scan(words, npoints)
        meta = jnp.pad(fix_k, (0, -ts.shape[0] % (2 * window)))
        return (jnp.concatenate([ts, vals, meta.reshape(-1, 2 * window)]),
                *f32)

    return jax.jit(run, out_shardings=placed)


def decode_plane(words, npoints, *, window: int, unit_nanos: int = 1,
                 with_f32: bool = False, ran_on: list | None = None):
    """Fused whole-plane decode -> (ts int64 [N, W] nanos, vals f64
    [N, W][, vals_f32 [N, W]]).

    ONE device program replaces the five host passes the unfused decode()
    paid per plane (int64 cumsum, time-unit multiply, u64 view merge,
    int->float convert, mode select): timestamps accumulate in the scan
    carry and are unit-scaled on device, int-mode k=0 values convert to
    exact f64 bits on device (|m| < 2^53, no rounding), and the planes
    leave the device as rows of native-order 64-bit cells, so the host
    reinterprets the one buffer it fetched. A call is one upload, one
    program and one fetch (`with_f32` makes it two): host arrays go to
    the program as they are, so the launch carries them up and nobody
    waits for them (a `device_put` of the pair before the launch made
    the call 0.1 ms longer on the chip's host: PERF.md section 7);
    inputs already on a device are used where they are. Counters
    `codec.decode.calls` / `.uploads` / `.fetches`. Only rows with
    decimal exponent k>0 pay a host fixup — f64 division by 10^k has no
    exact integer formulation — and only then is the values plane a copy.
    Returned planes are C-contiguous each on its own, and may be
    read-only views of the fetched buffer.

    with_f32 additionally returns the float32 downcast plane computed on
    device (bits64.f64_bits_to_f32, bit-identical to numpy's astype) —
    the plan compiler's `value` fetch staging consumes this instead of
    running its own downcast pass. `ran_on`, a list, receives the
    devices that hold the result (a caller that counts where its rows
    were decoded: client/session.py::_one_pass_points)."""
    from ..parallel import telemetry

    route = _decode_route()
    telemetry.codec_route("decode", route == "pallas")
    row_mesh = _row_mesh(words)
    run = _decode_fused_jit(int(window), int(unit_nanos), bool(with_f32),
                            route, row_mesh)
    # No one-row program on the Pallas route: the kernel's tile cut to
    # one lane, [window, 1] -> [1, window], is a degenerate reshape, and
    # XLA:TPU lowers those as u32 reduce-adds over a one-wide dimension
    # inside the fused unit multiply; on a v5e that read 12 of a row's
    # 128 timestamps ~2^31 ns low (PERF.md section 6, PR 32; the
    # kernel's own ticks, the multiply alone and the XLA scan were
    # exact). The row goes twice: doubled on the host before it goes up,
    # or on the device it is on.
    lone = route == "pallas" and int(np.shape(words)[0]) == 1
    # The call's anatomy, as stretches of the detailed span it runs
    # under (the session's client.fetch_tagged, a cold read's
    # query.fetch): `h2d` (the host's part of the upload: the launch
    # carries the arrays up), `launch`, `device_wait` (the device's work
    # and the one fetch), `d2h` (the f32 plane's, when asked for),
    # `layout` (the views and the k > 0 fix-up). No stretch synchronises
    # anything the call did not wait for already.
    with tracing.phase("h2d"):
        held = isinstance(words, jax.Array), isinstance(npoints, jax.Array)
        if not held[0]:
            words = np.asarray(words)
        if not held[1]:
            npoints = np.asarray(npoints, np.int32)
        if lone:
            words = (jnp if held[0] else np).concatenate([words, words])
            npoints = (jnp if held[1] else np).concatenate([npoints, npoints])
    uploads = 0 if all(held) else 1
    fetches = 2 if with_f32 else 1
    _DECODE_CALLS.inc()
    _DECODE_UPLOADS.inc(uploads)
    _DECODE_FETCHES.inc(fetches)
    sp = tracing.detail()
    if sp is not None:
        sp.add_cost("upload_n", uploads)
        sp.add_cost("fetch_n", fetches)
    if route == "pallas":
        from ..parallel import guard

        def _pallas_decode():
            key = (int(window), int(unit_nanos), bool(with_f32), route)
            timed = key not in _DECODE_TIMED
            t_start = time.perf_counter() if timed else 0.0
            res = run(words, npoints)
            if timed:
                _DECODE_TIMED.add(key)
                jax.block_until_ready(res)
                telemetry.codec_compile_recorded(
                    "decode", time.perf_counter() - t_start)
            return res

        def _xla_decode(_err):
            # The XLA scan twin — bit-identical across the property
            # corpus — rebuilt under its own lru key ("xla" rides in the
            # cache key, so no cache surgery is needed to reroute).
            fb = _decode_fused_jit(int(window), int(unit_nanos),
                                   bool(with_f32), "xla", row_mesh)
            return fb(words, npoints)

        with tracing.phase("launch"):
            out = guard.dispatch("codec.decode", _pallas_decode, _xla_decode)
    else:
        with tracing.phase("launch"):
            out = run(words, npoints)
    if ran_on is not None:
        ran_on.extend(out[0].devices())
    with tracing.phase("device_wait"):
        buf = np.asarray(out[0])
    with tracing.phase("d2h"):
        f32 = np.asarray(out[1]) if with_f32 else None
    with tracing.phase("layout"):
        n = words.shape[0]
        ts = buf[:n].view(np.int64)
        vals = buf[n:2 * n].view(np.float64)
        fix_k = buf[2 * n:].reshape(-1)[:n]
        rows = np.flatnonzero(fix_k)
        if rows.size:
            fixed = vals[rows].view(np.int64).astype(np.float64) \
                / np.power(10.0, fix_k[rows].astype(np.float64))[:, None]
            if not vals.flags.writeable:
                vals = vals.copy()
            vals[rows] = fixed
            if with_f32:
                if not f32.flags.writeable:
                    f32 = f32.copy()
                f32[rows] = fixed.astype(np.float32)
    if lone:
        ts, vals = ts[:1], vals[:1]
        f32 = f32[:1] if with_f32 else None
    return (ts, vals, f32) if with_f32 else (ts, vals)


def decode(words, npoints, window: int):
    """Decode device streams -> host (timestamps int64 [N, W] ticks,
    values f64). Runs the fused plane decode at unit scale 1 — the
    merge/concat recode paths dogfood the same program serving reads."""
    return decode_plane(words, npoints, window=window, unit_nanos=1)
