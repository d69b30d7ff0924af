"""64-bit integer bit manipulation on (hi, lo) uint32 pairs, in 32-bit lanes.

TPU VPU lanes are 32-bit; XLA emulates 64-bit integers as pairs anyway, and
staying in explicit u32 pairs keeps the codec kernels (m3_tpu/ops/tsz.py) free
of the global jax x64 flag and maps 1:1 onto what the hardware executes. All
functions are elementwise and broadcast/vmap-trivially.

A "pair" is a tuple (hi, lo) of uint32 arrays: value = hi * 2^32 + lo.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

U32 = jnp.uint32


def pair(hi, lo):
    return jnp.asarray(hi, U32), jnp.asarray(lo, U32)


# Index of the HIGH u32 word within a native-order pair view of a 64-bit
# buffer — THE one endianness decision, shared by the strided host split
# (from_u64_np) and the zero-copy device-split path (ingest.make_raw_batch).
import sys as _sys

PAIR_HI = 0 if _sys.byteorder == "big" else 1


def pair_view_np(x):
    """Zero-copy interleaved u32 pair view of a 64-bit numpy buffer:
    [..., 2] in native order (index PAIR_HI = high word). Narrow ints are
    widened first (a raw view would pair adjacent elements into bogus
    64-bit values); floats are viewed bitwise."""
    import numpy as np

    x = np.ascontiguousarray(x)
    if x.dtype.kind in "iu" and x.dtype.itemsize < 8:
        x = x.astype(np.uint64)
    elif x.dtype.kind not in "iu" or x.dtype.itemsize != 8:
        if x.dtype.itemsize != 8:
            # a raw view of narrow floats would pair ADJACENT elements
            # into bogus 64-bit values — fail loudly instead
            raise TypeError(
                f"pair_view_np needs a 64-bit buffer, got {x.dtype}")
        x = x.view(np.uint64)
    return x.view(np.uint32).reshape(*x.shape, 2)


def from_u64_np(x):
    """Host helper: split numpy uint64/int64 array into (hi, lo) u32 arrays.

    Uses a zero-copy u32-pair view of the 64-bit buffer instead of
    shift/mask arithmetic (4 full passes -> 2 strided copies; this runs
    over every datapoint of every sealed block on the ingest path)."""
    import numpy as np

    pairs = pair_view_np(x)
    return (np.ascontiguousarray(pairs[..., PAIR_HI]),
            np.ascontiguousarray(pairs[..., 1 - PAIR_HI]))


def to_u64_np(hi, lo):
    """Host helper: combine (hi, lo) u32 numpy arrays into uint64."""
    import numpy as np

    return (np.asarray(hi, dtype=np.uint64) << np.uint64(32)) | np.asarray(lo, dtype=np.uint64)


def xor64(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def or64(a, b):
    return a[0] | b[0], a[1] | b[1]


def and64(a, b):
    return a[0] & b[0], a[1] & b[1]


def not64(a):
    return ~a[0], ~a[1]


def eq0(a):
    return (a[0] | a[1]) == 0


def eq64(a, b):
    return (a[0] == b[0]) & (a[1] == b[1])


def add64(a, b):
    lo = a[1] + b[1]
    carry = (lo < a[1]).astype(U32)
    hi = a[0] + b[0] + carry
    return hi, lo


def sub64(a, b):
    lo = a[1] - b[1]
    borrow = (a[1] < b[1]).astype(U32)
    hi = a[0] - b[0] - borrow
    return hi, lo


def neg64(a):
    return add64(not64(a), (jnp.zeros_like(a[0]), jnp.ones_like(a[1])))


def _shl32(x, s):
    """x << s with s possibly 0..32; s>=32 yields 0 (XLA shift is UB at 32)."""
    s = jnp.asarray(s, U32)
    return jnp.where(s >= 32, jnp.zeros_like(x), x << (s & U32(31)))


def _shr32(x, s):
    s = jnp.asarray(s, U32)
    return jnp.where(s >= 32, jnp.zeros_like(x), x >> (s & U32(31)))


def shl64(a, s):
    """Logical left shift by dynamic s in [0, 64]."""
    hi, lo = a
    s = jnp.asarray(s, U32)
    hi_out = _shl32(hi, s) | _shr32(lo, U32(32) - s) | _shl32(lo, s - U32(32))
    lo_out = _shl32(lo, s)
    return hi_out, lo_out


def shr64(a, s):
    """Logical right shift by dynamic s in [0, 64]."""
    hi, lo = a
    s = jnp.asarray(s, U32)
    lo_out = _shr32(lo, s) | _shl32(hi, U32(32) - s) | _shr32(hi, s - U32(32))
    hi_out = _shr32(hi, s)
    return hi_out, lo_out


def sar63(a):
    """Arithmetic shift right by 63: all-ones if sign bit set, else zero."""
    sign = (a[0] >> U32(31)).astype(jnp.int32)
    mask = jnp.where(sign == 1, U32(0xFFFFFFFF), U32(0))
    return mask, mask


def shl1(a):
    hi, lo = a
    return (hi << U32(1)) | (lo >> U32(31)), lo << U32(1)


def zigzag64(a):
    """(x << 1) ^ (x >> 63) for two's complement pair."""
    return xor64(shl1(a), sar63(a))


def unzigzag64(z):
    """(z >> 1) ^ -(z & 1)."""
    lsb = z[1] & U32(1)
    mask = jnp.where(lsb == 1, U32(0xFFFFFFFF), U32(0))
    return xor64(shr64(z, 1), (mask, mask))


def clz32(x):
    return jax.lax.clz(jnp.asarray(x, U32)).astype(jnp.int32)


def ctz32(x):
    x = jnp.asarray(x, U32)
    isolated = x & (~x + U32(1))
    return jnp.where(x == 0, jnp.int32(32), 31 - clz32(isolated))


def clz64(a):
    hi, lo = a
    return jnp.where(hi != 0, clz32(hi), 32 + clz32(lo))


def ctz64(a):
    hi, lo = a
    return jnp.where(lo != 0, ctz32(lo), 32 + ctz32(hi))


def bitlen64(a):
    return 64 - clz64(a)


def i32_to_pair(x):
    """Sign-extend int32 array to a 64-bit pair."""
    x = jnp.asarray(x, jnp.int32)
    lo = x.astype(U32)
    hi = jnp.where(x < 0, U32(0xFFFFFFFF), U32(0))
    return hi, lo


def pair_to_i32(a):
    """Truncate pair to int32 (caller guarantees it fits)."""
    return a[1].astype(jnp.int32)


def mul64_const(a, c: int):
    """a * c mod 2^64 for a STATIC Python int c >= 0, as shl64/add64 over
    the set bits of c (binary decomposition at trace time). Lets the fused
    decode multiply tick pairs by a time-unit scale (up to minute-unit
    6e10 ns, which exceeds u32 range) without any 64-bit multiply op."""
    c = int(c)
    if c < 0:
        raise ValueError("mul64_const: c must be non-negative")
    if c == 1:
        return a
    zero = (jnp.zeros_like(a[0]), jnp.zeros_like(a[1]))
    acc = zero
    s = 0
    while c and s < 64:
        if c & 1:
            acc = add64(acc, shl64(a, U32(s)) if s else a)
        c >>= 1
        s += 1
    return acc


def i64_pair_to_f64_bits(a):
    """Exact f64 BITS of the signed 64-bit integer in pair `a`, pure u32
    math. Caller guarantees |value| < 2^53 (the int-mode k=0 encode
    contract, detect_int_mode), so the magnitude's top bit index e <= 52
    and mantissa = |value| << (52 - e) loses nothing — bit-identical to
    numpy's astype(int64).astype(float64) on that domain. Zero -> +0.0."""
    hi, lo = a
    neg = (hi >> U32(31)) == U32(1)
    mag = tuple(jnp.where(neg, n, p) for n, p in zip(neg64(a), a))
    nz = (mag[0] | mag[1]) != 0
    e = jnp.maximum(63 - clz64(mag), 0)
    mant = shl64(mag, jnp.clip(52 - e, 0, 63).astype(U32))
    bhi = (jnp.where(neg, U32(1), U32(0)) << U32(31)) \
        | ((e + 1023).astype(U32) << U32(20)) | (mant[0] & U32(0xFFFFF))
    z = U32(0)
    return (jnp.where(nz, bhi, z), jnp.where(nz, mant[1], z))


def f64_bits_to_f32(hi, lo):
    """Exact float64 -> float32 conversion from raw bit pairs, entirely in
    u32 integer math (round-to-nearest-even, matching numpy's astype):
    lets the ingest path derive the f32 aggregation values ON DEVICE from
    the same pair views it encodes, killing the last host prep pass and
    48MB/block of H2D (parallel/ingest.py make_raw_batch).

    Handles every IEEE case: normals, overflow->inf, underflow to f32
    denormals and zero (with the double rounding avoided by sticky-bit
    collection), inf passthrough, NaN -> quiet NaN, signed zeros."""
    hi = jnp.asarray(hi, U32)
    lo = jnp.asarray(lo, U32)
    sign = hi & U32(0x80000000)
    exp64 = (hi >> U32(20)) & U32(0x7FF)
    mant_hi = hi & U32(0xFFFFF)
    # 52-bit mantissa split: top 23 bits + 29 round/sticky bits.
    m23 = (mant_hi << U32(3)) | (lo >> U32(29))
    rest = lo & U32(0x1FFFFFFF)

    e32 = exp64.astype(jnp.int32) - 1023 + 127

    # -- normal path (1 <= e32 <= 254 before rounding) --------------------
    half = U32(0x10000000)
    round_up = (rest > half) | ((rest == half) & ((m23 & U32(1)) == U32(1)))
    m23r = m23 + round_up.astype(U32)
    carry = m23r >> U32(23)                 # mantissa overflow 2^23
    m_norm = jnp.where(carry > 0, U32(0), m23r & U32(0x7FFFFF))
    e_norm = e32 + carry.astype(jnp.int32)
    norm_bits = sign | (jnp.clip(e_norm, 0, 255).astype(U32) << U32(23)) | m_norm
    norm_bits = jnp.where(e_norm >= 255, sign | U32(0x7F800000), norm_bits)

    # -- underflow path (e32 <= 0): shift the FULL 24-bit significand -----
    # (implicit 1 + 23 mantissa bits) right by (1 - e32), collecting
    # shifted-out bits as round/sticky so only ONE rounding happens.
    shift = jnp.clip(1 - e32, 0, 32).astype(U32)      # >=25 -> zero anyway
    sig24 = U32(0x800000) | m23                        # implicit one
    kept = jnp.where(shift >= U32(24), U32(0), _shr32(sig24, shift))
    # bits shifted out of sig24 (low `shift` bits), as a 32-bit field
    dropped = jnp.where(shift >= U32(32), sig24,
                        sig24 & (_shl32(U32(1), shift) - U32(1)))
    # round position: the top dropped bit is the guard; sticky = lower
    # dropped bits OR the original 29 rest bits.
    guard_mask = jnp.where(shift == 0, U32(0), _shl32(U32(1), shift - U32(1)))
    guard = (dropped & guard_mask) != 0
    sticky = ((dropped & (guard_mask - U32(1))) != 0) | (rest != 0)
    sub_up = guard & (sticky | ((kept & U32(1)) == U32(1)))
    sub = kept + sub_up.astype(U32)
    # sub may carry into the exponent (becomes smallest normal) — the bit
    # layout handles that naturally: 0x800000 == exponent 1, mantissa 0.
    sub_bits = sign | sub

    # -- special exponents -------------------------------------------------
    is_inf_nan = exp64 == U32(0x7FF)
    is_nan = is_inf_nan & ((mant_hi | lo) != 0)
    spec_bits = jnp.where(is_nan, sign | U32(0x7FC00000),
                          sign | U32(0x7F800000))
    # f64 denormals (exp64==0) are far below f32 denormal range -> 0.
    is_zero64 = exp64 == U32(0)

    bits = jnp.where(is_inf_nan, spec_bits,
                     jnp.where(is_zero64, sign,
                               jnp.where(e32 <= 0, sub_bits, norm_bits)))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)
