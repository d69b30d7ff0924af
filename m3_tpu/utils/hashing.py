"""Murmur3-32 hashing for shard assignment (reference:
src/dbnode/sharding/shardset.go:30 uses murmur3.Sum32(id) % numShards, via
the stack-allocated m3db/stackmurmur3 fork).

Scalar path is pure Python (control-plane rates); `hash_batch` vectorizes
over many IDs with numpy for bulk shard routing of write batches."""

from __future__ import annotations

import functools
from typing import Sequence

import numpy as np

_C1 = 0xCC9E2D51
_C2 = 0x1B873593
_M32 = 0xFFFFFFFF


def _rotl32(x: int, r: int) -> int:
    return ((x << r) | (x >> (32 - r))) & _M32


def murmur3_32(data: bytes, seed: int = 0) -> int:
    """MurmurHash3 x86 32-bit, bit-exact with the reference's murmur3.Sum32."""
    h = seed & _M32
    n = len(data)
    full = n - n % 4
    for i in range(0, full, 4):
        k = int.from_bytes(data[i : i + 4], "little")
        k = (k * _C1) & _M32
        k = _rotl32(k, 15)
        k = (k * _C2) & _M32
        h ^= k
        h = _rotl32(h, 13)
        h = (h * 5 + 0xE6546B64) & _M32
    k = 0
    tail = data[full:]
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = (k * _C1) & _M32
        k = _rotl32(k, 15)
        k = (k * _C2) & _M32
        h ^= k
    h ^= n
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    h ^= h >> 16
    return h


# Bounded memo for hot-ID shard routing: the aggregator's timed wire
# hashes the same metric IDs once per datapoint (every window), and the
# pure-Python block mixer was ~13% of per-entry dispatch. Bounded in
# BOTH dimensions — entry count (lru) and key size (oversize IDs skip
# the cache entirely), because the wire calls this on client-supplied
# ids before any validation and 64k pinned multi-MB keys would be an
# unbounded-memory hazard, not a cache. 64k x <=256B is <= ~16MB.
_MURMUR_CACHE_MAX_KEY = 256
# the public wrapper below normalizes every non-bytes buffer before this
# memo sees it, so the unhashable/mutable-key hazard cannot reach it
_murmur3_32_lru = functools.lru_cache(maxsize=65536)(murmur3_32)  # m3lint: disable=cache-key-buffer


def murmur3_32_cached(data: bytes, seed: int = 0) -> int:
    if type(data) is not bytes:
        # bytearray/memoryview hash the same bytes but are unhashable (or
        # mutable — a cache key that can change underneath the memo), so
        # normalize before the cached path; mirrors the oversize bypass.
        data = bytes(data)
    if len(data) > _MURMUR_CACHE_MAX_KEY:
        return murmur3_32(data, seed)
    return _murmur3_32_lru(data, seed)


def _padded_words(ids: Sequence[bytes]):
    """(buf, words, lens): the ids as a zero-padded [N, maxlen] byte
    matrix, its little-endian u32 view, and their lengths."""
    n = len(ids)
    lens = np.fromiter(map(len, ids), np.int64, n)
    maxlen = int(lens.max(initial=1))
    padded = maxlen + (-maxlen) % 4
    buf = np.zeros((n, padded), np.uint8)
    # One concatenated buffer + boolean scatter instead of a frombuffer
    # per id: row-major mask order equals concatenation order — this
    # runs per write batch on the shard routing path, so the per-id
    # Python loop was measurable.
    joined = b"".join(ids)
    if joined:
        mask = np.arange(padded)[None, :] < lens[:, None]
        buf[mask] = np.frombuffer(joined, np.uint8)
    return buf, buf.view("<u4"), lens  # words: [n, padded // 4]


def hash_batch(ids: Sequence[bytes], seed: int = 0) -> np.ndarray:
    """Vectorized murmur3-32 over variable-length IDs.

    IDs are padded into a [N, maxlen] byte matrix; the 4-byte block mixing
    runs columnwise in numpy with per-row active masks, so throughput scales
    with the longest ID rather than per-ID Python loops."""
    if not len(ids):
        return np.zeros(0, np.uint32)
    buf, words, lens = _padded_words(ids)

    # Pallas route (ops.pallas_codec.hash_words, lane-parallel murmur3):
    # same padded-buffer layout, bit-identical output; gated on the codec
    # dispatch switch plus a column bound past which the VMEM tile stops
    # paying. The numpy loop below stays the fallback AND the oracle.
    try:
        from ..ops import pallas_codec
    except Exception:  # jax-less contexts keep the pure-numpy path
        pallas_codec = None
    if pallas_codec is not None:
        from ..parallel import guard

        use = (pallas_codec.enabled()
               and 0 < words.shape[1] <= pallas_codec.HASH_MAX_COLS
               and guard.available("codec.hash"))
        pallas_codec.route("hash", use)
        if use:
            out = guard.dispatch(
                "codec.hash",
                lambda: np.asarray(pallas_codec.hash_words(
                    words, lens, seed)),
                lambda _err: None)
            if out is not None:
                return out
            # Guarded fallback: fall through to the numpy loop below —
            # the declared oracle for this kernel.
    return _murmur3_words_host(buf, words, lens, seed)


def hash_batch_host(ids: Sequence[bytes], seed: int = 0) -> np.ndarray:
    """`hash_batch` on the host alone: no codec dispatch, no route
    counter, no jit keyed by the row count. For a serving path whose
    batches come in any size (the shard memo's misses, ShardSet.
    lookup_memo): a device call there would compile a new shape
    mid-traffic."""
    if not len(ids):
        return np.zeros(0, np.uint32)
    return _murmur3_words_host(*_padded_words(ids), seed)


def _murmur3_words_host(buf, words, lens, seed: int) -> np.ndarray:
    n, padded = buf.shape
    h = np.full(n, seed, np.uint32)
    nblocks = lens // 4
    with np.errstate(over="ignore"):
        for j in range(words.shape[1]):
            active = nblocks > j
            k = words[:, j] * np.uint32(_C1)
            k = (k << np.uint32(15)) | (k >> np.uint32(17))
            k = k * np.uint32(_C2)
            h2 = h ^ k
            h2 = (h2 << np.uint32(13)) | (h2 >> np.uint32(19))
            h2 = h2 * np.uint32(5) + np.uint32(0xE6546B64)
            h = np.where(active, h2, h)

        # Tail bytes.
        full = (lens - lens % 4).astype(np.int64)
        tail_len = (lens % 4).astype(np.int64)
        idx = np.minimum(full[:, None] + np.arange(3)[None, :], padded - 1)
        tb = np.take_along_axis(buf, idx, axis=1).astype(np.uint32)
        k = np.zeros(n, np.uint32)
        k = np.where(tail_len >= 3, k ^ (tb[:, 2] << np.uint32(16)), k)
        k = np.where(tail_len >= 2, k ^ (tb[:, 1] << np.uint32(8)), k)
        has_tail = tail_len >= 1
        k = np.where(has_tail, k ^ tb[:, 0], k)
        k = k * np.uint32(_C1)
        k = (k << np.uint32(15)) | (k >> np.uint32(17))
        k = k * np.uint32(_C2)
        h = np.where(has_tail, h ^ k, h)

        h ^= lens.astype(np.uint32)
        h ^= h >> np.uint32(16)
        h = h * np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h = h * np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h
