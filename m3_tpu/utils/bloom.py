"""Bloom filter over series IDs (reference: m3db/bloom used by fileset
seekers, src/dbnode/persist/fs/bloom_filter.go) — numpy bit array with k
murmur3 hashes derived from two base hashes (Kirsch-Mitzenmacher)."""

from __future__ import annotations

import math

import numpy as np

from .hashing import hash_batch, hash_batch_host, murmur3_32


class BloomFilter:
    def __init__(self, m_bits: int, k: int):
        self.m = max(int(m_bits), 8)
        self.k = max(int(k), 1)
        self.bits = np.zeros((self.m + 7) // 8, np.uint8)

    @staticmethod
    def for_capacity(n: int, false_positive_rate: float = 0.02) -> "BloomFilter":
        n = max(n, 1)
        m = int(-n * math.log(false_positive_rate) / (math.log(2) ** 2)) + 1
        k = max(int(round(m / n * math.log(2))), 1)
        return BloomFilter(m, k)

    def _positions(self, item: bytes) -> np.ndarray:
        h1 = murmur3_32(item)
        h2 = murmur3_32(item, seed=0x9747B28C)
        i = np.arange(self.k, dtype=np.uint64)
        return ((h1 + i * h2) % np.uint64(self.m)).astype(np.int64)

    def add(self, item: bytes):
        pos = self._positions(item)
        np.bitwise_or.at(self.bits, pos >> 3, (1 << (pos & 7)).astype(np.uint8))

    def add_batch(self, items, on_host: bool = False):
        """`on_host`: hash without the device route, whose program is
        keyed by the batch's row count (hashing.hash_batch_host): for a
        batch of no settled size. The bits are the same."""
        if not len(items):
            return
        hashed = hash_batch_host if on_host else hash_batch
        h1 = hashed(items).astype(np.uint64)
        h2 = hashed(items, seed=0x9747B28C).astype(np.uint64)
        i = np.arange(self.k, dtype=np.uint64)[None, :]
        pos = ((h1[:, None] + i * h2[:, None]) % np.uint64(self.m)).astype(np.int64).ravel()
        np.bitwise_or.at(self.bits, pos >> 3, (1 << (pos & 7)).astype(np.uint8))

    def __contains__(self, item: bytes) -> bool:
        pos = self._positions(item)
        return bool(((self.bits[pos >> 3] >> (pos & 7)) & 1).all())

    def tobytes(self) -> bytes:
        return self.bits.tobytes()

    @classmethod
    def frombytes(cls, data: bytes, m_bits: int, k: int) -> "BloomFilter":
        bf = cls(m_bits, k)
        bf.bits = np.frombuffer(data, np.uint8).copy()
        return bf
