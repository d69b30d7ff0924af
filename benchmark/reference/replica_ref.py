"""What replication must and must not change, in plain Python and numpy,
importing nothing of the program.

Answers. Replication must not change an answer: `evaluate`,
`parse_response` and `compare` are promql_ref's (the file beside this
one, loaded by its path), over the seed's data. Controls, told apart by
`compare`: "one_replica" answers as a read at consistency One would
from a replica that lacks the open buffer (promql_ref's "stale": sealed
blocks only); "bf16" and "stale" as there; "drop_replica_write" is the
replica_readback check's and leaves the answers sound.

Holdings. `holders(ids, placement, num_shards)`: the instances that
must hold each series under a placement (shard -> instance ids), by
murmur3-32 of the id modulo the shard count (M3's sharding;
`murmur3_32` here is written from the published algorithm, not from the
program's). An acknowledged write must be readable from at least
`majority(rf)` of them, and from all of them once nothing is in flight
and no node was lost.

Merge. `merge_replicas(ts_parts, vs_parts)`: k replicas' points of one
series as one series: ascending timestamps, one point a timestamp, the
LAST replica in the given order winning a timestamp several hold
(`LAST_PUSHED`)."""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "reference_promql_ref_for_replicas",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "promql_ref.py"))
_promql = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_promql)

parse_response = _promql.parse_response
compare = _promql.compare

_ANSWER_CONTROLS = {"one_replica": "stale", "drop_replica_write": None,
                    "bf16": "bf16", "stale": "stale", None: None}


def evaluate(cls: dict, cfg: dict, labels, vals: np.ndarray, req: dict,
             t0_s: int, control: Optional[str] = None, open_steps: int = 0):
    if control not in _ANSWER_CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    return _promql.evaluate(cls, cfg, labels, vals, req, t0_s,
                            control=_ANSWER_CONTROLS[control],
                            open_steps=open_steps)


# ------------------------------------------------------------------ holdings

_M32 = 0xFFFFFFFF


def _rotl(x: int, r: int) -> int:
    return ((x << r) | (x >> (32 - r))) & _M32


def murmur3_32(data: bytes, seed: int = 0) -> int:
    """MurmurHash3 x86 32-bit (Appleby's reference algorithm)."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h = seed & _M32
    n = len(data)
    for i in range(0, n - n % 4, 4):
        k = int.from_bytes(data[i:i + 4], "little")
        k = (_rotl((k * c1) & _M32, 15) * c2) & _M32
        h = (_rotl(h ^ k, 13) * 5 + 0xE6546B64) & _M32
    tail = data[n - n % 4:]
    if tail:
        k = int.from_bytes(tail, "little")
        h ^= (_rotl((k * c1) & _M32, 15) * c2) & _M32
    h ^= n
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    return h ^ (h >> 16)


def majority(replicas: int) -> int:
    return replicas // 2 + 1


def holders(ids: Sequence[bytes], placement: Dict[int, Sequence[str]],
            num_shards: int) -> List[Tuple[str, ...]]:
    """For each series id, the instances that must hold it."""
    return [tuple(sorted(placement.get(murmur3_32(sid) % num_shards, ())))
            for sid in ids]


# --------------------------------------------------------------------- merge


def merge_replicas(ts_parts: Sequence[np.ndarray],
                   vs_parts: Sequence[np.ndarray]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    merged: Dict[int, float] = {}
    for ts, vs in zip(ts_parts, vs_parts):
        for t, v in zip(np.asarray(ts).tolist(), np.asarray(vs).tolist()):
            merged[int(t)] = float(v)      # a later replica overwrites
    order = sorted(merged)
    return (np.array(order, np.int64),
            np.array([merged[t] for t in order], np.float64))
