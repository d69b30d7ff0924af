"""promql_ref (the file beside this one, loaded by its path: the plain
reference is not copied) with one more control, told apart by
`compare`: "unindexed" answers as a node would that indexes a series in
the index block of its first sighting only. Every series of the seed's
data is first seen at `datagen.T0`, so such a node finds nobody for a
query whose whole range (its first point's window included) lies after
the first index-block boundary past T0, and answers it with no series
at all; a query that still overlaps the first index block it answers
soundly. The index block is the configuration's (`dbnode.index_block`,
absent: the program's default, 4 hours)."""

from __future__ import annotations

import importlib.util
import os
from typing import Optional

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "reference_promql_ref_for_indexed",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "promql_ref.py"))
_promql = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_promql)

parse_response = _promql.parse_response
compare = _promql.compare

INDEX_BLOCK_S = 4 * 3600


def first_index_boundary_s(cfg: dict, t0_s: int) -> int:
    """The first index-block boundary after the data's first instant."""
    size = int(cfg.get("index_block_s", INDEX_BLOCK_S))
    return t0_s - t0_s % size + size


def starts_past_first_index_block(cls: dict, cfg: dict, req: dict,
                                  t0_s: int) -> bool:
    window_s = int(cls["reference"].get("window_s", _promql.LOOKBACK_S))
    return req["start_s"] - window_s >= first_index_boundary_s(cfg, t0_s)


def evaluate(cls: dict, cfg: dict, labels, vals: np.ndarray, req: dict,
             t0_s: int, control: Optional[str] = None, open_steps: int = 0):
    if control == "unindexed":
        if starts_past_first_index_block(cls, cfg, req, t0_s):
            return {}
        control = None
    return _promql.evaluate(cls, cfg, labels, vals, req, t0_s,
                            control=control, open_steps=open_steps)
