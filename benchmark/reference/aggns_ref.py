"""The plain reference of a coordinator with a namespace list: numpy
float64 over the seed's 10-second data, independent of the program.

(a) The aggregated truth is derived from the 10 s truth it is handed,
never drawn apart: a `downsample.all` namespace at resolution R holds,
per series, the `last` sample of each closed window [t, t + R), stamped
t + R (the rule the configuration states: minute [t, t + 60 s) of
scrapes at t ... t + 50 s is one point at t + 60 s holding the scrape of
t + 50 s).

(b) Its own copy of the resolver's rule decides which namespace answers
a request, from the configuration's `dbnode.coordinator.namespaces`, the
coordinator's clock (one cadence after the newest held scrape) and the
oldest instant the request fetches: a query the program sent to another
namespace is a wrong answer here (10 s and 1-minute data differ), not a
silent one.

(c) The class's window function (`reference` in the class file) runs
over the resolved namespace's truth with Prometheus' window (t - w, t],
then the class's threshold and grouping, as promql_ref does over the one
namespace it knows. `parse_response` and `compare` are promql_ref's (the
file beside this one, loaded by its path).

`control` computes the answer with one thing broken, and `compare` has
to tell it apart: "wrong_namespace" answers a request the rule sends to
one namespace from the other; "bf16" does the arithmetic in bfloat16;
"stale" answers without the live stretch (the open buffers)."""

from __future__ import annotations

import importlib.util
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "reference_promql_ref_for_aggns",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "promql_ref.py"))
_promql = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_promql)

parse_response = _promql.parse_response
compare = _promql.compare
LOOKBACK_S = _promql.LOOKBACK_S

_UNITS = {"s": 1, "m": 60, "h": 3600, "d": 86400}


def seconds(text: str) -> int:
    """'90s', '20m', '2h', '3d' -> seconds."""
    m = re.fullmatch(r"(\d+)([smhd])", text.strip())
    if m is None:
        raise ValueError(f"not a duration: {text!r}")
    return int(m.group(1)) * _UNITS[m.group(2)]


def namespaces(cfg: dict) -> List[dict]:
    """The coordinator's namespace list with what the node keeps of each:
    name, aggregated, retention_s, resolution_s, complete, block_s."""
    node = {ns["name"]: ns for ns in cfg["dbnode"]["namespaces"]}
    out = []
    for ns in cfg["dbnode"]["coordinator"]["namespaces"]:
        agg = ns.get("type", "unaggregated") == "aggregated"
        out.append({
            "name": ns["namespace"], "aggregated": agg,
            "retention_s": seconds(ns["retention"]),
            "resolution_s": seconds(ns["resolution"]) if agg else 0,
            "complete": agg and (ns.get("downsample") or {}).get("all", True),
            "block_s": seconds(node[ns["namespace"]]["block_size"])})
    return out


def resolve(nss: List[dict], now_s: int, fetch_start_s: int
            ) -> Tuple[List[dict], str]:
    """The rule, written a second time: (namespaces that answer, finest
    first; "unaggregated" | "aggregated" | "partial")."""
    raw = [ns for ns in nss if not ns["aggregated"]][0]
    if now_s - raw["retention_s"] <= fetch_start_s:
        return [raw], "unaggregated"
    reach = [ns for ns in nss if ns["aggregated"]
             and now_s - ns["retention_s"] <= fetch_start_s]
    whole = [ns for ns in reach if ns["complete"]]
    if whole:
        best = sorted(whole, key=lambda ns: (ns["resolution_s"],
                                             -ns["retention_s"]))[0]
        finer = sorted((ns for ns in reach if not ns["complete"]
                        and ns["resolution_s"] < best["resolution_s"]),
                       key=lambda ns: ns["resolution_s"])
        return finer + [best], "aggregated"
    longest = sorted((ns for ns in nss if ns["aggregated"]),
                     key=lambda ns: (-ns["retention_s"],
                                     ns["resolution_s"]))[0]
    return [raw, longest], "partial"


def fetch_start_s(cls: dict, req: dict) -> int:
    """The oldest instant the request fetches: its first point's window,
    and the instant selector's lookback under a subquery."""
    window_s = int(cls["reference"].get("window_s", LOOKBACK_S))
    return int(req["start_s"]) - window_s - LOOKBACK_S


def truth(ns: dict, vals: np.ndarray, idx: np.ndarray, t0_s: int,
          cadence_s: int, now_s: int, held: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """(timestamps [P], values [len(idx), P]) of what a namespace holds
    of the selected series: from the block that straddles the edge of
    its retention to the newest closed window."""
    oldest_s = now_s - ns["retention_s"]
    oldest_s -= oldest_s % ns["block_s"]
    if not ns["aggregated"]:
        k = np.arange(max(0, -(-(oldest_s - t0_s) // cadence_s)), held)
        return t0_s + k * cadence_s, vals[idx][:, k]
    if not ns["complete"]:
        raise ValueError(f"namespace {ns['name']!r} is partial: what a rule "
                         "set sends there is not modelled")
    res = ns["resolution_s"]
    per = res // cadence_s      # scrapes a window
    # window k (k >= 1) is [t0 + (k-1) res, t0 + k res), stamped t0 + k res,
    # and holds the last of its scrapes: step k * per - 1
    k = np.arange(max(1, -(-(oldest_s - t0_s) // res)), held // per + 1)
    return t0_s + k * res, vals[idx][:, k * per - 1]


def merged(parts: List[Tuple[np.ndarray, np.ndarray]]
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Several namespaces' truth as one run: the earlier (finer) part
    wins an equal timestamp."""
    if len(parts) == 1:
        return parts[0]
    t = np.concatenate([p[0] for p in parts])
    v = np.concatenate([p[1] for p in parts], axis=1)
    order = np.argsort(t, kind="stable")
    t, v = t[order], v[:, order]
    keep = np.ones(len(t), bool)
    keep[1:] = t[1:] != t[:-1]
    return t[keep], v[:, keep]


def _window_rows(ts: np.ndarray, v: np.ndarray, times_s: np.ndarray,
                 window_s: int, fn: str, dtype) -> np.ndarray:
    out = np.full((v.shape[0], len(times_s)), np.nan, np.float64)
    lo = np.searchsorted(ts, times_s - window_s, side="right")
    hi = np.searchsorted(ts, times_s, side="right")
    for j, (a, b) in enumerate(zip(lo.tolist(), hi.tolist())):
        if b <= a:
            continue
        w = v[:, a:b].astype(dtype)
        if fn == "max":
            r = w.max(axis=1)
        elif fn == "avg":
            r = w.sum(axis=1, dtype=dtype) / dtype(w.shape[1])
        elif fn == "last":
            r = w[:, -1]
        else:
            raise ValueError(f"unknown window function {fn!r}")
        out[:, j] = r.astype(np.float64)
    return out


def evaluate(cls: dict, cfg: dict, labels: List[Dict[str, str]],
             vals: np.ndarray, req: dict, t0_s: int,
             control: Optional[str] = None,
             open_steps: int = 0) -> Dict[frozenset, np.ndarray]:
    """The class's answer to one request: label set -> row of values at
    start, start + step, ... end (NaN where there is no point)."""
    ref = cls["reference"]
    cadence_s = int(cfg["cadence_s"])
    held = vals.shape[1]
    now_s = t0_s + held * cadence_s
    dtype = np.float64
    if control == "bf16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    elif control == "stale":
        held -= open_steps
    elif control not in (None, "wrong_namespace"):
        raise ValueError(f"unknown control {control!r}")
    nss = namespaces(cfg)
    picked, how = resolve(nss, now_s, fetch_start_s(cls, req))
    if control == "wrong_namespace":
        picked = [ns for ns in nss
                  if ns["aggregated"] != (how == "aggregated")][:1]
    idx = _promql.select(cfg, req["hosts"], req["fields"])
    ts, v = merged([truth(ns, vals, idx, t0_s, cadence_s, now_s, held)
                    for ns in picked])
    times = np.arange(req["start_s"], req["end_s"] + 1, req["step_s"])
    window_s = int(ref.get("window_s", LOOKBACK_S))
    rows = _window_rows(ts, v, times, window_s, ref["window_fn"], dtype)
    thr = ref.get("keep_above")
    if thr is not None:
        rows = np.where(rows > thr, rows, np.nan)
    by = ref.get("group_by")
    if by is None:
        drop = () if ref.get("keep_name") else ("__name__",)
        return {frozenset((k, val) for k, val in labels[i].items()
                          if k not in drop): rows[r]
                for r, i in enumerate(idx)}
    groups: Dict[frozenset, List[int]] = {}
    for r, i in enumerate(idx):
        groups.setdefault(frozenset((k, labels[i][k]) for k in by),
                          []).append(r)
    out = {}
    for key, members in groups.items():
        g = rows[members]
        some = np.isfinite(g).any(axis=0)
        if dtype is not np.float64:   # the control groups in its precision too
            g = g.astype(dtype).astype(np.float64)
        with np.errstate(all="ignore"):
            if ref["group_fn"] == "max":
                val = np.nanmax(np.where(some, g, 0.0), axis=0)
            elif ref["group_fn"] == "avg":
                val = np.nanmean(np.where(some, g, 0.0), axis=0)
                if dtype is not np.float64:
                    val = val.astype(dtype).astype(np.float64)
            else:
                raise ValueError(f"unknown group function {ref['group_fn']!r}")
        out[key] = np.where(some, val, np.nan)
    return out


def aggregated_truth(vals: np.ndarray, cadence_s: int, resolution_s: int
                     ) -> np.ndarray:
    """[series, K]: column k - 1 is window k's `last` (stamped
    t0 + k * resolution), for every closed window of the held steps."""
    per = resolution_s // cadence_s
    return vals[:, per - 1::per]
