"""What each query class must answer, computed in numpy float64 straight
from the generated samples. Not a PromQL engine: a class file's
`reference` block names one of a few shapes (a per-series function over
a trailing window, an optional threshold, an optional grouping) and this
evaluates that shape with Prometheus' window (t - w, t].

`control` computes the same answer with one thing broken, and has to be
told apart by `compare`: "bf16" does the arithmetic in bfloat16 (the
next precision below the f32 the compiled route accumulates in);
"stale" answers from sealed blocks only, as a read that misses the open
buffer would."""

from __future__ import annotations

import json
from typing import Dict, List, Optional

import numpy as np

S = 1_000_000_000
LOOKBACK_S = 300     # Prometheus' default lookback for an instant selector


def select(cfg: dict, hosts: Optional[List[int]],
           fields: Optional[List[int]]) -> np.ndarray:
    nf = len(cfg["schema"]["fields"])
    h = np.arange(cfg["scale"]) if hosts is None else np.asarray(hosts)
    f = np.arange(nf) if fields is None else np.asarray(fields)
    return (h[:, None] * nf + f[None, :]).ravel()


def _window_rows(vals: np.ndarray, idx: np.ndarray, t0_s: int, cadence_s: int,
                 times_s: np.ndarray, window_s: int, fn: str, held: int,
                 dtype) -> np.ndarray:
    """fn over the samples of each selected series in (t - window, t]."""
    out = np.full((len(idx), len(times_s)), np.nan, np.float64)
    sub = vals[idx]
    for j, t in enumerate(times_s):
        hi = min((int(t) - t0_s) // cadence_s, held - 1)
        lo = max((int(t) - window_s - t0_s) // cadence_s + 1, 0)
        if hi < lo:
            continue
        w = sub[:, lo:hi + 1].astype(dtype)
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
    dtype = np.float64
    held = vals.shape[1]
    if control == "bf16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    elif control == "stale":
        held -= open_steps
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")
    idx = select(cfg, req["hosts"], req["fields"])
    times = np.arange(req["start_s"], req["end_s"] + 1, req["step_s"])
    window_s = int(ref.get("window_s", LOOKBACK_S))
    rows = _window_rows(vals, idx, t0_s, int(cfg["cadence_s"]), times,
                        window_s, ref["window_fn"], held, dtype)
    thr = ref.get("keep_above")
    if thr is not None:
        rows = np.where(rows > thr, rows, np.nan)
    by = ref.get("group_by")
    if by is None:
        drop = () if ref.get("keep_name") else ("__name__",)
        return {frozenset((k, v) for k, v in labels[i].items()
                          if k not in drop): rows[r]
                for r, i in enumerate(idx)}
    groups: Dict[frozenset, List[int]] = {}
    for r, i in enumerate(idx):
        key = frozenset((k, labels[i][k]) for k in by)
        groups.setdefault(key, []).append(r)
    out = {}
    for key, members in groups.items():
        g = rows[members]
        some = np.isfinite(g).any(axis=0)
        if dtype is not np.float64:   # the control groups in its precision too
            g = g.astype(dtype).astype(np.float64)
        with np.errstate(all="ignore"):
            if ref["group_fn"] == "max":
                v = np.nanmax(np.where(some, g, 0.0), axis=0)
            elif ref["group_fn"] == "avg":
                v = np.nanmean(np.where(some, g, 0.0), axis=0)
                if dtype is not np.float64:
                    v = v.astype(dtype).astype(np.float64)
            else:
                raise ValueError(f"unknown group function {ref['group_fn']!r}")
        out[key] = np.where(some, v, np.nan)
    return out


def parse_response(body: str, req: dict) -> Dict[frozenset, np.ndarray]:
    """A Prometheus matrix / vector response as label set -> row on the
    request's own grid."""
    resp = json.loads(body)
    if resp.get("status") != "success":
        raise ValueError(f"query failed: {body[:300]}")
    steps = (req["end_s"] - req["start_s"]) // req["step_s"] + 1
    out = {}
    for s in resp["data"]["result"]:
        row = np.full(steps, np.nan)
        pts = s["values"] if "values" in s else [s["value"]]
        for t, v in pts:
            row[int(round((float(t) - req["start_s"]) / req["step_s"]))] = \
                float(v)
        out[frozenset(s["metric"].items())] = row
    return out


def compare(got: Dict[frozenset, np.ndarray],
            want: Dict[frozenset, np.ndarray]) -> dict:
    """The numbers `correct` is decided on, for one answer:
    label sets that differ, points present on one side only, and the
    worst gap of a served value from the reference's, relative to the
    reference's value or to a hundredth of the answer's largest, whichever
    is larger (a mean near zero is as exact as f32 sums of numbers up to
    100 make it, not relatively)."""
    want = {k: v for k, v in want.items() if np.isfinite(v).any()}
    got = {k: v for k, v in got.items() if np.isfinite(v).any()}
    both = [k for k in want if k in got]
    out = {"label_sets_differ": len(set(want) ^ set(got)),
           "points_missing_or_extra": 0, "worst_rel_gap": 0.0,
           "values": 0}
    if not both:
        return out
    g = np.stack([got[k] for k in both])
    w = np.stack([want[k] for k in both])
    fg, fw = np.isfinite(g), np.isfinite(w)
    out["points_missing_or_extra"] = int((fg != fw).sum())
    m = fg & fw
    out["values"] = int(m.sum())
    if m.any():
        scale = float(np.abs(w[m]).max())
        denom = np.maximum(np.abs(w[m]), 1e-2 * scale if scale else 1.0)
        out["worst_rel_gap"] = float((np.abs(g[m] - w[m]) / denom).max())
    return out
