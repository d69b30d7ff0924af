"""What each query class must answer when every target is scraped at its
own offset inside the interval (Prometheus: `offset + k * interval`, in
whole milliseconds), computed in numpy float64 per series over that
series' OWN timestamps with Prometheus' window (t - w, t]. Not a PromQL
engine: a class file's `reference` block names one of a few shapes (a
per-series function over a trailing window, an optional threshold, an
optional grouping), as `promql_ref.py` reads them. Nothing here indexes
a cadence the series share: a window's samples are found by searching
the series' own sorted timestamps, so a series may have any timestamps
and any holes (`visible`: which (host, scrape) pairs the answer may
see — the acknowledged ones, at a write frontier).

`control` computes the same answer with one thing broken, and has to be
told apart by `compare`: "bf16" does the arithmetic in bfloat16 (the
next precision below the f32 the compiled route accumulates in);
"stale" answers from sealed blocks only, as a read that misses the open
buffer would; "aligned" stamps every target on the shared grid, offset
0, as TSBS does and no Prometheus.

At a write frontier an answer may also hold samples that were in flight
while it was computed: `candidates` lists, for every output (row, step),
the in-flight values its window can reach, and `compare_frontier` holds
the served value to the acknowledged answer joined with SOME subset of
them (for a MAX of MAXes: the acknowledged maximum, or an in-flight
value above it) and to nothing else."""

from __future__ import annotations

import json
from typing import Dict, List, Optional

import numpy as np

S = 1_000_000_000
MS = 1_000_000
LOOKBACK_S = 300     # Prometheus' default lookback for an instant selector
CONTROLS = ("bf16", "stale", "aligned")


def select(cfg: dict, hosts: Optional[List[int]],
           fields: Optional[List[int]]) -> np.ndarray:
    nf = len(cfg["schema"]["fields"])
    h = np.arange(cfg["scale"]) if hosts is None else np.asarray(hosts)
    f = np.arange(nf) if fields is None else np.asarray(fields)
    return (h[:, None] * nf + f[None, :]).ravel()


def series_times_ns(cfg: dict, t0_s: int, off_ms: int, steps: int
                    ) -> np.ndarray:
    """One target's own scrape timestamps: t0 + offset + k * interval."""
    return (t0_s * S + int(off_ms) * MS
            + np.arange(steps, dtype=np.int64) * int(cfg["cadence_s"]) * S)


def _reduce(w: np.ndarray, fn: str, dtype):
    w = w.astype(dtype)
    if fn == "max":
        return w.max()
    if fn == "avg":
        return w.sum(dtype=dtype) / dtype(len(w))
    if fn == "last":
        return w[-1]
    raise ValueError(f"unknown window function {fn!r}")


def _window_row(t: np.ndarray, v: np.ndarray, x_ns: np.ndarray,
                window_ns: int, fn: str, dtype) -> np.ndarray:
    """fn over one series' samples (sorted times t, values v) in
    (x - window, x] for each output time x."""
    out = np.full(len(x_ns), np.nan, np.float64)
    lo = np.searchsorted(t, x_ns - window_ns, side="right")
    hi = np.searchsorted(t, x_ns, side="right")
    for j in range(len(x_ns)):
        if hi[j] > lo[j]:
            out[j] = np.float64(_reduce(v[lo[j]:hi[j]], fn, dtype))
    return out


def _group_keys(ref: dict, labels, idx):
    """Output row key of every selected series, in order."""
    by = ref.get("group_by")
    if by is None:
        drop = () if ref.get("keep_name") else ("__name__",)
        return [frozenset((k, v) for k, v in labels[i].items()
                          if k not in drop) for i in idx]
    return [frozenset((k, labels[i][k]) for k in by) for i in idx]


def evaluate(cls: dict, cfg: dict, labels: List[Dict[str, str]],
             vals: np.ndarray, req: dict, t0_s: int,
             control: Optional[str] = None, open_steps: int = 0,
             offsets_ms: Optional[np.ndarray] = None,
             visible: Optional[np.ndarray] = None
             ) -> Dict[frozenset, np.ndarray]:
    """The class's answer to one request: label set -> row of values at
    start, start + step, ... end (NaN where there is no point).
    `offsets_ms` [hosts] are the targets' offsets; `visible`
    [hosts, steps] bool says which scrapes the answer may see (None:
    every column of `vals`)."""
    ref = cls["reference"]
    dtype = np.float64
    steps = vals.shape[1]
    nf = len(cfg["schema"]["fields"])
    if offsets_ms is None:
        raise ValueError("promql_offset_ref needs the targets' offsets")
    if control == "bf16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    elif control == "stale":
        steps -= open_steps
    elif control == "aligned":
        offsets_ms = np.zeros_like(offsets_ms)
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")
    idx = select(cfg, req["hosts"], req["fields"])
    x_ns = np.arange(req["start_s"], req["end_s"] + 1, req["step_s"],
                     dtype=np.int64) * S
    window_ns = int(ref.get("window_s", LOOKBACK_S)) * S
    rows = np.full((len(idx), len(x_ns)), np.nan, np.float64)
    for r, i in enumerate(idx):
        h = int(i) // nf
        t = series_times_ns(cfg, t0_s, offsets_ms[h], steps)
        v = vals[i, :steps]
        if visible is not None:
            keep = visible[h, :steps]
            t, v = t[keep], v[keep]
        rows[r] = _window_row(t, v, x_ns, window_ns, ref["window_fn"], dtype)
    thr = ref.get("keep_above")
    if thr is not None:
        rows = np.where(rows > thr, rows, np.nan)
    keys = _group_keys(ref, labels, idx)
    if ref.get("group_by") is None:
        return dict(zip(keys, rows))
    groups: Dict[frozenset, List[int]] = {}
    for r, key in enumerate(keys):
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


def candidates(cls: dict, cfg: dict, labels: List[Dict[str, str]],
               vals: np.ndarray, req: dict, t0_s: int,
               offsets_ms: np.ndarray, in_flight: List[tuple]
               ) -> Dict[frozenset, Dict[int, List[float]]]:
    """For every output (row, step) that a sample in flight can reach:
    the in-flight values inside its window. `in_flight` lists (host,
    scrape) pairs. Only a MAX over a MAX can be held to a subset of
    candidates; any other shape with a candidate raises."""
    ref = cls["reference"]
    nf = len(cfg["schema"]["fields"])
    idx = select(cfg, req["hosts"], req["fields"])
    keys = _group_keys(ref, labels, idx)
    x_ns = np.arange(req["start_s"], req["end_s"] + 1, req["step_s"],
                     dtype=np.int64) * S
    window_ns = int(ref.get("window_s", LOOKBACK_S)) * S
    by_host: Dict[int, List[int]] = {}
    for h, k in in_flight:
        by_host.setdefault(int(h), []).append(int(k))
    out: Dict[frozenset, Dict[int, List[float]]] = {}
    for r, i in enumerate(idx):
        for k in by_host.get(int(i) // nf, ()):
            if k >= vals.shape[1]:
                continue
            t = t0_s * S + int(offsets_ms[int(i) // nf]) * MS \
                + k * int(cfg["cadence_s"]) * S
            for j in np.flatnonzero((x_ns - window_ns < t) & (t <= x_ns)):
                out.setdefault(keys[r], {}).setdefault(int(j), []).append(
                    float(vals[i, k]))
    if out and (ref["window_fn"] != "max"
                or ref.get("group_fn", "max") != "max"
                or ref.get("keep_above") is not None):
        raise ValueError("a write frontier is held exactly for MAX classes "
                         f"alone, not {ref!r}")
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


def compare_frontier(got: Dict[frozenset, np.ndarray],
                     want: Dict[frozenset, np.ndarray],
                     cands: Dict[frozenset, Dict[int, List[float]]]) -> dict:
    """The numbers `correct` is decided on, for one answer. `want` is the
    reference over the acknowledged samples. An output (row, step) with
    no candidate must equal it; one with candidates must equal it or a
    candidate above it (a candidate where it has no point). Gaps are
    relative to the reference's value or to a hundredth of the answer's
    largest, whichever is larger, as `promql_ref.compare` has them.
    `frontier_pairs`: (row, step) pairs with a candidate;
    `took_in_flight`: those of them whose value was a candidate's."""
    reach = {k for k, c in cands.items() if c}
    want = {k: v for k, v in want.items()
            if np.isfinite(v).any() or k in reach}
    got = {k: v for k, v in got.items() if np.isfinite(v).any()}
    # a row the acknowledged samples leave empty may be absent
    optional = {k for k in want if not np.isfinite(want[k]).any()}
    out = {"label_sets_differ": len((set(want) - optional) ^ (set(got)
                                                              - optional)),
           "points_missing_or_extra": 0, "worst_rel_gap": 0.0, "values": 0,
           "frontier_pairs": 0, "took_in_flight": 0}
    both = [k for k in want if k in got]
    if not both:
        return out
    finite = [want[k][np.isfinite(want[k])] for k in both]
    finite = np.concatenate(finite) if finite else np.zeros(0)
    scale = float(np.abs(finite).max()) if finite.size else 0.0
    floor = 1e-2 * scale if scale else 1.0

    def gap(g: float, w: float) -> float:
        return abs(g - w) / max(abs(w), floor)

    for k in both:
        g_row, w_row, c_row = got[k], want[k], cands.get(k, {})
        for j in range(len(w_row)):
            g, w = float(g_row[j]), float(w_row[j])
            open_to = [c for c in c_row.get(j, ())
                       if not np.isfinite(w) or c > w]
            out["frontier_pairs"] += bool(c_row.get(j))
            if not np.isfinite(g):
                # no point: right only where the acknowledged samples
                # give none either
                out["points_missing_or_extra"] += bool(np.isfinite(w))
                continue
            acked = [w] if np.isfinite(w) else []
            choices = acked + open_to
            if not choices:
                out["points_missing_or_extra"] += 1
                continue
            out["values"] += 1
            gaps = [gap(g, c) for c in choices]
            best = int(np.argmin(gaps))
            out["worst_rel_gap"] = max(out["worst_rel_gap"], gaps[best])
            out["took_in_flight"] += best >= len(acked)
    return out


def compare(got: Dict[frozenset, np.ndarray],
            want: Dict[frozenset, np.ndarray]) -> dict:
    """`compare_frontier` with nothing in flight: label sets that differ,
    points present on one side only, and the worst relative gap."""
    return compare_frontier(got, want, {})
