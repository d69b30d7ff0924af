"""What a range function over a PLAIN range selector must answer, in
numpy float64 straight from the samples, a loop a step, Prometheus' own
rules written out (promql/functions.go): the window of an evaluation at
T is the raw samples with T - w < t <= T; `rate`/`increase`/`delta`
difference the window's samples (a counter's drop is a reset: the value
after it counts whole), then extrapolate to a window edge that lies
within 1.1 mean sample intervals of the first or last sample, else by
half an interval, a counter never below zero; `irate`/`idelta` take the
last two samples. Nothing of the program is imported.

One rule is written more exactly than Go writes it. "Within 1.1 mean
sample intervals" chooses between two extrapolations that differ by half
an interval, and exact ties are common where whole-second query times
meet scrapes on their cadence (a first sample 11 s inside the window at
a 10 s cadence). Upstream compares `durationToStart <
averageDurationBetweenSamples * 1.1` in float64, where a tie hangs on
how 1.1 times the interval rounds (10, 15, 30 and 60 s round to the
product itself, so the tie is not within; 7 s does not). Here the
comparison is cross-multiplied, 10 d (n - 1) < 11 (t_last - t_first),
which is the rule itself and exact for whole-second times: a tie is
never within (DIVERGENCES.md).

`window_values` is one function over one timestamp list (what
`tests/test_range_selector_raw_samples.py` holds both routes to, on
timestamps of its own making); `evaluate` is a query class's answer from
the benchmark's generated matrix, as `promql_ref.evaluate` is for the
gauges. A class file's `reference` block: `fn` (a range function's
name), `window_s`, optional `group_by` + `group_fn` (`sum`).

Controls of `evaluate`, each of which `compare` must tell apart:
"gridded" keeps one sample a gcd(step, range) cell and takes the cell's
end for its time (the program before PR 42: at range == step a window is
ONE cell, so a rate has nothing to difference); "bf16" does the
arithmetic in bfloat16; "stale" answers from sealed blocks only."""

from __future__ import annotations

import importlib.util
import math
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "reference_promql_ref_for_counters",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "promql_ref.py"))
_promql = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_promql)

parse_response = _promql.parse_response
compare = _promql.compare
select = _promql.select


def _extrapolated(t, v, T: float, w: float, counter: bool, rate: bool,
                  dtype) -> np.ndarray:
    """extrapolatedRate over rows of `v` that share the sample times `t`
    (seconds), all inside (T - w, T]."""
    rows = v.shape[0]
    if len(t) < 2:
        return np.full(rows, np.nan)
    v = v.astype(dtype)
    d = v[:, 1:] - v[:, :-1]
    if counter:
        d = np.where(d < 0, v[:, 1:], d)
    result = d.sum(axis=1, dtype=dtype).astype(np.float64)
    first = v[:, 0].astype(np.float64)
    to_start = np.full(rows, t[0] - (T - w))
    to_end = T - t[-1]
    sampled = t[-1] - t[0]
    mean_gap = sampled / (len(t) - 1)
    gaps = len(t) - 1

    def near(dur, span=sampled):
        """dur < 1.1 mean sample intervals, cross-multiplied: exact for
        whole-second times (module docstring)."""
        return dur * gaps * 10.0 < span * 11.0

    start_near = near(to_start)
    if counter:
        with np.errstate(divide="ignore", invalid="ignore"):
            to_zero = sampled * (first / result)
        clamps = (result > 0) & (first >= 0)
        # to_zero < limit is first / result < 1.1 / gaps
        start_near = start_near | (clamps & near(first, result))
        to_start = np.where(clamps & (to_zero < to_start), to_zero, to_start)
    span = (sampled + np.where(start_near, to_start, mean_gap / 2)
            + (to_end if near(to_end) else mean_gap / 2))
    out = result * (span / sampled)
    if rate:
        out = out / w
    if dtype is not np.float64:
        out = out.astype(dtype).astype(np.float64)
    return out


def _instant(t, v, rate: bool) -> np.ndarray:
    if len(t) < 2:
        return np.full(v.shape[0], np.nan)
    last, prev = v[:, -1], v[:, -2]
    if not rate:
        return last - prev
    return np.where(last < prev, last, last - prev) / (t[-1] - t[-2])


def _regression(t, v, at: float):
    """Prometheus' linearRegression with x counted from `at`."""
    x = t - at
    n = float(len(t))
    sx, sy = x.sum(), v.sum(axis=1)
    sxy, sxx = (v * x[None, :]).sum(axis=1), (x * x).sum()
    const = (v == v[:, :1]).all(axis=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        slope = (sxy - sx * sy / n) / (sxx - sx * sx / n)
        intercept = sy / n - slope * sx / n
    return np.where(const, 0.0, slope), np.where(const, v[:, 0], intercept)


def _holt_winters(v: np.ndarray, sf: float, tf: float) -> float:
    s0, s1, b = 0.0, v[0], v[1] - v[0]
    for i in range(1, len(v)):
        if i > 1:
            b = tf * (s1 - s0) + (1 - tf) * b
        s0, s1 = s1, sf * v[i] + (1 - sf) * (s1 + b)
    return s1


def _quantile(v: np.ndarray, q: float) -> np.ndarray:
    s = np.sort(v, axis=1)
    n = s.shape[1]
    rank = q * (n - 1)
    lo = max(0, int(math.floor(rank)))
    hi = min(n - 1, lo + 1)
    weight = rank - math.floor(rank)
    return s[:, lo] * (1 - weight) + s[:, hi] * weight


def window_rows(fn: str, t: np.ndarray, v: np.ndarray, T: float, w: float,
                args: Sequence[float] = (), dtype=np.float64) -> np.ndarray:
    """`fn` at evaluation time T over rows `v` [rows, n] sampled at the
    shared, sorted times `t` (seconds): one value a row, NaN for no
    point."""
    keep = (t > T - w) & (t <= T)
    t, v = t[keep], v[:, keep]
    rows = v.shape[0]
    if not len(t):
        return np.full(rows, np.nan)
    if fn in ("rate", "increase", "delta"):
        return _extrapolated(t, v, T, w, fn != "delta", fn == "rate", dtype)
    v = v.astype(np.float64)
    if fn in ("irate", "idelta"):
        return _instant(t, v, fn == "irate")
    if fn in ("deriv", "predict_linear"):
        if len(t) < 2:
            return np.full(rows, np.nan)
        if fn == "deriv":
            return _regression(t, v, t[0])[0]
        slope, intercept = _regression(t, v, T)
        return slope * args[0] + intercept
    if fn == "holt_winters":
        if len(t) < 2:
            return np.full(rows, np.nan)
        return np.array([_holt_winters(r, args[0], args[1]) for r in v])
    if fn == "changes":
        return (v[:, 1:] != v[:, :-1]).sum(axis=1).astype(np.float64)
    if fn == "resets":
        return (v[:, 1:] < v[:, :-1]).sum(axis=1).astype(np.float64)
    if fn == "quantile_over_time":
        return _quantile(v, args[0])
    kind = fn[:-len("_over_time")]
    if kind == "sum":
        return v.sum(axis=1)
    if kind == "avg":
        return v.mean(axis=1)
    if kind == "min":
        return v.min(axis=1)
    if kind == "max":
        return v.max(axis=1)
    if kind == "count":
        return np.full(rows, float(len(t)))
    if kind == "last":
        return v[:, -1]
    if kind == "present":
        return np.ones(rows)
    if kind == "stdvar":
        return v.var(axis=1)
    if kind == "stddev":
        return v.std(axis=1)
    raise ValueError(f"unknown range function {fn!r}")


def window_values(fn: str, t_s: np.ndarray, v: np.ndarray,
                  times_s: Sequence[float], window_s: float,
                  args: Sequence[float] = ()) -> np.ndarray:
    """One series: `fn` over (T - w, T] at every T of `times_s`."""
    t_s = np.asarray(t_s, np.float64)
    row = np.asarray(v, np.float64)[None, :]
    return np.array([window_rows(fn, t_s, row, float(T), float(window_s),
                                 args)[0] for T in times_s])


def _gridded(t: np.ndarray, v: np.ndarray, T: float, w: float, cell: float):
    """The window as the program before PR 42 saw it: the latest sample
    of each `cell`-wide cell ending at T - m*cell, timed at the cell's
    end."""
    ends = T - cell * np.arange(int(round(w / cell)))[::-1]
    at = np.searchsorted(t, ends, side="right") - 1
    ok = (at >= 0) & (t[np.clip(at, 0, len(t) - 1)] > ends - cell)
    return ends[ok], v[:, at[ok]]


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
    elif control not in (None, "gridded", "drop"):   # drop: the read-back's
        raise ValueError(f"unknown control {control!r}")
    idx = select(cfg, req["hosts"], req["fields"])
    cadence = int(cfg["cadence_s"])
    t = (t0_s + cadence * np.arange(held)).astype(np.float64)
    sub = vals[idx, :held]
    times = np.arange(req["start_s"], req["end_s"] + 1, req["step_s"])
    w = float(ref["window_s"])
    rows = np.full((len(idx), len(times)), np.nan)
    for j, T in enumerate(times):
        tw, vw = t, sub
        if control == "gridded":
            tw, vw = _gridded(t, sub, float(T), w,
                              float(math.gcd(int(req["step_s"]), int(w))))
        rows[:, j] = window_rows(ref["fn"], tw, vw, float(T), w,
                                 ref.get("args", ()), dtype)
    by = ref.get("group_by")
    if by is None:
        return {frozenset((k, v) for k, v in labels[i].items()
                          if k != "__name__"): rows[r]
                for r, i in enumerate(idx)}
    if ref["group_fn"] != "sum":
        raise ValueError(f"unknown group function {ref['group_fn']!r}")
    groups: Dict[frozenset, List[int]] = {}
    for r, i in enumerate(idx):
        groups.setdefault(frozenset((k, labels[i][k]) for k in by),
                          []).append(r)
    out = {}
    for key, members in groups.items():
        g = rows[members]
        some = np.isfinite(g).any(axis=0)
        total = np.nansum(g, axis=0)
        if dtype is not np.float64:   # the control groups in its precision too
            total = total.astype(dtype).astype(np.float64)
        out[key] = np.where(some, total, np.nan)
    return out
