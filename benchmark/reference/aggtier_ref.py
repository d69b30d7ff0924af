"""The plain reference of the aggregation tier: what the 1-minute
namespace must hold, and what a panel over it must answer, when a
Prometheus fleet's remote-write requests are rolled up by an
m3aggregator pair and written back by the coordinator's m3msg ingester.
numpy float64 over the seed's truth; nothing here imports the program.

(a) **The aggregates**, from the truth (`vals [series, steps]`), each
host's scrape offset (`harness/promoffsets.py`) and the list of
acknowledged writes (`acked [hosts, steps]`: scrape k of host h was in a
write request answered 200 in full) ALONE: for every series and every
minute `[m, m + R)`, the acknowledged sample of greatest timestamp in
it, stamped `m + R`, once. A sample's minute is the one its OWN
timestamp `T0 + offset + k * interval` lies in (the tier is sent timed
metrics), not the one it arrived in. A minute with no acknowledged
sample has no point (`NaN`). Which minutes are CLOSED is the caller's to
say: it knows the clock.

(b) **The resolver's rule** is `aggns_ref`'s (the file beside this one,
loaded by its path): a request this reference is asked about has to
resolve to the complete aggregated namespace alone, and one that does
not raises `NotAggregated` — the check counts it.

(c) **The class's shape** (`reference` in the class file) over those
points: every class of the deck is a subquery at the namespace's own
resolution, `fn_over_time(cpu{...}[w:1m])`, so an output at t is `fn`
over the subquery's steps s in `(t - w, t]` (whole minutes), each the
instant selector's value at s: the newest point in `(s - 5 m, s]`. With
every minute held that is the point stamped s; where the newest minute
is not there YET, s takes the minute before it (the lookback), it does
not go empty. Then the grouping, as `promql_ref` evaluates it;
`parse_response` and `compare` are `promql_offset_ref`'s.

At the aggregated frontier (a minute's rows being produced, consumed and
written while panels read) a series' newest point may or may not be in
an answer, a series: `series_rows(..., points=...)` is evaluated over
the points an answer MUST hold and again over those joined with the
points it MAY hold, and `compare_frontier` accepts, for a MAX of MAXes,
any group value that some choice a series between its two rows gives,
and nothing else.

`control` computes the answer with one thing broken: "stale" answers
without any point past `stale_after_s` (a tier that stopped flushing
when its history's filesets ended); "bf16" does the arithmetic in
bfloat16."""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Optional, Tuple

import numpy as np


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        "reference_%s_for_aggtier" % name,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_aggns = _beside("aggns_ref")
_offset = _beside("promql_offset_ref")

parse_response = _offset.parse_response
compare = _offset.compare
select = _offset.select
LOOKBACK_S = _offset.LOOKBACK_S
CONTROLS = ("stale", "bf16")
MS_PER_S = 1000


class NotAggregated(ValueError):
    """The rule sends this request somewhere else than to the complete
    aggregated namespace alone."""


def aggregated_namespace(cfg: dict) -> dict:
    return [ns for ns in _aggns.namespaces(cfg) if ns["complete"]][0]


def minute_points(cfg: dict, vals: np.ndarray, acked: np.ndarray,
                  offsets_ms: np.ndarray, t0_s: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(stamps_s [K], values [series, K]): for every minute that a held
    step falls in, the acknowledged sample of greatest timestamp a
    series, stamped at the minute's end; NaN where a series has none."""
    res_s = aggregated_namespace(cfg)["resolution_s"]
    cadence_ms = int(cfg["cadence_s"]) * MS_PER_S
    nf = len(cfg["schema"]["fields"])
    hosts, steps = acked.shape
    if vals.shape[0] != hosts * nf or vals.shape[1] < steps:
        raise ValueError("acked is [hosts, steps] of the same truth")
    # minute of (host, step): its own timestamp's, counted from the
    # minute t0 lies in
    origin_ms = (t0_s % res_s) * MS_PER_S
    at_ms = (origin_ms + np.arange(steps, dtype=np.int64)[None, :]
             * cadence_ms + np.asarray(offsets_ms, np.int64)[:, None])
    minute = at_ms // (res_s * MS_PER_S)                # [hosts, steps]
    k_minutes = int(minute.max()) + 1 if steps else 0
    out = np.full((hosts * nf, k_minutes), np.nan, np.float64)
    # steps ascend in time a host, so the LAST acknowledged step of a
    # minute is its sample of greatest timestamp
    per = res_s * MS_PER_S // cadence_ms
    if (per * cadence_ms == res_s * MS_PER_S and origin_ms == 0
            and (np.asarray(offsets_ms) < cadence_ms).all()):
        # every host's minute j is steps j * per .. j * per + per - 1:
        # one pass a position in the minute, later positions winning
        pad = k_minutes * per - steps
        ack = np.pad(acked, ((0, 0), (0, pad))).reshape(hosts, k_minutes, per)
        last = np.where(ack.any(axis=2),
                        per - 1 - np.argmax(ack[:, :, ::-1], axis=2), -1)
        step = np.arange(k_minutes)[None, :] * per + last    # [hosts, K]
        picked = np.repeat(step, nf, axis=0)                 # [series, K]
        got = np.take_along_axis(
            vals, np.clip(picked, 0, vals.shape[1] - 1), axis=1)
        out = np.where(np.repeat(last, nf, axis=0) >= 0,
                       got.astype(np.float64), np.nan)
    else:
        hs, ks = np.nonzero(acked)
        order = np.argsort(ks, kind="stable")
        hs, ks = hs[order], ks[order]
        js = minute[hs, ks]
        for f in range(nf):
            out[hs * nf + f, js] = vals[hs * nf + f, ks]
    stamps = (t0_s - t0_s % res_s) + (np.arange(k_minutes) + 1) * res_s
    return stamps, out


def _looked_back(ts: np.ndarray, v: np.ndarray, res_s: int) -> np.ndarray:
    """The instant selector's value at every stamp: the newest point in
    (s - lookback, s]. The stamps are consecutive minutes."""
    if len(ts) > 1 and not (np.diff(ts) == res_s).all():
        raise ValueError("the stamps are not consecutive windows")
    out = v.copy()
    for back in range(1, -(-LOOKBACK_S // res_s)):
        if back * res_s >= LOOKBACK_S:
            break
        older = np.full_like(v, np.nan)
        older[:, back:] = v[:, :-back]
        out = np.where(np.isnan(out), older, out)
    return out


def _reduce_rows(ts: np.ndarray, v: np.ndarray, times_s: np.ndarray,
                 window_s: int, fn: str, dtype, res_s: int) -> np.ndarray:
    v = _looked_back(ts, v, res_s)
    out = np.full((v.shape[0], len(times_s)), np.nan, np.float64)
    lo = np.searchsorted(ts, times_s - window_s, side="right")
    hi = np.searchsorted(ts, times_s, side="right")
    with np.errstate(all="ignore"):
        for j, (a, b) in enumerate(zip(lo.tolist(), hi.tolist())):
            if b <= a:
                continue
            w = v[:, a:b]
            some = np.isfinite(w).any(axis=1)
            if dtype is not np.float64:
                w = w.astype(dtype).astype(np.float64)
            if fn == "max":
                r = np.nanmax(np.where(some[:, None], w, -np.inf), axis=1)
            elif fn == "avg":
                r = np.nanmean(np.where(some[:, None], w, 0.0), axis=1)
                if dtype is not np.float64:
                    r = r.astype(dtype).astype(np.float64)
            elif fn == "last":
                idx = np.where(np.isfinite(w), np.arange(w.shape[1]), -1)
                r = w[np.arange(len(w)), idx.max(axis=1)]
            else:
                raise ValueError(f"unknown window function {fn!r}")
            out[:, j] = np.where(some, r, np.nan)
    return out


def _must_resolve_to_aggregated(cls: dict, cfg: dict, req: dict, now_s: int):
    picked, how = _aggns.resolve(_aggns.namespaces(cfg), now_s,
                                 _aggns.fetch_start_s(cls, req))
    if how != "aggregated" or len(picked) != 1 or not picked[0]["complete"]:
        raise NotAggregated(
            f"a fetch from {_aggns.fetch_start_s(cls, req)} at now {now_s} "
            f"resolves to {[ns['name'] for ns in picked]} ({how})")


def _group_keys(ref: dict, labels, idx):
    return _offset._group_keys(ref, labels, idx)


def series_rows(cls: dict, cfg: dict, labels: List[Dict[str, str]],
                vals: np.ndarray, req: dict, t0_s: int,
                control: Optional[str] = None, open_steps: int = 0,
                points: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                now_s: Optional[int] = None,
                stale_after_s: Optional[int] = None,
                selected: bool = False):
    """(keys, rows [series selected, steps]): each selected series' row
    before the grouping, and the output row it belongs to. `points` is
    `minute_points`' pair with whatever the answer may not hold set to
    NaN (absent: every minute the held steps close, all acknowledged, on
    the shared grid); `now_s` the coordinator's clock when the request
    was due (absent: one cadence after the newest held step)."""
    ref = cls["reference"]
    cadence_s = int(cfg["cadence_s"])
    held = vals.shape[1]
    if now_s is None:
        now_s = t0_s + held * cadence_s
    _must_resolve_to_aggregated(cls, cfg, req, now_s)
    res_s = aggregated_namespace(cfg)["resolution_s"]
    if points is None:
        hosts = int(cfg["scale"])
        stamps, pv = minute_points(cfg, vals, np.ones((hosts, held), bool),
                                   np.zeros(hosts, np.int64), t0_s)
        closed = stamps <= t0_s + held * cadence_s
        stamps, pv = stamps[closed], pv[:, closed]
    else:
        stamps, pv = points
    dtype = np.float64
    if control == "bf16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    elif control == "stale":
        cut = stale_after_s if stale_after_s is not None \
            else t0_s + (held - open_steps) * cadence_s
        keep = stamps <= cut
        stamps, pv = stamps[keep], pv[:, keep]
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")
    idx = select(cfg, req["hosts"], req["fields"])
    times = np.arange(req["start_s"], req["end_s"] + 1, req["step_s"])
    window_s = int(ref.get("window_s", LOOKBACK_S))
    rows = _reduce_rows(stamps, pv if selected else pv[idx], times,
                        window_s, ref["window_fn"], dtype, res_s)
    thr = ref.get("keep_above")
    if thr is not None:
        rows = np.where(rows > thr, rows, np.nan)
    return _group_keys(ref, labels, idx), rows, dtype


def grouped(ref: dict, keys, rows: np.ndarray, dtype=np.float64
            ) -> Dict[frozenset, np.ndarray]:
    if ref.get("group_by") is None:
        return {keys[r]: rows[r] for r in range(len(keys))}
    groups: Dict[frozenset, List[int]] = {}
    for r in range(len(keys)):
        groups.setdefault(keys[r], []).append(r)
    out = {}
    for key, members in groups.items():
        g = rows[members]
        some = np.isfinite(g).any(axis=0)
        if dtype is not np.float64:
            g = g.astype(dtype).astype(np.float64)
        with np.errstate(all="ignore"):
            if ref["group_fn"] == "max":
                val = np.nanmax(np.where(some, g, 0.0), axis=0)
            elif ref["group_fn"] == "avg":
                val = np.nanmean(np.where(some, g, 0.0), axis=0)
            else:
                raise ValueError(f"unknown group function {ref['group_fn']!r}")
        out[key] = np.where(some, val, np.nan)
    return out


def evaluate(cls: dict, cfg: dict, labels: List[Dict[str, str]],
             vals: np.ndarray, req: dict, t0_s: int,
             control: Optional[str] = None, open_steps: int = 0,
             **kw) -> Dict[frozenset, np.ndarray]:
    """The class's answer to one request over the aggregated points:
    label set -> row of values at start, start + step, ... end
    (`series_rows`, then the grouping)."""
    keys, rows, dtype = series_rows(cls, cfg, labels, vals, req, t0_s,
                                    control, open_steps, **kw)
    return grouped(cls["reference"], keys, rows, dtype)


def compare_frontier(got: Dict[frozenset, np.ndarray], ref: dict, keys,
                     rows_must: np.ndarray, rows_may: np.ndarray) -> dict:
    """The numbers `correct` is decided on, for one answer at the
    aggregated frontier. `rows_must` are the selected series' rows over
    the points the answer must hold, `rows_may` over those joined with
    the points it may hold. Where the two agree the grouped answer must
    equal theirs, as `compare` holds it; an output (group, step) where
    they differ is a frontier pair, and its served value must be one
    that SOME choice a member series between its two values gives the
    group's MAX: equal to a member's value of either kind, and not
    below any member's smaller one. Gaps are relative as in `compare`."""
    same = (rows_must == rows_may) | (np.isnan(rows_must)
                                      & np.isnan(rows_may))
    want = grouped(ref, keys, rows_must)
    if same.all():
        return dict(compare(got, want), frontier_pairs=0, took_in_flight=0)
    if ref.get("group_fn", "max") != "max" or ref["window_fn"] != "max":
        raise ValueError("the aggregated frontier is held exactly for MAX "
                         f"classes alone, not {ref!r}")
    with_may = grouped(ref, keys, rows_may)
    members: Dict[frozenset, List[int]] = {}
    for r, key in enumerate(keys):
        members.setdefault(key, []).append(r)
    got = {k: v for k, v in got.items() if np.isfinite(v).any()}
    present = {k for k in want if np.isfinite(want[k]).any()
               or np.isfinite(with_may[k]).any()}
    optional = {k for k in present if not np.isfinite(want[k]).any()}
    out = {"label_sets_differ": len((present - optional) ^ (set(got)
                                                           - optional)),
           "points_missing_or_extra": 0, "worst_rel_gap": 0.0, "values": 0,
           "frontier_pairs": 0, "took_in_flight": 0}
    finite = np.concatenate([w[np.isfinite(w)] for w in want.values()]
                            or [np.zeros(0)])
    scale = float(np.abs(finite).max()) if finite.size else 0.0
    floor = 1e-2 * scale if scale else 1.0

    def gap(g: float, w: float) -> float:
        return abs(g - w) / max(abs(w), floor)

    for key in present & set(got):
        rows = members[key]
        for j in range(rows_must.shape[1]):
            g = float(got[key][j])
            a, b = rows_must[rows, j], rows_may[rows, j]
            if same[rows, j].all():
                w = float(want[key][j])
                if np.isfinite(g) != np.isfinite(w):
                    out["points_missing_or_extra"] += 1
                elif np.isfinite(g):
                    out["values"] += 1
                    out["worst_rel_gap"] = max(out["worst_rel_gap"],
                                               gap(g, w))
                continue
            out["frontier_pairs"] += 1
            lo = np.fmin(a, b)          # NaN where either choice is none
            need = np.where(np.isnan(a) | np.isnan(b), -np.inf, lo)
            if not np.isfinite(g):
                # no point: every member may have none
                out["points_missing_or_extra"] += bool(
                    (np.isfinite(need)).any())
                continue
            choices = np.concatenate([a, b])
            choices = choices[np.isfinite(choices)
                              & (choices >= need.max())]
            if not choices.size:
                out["points_missing_or_extra"] += 1
                continue
            out["values"] += 1
            gaps = [gap(g, float(c)) for c in choices]
            out["worst_rel_gap"] = max(out["worst_rel_gap"], min(gaps))
            w = float(want[key][j])
            out["took_in_flight"] += not (np.isfinite(w)
                                          and gap(g, w) == min(gaps))
    return out


