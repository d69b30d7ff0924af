"""Whole-plan compilation: one jitted program per PromQL physical plan
over the shard x time mesh (ROADMAP item 1; the Titanax
compile_step_with_plan shape from SNIPPETS.md [3]).

The interpreter (query/executor.py, retained as the oracle
`Engine.execute_range_ref`) dispatches one jitted kernel per temporal op
per block with host round trips between operators and a fully host-side
aggregation fan-in. Here the plan IR (query/plan.py) lowers into ONE
traced function: operator chains fuse, cross-shard aggregation fan-in
becomes XLA collectives (psum/pmin/pmax over ICI via jax.shard_map)
instead of host gather, and the only device->host transfer is the final
result. In/out shardings match the layout the selector staging places
(rows partitioned over the mesh "shard" axis, NamedSharding
P("shard", None)), so a staged grid feeds the program without
repartitioning — SNIPPETS.md [1]'s advice of matching a producer's
out_axis_resources to the consumer's in_axis_resources.

Compiled executables are cached per (plan structure, pow2 shape bucket,
mesh) — `telemetry.plan_cache` counts hits/misses/compile wall — with
row/time padding chosen so one executable serves every query with the
same plan shape: rows pad with NaN (masked everywhere), the time axis
pads past the real output and the host slices it back. Selector label
matchers are stripped from the key (one executable serves every metric
with the same plan shape); scalar literals ride as runtime slots (one
executable serves every threshold).

Counter-sum exactness (the query/executor.py:789 contract): an
aggregate sum/avg DIRECTLY over a raw selector decomposes each series
as baseline + residual (ops/temporal.center). The device accumulates
only the small f32 residuals (per-shard partials combine via psum —
still residual-space, still small), while the baseline mass — where
plain f32 accumulation of 1e9-magnitude counters loses the f64
host-reduce semantics — is accounted on the host in exact f64 (group
baseline totals minus per-missing-cell corrections).
tests/test_plan_compile.py proves this against the interpreter oracle
over seeded counter grids.

The lowering rules (`_lower_*`) run under jax trace: they must never
sync a traced value to the host (np.asarray / jax.device_get / .item()
mid-plan is exactly the per-op dispatch this module replaces) — m3lint's
`host-sync-in-plan` rule gates it.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import guard as pguard
from . import telemetry
from ..ops import series_agg, temporal
from ..utils import numwatch, tracing
from ..query import explain as qexplain
from ..query import plan as qplan
from ..query import promql
from ..query.plan import (
    Aggregate, Binary, Fetch, InstantFunc, Plan, PlanNode, RangeFunc,
    RankAgg, ScalarConst, SubqueryFunc, SERIES, SCALAR, _preorder,
)

_F32 = jnp.float32


class PlanFallback(Exception):
    """The bound plan can't execute compiled (shape pathology, missing
    backend feature); the executor falls back to the interpreter.
    Carries a typed `FallbackReason` (default BACKEND_GAP) so the
    telemetry/EXPLAIN taxonomy covers compile-time bail-outs too."""

    def __init__(self, detail: str = "",
                 reason: "qplan.FallbackReason" = None):
        self.reason = reason or qplan.FallbackReason.BACKEND_GAP
        self.detail = detail
        super().__init__(f"{self.reason.value}: {detail}" if detail
                         else self.reason.value)


# --------------------------------------------------------------- geometry


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Static shape signature of one compiled executable: pow2 row/time
    buckets per fetch, group buckets per aggregate, row buckets per
    vector-vector binary, inner-grid widths per subquery, and
    (group, group-size) buckets per rank aggregation — each entry
    aligned to its node kind's plan-preorder occurrence order."""

    t_pad: int                       # padded output steps
    s_pads: Tuple[int, ...]          # per plan.fetches entry
    f_exts: Tuple[int, ...]          # staged grid width per fetches entry
    g_pads: Tuple[int, ...]          # per Aggregate node, preorder
    r_pads: Tuple[int, ...]          # per vv Binary node, preorder
    sub_pads: Tuple[int, ...]        # per SubqueryFunc node, preorder
    rank_pads: Tuple[Tuple[int, int], ...]  # per RankAgg: (g_pad, smax_pad)
    n_shard: int                     # 1 = single-device


# The aux-array ordering contract between bind(), _aux_layout(),
# geometry_for() and execute() hangs on ONE preorder walk: plan.py's.
def _is_vv(node: PlanNode) -> bool:
    return (isinstance(node, Binary) and node.lhs.edge.kind == SERIES
            and node.rhs.edge.kind == SERIES)


def _row_bucket(s: int, n_shard: int) -> int:
    """Rows padded to n_shard * bucket(per-device rows): the shard axis
    divides evenly and one executable serves a half-octave bucket of
    sizes (plan.next_bucket)."""
    per_dev = max(1, -(-s // n_shard))
    return n_shard * qplan.next_bucket(per_dev)


def _widths(root: PlanNode, t_pad: int,
            sub_pads: Optional[Tuple[int, ...]] = None
            ) -> Tuple[Dict[int, int], Tuple[int, ...]]:
    """Per-node padded TIME width: t_pad outside subqueries; inside a
    SubqueryFunc, the inner resolution grid's padded width — long enough
    that contiguous strided windows cover every padded output step
    (shared mode), or the bucketed inner-grid length (packed mode, where
    the bind-time column map does the indexing). With `sub_pads` given
    (trace time, on the stripped plan whose inner_steps is zeroed) the
    recorded Geometry widths are consumed instead of recomputed."""
    width_of: Dict[int, int] = {}
    pads_out: List[int] = []
    it = iter(sub_pads) if sub_pads is not None else None

    def walk(n: PlanNode, w: int):
        width_of[id(n)] = w
        if isinstance(n, SubqueryFunc):
            if it is not None:
                w_in = next(it)
            elif n.packed:
                w_in = qplan.next_bucket(max(n.inner_steps, 1))
            else:
                w_in = (w - 1) * n.stride + n.W
            pads_out.append(w_in)
            walk(n.arg, w_in)
            return
        for fld in dataclasses.fields(n):
            v = getattr(n, fld.name)
            if isinstance(v, PlanNode):
                walk(v, w)
            elif isinstance(v, tuple):
                for item in v:
                    if isinstance(item, PlanNode):
                        walk(item, w)

    walk(root, t_pad)
    return width_of, tuple(pads_out)


def _fetch_exts(root: PlanNode, width_of: Dict[int, int],
                fetches: Tuple[Fetch, ...]) -> Tuple[int, ...]:
    """Staged grid width per fetches entry: the max extended-grid length
    any occurrence of that (equality-keyed) fetch needs in its time
    context — consumers slice down to their own need."""
    need: Dict[Fetch, int] = {}
    for n in _preorder(root, []):
        if isinstance(n, Fetch):
            ext = _ext_len(n, width_of[id(n)])
            need[n] = max(need.get(n, 0), ext)
    return tuple(need[f] for f in fetches)


def geometry_for(bound: "qplan.Bound", n_shard: int) -> Geometry:
    plan = bound.plan
    t_pad = qplan.next_bucket(plan.steps)
    s_pads = tuple(_row_bucket(bound.fetches[f].grid.shape[0], n_shard)
                   for f in plan.fetches)
    width_of, sub_pads = _widths(plan.root, t_pad)
    f_exts = _fetch_exts(plan.root, width_of, plan.fetches)
    nodes: List[PlanNode] = []
    _preorder(plan.root, nodes)
    g_pads = tuple(qplan.next_bucket(max(1, bound.aux[id(n)]["n_groups"]))
                   for n in nodes if isinstance(n, Aggregate))
    r_pads = tuple(qplan.next_bucket(max(1, len(bound.aux[id(n)]["many_idx"])))
                   for n in nodes if _is_vv(n))
    rank_pads = tuple(
        (qplan.next_bucket(max(1, bound.aux[id(n)]["n_groups"])),
         qplan.next_bucket(max(1, bound.aux[id(n)]["smax"])))
        for n in nodes if isinstance(n, RankAgg))
    return Geometry(t_pad, s_pads, f_exts, g_pads, r_pads, sub_pads,
                    rank_pads, n_shard)


# ---------------------------------------------------------- input staging

# Which prepared arrays a fetch contributes, per consumer need, and how
# many arrays each kind flattens to.
#   ratec: (adj, finite, grid32)   rate/increase (ops/temporal.rate_inputs)
#   rated: (adj, finite)           delta
#   resid: (resid, base32)         *_over_time / regression / exact sums
#   value: (value32,)              elementwise / binary / min-max-count
#   value2: (hi, lo)               exact double-f32 split (topk ranking)
#   trel: (trel,)                  packed range lanes' own times
_KIND_ARITY = {"ratec": 3, "rated": 2, "resid": 2, "value": 1, "value2": 2,
               "trel": 1}
_RATE_COUNTER = frozenset({"rate", "increase"})
# Range functions that read sample TIMES (ops/temporal.py): over a packed
# fetch they take its lane-time plane, over a dense one the window edge.
_TIMED_FUNCS = frozenset({"rate", "increase", "delta", "irate", "deriv",
                          "predict_linear"})


def _reads_lane_times(node: Optional[PlanNode]) -> bool:
    """A range function that needs its packed fetch's `trel` plane."""
    return (isinstance(node, RangeFunc) and node.arg.packed
            and node.func in _TIMED_FUNCS)


def _consumer_kinds(consumer: Optional[PlanNode]) -> Tuple[str, ...]:
    """Which staged-input kinds one consumer reads off a direct Fetch."""
    if isinstance(consumer, (RangeFunc, SubqueryFunc)):
        f = consumer.func
        timed = ("trel",) if _reads_lane_times(consumer) else ()
        if f in ("rate", "increase", "delta"):
            return (("ratec",) if f in _RATE_COUNTER else ("rated",)) + timed
        if f in ("irate", "idelta"):
            # residual-space diffs + the absolute plane for the counter
            # reset branch (temporal.instant_math)
            return ("resid", "value") + timed
        return ("resid",) + timed
    if isinstance(consumer, Aggregate) and consumer.exact:
        return ("resid",)
    if isinstance(consumer, RankAgg) and consumer.op != "quantile":
        # topk/bottomk MEMBERSHIP is discrete: rank on the exact
        # double-f32 split so sub-ulp counter differences don't scramble
        # the surviving series set (series_agg.packed_topk_keep_math).
        return ("value2",)
    return ("value",)


def fetch_kinds(root: PlanNode) -> Dict[Fetch, Tuple[str, ...]]:
    """Deterministic (sorted) set of staged-input kinds per fetch,
    keyed by Fetch equality (equal selectors share staged inputs)."""
    kinds: Dict[Fetch, set] = {}

    def walk(node: PlanNode, consumer: Optional[PlanNode]):
        if isinstance(node, Fetch):
            kinds.setdefault(node, set()).update(_consumer_kinds(consumer))
            return
        for fld in dataclasses.fields(node):
            v = getattr(node, fld.name)
            if isinstance(v, PlanNode):
                walk(v, node)
            elif isinstance(v, tuple):
                for item in v:
                    if isinstance(item, PlanNode):
                        walk(item, node)

    walk(root, None)
    return {f: tuple(sorted(ks)) for f, ks in kinds.items()}


def _ext_len(f: Fetch, width: int) -> int:
    """Padded extended-grid length for a fetch in a `width`-wide time
    context: long enough that the strided window output covers every
    padded column. Every output step j < real steps reads window cells
    [j*stride, j*stride + W) — real cells only, so end-padding is
    exact. Staged widths are Geometry.f_exts = the max of this over a
    fetch's occurrences (via _fetch_exts); consumers slice down to
    their own need."""
    if f.role == "instant":
        return width
    return (width - 1) * f.stride + f.W


def _pad_grid(grid: np.ndarray, s_pad: int, ext_pad: int) -> np.ndarray:
    S, T = grid.shape
    if S == s_pad and T == ext_pad:
        return grid
    out = np.full((s_pad, ext_pad), np.nan, dtype=grid.dtype)
    out[:S, :T] = grid
    return out


def stage_value_plane(grid: np.ndarray, s_pad: int, ext_pad: int
                      ) -> np.ndarray:
    """Padded f32 staging for a `value`-kind fetch plane in ONE pass:
    allocate the padded plane at f32 and downcast-copy the grid straight
    into it, replacing the f64 pad + separate astype(float32) two-pass
    (which materialized an [s_pad, ext_pad] f64 intermediate per fetch).
    Identical cells: NaN padding survives the downcast and copyto's
    unsafe cast is exactly astype's round-to-nearest."""
    S, T = grid.shape
    out = np.full((s_pad, ext_pad), np.nan, np.float32)
    np.copyto(out[:S, :T], grid, casting="unsafe")
    return out


def _stage_fetch(bf: "qplan.BoundFetch", kinds: Tuple[str, ...],
                 s_pad: int, ext_pad: int, mesh: Optional[Mesh]):
    """Prepared, padded, placed input arrays for one fetch — content/id
    cached via ops/temporal's derived cache, so a repeat query (the grid
    cache returning the same consolidated grid object, e.g. served off
    the block cache's resident decoded planes) reuses the staged device
    arrays without re-upload or repartitioning."""
    mesh_tag = "1" if mesh is None else f"{mesh.shape['shard']}@{id(mesh)}"
    kind_tag = f"plan:{','.join(kinds)}:{s_pad}x{ext_pad}:{mesh_tag}"

    def build(g):
        # The padded f64 intermediate is only needed by the non-"value"
        # kinds; a plain value fetch stages through the one-pass f32 path.
        gp = (_pad_grid(g, s_pad, ext_pad)
              if any(kind != "value" for kind in kinds) else None)
        arrs: List[np.ndarray] = []
        for kind in kinds:
            if kind in ("ratec", "rated"):
                adj, finite, grid32 = temporal.rate_inputs(
                    gp, kind == "ratec")
                arrs += [adj, finite]
                if kind == "ratec":
                    arrs.append(grid32)
            elif kind == "resid":
                resid, base = temporal.center(gp)
                # DELIBERATE downcast: base32 feeds only the device
                # plane (predict_linear/holt_winters adds); the exact
                # f64 baseline mass is re-derived on the host by
                # _exact_base_contrib from the same grid, so nothing
                # the f32 copy drops ever reaches a counter sum.
                arrs += [resid, base.astype(np.float32)]  # m3lint: disable=f64-downcast-on-exact-path
            elif kind == "value2":
                # Exact double-f32 split of the f64 grid: hi + lo
                # round-trips the value to ~2e-4 absolute, and the lo
                # plane is what makes compiled topk ranking faithful to
                # the interpreter's f64 sort at counter magnitudes.
                hi = gp.astype(np.float32)
                lo = (gp - hi.astype(np.float64)).astype(np.float32)
                arrs += [hi, lo]
            elif kind == "trel":
                lanes = np.zeros((s_pad, ext_pad), np.float32)
                lanes[:bf.trel.shape[0], :bf.trel.shape[1]] = bf.trel
                arrs.append(lanes)
            else:  # "value"
                arrs.append(stage_value_plane(g, s_pad, ext_pad))
        if mesh is not None:
            sh2 = NamedSharding(mesh, P("shard", None))
            sh1 = NamedSharding(mesh, P("shard"))
            placed = tuple(
                jax.device_put(a, sh1 if a.ndim == 1 else sh2)  # m3lint: disable=unbudgeted-device-put
                for a in arrs)
            # Charged at the canonicalized device sizes; the derived
            # cache's HBM-budget tenant bounds the resident total.
            return placed, sum(int(getattr(a, "nbytes", 0)) for a in placed)
        if temporal._cache_enabled():
            placed = tuple(temporal._put(a) for a in arrs)
            return placed, sum(int(getattr(a, "nbytes", 0)) for a in placed)
        return tuple(arrs), 0

    return temporal._derived(bf.grid, kind_tag, build)


# --------------------------------------------------------- lowering rules
#
# Each _lower_* rule emits the traced computation for one plan node.
# Everything here runs under jax trace: touching the host
# (np.asarray / device_get / .item()) would reintroduce the per-op
# dispatch this module exists to remove — m3lint's host-sync-in-plan
# rule gates it.

_MATH_JNP = {
    "abs": jnp.abs, "ceil": jnp.ceil, "floor": jnp.floor, "exp": jnp.exp,
    "sqrt": jnp.sqrt, "ln": jnp.log, "log2": jnp.log2, "log10": jnp.log10,
    "sgn": jnp.sign, "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "asinh": jnp.arcsinh, "acosh": jnp.arccosh, "atanh": jnp.arctanh,
    "deg": jnp.degrees, "rad": jnp.radians,
    "neg": lambda v: -v,
}

_BIN_JNP = {
    "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply, "/": jnp.divide,
    "==": lambda a, b: (a == b).astype(_F32),
    "!=": lambda a, b: (a != b).astype(_F32),
    "<": lambda a, b: (a < b).astype(_F32),
    ">": lambda a, b: (a > b).astype(_F32),
    "<=": lambda a, b: (a <= b).astype(_F32),
    ">=": lambda a, b: (a >= b).astype(_F32),
}


class _Ctx:
    """Trace-time emission context: staged inputs per fetch, bind-time
    index arrays per node path, scalar slots, per-node time widths,
    mesh-axis state."""

    def __init__(self, plan: Plan, geom: Geometry, fetch_ins, aux_ins,
                 slots, sharded: bool):
        self.plan = plan
        self.geom = geom
        self.fetch_ins = fetch_ins          # {Fetch: {kind: (arrays...)}}
        self.aux_ins = aux_ins              # {path: (arrays...)}
        self.slots = slots
        self.sharded = sharded
        self.cache: Dict[int, object] = {}
        nodes: List[PlanNode] = []
        _preorder(plan.root, nodes)
        self.path_of = {id(n): i for i, n in enumerate(nodes)}
        self.g_pad_of = dict(zip(
            (id(n) for n in nodes if isinstance(n, Aggregate)),
            geom.g_pads))
        self.rank_pads_of = dict(zip(
            (id(n) for n in nodes if isinstance(n, RankAgg)),
            geom.rank_pads))
        self.width_of, _ = _widths(plan.root, geom.t_pad, geom.sub_pads)
        self.root_agg: Optional[tuple] = None   # (s, cnt) for sum/avg root


def _lower_fetch(ctx: _Ctx, node: Fetch):
    """A bare selector consumed as values: the absolute f32 plane,
    sliced to this occurrence's padded grid width."""
    (value,) = ctx.fetch_ins[node]["value"]
    return value[:, :ctx.width_of[id(node)]]


def _range_body(ctx: _Ctx, f: str, ins: Dict[str, tuple], *, W: int,
                stride: int, step_s: float, range_s: float,
                params: Tuple[float, ...], edge=None, trel=None):
    """The shared windowed-kernel ladder: one range function over
    prepared inputs (`ins` maps kind -> arrays already sliced/gathered
    to the window layout). Serves both RangeFunc (host-staged selector
    inputs; `edge` or `trel` place its raw samples in time) and
    SubqueryFunc (inner-plane inputs, possibly packed; lane positions
    are the times)."""
    if f in ("rate", "increase", "delta"):
        adj, finite = ins["diff"][0], ins["diff"][1]
        grid32 = ins["diff"][2] if f in _RATE_COUNTER else None
        return temporal.rate_math(
            adj, finite, grid32, edge, trel, W=W, step_s=step_s,
            range_s=range_s, is_counter=f in _RATE_COUNTER,
            is_rate=f == "rate", stride=stride)
    if f in ("irate", "idelta"):
        resid, grid32 = ins["instant"]
        return temporal.instant_math(
            resid, grid32, trel if f == "irate" else None, W=W,
            step_s=step_s, is_rate=f == "irate", stride=stride)
    resid, base32 = ins["resid"]
    if f == "quantile_over_time":
        return temporal.quantile_ot_math(resid, base32, W=W,
                                         q=float(params[0]), stride=stride)
    if f.endswith("_over_time"):
        return temporal.over_time_math(
            resid, base32, W=W, kind=f[:-len("_over_time")], stride=stride)
    if f in ("changes", "resets"):
        return temporal.changes_resets_math(
            resid, W=W, count_resets=f == "resets", stride=stride)
    if f == "deriv":
        return temporal.regression_math(
            resid, None, trel, W=W, step_s=step_s, predict_offset_s=0.0,
            is_deriv=True, stride=stride)
    if f == "predict_linear":
        return temporal.regression_math(
            resid, edge, trel, W=W, step_s=step_s,
            predict_offset_s=float(params[0]),
            is_deriv=False, stride=stride) + base32[:, None]
    # holt_winters (lowering admits nothing else)
    return temporal.holt_winters_math(
        resid, W=W, sf=float(params[0]), tf=float(params[1]),
        stride=stride) + base32[:, None]


def _lower_rangefunc(ctx: _Ctx, node: RangeFunc):
    f = node.func
    fetch = node.arg
    W, stride = fetch.W, fetch.stride
    w_out = ctx.width_of[id(node)]
    ext = (w_out - 1) * stride + W
    staged = ctx.fetch_ins[fetch]

    if f == "absent_over_time":
        # Window presence counts, then ONE cross-row (and cross-shard)
        # reduce: 1 where NO series has a sample in the window.
        resid, _base32 = staged["resid"]
        cnt = temporal._wsum(jnp.isfinite(resid[:, :ext]), W, stride)
        total = cnt.sum(axis=0, keepdims=True)
        # DELIBERATE: static program structure (mesh mode + edge
        # sharding), same as the aggregate fan-in branches.
        if ctx.sharded and fetch.edge.sharding == qplan.SHARDED:  # m3lint: disable=jax-traced-branch
            total = jax.lax.psum(total, "shard")
        return jnp.where(total > 0, jnp.nan, 1.0)

    ins: Dict[str, tuple] = {}
    if f in ("rate", "increase", "delta"):
        kind = "ratec" if f in _RATE_COUNTER else "rated"
        ins["diff"] = tuple(a[:, :ext] for a in staged[kind])
    elif f in ("irate", "idelta"):
        resid, _base32 = staged["resid"]
        (value,) = staged["value"]
        ins["instant"] = (resid[:, :ext], value[:, :ext])
    else:
        resid, base32 = staged["resid"]
        ins["resid"] = (resid[:, :ext], base32)
    # Where the window's raw samples lie in time: a packed fetch's own
    # lane times, else the edge this query's phase leaves on the cadence.
    timed = _reads_lane_times(node)
    trel = staged["trel"][0][:, :ext] if timed else None
    (edge,) = ctx.aux_ins[ctx.path_of[id(node)]]
    out = _range_body(ctx, f, ins, W=W, stride=stride,
                      step_s=node.step_ns / 1e9,
                      range_s=node.range_ns / 1e9, params=node.params,
                      edge=None if timed else edge, trel=trel)
    return out[:, :w_out]


def _sub_gather(arr, cols, fill):
    """Packed-window gather: [S, T_in] columns by the bind-time index
    map; lanes with col -1 (outside the window) take `fill`."""
    valid = (cols >= 0)[None, :]
    g = arr[:, jnp.maximum(cols, 0)]
    return jnp.where(valid, g, fill)


def _lower_subqueryfunc(ctx: _Ctx, node: SubqueryFunc):
    """f(expr[r:s]): window the inner plane. Direct selector inners read
    their host-staged exact-f64 preps (the same kinds RangeFunc uses, on
    the inner resolution grid); composite inners prep in-trace at the
    plane's f32 (temporal.center_math / rate_inputs_math — the lowering
    only admits difference-space planes there). Packed mode first
    gathers each output step's drifting window through the bind-time
    column map; shared mode reads contiguous strided windows."""
    f = node.func
    w_out = ctx.width_of[id(node)]
    inner_w = ctx.width_of[id(node.arg)]
    direct = isinstance(node.arg, Fetch)
    if node.packed:
        (cols,) = ctx.aux_ins[ctx.path_of[id(node)]]
        W = stride = node.W
    else:
        cols = None
        W, stride = node.W, node.stride

    def windowed(a, fill):
        a = a[:, :inner_w]
        return a if cols is None else _sub_gather(a, cols, fill)

    ins: Dict[str, tuple] = {}
    if f in ("rate", "increase", "delta"):
        counter = f in _RATE_COUNTER
        if direct:
            kind = "ratec" if counter else "rated"
            arrs = ctx.fetch_ins[node.arg][kind]
            adj, finite = arrs[0], arrs[1]
            grid32 = arrs[2] if counter else None
        else:
            plane = _emit(ctx, node.arg)
            adj, finite, z = temporal.rate_inputs_math(plane, counter)
            grid32 = z if counter else None
        ins["diff"] = (windowed(adj, 0.0), windowed(finite, False)) + (
            (windowed(grid32, 0.0),) if counter else ())
    elif f in ("irate", "idelta"):
        if direct:
            resid, _b = ctx.fetch_ins[node.arg]["resid"]
            (value,) = ctx.fetch_ins[node.arg]["value"]
        else:
            plane = _emit(ctx, node.arg)
            resid, _base = temporal.center_math(plane)
            value = plane
        ins["instant"] = (windowed(resid, jnp.nan),
                          windowed(value, jnp.nan))
    else:
        if direct:
            resid, base32 = ctx.fetch_ins[node.arg]["resid"]
        else:
            plane = _emit(ctx, node.arg)
            resid, base32 = temporal.center_math(plane)
        ins["resid"] = (windowed(resid, jnp.nan), base32)
    out = _range_body(ctx, f, ins, W=W, stride=stride,
                      step_s=node.res_ns / 1e9,
                      range_s=node.range_ns / 1e9, params=node.params)
    return out[:, :w_out]


def _lower_rankagg(ctx: _Ctx, node: RankAgg):
    """topk/bottomk/quantile: gather rows into the bind-time group
    packing, sort-select along the packed axis (ops/series_agg), k / q
    riding as a runtime slot. topk/bottomk return the argument plane
    masked to the per-step winners (the data-dependent surviving row SET
    is filtered on the host at the root finish)."""
    perm, inv = ctx.aux_ins[ctx.path_of[id(node)]]
    g_pad, smax_pad = ctx.rank_pads_of[id(node)]
    kq = ctx.slots[node.param.slot]
    if node.op == "quantile":
        v = _emit(ctx, node.arg)
        packed = series_agg.packed_gather_math(v, perm, g_pad, smax_pad)
        return series_agg.packed_quantile_math(packed, kq)
    if isinstance(node.arg, Fetch):
        # Raw selector plane: the host-staged exact double-f32 split —
        # sub-ulp counter differences must still rank like f64.
        hi, lo = ctx.fetch_ins[node.arg]["value2"]
        w = ctx.width_of[id(node.arg)]
        v, vlo = hi[:, :w], lo[:, :w]
    else:
        v = _emit(ctx, node.arg)
        vlo = jnp.zeros_like(v)
    packed_hi = series_agg.packed_gather_math(v, perm, g_pad, smax_pad)
    packed_lo = series_agg.packed_gather_math(vlo, perm, g_pad, smax_pad)
    # int(k) truncation parity with the interpreter's _const_param.
    keep = series_agg.packed_topk_keep_math(packed_hi, packed_lo,
                                            jnp.floor(kq),
                                            node.op == "topk")
    flat = keep.reshape(g_pad * smax_pad, keep.shape[-1])
    valid_row = (inv >= 0)[:, None]
    keep_rows = jnp.where(valid_row, flat[jnp.maximum(inv, 0)], False)
    return jnp.where(keep_rows, v, jnp.nan)


def _lower_instantfunc(ctx: _Ctx, node: InstantFunc):
    v = _emit(ctx, node.arg)
    if node.func == "timestamp":
        # Step times ride as a bind-time aux vector (f32 — documented
        # divergence: unix seconds round to ~128s granularity on the f32
        # value plane, far inside the oracle tolerance at 1.7e9).
        (times,) = ctx.aux_ins[ctx.path_of[id(node)]]
        return jnp.where(jnp.isfinite(v), times[None, :], jnp.nan)
    fn = _MATH_JNP.get(node.func)
    if fn is not None:
        return fn(v)
    params = [ctx.slots[p.slot] for p in node.params]
    if node.func == "round":
        # DELIBERATE: branches on the STATIC slot arity (plan structure),
        # not the traced slot values inside the list.
        if not params:  # m3lint: disable=jax-traced-branch
            return jnp.round(v)
        return jnp.round(v / params[0]) * params[0]
    if node.func == "clamp":
        return jnp.clip(v, params[0], params[1])
    if node.func == "clamp_min":
        return jnp.maximum(v, params[0])
    if node.func == "clamp_max":
        return jnp.minimum(v, params[0])
    raise PlanFallback(f"instant func {node.func}")  # pragma: no cover


def _lower_aggregate(ctx: _Ctx, node: Aggregate):
    """Cross-series reduce with collective fan-in (psum/pmin/pmax over
    the mesh shard axis). Returns the collapsed f32 [G_pad, t_pad] plane;
    a sum/avg ROOT additionally records its (residual-sum, count)
    components so the host can finish in exact f64."""
    (gids,) = ctx.aux_ins[ctx.path_of[id(node)]]
    g_pad = ctx.g_pad_of[id(node)]
    # Collectives only when the CHILD rows are partitioned over the mesh:
    # a replicated child (an inner aggregate's output) is already whole
    # on every device, and a psum would multiply it by the shard count.
    fan_in = ctx.sharded and node.arg.edge.sharding == qplan.SHARDED
    if node.exact:
        resid, _base32 = ctx.fetch_ins[node.arg]["resid"]
        v = resid[:, :ctx.width_of[id(node)]]
    else:
        v = _emit(ctx, node.arg)
    mask = jnp.isfinite(v)
    cnt = jax.ops.segment_sum(mask.astype(_F32), gids, num_segments=g_pad)
    op = node.op
    if op in ("stddev", "stdvar"):
        # Population moments (promql stddev/stdvar; series_agg's segment
        # kernel): mean first, then the squared-deviation reduce — each
        # stage fanning in across shards before the next reads it.
        z = jnp.where(mask, v, 0.0)
        s = jax.ops.segment_sum(z, gids, num_segments=g_pad)
        if fan_in:  # m3lint: disable=jax-traced-branch
            s = jax.lax.psum(s, "shard")
            cnt = jax.lax.psum(cnt, "shard")
        mu = s / jnp.maximum(cnt, 1)
        dev = jnp.where(mask, v - mu[gids], 0.0)
        m2 = jax.ops.segment_sum(dev * dev, gids, num_segments=g_pad)
        if fan_in:  # m3lint: disable=jax-traced-branch
            m2 = jax.lax.psum(m2, "shard")
        var = m2 / jnp.maximum(cnt, 1)
        out = jnp.sqrt(var) if op == "stddev" else var
        return jnp.where(cnt > 0, out, jnp.nan)
    if op in ("sum", "avg"):
        s = jax.ops.segment_sum(jnp.where(mask, v, 0.0), gids,
                                num_segments=g_pad)
        # DELIBERATE (x4 below): fan_in is static program structure — the
        # mesh mode and the child edge's sharding annotation — fixed at
        # trace time; the collectives are emitted or not per executable.
        if fan_in:  # m3lint: disable=jax-traced-branch
            s = jax.lax.psum(s, "shard")
            cnt = jax.lax.psum(cnt, "shard")
        if node is ctx.plan.root:
            ctx.root_agg = (s, cnt)
        out = s / jnp.maximum(cnt, 1) if op == "avg" else s
        return jnp.where(cnt > 0, out, jnp.nan)
    if fan_in:  # m3lint: disable=jax-traced-branch
        cnt = jax.lax.psum(cnt, "shard")
    if op == "count":
        return jnp.where(cnt > 0, cnt, jnp.nan)
    if op == "group":
        return jnp.where(cnt > 0, 1.0, jnp.nan)
    if op == "min":
        m = jax.ops.segment_min(jnp.where(mask, v, jnp.inf), gids,
                                num_segments=g_pad)
        if fan_in:  # m3lint: disable=jax-traced-branch
            m = jax.lax.pmin(m, "shard")
        return jnp.where(cnt > 0, m, jnp.nan)
    # max (lowering admits nothing else)
    m = jax.ops.segment_max(jnp.where(mask, v, -jnp.inf), gids,
                            num_segments=g_pad)
    if fan_in:  # m3lint: disable=jax-traced-branch
        m = jax.lax.pmax(m, "shard")
    return jnp.where(cnt > 0, m, jnp.nan)


def _lower_binary(ctx: _Ctx, node: Binary):
    le, re_ = node.lhs.edge, node.rhs.edge
    comparison = node.op in promql.COMPARISON_OPS
    fn = _BIN_JNP[node.op]
    if le.kind == SCALAR and re_.kind == SCALAR:
        lv = _emit(ctx, node.lhs)
        rv = _emit(ctx, node.rhs)
        out = fn(lv, rv)
        if comparison and not node.bool_mode:
            return jnp.where(out > 0, lv, jnp.nan)
        return out
    if le.kind == SERIES and re_.kind == SERIES:
        many_idx, one_idx = ctx.aux_ins[ctx.path_of[id(node)]]
        lhs_v = _emit(ctx, node.lhs)
        rhs_v = _emit(ctx, node.rhs)
        many_v = rhs_v if node.swap else lhs_v
        one_v = lhs_v if node.swap else rhs_v
        # Index rows past the real match count pad with -1: a 0-padded
        # gather would replay row 0's FINITE values into the padding lanes,
        # and a downstream aggregate would fold that garbage into group 0.
        valid = (many_idx >= 0)[:, None]
        a = many_v[jnp.maximum(many_idx, 0)]
        b = one_v[jnp.maximum(one_idx, 0)]
        out = fn(b, a) if node.swap else fn(a, b)
        if comparison and not node.bool_mode:
            return jnp.where(valid & (out > 0), a, jnp.nan)
        both = jnp.isfinite(a) & jnp.isfinite(b)
        return jnp.where(valid & both, out, jnp.nan)
    # vector <op> scalar (either side)
    vec_left = le.kind == SERIES
    vec = _emit(ctx, node.lhs if vec_left else node.rhs)
    sc = _emit(ctx, node.rhs if vec_left else node.lhs)
    out = fn(vec, sc) if vec_left else fn(sc, vec)
    if comparison:
        if node.bool_mode:
            return jnp.where(jnp.isfinite(vec), out, jnp.nan)
        return jnp.where(out > 0, vec, jnp.nan)
    return out


def _emit(ctx: _Ctx, node: PlanNode):
    key = id(node)
    # DELIBERATE: the memo is keyed on PLAN NODE identity (static DAG
    # structure), not on any traced value.
    if key in ctx.cache:  # m3lint: disable=jax-traced-branch
        return ctx.cache[key]
    if isinstance(node, Fetch):
        val = _lower_fetch(ctx, node)
    elif isinstance(node, RangeFunc):
        val = _lower_rangefunc(ctx, node)
    elif isinstance(node, SubqueryFunc):
        val = _lower_subqueryfunc(ctx, node)
    elif isinstance(node, RankAgg):
        val = _lower_rankagg(ctx, node)
    elif isinstance(node, InstantFunc):
        val = _lower_instantfunc(ctx, node)
    elif isinstance(node, Aggregate):
        val = _lower_aggregate(ctx, node)
    elif isinstance(node, Binary):
        val = _lower_binary(ctx, node)
    elif isinstance(node, ScalarConst):
        val = ctx.slots[node.slot]
    else:  # pragma: no cover
        raise PlanFallback(type(node).__name__)
    ctx.cache[key] = val
    return val


# -------------------------------------------------------------- compiler


def _aux_layout(root: PlanNode) -> List[Tuple[int, int]]:
    """(preorder path, arity) per aux-consuming node: aggregates take one
    group-id array; vector-vector binaries two index arrays; rank
    aggregations a perm + inverse-perm pair; packed subqueries one
    column map; timestamp() one step-time vector; a range function its
    window edge. The stager and the trace-time unflattener both follow
    this order."""
    nodes: List[PlanNode] = []
    _preorder(root, nodes)
    out = []
    for i, n in enumerate(nodes):
        if isinstance(n, Aggregate):
            out.append((i, 1))
        elif _is_vv(n):
            out.append((i, 2))
        elif isinstance(n, RankAgg):
            out.append((i, 2))
        elif isinstance(n, SubqueryFunc) and n.packed:
            out.append((i, 1))
        elif isinstance(n, InstantFunc) and n.func == "timestamp":
            out.append((i, 1))
        elif isinstance(n, RangeFunc):
            out.append((i, 1))
    return out


@telemetry.jit_builder("plan")
@functools.lru_cache(maxsize=int(os.environ.get("M3_TPU_PLAN_CACHE", "128")))
def _plan_executable(stripped: PlanNode, geom: Geometry,
                     mesh: Optional[Mesh], kinds_sig: tuple):
    """Build + jit ONE program for a plan structure. Keyed on the
    matcher-stripped plan, the pow2 geometry bucket and the mesh — one
    executable serves every query (any metric, any threshold, any series
    count within the bucket) with this plan shape."""
    fetches = tuple(f for f, _ in kinds_sig)
    kinds_by_fetch = dict(kinds_sig)
    sharded = geom.n_shard > 1
    plan = Plan(stripped, 0, 0, fetches, sharded)
    layout = _aux_layout(stripped)
    root_is_sum = (isinstance(stripped, Aggregate)
                   and stripped.op in ("sum", "avg"))

    def body(fetch_flat, aux_flat, slots):
        fetch_ins = {}
        i = 0
        for f in fetches:
            per = {}
            for kind in kinds_by_fetch[f]:
                n = _KIND_ARITY[kind]
                per[kind] = tuple(fetch_flat[i:i + n])
                i += n
            fetch_ins[f] = per
        aux_ins = {}
        k = 0
        for path, arity in layout:
            aux_ins[path] = tuple(aux_flat[k:k + arity])
            k += arity
        ctx = _Ctx(plan, geom, fetch_ins, aux_ins, slots, sharded)
        root_val = _emit(ctx, plan.root)
        extras = ctx.root_agg if root_is_sum else ()
        return root_val, (extras if extras is not None else ())

    if not sharded:
        return jax.jit(body)

    fetch_specs = []
    for f in fetches:
        for kind in kinds_by_fetch[f]:
            for j in range(_KIND_ARITY[kind]):
                # baseline vectors ([S]) shard on their only axis
                one_d = kind == "resid" and j == 1
                fetch_specs.append(P("shard") if one_d
                                   else P("shard", None))
    # agg group-id vectors shard with their child's rows; aggregates over
    # replicated children take replicated ids; every other aux kind
    # (subquery column maps, timestamp times) is a replicated index
    # vector (vv binaries and rank aggs never mesh — mesh_ok is False)
    nodes: List[PlanNode] = []
    _preorder(stripped, nodes)
    aux_specs: List = []
    for n in nodes:
        if isinstance(n, Aggregate):
            aux_specs.append(P("shard")
                             if n.arg.edge.sharding == qplan.SHARDED
                             else P())
        elif _is_vv(n) or isinstance(n, RankAgg):
            aux_specs += [P(), P()]
        elif isinstance(n, SubqueryFunc) and n.packed:
            aux_specs.append(P())
        elif isinstance(n, InstantFunc) and n.func == "timestamp":
            aux_specs.append(P())
        elif isinstance(n, RangeFunc):
            aux_specs.append(P())
    aux_specs = tuple(aux_specs)
    root_edge = stripped.edge
    out_root_spec = (P("shard", None)
                     if root_edge.kind == SERIES
                     and root_edge.sharding == qplan.SHARDED else P())
    extras_spec = (P(), P()) if root_is_sum else ()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tuple(fetch_specs), aux_specs, P()),
        out_specs=(out_root_spec, extras_spec), check_vma=False)
    return jax.jit(fn)


# -------------------------------------------------------------- execution


def _bucket_sig(geom: Geometry) -> str:
    """Compact shape-bucket label for ANALYZE device stages: padded rows
    per fetch x padded steps @ shard count — a closed set (quarter-octave
    buckets), safe as a stage-name suffix."""
    rows = "+".join(str(s) for s in geom.s_pads) or "0"
    return f"s{rows}xt{geom.t_pad}@{geom.n_shard}"


@functools.lru_cache(maxsize=256)
def _compile_sig(root: PlanNode, fetches: Tuple[Fetch, ...]):
    """Matcher-stripped compile key + per-fetch staged-input kinds for a
    plan structure — pure of (root, fetches), memoized so a repeated
    query shape doesn't rebuild the projection every dispatch."""
    fetch_index = {f: i for i, f in enumerate(fetches)}
    kinds = fetch_kinds(root)
    stripped = qplan.strip(root, fetch_index)
    kinds_sig = tuple((qplan.strip(f, fetch_index), kinds[f])
                      for f in fetches)
    return stripped, kinds_sig, kinds


def execute(bound: "qplan.Bound", mesh: Optional[Mesh]):
    """Run one bound plan compiled: stage inputs, fetch (or build) the
    plan executable, dispatch ONE program, host-finish. Returns
    (values, tags, fetch_fn): scalar roots materialize `values` [steps]
    f64 directly; series roots return fetch_fn, a closure lazily
    materializing the [rows, steps] f64 plane (LazyBlock
    double-buffering across a dashboard burst)."""
    plan = bound.plan
    sharded = (mesh is not None and plan.mesh_ok
               and mesh.shape["shard"] > 1)
    use_mesh = mesh if sharded else None
    geom = geometry_for(bound, mesh.shape["shard"] if sharded else 1)
    stripped, kinds_sig, kinds = _compile_sig(plan.root, plan.fetches)

    # --- staged fetch inputs (device-resident via the derived cache)
    fetch_flat: List = []
    for fi, f in enumerate(plan.fetches):
        arrs = _stage_fetch(bound.fetches[f], kinds[f], geom.s_pads[fi],
                            geom.f_exts[fi], use_mesh)
        fetch_flat.extend(arrs)

    # --- aux inputs (bind-time host label algebra -> index arrays)
    nodes: List[PlanNode] = []
    _preorder(plan.root, nodes)
    pad_rows = _padded_rows_map(bound, geom, nodes)
    width_of, _ = _widths(plan.root, geom.t_pad, geom.sub_pads)
    aux_flat: List[np.ndarray] = []
    vv_i = rank_i = 0
    for n in nodes:
        if isinstance(n, Aggregate):
            a = bound.aux[id(n)]
            g = np.zeros(pad_rows[id(n.arg)], dtype=np.int32)
            g[:len(a["group_ids"])] = a["group_ids"]
            aux_flat.append(g)
        elif _is_vv(n):
            a = bound.aux[id(n)]
            r_pad = geom.r_pads[vv_i]
            vv_i += 1
            mi = np.full(r_pad, -1, dtype=np.int32)
            oi = np.full(r_pad, -1, dtype=np.int32)
            mi[:len(a["many_idx"])] = a["many_idx"]
            oi[:len(a["one_idx"])] = a["one_idx"]
            aux_flat += [mi, oi]
        elif isinstance(n, RankAgg):
            a = bound.aux[id(n)]
            g_pad, smax_pad = geom.rank_pads[rank_i]
            rank_i += 1
            gids = a["group_ids"].astype(np.int64)
            perm = np.full(g_pad * smax_pad, -1, dtype=np.int32)
            inv = np.full(pad_rows[id(n.arg)], -1, dtype=np.int32)
            if len(gids):
                # Stable order packs each group's rows in their original
                # row order (the interpreter's flatnonzero tie-break).
                order = np.argsort(gids, kind="stable")
                sorted_g = gids[order]
                starts = np.searchsorted(
                    sorted_g, np.arange(max(a["n_groups"], 1)))
                slots_in_g = np.arange(len(gids)) - starts[sorted_g]
                packed_idx = (sorted_g * smax_pad
                              + slots_in_g).astype(np.int32)
                perm[packed_idx] = order
                inv[order] = packed_idx
            aux_flat += [perm, inv]
        elif isinstance(n, SubqueryFunc) and n.packed:
            a = bound.aux[id(n)]
            cols = np.full(width_of[id(n)] * n.W, -1, dtype=np.int32)
            cols[:len(a["cols"])] = a["cols"]
            aux_flat.append(cols)
        elif isinstance(n, InstantFunc) and n.func == "timestamp":
            a = bound.aux[id(n)]
            times = np.zeros(width_of[id(n)], dtype=np.float32)
            times[:len(a["times"])] = a["times"]
            aux_flat.append(times)
        elif isinstance(n, RangeFunc):
            aux_flat.append(bound.aux[id(n)]["edge"])

    slots = np.asarray(bound.slots, dtype=np.float32)
    if slots.size == 0:
        slots = np.zeros(1, dtype=np.float32)

    # Shape-bucket key for the compute-fault quarantine: a bucket whose
    # executable faulted post-compile must route to the interpreter
    # WITHOUT rebuilding (lru_cache has no per-key eviction — the guard
    # clears the whole builder cache on quarantine, and this pre-builder
    # probe keeps the poisoned bucket from recompiling until its TTL).
    bucket = (_bucket_sig(geom), hash((stripped, kinds_sig)))
    if pguard.is_quarantined("plan", bucket):
        telemetry.compute_route("plan", primary=False)
        raise PlanFallback(
            f"quarantined shape bucket {bucket[0]}",
            reason=qplan.FallbackReason.DEVICE_FAULT)

    fn = _plan_executable(stripped, geom, use_mesh, kinds_sig)
    missed = isinstance(fn, telemetry._CompileTimed)
    if missed:
        telemetry.plan_cache_miss()
    else:
        telemetry.plan_cache_hit()
    if sharded:
        telemetry.mesh_dispatch("plan", cells=int(bound.total_cells))

    # ANALYZE: with a context active the dispatch synchronizes so the
    # stage records the true program wall (keyed by shape bucket); off,
    # the cost is this one thread-local read and the async pipeline is
    # untouched.
    actx = qexplain.current()
    sync = missed or actx is not None
    t0 = time.perf_counter() if sync else 0.0

    def _fault_fallback(err):
        # The interpreter is the plan route's proven oracle: surface the
        # typed DEVICE_FAULT reason so the executor's existing fallback
        # path counts it (telemetry.plan_fallback scope=runtime) and
        # EXPLAIN shows the route the execution actually took.
        raise PlanFallback(
            f"device fault: {err}" if err is not None
            else "plan route degraded",
            reason=qplan.FallbackReason.DEVICE_FAULT)

    root_val, extras = pguard.dispatch(
        "plan",
        lambda: fn(tuple(fetch_flat), tuple(aux_flat), slots),
        _fault_fallback,
        key=bucket, evict=_plan_executable.cache_clear)
    if sync:
        with tracing.phase("device_wait"):
            (root_val, extras) = jax.block_until_ready((root_val, extras))
        dt = time.perf_counter() - t0
        if missed:
            telemetry.plan_compile_recorded(dt)
        if actx is not None:
            # A cache miss's first invocation fuses trace+XLA compile
            # with the execution — name the stage so a one-time compile
            # can't be misread as steady-state program wall.
            name = f"device_program[{_bucket_sig(geom)}]"
            if missed:
                name += "+compile"
                actx.event("plan_cache_miss")
            actx.add(name, dt)

    # --- host finish
    steps = plan.steps
    root = plan.root
    if numwatch.installed():
        # Numerics witness (M3_TPU_NUMERICS=1, smoke tiers only):
        # observe the PADDED program output before the host slices it —
        # live lanes are the bound result rows x real steps, and every
        # padding ROW past them must still be NaN (a finite value there
        # means a padding lane's value survived the masks).
        numwatch.observe_result(
            "plan", root_val,
            live_rows=(None if root.edge.kind == SCALAR
                       else len(bound.out_tags)),
            live_cols=steps)
    if root.edge.kind == SCALAR:
        val = np.asarray(root_val, dtype=np.float64)
        return np.full(steps, float(val)), bound.out_tags, None

    n_rows = len(bound.out_tags)
    result_bytes = n_rows * steps * (
        8 if isinstance(root, Aggregate) and root.op in ("sum", "avg")
        else 4)

    if isinstance(root, RankAgg) and root.op in ("topk", "bottomk"):
        # Eager host finish: the surviving SERIES SET is data-dependent
        # (rows in the k best at any step), so the tags can only be
        # fixed after materialization — the interpreter's all-NaN row
        # drop, applied to the masked plane.
        with tracing.phase("device_wait", stage="result_materialize"):
            vals = np.asarray(root_val)[:n_rows, :steps]
            telemetry.count_d2h(result_bytes)
            keep = ~np.all(np.isnan(vals), axis=1)
            tags = [t for t, k in zip(bound.out_tags, keep) if k]
            vals = np.ascontiguousarray(vals[keep])
        if actx is not None:
            actx.event("d2h_bytes", result_bytes)
        return None, tags, (lambda: vals)

    if isinstance(root, Aggregate) and root.op in ("sum", "avg"):
        s_dev, cnt_dev = extras
        # The async D2H starts on the arrays fetch() actually reads (a
        # sum/avg root finishes from its (s, cnt) components, not the
        # collapsed root plane).
        temporal._copy_async(s_dev, cnt_dev)

        def fetch():
            with tracing.phase("device_wait", stage="result_materialize"):
                s = np.asarray(s_dev, dtype=np.float64)[:n_rows, :steps]
                cnt = np.asarray(cnt_dev, dtype=np.float64)[:n_rows, :steps]
                telemetry.count_d2h(result_bytes)
                if root.exact:
                    s = s + _exact_base_contrib(bound, root, n_rows, steps)
                out = s / np.maximum(cnt, 1) if root.op == "avg" else s
                result = np.where(cnt > 0, out, np.nan)
            if actx is not None:
                actx.event("d2h_bytes", result_bytes)
            return result

        return None, bound.out_tags, fetch

    temporal._copy_async(root_val)

    def fetch():
        with tracing.phase("device_wait", stage="result_materialize"):
            telemetry.count_d2h(result_bytes)
            # f32, like the per-op interpreter path's result planes: the
            # padded [rows_pad, t_pad] plane is sliced, not up-converted.
            result = np.asarray(root_val)[:n_rows, :steps]
        if actx is not None:
            actx.event("d2h_bytes", result_bytes)
        return result

    return None, bound.out_tags, fetch


def _padded_rows_map(bound: "qplan.Bound", geom: Geometry,
                     nodes: List[PlanNode]) -> Dict[int, int]:
    """Padded row count of every series-valued node's output plane (the
    length its consumer's per-row index inputs must be padded to)."""
    plan = bound.plan
    g_iter = iter(geom.g_pads)
    r_iter = iter(geom.r_pads)
    rank_iter = iter(geom.rank_pads)
    g_of: Dict[int, int] = {}
    r_of: Dict[int, int] = {}
    rank_of: Dict[int, Tuple[int, int]] = {}
    for n in nodes:
        if isinstance(n, Aggregate):
            g_of[id(n)] = next(g_iter)
        elif _is_vv(n):
            r_of[id(n)] = next(r_iter)
        elif isinstance(n, RankAgg):
            rank_of[id(n)] = next(rank_iter)

    out: Dict[int, int] = {}

    def rows(n: PlanNode) -> int:
        key = id(n)
        if key in out:
            return out[key]
        if isinstance(n, Fetch):
            r = geom.s_pads[plan.fetches.index(n)]
        elif isinstance(n, RangeFunc):
            r = 1 if n.func == "absent_over_time" else rows(n.arg)
        elif isinstance(n, (SubqueryFunc, InstantFunc)):
            r = rows(n.arg)
        elif isinstance(n, Aggregate):
            r = g_of[key]
        elif isinstance(n, RankAgg):
            # quantile collapses to group rows; topk keeps arg rows.
            r = rank_of[key][0] if n.op == "quantile" else rows(n.arg)
        elif isinstance(n, Binary):
            if _is_vv(n):
                r = r_of[key]
            elif n.lhs.edge.kind == SERIES:
                r = rows(n.lhs)
            else:
                r = rows(n.rhs)
        else:
            r = 0
        out[key] = r
        return r

    for n in nodes:
        rows(n)
    return out


def _exact_base_contrib(bound: "qplan.Bound", root: Aggregate,
                        n_rows: int, steps: int) -> np.ndarray:
    """Exact-f64 baseline mass for a counter sum: per-group baseline
    totals minus the baselines of MISSING cells (host, f64 — the part
    where f32 device accumulation of 1e9-magnitude counters would lose
    the host-reduce semantics). The common fully-dense case costs one
    isfinite pass; only rows with gaps pay the correction."""
    fetch = root.arg
    bf = bound.fetches[fetch]
    grid = bf.grid[:, :steps]
    finite = np.isfinite(grid)
    _, base = temporal.center(bf.grid)
    gids = bound.aux[id(root)]["group_ids"].astype(np.int64)
    g = int(bound.aux[id(root)]["n_groups"])
    base_g = np.zeros(g, dtype=np.float64)
    np.add.at(base_g, gids, base)
    out = np.repeat(base_g[:n_rows, None], steps, axis=1)
    missing_rows = np.nonzero(~finite.all(axis=1))[0]
    if missing_rows.size:
        corr = np.zeros((g, steps), dtype=np.float64)
        sub = np.where(finite[missing_rows], 0.0,
                       base[missing_rows][:, None])
        np.add.at(corr, gids[missing_rows], sub)
        out = out - corr[:n_rows]
    return out
