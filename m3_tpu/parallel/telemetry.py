"""JAX/TPU runtime telemetry: the compile/dispatch/transfer visibility
layer (reference: the reference exposes its runtime internals through
tally scopes on every component; the TPU build's equivalent blind spot
was XLA — jit cache behavior, compile stalls, shape-bucket churn, and
host<->device transfer volume were invisible at runtime, so "whole-plan
pjit wins" claims had nothing to measure against).

Everything exports through `utils.instrument` under the `telemetry.*`
scope (visible in /debug/vars and the self-scrape pipeline) and tags the
ACTIVE span via `utils.tracing.count_cost`, so a traced query that paid a
compile shows `jit_compile` in its cost tags.

  jit_builder(name)   decorator stacked ABOVE the repo's
                      `functools.lru_cache` jit-builder idiom (the inner
                      decorator stays visible to m3lint's traced-fn
                      discovery): counts builder cache hits vs misses
                      from cache_info() deltas, and wraps each MISS's
                      returned jitted callable so its FIRST invocation —
                      where tracing + XLA compilation actually happen —
                      is timed into the `telemetry.jit.compile_s`
                      histogram.

  record_bucket(path, key)
                      pow2 shape-bucket tracking for the batched decode
                      paths: first sight of a (path, geometry) bucket is
                      a `bucket_miss` (a fresh compile for that shape),
                      repeats are hits. Bounded by eviction.

  count_h2d / count_d2h
                      host<->device transfer bytes at the choke points
                      (the upload cache's inserts, LazyBlock result materialization).

  mesh_dispatch(kernel)
                      per-kernel mesh-program dispatch counter (flush
                      encode, sharded aggregation) — the denominator for
                      "did this query actually run on the mesh".

This module deliberately imports NOTHING from jax/ops/parallel so it is
a leaf every layer (ops kernels included) can import without cycles.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Optional

from ..utils import tracing
from ..utils.instrument import ROOT

_SCOPE = ROOT.sub_scope("telemetry")
_JIT = _SCOPE.sub_scope("jit")
_XFER = _SCOPE.sub_scope("transfer")
_BUCKETS = _SCOPE.sub_scope("shape_bucket")
_MESH = _SCOPE.sub_scope("mesh")

# Compile wall time in seconds; boundaries skewed high — XLA compiles are
# 10ms..10s, not the default sub-ms request buckets.
_COMPILE_BOUNDS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


class _CompileTimed:
    """Wrap a freshly-built jitted callable so its FIRST call (trace +
    XLA compile) is timed; later calls pass through one attribute check.
    Thread-safe in the benign direction: a race times the compile twice,
    never misses it."""

    __slots__ = ("fn", "name", "done")

    def __init__(self, fn: Callable, name: str):
        self.fn = fn
        self.name = name
        self.done = False

    def __call__(self, *args, **kwargs):
        if self.done:
            return self.fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        self.done = True
        _JIT.counter("compiles").inc()
        _JIT.histogram("compile_s", _COMPILE_BOUNDS).record(dt)
        _SCOPE.sub_scope("jit", builder=self.name).counter("compiles").inc()
        tracing.count_cost("jit_compile")
        return out


def jit_builder(name: str):
    """Stack above an lru_cache'd jit-builder:

        @telemetry.jit_builder("rate")
        @functools.lru_cache(maxsize=256)
        def _rate_fn(...): ... return jax.jit(fn)

    Hits/misses come from the wrapped cache's own cache_info() (no
    second cache, no key divergence); a miss's result is wrapped so its
    first invocation records compile wall time. The lru_cache decorator
    stays on the function itself, keeping m3lint's jit-builder discovery
    (jax_rules) and the callers' cache_clear()/cache_info() surface
    intact."""

    def deco(cached: Callable):
        if not hasattr(cached, "cache_info"):  # defensive: wrong stacking
            raise TypeError(
                f"jit_builder({name!r}) must wrap an lru_cache'd builder")
        hits = _SCOPE.sub_scope("jit", builder=name).counter("hits")
        misses = _SCOPE.sub_scope("jit", builder=name).counter("misses")
        total_hits = _JIT.counter("hits")
        total_misses = _JIT.counter("misses")
        lock = threading.Lock()

        @functools.wraps(cached)
        def wrapper(*args, **kwargs):
            # cache_info() delta under a private lock: concurrent callers
            # must not double-count one miss (lru_cache itself is
            # thread-safe; only the delta read needs serializing).
            with lock:
                before = cached.cache_info().misses
                out = cached(*args, **kwargs)
                missed = cached.cache_info().misses != before
            if missed:
                misses.inc()
                total_misses.inc()
                # The BUILDING call gets the timing wrapper; the cache
                # itself keeps serving the raw jitted fn on later hits —
                # by then the first (timed) invocation already happened,
                # so hits lose nothing and never risk a stale wrapper.
                return _CompileTimed(out, name)
            hits.inc()
            total_hits.inc()
            return out

        wrapper.cache_info = cached.cache_info
        wrapper.cache_clear = cached.cache_clear
        wrapper.__wrapped__ = cached
        return wrapper

    return deco


# ------------------------------------------------------------ plan cache

_PLAN_CACHE = _SCOPE.sub_scope("plan_cache")


def plan_cache_hit():
    """One compiled-plan executable served from the plan cache."""
    _PLAN_CACHE.counter("hits").inc()


def plan_cache_miss():
    """One plan-cache miss: a fresh whole-plan trace + XLA compile is
    about to happen (its wall time lands via plan_compile_recorded)."""
    _PLAN_CACHE.counter("misses").inc()


def plan_compile_recorded(seconds: float):
    """Wall time of one whole-plan trace + compile (the first invocation
    of a plan-cache miss), tagged onto the active span so the slow-query
    log can attribute cold compiles."""
    _PLAN_CACHE.counter("compiles").inc()
    _PLAN_CACHE.histogram("compile_s", _COMPILE_BOUNDS).record(seconds)
    tracing.count_cost("plan_compile")


# ---------------------------------------------------------- plan fallbacks

_PLAN_FALLBACK = _SCOPE.sub_scope("plan_fallback")


def plan_fallback(reason: str, scope: str = "structural"):
    """One query that missed the compiled whole-plan route, tagged with
    its typed `query.plan.FallbackReason` VALUE (a closed set — raw
    query strings or other unbounded values must never ride as tag
    values; m3lint's `unbounded-telemetry-tag` rule gates it) and its
    SCOPE: "structural" (the query shape is outside the compiled
    surface) vs "runtime" (a data-dependent or operational routing
    decision — below-floor, kill switch, backend gap; see
    query.plan.fallback_scope). The split keeps coverage_report.py's
    structural re-lowering consistent with recorded routes: a
    below-floor miss on a small-series corpus is not a lowering gap.
    The reason-tagged counters are the fallback taxonomy /debug/vars,
    the self-scrape pipeline and scripts/coverage_report.py read."""
    _SCOPE.sub_scope("plan_fallback", reason=reason,
                     scope=scope).counter("count").inc()
    _PLAN_FALLBACK.counter("total").inc()
    tracing.count_cost("plan_fallback")


# ------------------------------------------------------------ transfers


def count_h2d(nbytes: int):
    """Host->device transfer bytes at an upload choke point."""
    if nbytes > 0:
        _XFER.counter("h2d_bytes").inc(int(nbytes))
        _XFER.counter("h2d_transfers").inc()
        tracing.count_cost("h2d_bytes", int(nbytes))


def count_d2h(nbytes: int):
    """Device->host transfer bytes at a result materialization point."""
    if nbytes > 0:
        _XFER.counter("d2h_bytes").inc(int(nbytes))
        _XFER.counter("d2h_transfers").inc()
        tracing.count_cost("d2h_bytes", int(nbytes))


# ---------------------------------------------------------- shape buckets

_BUCKET_LOCK = threading.Lock()
_SEEN_BUCKETS: set = set()
_BUCKET_CAP = 4096  # safety bound; real bucket sets are tens of entries


def record_bucket(path: str, key: tuple):
    """pow2 shape-bucket accounting for a batched decode/encode path: a
    first-seen (path, geometry) is a bucket MISS — the next dispatch with
    it compiles a fresh kernel — repeats are hits. The per-path miss
    counter is the "is bucketing actually bounding recompiles" signal."""
    k = (path, key)
    with _BUCKET_LOCK:
        if k in _SEEN_BUCKETS:
            hit = True
        else:
            hit = False
            if len(_SEEN_BUCKETS) >= _BUCKET_CAP:
                _SEEN_BUCKETS.clear()  # degenerate workload: restart
            _SEEN_BUCKETS.add(k)
    scope = _SCOPE.sub_scope("shape_bucket", path=path)
    if hit:
        scope.counter("hits").inc()
        _BUCKETS.counter("hits").inc()
    else:
        scope.counter("misses").inc()
        _BUCKETS.counter("misses").inc()
        tracing.count_cost("shape_bucket_miss")


# ------------------------------------------------------------ codec routes

_CODEC = _SCOPE.sub_scope("codec")


def codec_route(kernel: str, pallas: bool):
    """Count one codec dispatch for `kernel` in {"encode", "decode",
    "hash"}: Pallas kernel route (`telemetry.codec.pallas_<kernel>`) vs
    the XLA/numpy path (`telemetry.codec.xla_<kernel>`), tagged onto the
    active span — EXPLAIN/slow-query output shows which codec route a
    query actually took. The smoke tier asserts the pallas_* counters
    move when M3_TPU_PALLAS=1, proving dispatch rather than silently
    falling back."""
    name = ("pallas_" if pallas else "xla_") + kernel
    _CODEC.counter(name).inc()
    _CODEC.counter("pallas" if pallas else "fallback").inc()
    tracing.count_cost(f"codec_{name}")


def codec_compile_recorded(kernel: str, seconds: float):
    """Wall time of one codec kernel build's first invocation (trace +
    Mosaic lowering, or interpret-mode setup on CPU) — the codec twin of
    jit_builder's compile timing, same histogram bounds, span-tagged."""
    _SCOPE.sub_scope("codec", kernel=kernel).counter("compiles").inc()
    _CODEC.counter("compiles").inc()
    _CODEC.histogram("compile_s", _COMPILE_BOUNDS).record(seconds)
    tracing.count_cost("codec_pallas_compile")


# ---------------------------------------------------------- compute plane

_COMPUTE = _SCOPE.sub_scope("compute")


@functools.lru_cache(maxsize=None)
def _compute_route_counters(route: str):
    # The guard dispatches on hot interpreter paths (one per temporal
    # op invocation): resolve the tagged counter objects once per route
    # so the per-dispatch cost is two Counter.inc()s, not a sub_scope
    # build + registry lookup. The seam's cost has no reading on the
    # chip's host yet (ROADMAP C13).
    scope = _SCOPE.sub_scope("compute", route=route)
    return (scope.counter("primary"), scope.counter("fallback"),
            _COMPUTE.counter("primary"), _COMPUTE.counter("fallback"))


def compute_route(route: str, primary: bool):
    """Count one guarded dispatch for an accelerated `route` (plan,
    agg_flush, flush_encode, codec.*, block.decode, temporal.*): the
    primary accelerated path vs its proven fallback twin. `route` is a
    closed set — the guard registry's route names — never a query string
    (m3lint `unbounded-telemetry-tag` applies). Span-tagged so EXPLAIN
    and the slow-query log name the degraded route."""
    prim, fb, tot_prim, tot_fb = _compute_route_counters(route)
    if primary:
        prim.inc()
        tot_prim.inc()
    else:
        fb.inc()
        tot_fb.inc()
        tracing.count_cost(f"compute_fallback_{route}")


def compute_fault(route: str, kind: str):
    """One classified device/kernel fault on `route`, tagged with its
    `ComputeError` taxonomy kind (compile / oom / kernel / timeout — a
    closed set)."""
    _SCOPE.sub_scope("compute", route=route, kind=kind).counter(
        "faults").inc()
    _COMPUTE.counter("faults").inc()
    tracing.count_cost(f"compute_fault_{kind}")


def compute_trip(route: str, state: str):
    """One breaker state transition on `route` (state in {"open",
    "half_open", "closed"}). `open` transitions are the degradation
    signal HealthTracker's compute probe and /debug/vars surface."""
    _SCOPE.sub_scope("compute", route=route).counter(
        "trip_" + state).inc()
    if state == "open":
        _COMPUTE.counter("trips").inc()
        tracing.count_cost("compute_breaker_trip")


def compute_quarantine(route: str):
    """One shape-bucket executable quarantined on `route` (a post-compile
    fault dropped the cache entry and keyed the bucket into the TTL'd
    quarantine set — no recompile-crash-loop)."""
    _SCOPE.sub_scope("compute", route=route).counter("quarantined").inc()
    _COMPUTE.counter("quarantined").inc()
    tracing.count_cost("compute_quarantine")


def compute_oom_reclaim(route: str, freed: int):
    """One DeviceOOM-triggered HBMBudget cross-tenant reclaim before the
    single retry; `freed` accumulates bytes reclaimed."""
    _SCOPE.sub_scope("compute", route=route).counter("oom_reclaims").inc()
    _COMPUTE.counter("oom_reclaims").inc()
    if freed > 0:
        _COMPUTE.counter("oom_reclaimed_bytes").inc(int(freed))
    tracing.count_cost("compute_oom_reclaim")


# ------------------------------------------------------------- dispatches


def mesh_dispatch(kernel: str, cells: Optional[int] = None):
    """Count one mesh-program dispatch for `kernel` (flush_encode,
    agg_rate, ...); `cells` accumulates the dispatched volume."""
    scope = _SCOPE.sub_scope("mesh", kernel=kernel)
    scope.counter("dispatches").inc()
    _MESH.counter("dispatches").inc()
    if cells:
        scope.counter("cells").inc(int(cells))
    tracing.count_cost("mesh_dispatch")


def snapshot() -> dict:
    """The telemetry.* slice of the instrument registry (obs smoke and
    tests read this; /debug/vars carries the full registry anyway)."""
    return {k: v for k, v in ROOT.snapshot().items()
            if k.startswith("telemetry.")}
