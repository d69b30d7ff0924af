"""Guarded accelerated dispatch: the compute-fault plane (reference:
dbnode survives storage-node faults through panic-recovery and bootstrap
retries — the process restarts and replays; a TPU serving floor cannot
restart its way out of a poisoned shape bucket or a device OOM, so the
equivalent discipline is TYPED degradation at every dispatch seam).

Every accelerated route the perf PRs built — the whole-plan pjit execute
(`parallel/compile.py`), the mesh agg flush (`parallel/agg_flush.py`),
the mesh flush encode (`parallel/ingest.py`), the Pallas codec kernels
(`ops/pallas_codec.py` via the `ops/tsz.py` / `utils/hashing.py` route
pickers), the block plane decode (`storage/block.py`), and the temporal
jit builders — dispatches through `dispatch()`:

  classify     the JAX exception zoo collapses to a closed ComputeError
               taxonomy: CompileError / DeviceOOM / KernelFault /
               DispatchTimeout. Anything unclassifiable (a shape bug, a
               programming error) RE-RAISES — the guard degrades on
               device misbehavior, it never masks bugs as device faults.
  breaker      per-route failure-rate Breaker (utils/retry.py): repeated
               classified faults trip the route OPEN and every dispatch
               short-circuits to the route's proven fallback (the XLA
               twin for Pallas, the interpreter for the plan route, the
               single-device/host path for mesh flushes) until the
               cooldown's half-open probe succeeds.
  OOM retry    DeviceOOM triggers ONE forced `HBMBudget.reclaim_pass()`
               (cross-tenant LRU eviction even when the host ledger is
               under budget) then a single retry before falling back.
  quarantine   a shape-bucket executable that faults post-compile is
               keyed into a TTL'd quarantine set and its cache entry
               dropped via the caller's evictor, so a poisoned bucket
               routes straight to fallback instead of recompile-crash-
               looping.

Degradation is surfaced, never silent: `telemetry.compute.*` counts
routes/faults/trips per route (span-tagged — EXPLAIN and the slow-query
log name the degraded route), `HealthTracker` gains a compute-degraded
probe (tripped breakers read DEGRADED, never SHEDDING on their own), and
`debug_snapshot()` feeds /debug/vars breaker states + quarantined
buckets.

The dispatch seam itself is installable (mirroring `persist/diskio.py`'s
`_io` pattern): `testing/faultcomp.py` swaps in a seeded fault injector
whose schedule is a pure function of (seed, route, call-index). Output
validators (`validate=`) run ONLY while an injector seam is installed —
in production, silent-corruption detection stays the job of the numerics
witness and the serve-time integrity checks; the guard adds no per-value
work to clean dispatches.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, Hashable, Optional

from . import telemetry
from ..utils import retry as uretry
from ..utils import tracing

__all__ = [
    "ComputeError", "CompileError", "DeviceOOM", "KernelFault",
    "DispatchTimeout", "classify", "dispatch", "available",
    "set_disabled", "configure", "reset", "debug_snapshot",
    "install_seam", "uninstall_seam", "seam_active", "eager",
    "guarded_builder", "quarantined_keys", "poisoned",
    "GARBAGE_F", "GARBAGE_I",
]


# ------------------------------------------------------------- taxonomy


class ComputeError(Exception):
    """Base of the closed device/kernel fault taxonomy. `kind` values are
    telemetry tag values (closed set; m3lint `unbounded-telemetry-tag`
    applies to anything riding them)."""

    kind = "compute"

    def __init__(self, route: str, detail: str,
                 cause: Optional[BaseException] = None):
        super().__init__(f"{route}: {detail}")
        self.route = route
        self.detail = detail
        self.cause = cause


class CompileError(ComputeError):
    """Trace/lowering/XLA-compilation failure for a shape bucket."""
    kind = "compile"


class DeviceOOM(ComputeError):
    """Device RESOURCE_EXHAUSTED: allocation failed on-chip."""
    kind = "oom"


class KernelFault(ComputeError):
    """A dispatched program raised (or produced provably corrupt output
    under an injector seam) — the generic device-side execution fault."""
    kind = "kernel"


class DispatchTimeout(ComputeError):
    """A dispatch exceeded the route's wall-clock budget (hang/delay)."""
    kind = "timeout"


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")
_TIMEOUT_MARKERS = ("DEADLINE_EXCEEDED", "deadline exceeded", "timed out")
_COMPILE_MARKERS = ("compilation", "Compilation", "Mosaic",
                    "lowering", "UNIMPLEMENTED")


def _is_device_exc(exc: BaseException) -> bool:
    """The runtime's own error type marks a device/runtime-side failure
    (compile, execution, allocation). Anything Python raises while
    tracing or lowering — NotImplementedError from a missing Pallas
    lowering rule, an AssertionError in one — is a bug in the program
    and stays unclassified, so dispatch() re-raises it."""
    from jax.errors import JaxRuntimeError

    return isinstance(exc, JaxRuntimeError)


def classify(exc: BaseException, route: str) -> Optional[ComputeError]:
    """Collapse an exception into the ComputeError taxonomy, or None if
    it is not a device/kernel fault (the caller must re-raise — a
    TypeError from a shape bug is a bug, not degradation). Idempotent:
    an already-typed ComputeError passes through."""
    if isinstance(exc, ComputeError):
        return exc
    msg = str(exc)
    if any(m in msg for m in _OOM_MARKERS):
        return DeviceOOM(route, msg, exc)
    if _is_device_exc(exc):
        if any(m in msg for m in _TIMEOUT_MARKERS):
            return DispatchTimeout(route, msg, exc)
        if any(m in msg for m in _COMPILE_MARKERS):
            return CompileError(route, msg, exc)
        return KernelFault(route, msg, exc)
    if any(m in msg for m in _TIMEOUT_MARKERS):
        return DispatchTimeout(route, msg, exc)
    return None


# ------------------------------------------------------------------ seam


class DispatchSeam:
    """The installable dispatch seam (the `diskio._io` pattern for
    compute): production is a transparent passthrough; faultcomp installs
    a subclass whose `call` injects seeded faults."""

    def call(self, route: str, fn: Callable[[], Any]) -> Any:
        return fn()


_DEFAULT_SEAM = DispatchSeam()
_seam: DispatchSeam = _DEFAULT_SEAM


def install_seam(seam: DispatchSeam):
    global _seam
    _seam = seam


def uninstall_seam():
    global _seam
    _seam = _DEFAULT_SEAM


def seam_active() -> bool:
    return _seam is not _DEFAULT_SEAM


# -------------------------------------------------------- route registry


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


class GuardedRoute:
    """Per-route breaker + quarantine + kill switch."""

    def __init__(self, name: str,
                 opts: Optional[uretry.BreakerOptions] = None,
                 clock: Callable[[], float] = time.monotonic,
                 timeout_s: Optional[float] = None,
                 quarantine_ttl_s: Optional[float] = None,
                 oom_retry: bool = True):
        self.name = name
        self.clock = clock
        self.breaker = uretry.Breaker(
            opts or uretry.BreakerOptions(
                window=16, failure_ratio=0.5, min_samples=4,
                cooldown_s=_env_float("M3_TPU_COMPUTE_COOLDOWN_S", 5.0)),
            clock=clock, name=f"compute.{name}")
        self.timeout_s = (timeout_s if timeout_s is not None else
                          _env_float("M3_TPU_COMPUTE_TIMEOUT_S", 30.0))
        self.quarantine_ttl_s = (
            quarantine_ttl_s if quarantine_ttl_s is not None else
            _env_float("M3_TPU_COMPUTE_QUARANTINE_TTL_S", 300.0))
        self.oom_retry = oom_retry
        self.disabled = False
        self._qlock = threading.Lock()
        self._quarantine: Dict[Hashable, float] = {}

    # ---------------------------------------------------------- quarantine

    def quarantine_add(self, key: Hashable):
        with self._qlock:
            self._quarantine[key] = self.clock() + self.quarantine_ttl_s

    def quarantined(self, key: Hashable) -> bool:
        with self._qlock:
            exp = self._quarantine.get(key)
            if exp is None:
                return False
            if self.clock() >= exp:
                del self._quarantine[key]
                return False
            return True

    def quarantine_keys(self) -> list:
        now = self.clock()
        with self._qlock:
            expired = [k for k, exp in self._quarantine.items()
                       if now >= exp]
            for k in expired:
                del self._quarantine[k]
            return list(self._quarantine)

    # ------------------------------------------------------------- breaker

    def record_failure(self):
        before = self.breaker.state
        self.breaker.record_failure()
        after = self.breaker.state
        if after != before:
            telemetry.compute_trip(self.name, after)

    def record_success(self):
        before = self.breaker.state
        self.breaker.record_success()
        after = self.breaker.state
        if after != before:
            telemetry.compute_trip(self.name, after)


_LOCK = threading.Lock()
_ROUTES: Dict[str, GuardedRoute] = {}
_PROBE_WIRED = False


def _wire_health_probe_locked():
    # Lazy, once: tripped breakers read DEGRADED (0.8 sits between the
    # tracker's degraded_at=0.7 and shedding_at=0.95) — compute
    # degradation must never shed load on its own; the fallbacks still
    # serve correct results, just slower.
    global _PROBE_WIRED
    if _PROBE_WIRED:
        return
    from ..utils import health

    health.TRACKER.register("compute_degraded", _degradation)
    _PROBE_WIRED = True


def _degradation() -> float:
    with _LOCK:
        routes = list(_ROUTES.values())
    for r in routes:
        if r.disabled:
            continue  # an operator kill switch is policy, not an incident
        if r.breaker.state != uretry.Breaker.CLOSED:
            return 0.8
    return 0.0


def _route(name: str) -> GuardedRoute:
    with _LOCK:
        r = _ROUTES.get(name)
        if r is None:
            r = GuardedRoute(name)
            _ROUTES[name] = r
            _wire_health_probe_locked()
        return r


def configure(name: str, *,
              opts: Optional[uretry.BreakerOptions] = None,
              clock: Callable[[], float] = time.monotonic,
              timeout_s: Optional[float] = None,
              quarantine_ttl_s: Optional[float] = None,
              oom_retry: bool = True) -> GuardedRoute:
    """(Re)build a route with explicit breaker options / clock — the test
    surface for deterministic trip/half-open/quarantine-TTL campaigns."""
    with _LOCK:
        r = GuardedRoute(name, opts=opts, clock=clock, timeout_s=timeout_s,
                         quarantine_ttl_s=quarantine_ttl_s,
                         oom_retry=oom_retry)
        _ROUTES[name] = r
        _wire_health_probe_locked()
        return r


def set_disabled(name: str, disabled: bool):
    """Per-route kill switch (the per-kernel M3_TPU_PALLAS story: flip
    ONE codec kernel to its XLA twin mid-process without touching the
    global env)."""
    _route(name).disabled = bool(disabled)


def available(name: str) -> bool:
    """Cheap route-picker check: False when the route is killed or its
    breaker is OPEN. Does NOT consume a half-open probe slot — pickers
    that see True still dispatch through `dispatch()`, where the breaker
    does its bookkeeping."""
    with _LOCK:
        r = _ROUTES.get(name)
    if r is None:
        return True
    return not r.disabled and r.breaker.state != uretry.Breaker.OPEN


def quarantined_keys(name: str) -> list:
    with _LOCK:
        r = _ROUTES.get(name)
    return r.quarantine_keys() if r is not None else []


def is_quarantined(name: str, key: Hashable) -> bool:
    """Pre-builder quarantine probe: callers whose executable cache has
    no per-key eviction (functools.lru_cache) consult this BEFORE the
    builder so a poisoned bucket skips straight to fallback without
    rebuilding anything."""
    with _LOCK:
        r = _ROUTES.get(name)
    return r is not None and r.quarantined(key)


def reset():
    """Drop every route (breakers, quarantine, kill switches). Test
    hygiene only; the seam is managed separately (faultcomp.uninstall)."""
    with _LOCK:
        _ROUTES.clear()


# ------------------------------------------------------ corruption probe

# The poison values faultcomp writes into corrupted output planes. Guard
# owns the contract (faultcomp imports these) so call sites never import
# testing code: a fully-poisoned plane — every element NaN, or every
# element the garbage sentinel — is detectable without consulting the
# oracle, which is exactly what a hardware bit-smear on a whole tile
# looks like from the host.
GARBAGE_F = 6.02214076e23
GARBAGE_I = -559038737  # 0xDEADBEEF as int32


def _iter_leaves(out):
    if isinstance(out, (tuple, list)):
        for v in out:
            yield from _iter_leaves(v)
    elif isinstance(out, dict):
        for v in out.values():
            yield from _iter_leaves(v)
    elif hasattr(out, "dtype") and hasattr(out, "shape"):
        yield out


def poisoned(out) -> Optional[str]:
    """Default output validator: detail string when any array leaf is a
    fully-poisoned plane (all-NaN, or every element equal to the garbage
    sentinel cast to its dtype). Only consulted while an injector seam is
    installed — see `dispatch`."""
    import numpy as np

    for leaf in _iter_leaves(out):
        a = np.asarray(leaf)
        if a.size == 0:
            continue
        if a.dtype.kind == "f":
            if np.isnan(a).all():
                return f"all-NaN plane shape={a.shape}"
            if (a == np.asarray(GARBAGE_F).astype(a.dtype)).all():
                return f"garbage-filled plane shape={a.shape}"
        elif a.dtype.kind in "iu":
            if (a == np.asarray(GARBAGE_I).astype(a.dtype)).all():
                return f"garbage-filled plane shape={a.shape}"
    return None


# ------------------------------------------------------------- dispatch


def _oom_reclaim(route: str) -> int:
    from ..utils import hbm

    budget = hbm.shared_budget()
    freed = budget.reclaim()
    if freed == 0:
        # Host ledger under budget but the DEVICE said RESOURCE_EXHAUSTED:
        # force one cross-tenant LRU pass anyway.
        freed = budget.reclaim_pass()
    telemetry.compute_oom_reclaim(route, freed)
    return freed


def dispatch(route: str,
             primary: Callable[[], Any],
             fallback: Callable[[Optional[ComputeError]], Any],
             *,
             key: Optional[Hashable] = None,
             evict: Optional[Callable[[], None]] = None,
             validate: Optional[Callable[[Any], Optional[str]]] = poisoned):
    """Run `primary` through the guarded seam for `route`; on a
    classified fault, degrade to `fallback(err)`.

    `key` names the shape-bucket executable (quarantined on post-compile
    faults; `evict` drops its cache entry). `validate(out)` returns a
    detail string when the output is provably corrupt (default: the
    poisoned-plane probe) — consulted ONLY while an injector seam is
    installed (see module docstring). Unclassifiable exceptions re-raise
    untouched."""
    r = _route(route)
    if r.disabled:
        telemetry.compute_route(route, primary=False)
        return fallback(None)
    if key is not None and r.quarantined(key):
        telemetry.compute_route(route, primary=False)
        return fallback(KernelFault(route, f"quarantined bucket {key!r}"))
    if not r.breaker.allow():
        telemetry.compute_route(route, primary=False)
        return fallback(ComputeError(route, "breaker open"))

    # The allow() grant MUST settle exactly once (record_success /
    # record_failure / cancel) on every path — an unsettled grant leaks
    # the half-open probe slot and wedges the breaker half-open forever
    # (m3lint's lifecycle pass checks this). The finally below is the
    # backstop for exceptions raised between the grant and a settle
    # (telemetry, validate, the fallback itself).
    settled = False
    try:
        err: Optional[ComputeError] = None
        out: Any = None
        t0 = r.clock()
        try:
            # The enqueue: JAX returns before the device finishes, so on
            # a detailed span this is host time (`dispatch_ns`), and the
            # wait shows where the result is read (`device_wait_ns`).
            with tracing.phase("dispatch"):
                out = _seam.call(route, primary)
        except ComputeError as exc:
            err = exc
        except Exception as exc:  # noqa: BLE001 — classified or re-raised
            err = classify(exc, route)
            if err is None:
                r.breaker.cancel()  # not a device fault: release the slot
                settled = True
                raise
        if err is None:
            elapsed = r.clock() - t0
            if validate is not None and seam_active():
                bad = validate(out)
                if bad is not None:
                    err = KernelFault(route, f"corrupted output: {bad}")
            if err is None and elapsed > r.timeout_s:
                # The result is VALID (the program finished) but the
                # route is hanging: count the fault against the breaker
                # and keep the answer — repeated delays trip the route
                # to the faster fallback.
                r.record_failure()
                settled = True
                telemetry.compute_fault(route, DispatchTimeout.kind)
                telemetry.compute_route(route, primary=True)
                return out
            if err is None:
                r.record_success()
                settled = True
                telemetry.compute_route(route, primary=True)
                return out

        telemetry.compute_fault(route, err.kind)

        if isinstance(err, DeviceOOM) and r.oom_retry:
            _oom_reclaim(route)
            try:
                out = _seam.call(route, primary)
            except ComputeError as exc:
                err = exc
                telemetry.compute_fault(route, err.kind)
            except Exception as exc:  # noqa: BLE001 — same contract
                err2 = classify(exc, route)
                if err2 is None:
                    r.breaker.cancel()
                    settled = True
                    raise
                err = err2
                telemetry.compute_fault(route, err.kind)
            else:
                bad = (validate(out)
                       if validate is not None and seam_active() else None)
                if bad is None:
                    r.record_success()
                    settled = True
                    telemetry.compute_route(route, primary=True)
                    return out
                err = KernelFault(route, f"corrupted output: {bad}")
                telemetry.compute_fault(route, err.kind)

        r.record_failure()
        settled = True
        if key is not None:
            r.quarantine_add(key)
            telemetry.compute_quarantine(route)
            if evict is not None:
                try:
                    evict()
                except Exception:  # noqa: BLE001 — eviction best-effort;
                    pass  # the quarantine set already blocks the bucket
        telemetry.compute_route(route, primary=False)
        return fallback(err)
    finally:
        if not settled:
            r.breaker.cancel()


# -------------------------------------------------- fallback conveniences


def eager(fn: Callable, *args, **kwargs):
    """Universal jit fallback: run an (already-jitted) callable eagerly.
    `jax.disable_jit()` is consulted at call time, so it works on cached
    executables without retracing machinery of our own."""
    import jax

    with jax.disable_jit():
        return fn(*args, **kwargs)


class _GuardedFn:
    """Wraps a builder-returned jitted callable: each invocation
    dispatches through the guard with the eager twin as fallback."""

    __slots__ = ("route", "fn")

    def __init__(self, route: str, fn: Callable):
        self.route = route
        self.fn = fn

    def __call__(self, *args, **kwargs):
        return dispatch(
            self.route,
            lambda: self.fn(*args, **kwargs),
            lambda _err: eager(self.fn, *args, **kwargs))


def guarded_builder(route: str):
    """Stack ABOVE `telemetry.jit_builder` on a temporal jit builder:

        @guard.guarded_builder("temporal.rate")
        @telemetry.jit_builder("rate")
        @functools.lru_cache(maxsize=256)
        def _rate_fn(...): ... return jax.jit(fn)

    The callables the builder returns are wrapped so every invocation
    dispatches through the guard with the eager (disable_jit) path as
    the route's fallback. cache_info/cache_clear stay forwarded for the
    callers and m3lint's discovery."""

    def deco(builder: Callable):
        def wrapper(*args, **kwargs):
            return _GuardedFn(route, builder(*args, **kwargs))

        wrapper.cache_info = getattr(builder, "cache_info", None)
        wrapper.cache_clear = getattr(builder, "cache_clear", None)
        wrapper.__wrapped__ = builder
        wrapper.__name__ = getattr(builder, "__name__", "guarded")
        wrapper.__doc__ = getattr(builder, "__doc__", None)
        return wrapper

    return deco


# ---------------------------------------------------------- observability


def debug_snapshot() -> dict:
    """Breaker states + quarantined buckets for /debug/vars."""
    with _LOCK:
        routes = list(_ROUTES.values())
    out = {}
    for r in routes:
        out[r.name] = {
            "state": r.breaker.state,
            "disabled": r.disabled,
            "quarantined": sorted(repr(k) for k in r.quarantine_keys()),
        }
    return out
