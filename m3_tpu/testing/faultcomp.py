"""Seeded compute-fault injection for the guarded dispatch seam (the
fault-injection trilogy's device leg: testing/faultnet.py is the network
leg, testing/faultfs.py the disk leg, this the compute leg).

`ComputeFaultPlan` is frozen and seeded; the fault schedule is a PURE
FUNCTION of (seed, route, call-index): each intercepted dispatch makes
exactly ONE draw from `random.Random(f"{seed}/{route}/{index}")` against
cumulative thresholds in a FIXED order (compile_fail -> dispatch_raise
-> oom -> delay -> corrupt). `plan.schedule(route, n)` replays the first
n decisions without dispatching anything — tests assert the injector's
recorded decisions equal it verbatim.

`FaultComp` implements `parallel.guard.DispatchSeam`:

  compile_fail    raises XlaRuntimeError("INTERNAL: ... compilation ...")
                  — the guard classifies CompileError;
  dispatch_raise  raises XlaRuntimeError mid-dispatch — KernelFault;
  oom             raises XlaRuntimeError("RESOURCE_EXHAUSTED: ...") —
                  DeviceOOM, which triggers the guard's evict-then-retry
                  (the retry is a FRESH call index: a schedule can fault
                  the first attempt and clear the retry);
  delay           sleeps `delay_s` then dispatches normally — the route
                  still answers correctly, but past the guard's timeout
                  budget the slow dispatch counts against the breaker;
  corrupt         dispatches normally then POISONS every array leaf of
                  the output (all-NaN or all-garbage, `guard.GARBAGE_*`)
                  — proving the validators/oracles catch silent
                  corruption, not just raises. No Go analog: a
                  process-restart model can't even express this.

`route_filter` (substring match) scopes faults to one route family
(e.g. "codec." or "plan"). Install with `install(plan)` / `uninstall()`
or the `injected(plan)` context manager — they swap the module-level
seam in parallel/guard.py, exactly the `diskio._io` pattern.
"""

from __future__ import annotations

import contextlib
import dataclasses
import random
import threading
import time
from typing import Dict, List, Tuple

import numpy as np

from ..parallel import guard

__all__ = ["ComputeFaultPlan", "FaultComp", "NO_FAULT", "install",
           "uninstall", "injected"]

NO_FAULT = "ok"

# The runtime's own error class, so classify() sees the genuine article.
from jax.errors import JaxRuntimeError as XlaRuntimeError  # noqa: E402


@dataclasses.dataclass(frozen=True)
class ComputeFaultPlan:
    """Per-kind fault probabilities. All zero = benign passthrough (the
    injector still records decisions — and activates the guard's output
    validators — so determinism is testable without faults)."""

    seed: int = 0
    compile_fail: float = 0.0    # XLA/Mosaic compilation failure
    dispatch_raise: float = 0.0  # XlaRuntimeError mid-dispatch
    oom: float = 0.0             # device RESOURCE_EXHAUSTED
    delay: float = 0.0           # dispatch hang: sleep then answer
    corrupt: float = 0.0         # poisoned output planes (NaN/garbage)
    delay_s: float = 0.05        # hang duration for `delay`
    route_filter: str = ""       # substring: faults only matching routes

    _KINDS = ("compile_fail", "dispatch_raise", "oom", "delay", "corrupt")

    def _probs(self) -> Tuple[Tuple[str, float], ...]:
        return (("compile_fail", self.compile_fail),
                ("dispatch_raise", self.dispatch_raise),
                ("oom", self.oom),
                ("delay", self.delay),
                ("corrupt", self.corrupt))

    def matches(self, route: str) -> bool:
        return not self.route_filter or self.route_filter in route

    def decide_at(self, route: str, index: int) -> str:
        """ONE draw for dispatch `index` on `route` against cumulative
        thresholds in fixed order — a pure function of (seed, route,
        call-index); the whole schedule is reproducible from the plan."""
        draw = random.Random(f"{self.seed}/{route}/{index}").random()
        acc = 0.0
        for name, p in self._probs():
            acc += p
            if draw < acc:
                return name
        return NO_FAULT

    def schedule(self, route: str, n: int) -> List[str]:
        """The first n decisions for `route` — what the injector WILL
        do, computable without dispatching anything."""
        return [self.decide_at(route, i) for i in range(n)]


def _poison_tree(out, mode: str):
    """Replace every array leaf with a fully-poisoned plane of the same
    shape/dtype: all-NaN ("nan") or all guard.GARBAGE_* ("garbage").
    Non-array leaves and bool planes pass through untouched."""
    if isinstance(out, tuple):
        return tuple(_poison_tree(v, mode) for v in out)
    if isinstance(out, list):
        return [_poison_tree(v, mode) for v in out]
    if isinstance(out, dict):
        return {k: _poison_tree(v, mode) for k, v in out.items()}
    if not (hasattr(out, "dtype") and hasattr(out, "shape")):
        return out
    a = np.asarray(out)
    if a.dtype.kind == "f":
        val = np.nan if mode == "nan" else guard.GARBAGE_F
        bad = np.full(a.shape, np.asarray(val).astype(a.dtype),
                      dtype=a.dtype)
    elif a.dtype.kind in "iu":
        bad = np.full(a.shape, np.asarray(guard.GARBAGE_I).astype(a.dtype),
                      dtype=a.dtype)
    else:
        return out
    try:  # hand back the flavor the caller dispatched (device array in,
        import jax.numpy as jnp  # device array out)
        return jnp.asarray(bad)
    except Exception:  # pragma: no cover - jax always importable in-tree
        return bad


class FaultComp(guard.DispatchSeam):
    """Seeded fault-injecting dispatch seam. Thread-safe; `decisions`
    and `faults_injected` mirror faultnet/faultfs observability so
    scenarios can assert the chaos actually happened."""

    def __init__(self, plan: ComputeFaultPlan):
        self.plan = plan
        self._lock = threading.Lock()
        self._calls: Dict[str, int] = {}
        self.decisions: Dict[str, List[str]] = {}
        self.faults_injected = 0

    def call(self, route: str, fn):
        if not self.plan.matches(route):
            return fn()
        with self._lock:
            index = self._calls.get(route, 0)
            self._calls[route] = index + 1
            d = self.plan.decide_at(route, index)
            self.decisions.setdefault(route, []).append(d)
            if d != NO_FAULT:
                self.faults_injected += 1
        # Apply OUTSIDE the lock: fn may sleep, re-enter, or dispatch a
        # nested guarded route.
        if d == "compile_fail":
            raise XlaRuntimeError(
                "INTERNAL: injected XLA compilation failure "
                f"(route={route}, index={index})")
        if d == "dispatch_raise":
            raise XlaRuntimeError(
                "INTERNAL: injected device fault during program execution "
                f"(route={route}, index={index})")
        if d == "oom":
            raise XlaRuntimeError(
                "RESOURCE_EXHAUSTED: injected: attempting to allocate "
                f"2.0G on device (route={route}, index={index})")
        if d == "delay":
            time.sleep(self.plan.delay_s)
            return fn()
        if d == "corrupt":
            out = fn()
            # Position-style derived rng (faultfs idiom): the NaN-vs-
            # garbage pick never perturbs the decision stream.
            mode_rng = random.Random(
                f"{self.plan.seed}/pos/{route}/{index}")
            return _poison_tree(
                out, "nan" if mode_rng.random() < 0.5 else "garbage")
        return fn()


# ------------------------------------------------------------ installation


def install(plan: ComputeFaultPlan) -> FaultComp:
    """Swap the guarded dispatch seam to a fault injector; returns it."""
    seam = FaultComp(plan)
    guard.install_seam(seam)
    return seam


def uninstall() -> None:
    guard.uninstall_seam()


@contextlib.contextmanager
def injected(plan: ComputeFaultPlan):
    seam = install(plan)
    try:
        yield seam
    finally:
        uninstall()
