"""Shared device-memory (HBM) budget for every cache that pins device or
host buffers on the serving path (reference: the byte-bounded WiredList of
src/dbnode/storage/block/wired_list.go:77, generalized to ONE budget over
every resident tier the way dbnode's cache policies share the wired-list
capacity).

Before this, each cache carried its own ceiling (`M3_TPU_UPLOAD_CACHE_BYTES`,
`M3_TPU_DERIVED_CACHE_BYTES`, ...) and nothing bounded their SUM — three
caches at their individual limits could pin more HBM than the chip has,
starving the kernels they exist to feed. `HBMBudget` is the process-wide
cap: tenants register a usage probe plus an evict-one callback, and
`reclaim()` rotates across tenants evicting least-recently-used entries
until the total fits (per-tenant ceilings still apply first, so existing
knobs keep their meaning as shares of the global budget).

Accounting is PULL-based — the budget reads each tenant's live byte
counter instead of mirroring charges — so a tenant that clears itself
(tests monkeypatching a cache, a namespace drop) can never leave phantom
bytes behind in a push-ledger.

Locking: the budget lock is only ever held to snapshot the tenant table;
evict callbacks run with NO budget lock held, so a tenant is free to take
its own lock inside them (tenant lock -> budget lock is the one permitted
order; callers must invoke `reclaim()` only outside their own locks when
their evictor takes that lock).

Uploads on the storage/query serving path go through a registered tenant
(m3lint's `unbudgeted-device-put` rule flags raw `jax.device_put` there);
staging a device program consumes and frees itself carries a justified
suppression instead.

Saturation exports through instrument gauges (`hbm.bytes`,
`hbm.saturation`) and `pressure()` registers as a HealthTracker probe:
pressure stays 0.0 while reclaim keeps the total inside the budget (a full
LRU cache is a HEALTHY steady state, not an incident) and rises only when
pinned bytes exceed the budget and eviction cannot free them — the
memory-pressure analog of the admission gates' depth saturation.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, Optional

from .instrument import ROOT

__all__ = ["HBMBudget", "shared_budget"]

DEFAULT_BUDGET_BYTES = 2 * 1024 * 1024 * 1024


class HBMBudget:
    """One byte budget across every registered resident-memory tenant."""

    def __init__(self, limit_bytes: int, name: str = "hbm"):
        if limit_bytes <= 0:
            raise ValueError(f"budget must be positive, got {limit_bytes}")
        self.limit = int(limit_bytes)
        self.name = name
        self._lock = threading.Lock()
        self._usage: Dict[str, Callable[[], int]] = {}
        self._evictors: Dict[str, Callable[[], int]] = {}
        # Rotation cursor: reclaim starts each pass one tenant further
        # along, approximating global LRU without a cross-tenant clock.
        self._rotation = 0
        self._metrics = ROOT.sub_scope(name)

    # ---------------------------------------------------------------- tenants

    def register(self, tenant: str, usage: Callable[[], int],
                 evict_one: Optional[Callable[[], int]] = None):
        """Add a tenant: `usage()` returns its current resident bytes;
        `evict_one()` (optional) drops its least-recently-used entry and
        returns the bytes freed (0 when it cannot shrink further)."""
        with self._lock:
            self._usage[tenant] = usage
            if evict_one is not None:
                self._evictors[tenant] = evict_one
            else:
                self._evictors.pop(tenant, None)

    def unregister(self, tenant: str):
        with self._lock:
            self._usage.pop(tenant, None)
            self._evictors.pop(tenant, None)

    # --------------------------------------------------------------- readings

    def total(self) -> int:
        with self._lock:
            probes = list(self._usage.values())
        total = 0
        for fn in probes:
            try:
                total += max(0, int(fn()))
            except Exception:  # noqa: BLE001 — a dead probe contributes 0
                pass
        return total

    def usage(self) -> Dict[str, int]:
        with self._lock:
            probes = dict(self._usage)
        out = {}
        for tenant, fn in probes.items():
            try:
                out[tenant] = max(0, int(fn()))
            except Exception:  # noqa: BLE001
                out[tenant] = 0
        return out

    def saturation(self) -> float:
        return min(1.0, self.total() / self.limit)

    def pressure(self) -> float:
        """Health-probe reading: 0 while the budget holds (a full cache is
        healthy), rising toward 1 as unreclaimable bytes exceed the limit
        (at 2x the budget the probe reads fully saturated)."""
        total = self.total()
        if total <= self.limit:
            return 0.0
        return min(1.0, (total - self.limit) / self.limit)

    # --------------------------------------------------------------- reclaim

    def reclaim(self) -> int:
        """Evict LRU entries across tenants (rotating the starting tenant
        so no single cache absorbs all evictions) until the total fits the
        budget or a full pass frees nothing. Returns bytes freed. Called
        with NO tenant locks held (evictors take their own)."""
        freed = 0
        while self.total() > self.limit:
            with self._lock:
                names = list(self._evictors)
                if not names:
                    break
                start = self._rotation % len(names)
                self._rotation += 1
                evictors = [(n, self._evictors[n])
                            for n in names[start:] + names[:start]]
            pass_freed = 0
            for _name, evict in evictors:
                try:
                    pass_freed += max(0, int(evict()))
                except Exception:  # noqa: BLE001 — one tenant's failure
                    pass               # must not wedge global reclaim
                if self.total() <= self.limit:
                    break
            if pass_freed == 0:
                break
            freed += pass_freed
        self._metrics.gauge("bytes").update(self.total())
        self._metrics.gauge("saturation").update(self.saturation())
        return freed

    def reclaim_pass(self) -> int:
        """ONE forced eviction rotation regardless of the tracked total:
        a device-reported OOM (`RESOURCE_EXHAUSTED`) means the chip is out
        of memory even if the host-side ledger is under budget (fragmentation,
        untracked scratch, another process), so the compute-fault guard
        frees one LRU entry per tenant before its single dispatch retry.
        Returns bytes freed. Same locking contract as reclaim()."""
        with self._lock:
            names = list(self._evictors)
            if not names:
                return 0
            start = self._rotation % len(names)
            self._rotation += 1
            evictors = [(n, self._evictors[n])
                        for n in names[start:] + names[:start]]
        freed = 0
        for _name, evict in evictors:
            try:
                freed += max(0, int(evict()))
            except Exception:  # noqa: BLE001 — one tenant's failure
                pass               # must not wedge the OOM retry
        self._metrics.gauge("bytes").update(self.total())
        self._metrics.gauge("saturation").update(self.saturation())
        return freed


def shared_budget() -> HBMBudget:
    """The budget of the calling thread's scope (parallel/scope.py): the
    process-wide one, or that of a service that was given devices of its
    own (`M3_TPU_HBM_BUDGET_BYTES` each, default 2GiB). First use wires
    `pressure()` into the process HealthTracker as the memory-pressure
    probe beside the admission gates' depth probes."""
    from ..parallel import scope as dscope

    return dscope.current().owned("hbm", _make_budget)


def _make_budget(sc) -> HBMBudget:
    budget = HBMBudget(int(os.environ.get(
        "M3_TPU_HBM_BUDGET_BYTES", str(DEFAULT_BUDGET_BYTES))))
    from .health import TRACKER

    TRACKER.register("hbm_pressure" + ("." + sc.name if sc.name else ""),
                     budget.pressure)
    return budget
