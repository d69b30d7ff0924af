"""Device scope: the devices one service owns.

Four things are held once per scope (`owned`): the flush mesh
(parallel/ingest.py), the query mesh (query/executor.py), the device
block cache (storage/block_cache.py) and the HBM budget (utils/hbm.py).
A service process owns every attached device, so its one scope is
DEFAULT. Where several services share one process — three dbnodes and a
coordinator on a four-chip host, under a benchmark or a test that owns
the chips — each is given a scope of its own (`devices` in its
configuration).

A thread works for a service while it is inside `with scope:` — the
node's RPC handler threads, its mediator's tick, the coordinator's HTTP
handler threads. Inside, `current()` is that scope, and JAX's default
device is the scope's first, so host arrays entering a jitted call land
there. Outside any scope `current()` is DEFAULT: every attached device,
one of everything."""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Dict, Optional, Sequence, Tuple

_LOCAL = threading.local()


class DeviceScope:
    """`indices`: positions in `jax.devices()`; None owns all of them."""

    def __init__(self, indices: Optional[Sequence[int]] = None,
                 name: str = ""):
        self.indices = None if indices is None else tuple(
            int(i) for i in indices)
        self.name = name
        self._owned: Dict[str, object] = {}
        self._lock = threading.RLock()

    @property
    def devices(self) -> Tuple:
        import jax

        devs = jax.devices()
        if self.indices is None:
            return tuple(devs)
        return tuple(devs[i] for i in self.indices)

    def owned(self, key: str, make: Callable[["DeviceScope"], object]):
        """This scope's one `key`, made on first use, under the scope's
        lock (re-entrant: a block cache asks for its budget)."""
        try:
            return self._owned[key]
        except KeyError:
            pass
        with self._lock:
            if key not in self._owned:
                self._owned[key] = make(self)
            return self._owned[key]

    def put(self, key: str, value) -> None:
        """Install this scope's `key` (a test's or a smoke script's own
        block cache in the process's place)."""
        with self._lock:
            self._owned[key] = value

    def clear(self, key: str) -> None:
        """Forget this scope's `key`: the next use makes it anew."""
        with self._lock:
            self._owned.pop(key, None)

    def __enter__(self):
        stack = _LOCAL.__dict__.setdefault("stack", [])
        ctx = None
        if self.indices is not None:
            import jax

            ctx = jax.default_device(self.devices[0])
            ctx.__enter__()
        stack.append((self, ctx))
        return self

    def __exit__(self, *exc):
        _scope, ctx = _LOCAL.stack.pop()
        if ctx is not None:
            ctx.__exit__(*exc)
        return False

    def __repr__(self):
        return f"DeviceScope({self.name or 'default'}, {self.indices})"


DEFAULT = DeviceScope()


def current() -> DeviceScope:
    stack = getattr(_LOCAL, "stack", None)
    return stack[-1][0] if stack else DEFAULT


def device_tag() -> str:
    """The calling thread's devices as a span tag: their ids, "1" or
    "0,1,2,3"."""
    return current().owned(
        "tag", lambda sc: ",".join(str(d.id) for d in sc.devices))


def from_config(devices: Optional[Sequence[int]], name: str
                ) -> Optional[DeviceScope]:
    """A service's scope from its configuration's `devices` key; None
    (the key absent or empty) when it owns every attached device."""
    return DeviceScope(devices, name) if devices else None


def entered(scope: Optional[DeviceScope]):
    """`with entered(db.scope):` — a no-op for a service with no scope."""
    return scope if scope is not None else _NO_SCOPE


_NO_SCOPE = contextlib.nullcontext()
