"""Query placement: device vs host, per interpreter evaluation.

An interpreter-route range function or large grouped reduce can run its
jitted kernels on the default accelerator or on the CPU backend of the
same process (the same XLA kernels compiled for the host). This module
picks between them from a cost model:

    host_cost  = cells / host_rate
    accel_cost = rtt + result_bytes / d2h_bw + cells / accel_rate

Every term is a measurement of THIS process: d2h_bw and rtt from a
periodic 1MB round trip on the default device (refreshed every
PROBE_REFRESH_S), host_rate / accel_rate as EWMAs of observed
evaluations on each side. Until both rates have been observed the
evaluation stays on the accelerator: nothing leaves the chip on an
assumed rate. Queries on the compiled whole-plan route
(parallel/compile.py) never come through here.

Whether this module survives at all is ROADMAP C2's decision, to be
taken from an on-chip measurement of forced-host vs forced-device.

Env: M3_TPU_QUERY_PLACEMENT = auto (default) | device | host.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

import numpy as np

PROBE_REFRESH_S = float(os.environ.get("M3_TPU_PLACEMENT_PROBE_S", "60"))
_PROBE_BYTES = 1 << 20


def _ewma(old: Optional[float], new: float, alpha: float = 0.3) -> float:
    return new if old is None else (1 - alpha) * old + alpha * new


class QueryPlacement:
    """Per-engine placement chooser + online cost model."""

    def __init__(self):
        self._lock = threading.Lock()
        self._mode = os.environ.get("M3_TPU_QUERY_PLACEMENT", "auto")
        self._host_rate: Optional[float] = None
        self._accel_rate: Optional[float] = None
        self._d2h_bw: Optional[float] = None   # bytes/s
        self._rtt: Optional[float] = None      # seconds
        self._probed_at: Optional[float] = None
        self._probe_fn = None
        self._cpu_device = None
        self._cpu_checked = False

    # -- devices -----------------------------------------------------------

    def _host_device(self):
        """The CPU backend device, or None when unavailable / already the
        default (JAX_PLATFORMS=cpu runs have nothing to place)."""
        if not self._cpu_checked:
            self._cpu_checked = True
            import jax

            try:
                if jax.default_backend() != "cpu":
                    self._cpu_device = jax.local_devices(backend="cpu")[0]
            except Exception:  # no cpu platform registered
                self._cpu_device = None
        return self._cpu_device

    # -- link probe --------------------------------------------------------

    def _claim_probe(self, now: float) -> bool:
        """Freshness guard, check-and-set under the lock: concurrent first
        queries must not each fire a 1MB probe and share the transfer path
        (each would measure ~bw/N and seed the EWMA low). None (never
        probed) always probes — a 0.0 sentinel would compare against raw
        monotonic time and skip every probe for the first PROBE_REFRESH_S
        after boot (CLOCK_MONOTONIC is uptime on Linux)."""
        with self._lock:
            if (self._probed_at is not None
                    and now - self._probed_at < PROBE_REFRESH_S):
                return False
            self._probed_at = now
            return True

    def _probe_link(self) -> None:
        """Measure D2H bandwidth + dispatch RTT of the default accelerator
        with a 1MB round trip. Serialized; refreshed every PROBE_REFRESH_S."""
        import jax
        import jax.numpy as jnp

        now = time.monotonic()
        if not self._claim_probe(now):
            return
        try:
            if self._probe_fn is None:
                # Jitted once per instance: a fresh lambda each probe
                # would re-pay the XLA compile every refresh (jit caches
                # by function identity).
                self._probe_fn = jax.jit(lambda x: x + 1)
            f = self._probe_fn
            tiny = jnp.arange(8)
            # Warm dispatch first: the initial call pays XLA compile +
            # backend warmup and would poison the RTT EWMA for the whole
            # refresh horizon — time the SECOND round trip, which is
            # pure dispatch + D2H.
            np.asarray(f(tiny))
            t0 = time.perf_counter()
            np.asarray(f(tiny))
            rtt = time.perf_counter() - t0
            # DELIBERATE raw put: a fixed 1MB link-bandwidth probe,
            # serialized and immediately fetched back — not block traffic.
            buf = jax.device_put(  # m3lint: disable=unbudgeted-device-put
                np.zeros(_PROBE_BYTES // 4, dtype=np.float32))
            jax.block_until_ready(buf)
            t0 = time.perf_counter()
            np.asarray(buf)
            dt = max(time.perf_counter() - t0, 1e-6)
            with self._lock:
                self._rtt = _ewma(self._rtt, rtt)
                self._d2h_bw = _ewma(self._d2h_bw, _PROBE_BYTES / dt)
        except Exception:
            pass  # a failed probe leaves the prior model in place

    # -- decision ----------------------------------------------------------

    def choose(self, cells: int, result_bytes: int):
        """Device to place this evaluation on: None = default accelerator,
        or the CPU backend device for host evaluation."""
        if self._mode == "device":
            return None
        host_dev = self._host_device()
        if host_dev is None:
            return None
        if self._mode == "host":
            return host_dev
        self._probe_link()
        with self._lock:
            host_rate, accel_rate = self._host_rate, self._accel_rate
            bw, rtt = self._d2h_bw, self._rtt
        if None in (host_rate, accel_rate, bw, rtt):
            # A term of the model is still unmeasured: stay on the
            # accelerator rather than leave it on an assumption.
            return None
        host_cost = cells / host_rate
        accel_cost = rtt + result_bytes / bw + cells / accel_rate
        return host_dev if host_cost < accel_cost else None

    # -- model updates -----------------------------------------------------

    def observe(self, device, cells: int, result_bytes: int,
                seconds: float) -> None:
        """Fold an observed evaluation (dispatch -> result on host) back
        into the rate model for the path that served it."""
        if seconds <= 0 or cells <= 0:
            return
        with self._lock:
            if device is not None:  # host-placed
                self._host_rate = _ewma(self._host_rate, cells / seconds)
            else:
                bw = self._d2h_bw
                transfer = (result_bytes / bw) if bw else 0.0
                if transfer >= 0.8 * seconds:
                    # Modeled transfer swallows (or exceeds) the whole
                    # observation — the decomposition is unreliable (a
                    # stale bw would clamp compute to ~0 and inject an
                    # absurd rate sample). Wait for the probe to catch up
                    # instead.
                    return
                compute = max(seconds - transfer - (self._rtt or 0.0), 1e-5)
                self._accel_rate = _ewma(self._accel_rate, cells / compute)

    def snapshot(self) -> dict:
        """Observability: /debug/vars, chip_smoke.py, bench extra."""
        with self._lock:
            return {
                "mode": self._mode,
                "host_rate_cells_s": self._host_rate,
                "accel_rate_cells_s": self._accel_rate,
                "d2h_bw_mb_s": (self._d2h_bw / 2**20
                                if self._d2h_bw else None),
                "rtt_ms": (self._rtt * 1e3 if self._rtt else None),
            }
