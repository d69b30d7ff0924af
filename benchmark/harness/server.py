"""The system under test, booted as its users boot it, inside the
benchmark's own process (a chip belongs to one process), under an
injected clock. What is booted and how it is filled are files found by
name (`deployments/`, `setups/`); the counters, the compile log and the
served-path verdict around it are copied from chip_smoke.py, not
imported: later PRs may change the program, and not the yardstick.

From the program this takes the system itself, its spans
(utils/tracing), its counters (utils/instrument.ROOT) and its guard
snapshot; nothing else."""

from __future__ import annotations

import gc
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from . import datagen, spec

S = datagen.S
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
FAULT_COUNTERS = ("faults", "trips", "trip_open", "quarantined",
                  "oom_reclaims", "fallback")


class CompileLog:
    """Counts XLA backend compiles through jax.monitoring, each with the
    monotonic time it ended at, so the window's can be told apart. Every
    other timed event of JAX's (a trace, a lowering, a fetch from the
    persistent cache) is kept too: (ended_ns, event, seconds)."""

    def __init__(self):
        import jax

        self.ended_ns: List[int] = []
        self.seconds = 0.0
        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event: str, secs: float, **_kw):
        self.events.append((time.perf_counter_ns(), event, secs))
        if event == BACKEND_COMPILE_EVENT:
            self.ended_ns.append(self.events[-1][0])
            self.seconds += secs

    def between(self, t0: int, t1: int) -> int:
        return sum(1 for t in self.ended_ns if t0 <= t <= t1)


class GcLog:
    """Collections of the server process, by generation, through
    gc.callbacks. The harness only watches: it does not freeze, tune or
    disable the collector."""

    def __init__(self):
        self.events: List[tuple] = []   # (start_ns, end_ns, generation)
        self._start = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict):
        if phase == "start":
            self._start = time.perf_counter_ns()
        else:
            self.events.append((self._start, time.perf_counter_ns(),
                                info["generation"]))

    def close(self):
        gc.callbacks.remove(self._cb)


def counters() -> Dict[str, float]:
    from m3_tpu.utils.instrument import ROOT

    return {k: v for k, v in ROOT.snapshot().items()
            if isinstance(v, (int, float))}


class Server:
    """Whatever `benchmark/deployments/<deployment_kind>.py` booted, with
    the injected clock, the logs and the counters around it."""

    def __init__(self, cell, seed: int, workdir: str):
        self.cell, self.seed, self.workdir = cell, seed, workdir
        self.cfg = cell.config
        self.clock_file = os.path.join(workdir, "clock.i64")
        np.zeros(1, np.int64).tofile(self.clock_file)
        self.clock = np.memmap(self.clock_file, np.int64, "r+", shape=(1,))
        self.clock[0] = datagen.T0
        self.compile_log = CompileLog()
        self.gc_log = GcLog()
        self.counters0 = counters()
        clock = self.clock
        self.handle = spec.load_part("deployments", cell.deployment).boot(
            cell, workdir, lambda: int(clock[0]))
        self.base = self.handle.base
        # mediator ticks: (asked for by the cadence, began, ended), ns
        self.ticks: List[tuple] = []
        from m3_tpu.storage.mediator import Mediator

        self.mediator = Mediator(self.handle.db, self.handle.persist)
        self.vals: Optional[np.ndarray] = None
        self.labels: List[dict] = []

    # ------------------------------------------------------------------- load

    def tick(self, asked_ns: Optional[int] = None) -> dict:
        t0 = time.perf_counter_ns()
        stats = self.mediator.run_once()
        self.ticks.append((asked_ns or t0, t0, time.perf_counter_ns()))
        return stats

    def replay_scrapes(self, write_scrape, say):
        """The set-up's `load_steps` scrapes, each handed to
        `write_scrape(k, ts_ns, values)`: the clock following the data,
        the mediator ticking where the traffic file says a live node's
        would have, then the clock moved on so every full block that is
        due seals and flushes."""
        setup = self.cell.traffic["setup"]
        cadence = int(self.cfg["cadence_s"]) * S
        tick_at = set(setup.get("tick_at_steps", []))
        for k in range(int(setup["load_steps"])):
            ts = int(datagen.step_ts(self.cfg, k))
            self.clock[0] = ts + cadence
            write_scrape(k, ts, self.vals[:, k].astype(np.float64))
            if k in tick_at:
                say(f"mediator at step {k}: {self.tick()}")
        self.clock[0] += int(setup["final_clock_advance_s"]) * S
        say(f"mediator at end of load: {self.tick()}")

    def load(self, say) -> dict:
        """The set-up's fixed load from the seed, by
        `benchmark/setups/<setup.via>.py`; then what every set-up must
        have left behind: the blocks the traffic file expects, sealed and
        flushed."""
        setup = self.cell.traffic["setup"]
        steps = int(setup["load_steps"])
        extra = int(self.cell.traffic.get("max_window_steps", 0))
        self.labels = datagen.series_labels(self.cfg, self.seed)
        self.vals = datagen.walk(self.cfg, self.seed, steps + extra)
        t_load = time.perf_counter()
        facts = spec.load_part("setups", self.cell.setup_via).load(self, say)
        name = self.handle.namespace
        ns = self.handle.db.namespace(name)
        sealed = sorted({bs for sh in ns.shards.values() for bs in sh.blocks})
        filesets = sum(len(self.handle.persist.list_filesets(name, sid))
                       for sid in ns.shards)
        want = int(setup["sealed_blocks"])
        if len(sealed) != want or filesets < want * len(ns.shards):
            raise RuntimeError(
                f"set-up sealed {len(sealed)} block starts and flushed "
                f"{filesets} filesets; the traffic file expects {want} blocks "
                f"of {len(ns.shards)} shards")
        return dict(facts, sealed_blocks=len(sealed), filesets=filesets,
                    load_s=time.perf_counter() - t_load)

    def data_dir_bytes(self) -> int:
        total = 0
        for dp, _dirs, files in os.walk(os.path.join(self.workdir, "data")):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(dp, f))
                except OSError:
                    pass
        return total

    # ------------------------------------------------------------- the window

    def start_ticker(self, interval_s: float, t0_ns: int):
        """The mediator on its cadence in real time, for the window: the
        first tick `interval_s` after the window's first instant, each
        later one `interval_s` after the last has ended (the program's
        own `Mediator.start` loop)."""
        stop = threading.Event()

        def loop():
            asked = t0_ns + int(interval_s * 1e9)
            while not stop.wait(max(0.0, (asked - time.perf_counter_ns())
                                    / 1e9)):
                self.tick(asked)
                asked = time.perf_counter_ns() + int(interval_s * 1e9)

        th = threading.Thread(target=loop, daemon=True)
        th.start()

        def stopper():
            stop.set()
            th.join()

        return stopper

    # ---------------------------------------------------------------- verdict

    def verdict(self, c0: dict, c1: dict, allow_fallback=("below-floor",)):
        """chip_smoke.phase_served_verdict, as numbers beside limits of 0:
        nothing on the path may have degraded or run somewhere else than
        the program says. `c0`/`c1` bracket what is judged."""
        from m3_tpu.ops import pallas_codec
        from m3_tpu.parallel import guard
        from m3_tpu.utils import retry as uretry

        c = {k: v - c0.get(k, 0) for k, v in c1.items()}
        faults = {k: v for k, v in c.items()
                  if v and k.startswith("telemetry.compute.")
                  and any(s in k for s in FAULT_COUNTERS)}
        runtime = {k: v for k, v in c.items()
                   if v and k.startswith("telemetry.plan_fallback.count")
                   and "scope=runtime" in k
                   and not any("reason=%s," % a in k for a in allow_fallback)}
        not_closed = {r: s["state"] for r, s in guard.debug_snapshot().items()
                      if s["state"] != uretry.Breaker.CLOSED}
        other = "xla_" if pallas_codec.enabled() else "pallas_"
        off_gate = {k: v for k, v in c.items()
                    if v and k.startswith("telemetry.codec." + other)
                    and k.rsplit("_", 1)[-1] in ("encode", "decode", "hash")}
        host_placed = c.get("query.placement.host", 0)
        return [
            ("compute_fault_counters_moved", len(faults), 0, faults),
            ("runtime_plan_fallbacks_not_allowed", len(runtime), 0, runtime),
            ("breakers_not_closed", len(not_closed), 0, not_closed),
            ("codec_dispatches_off_gate", len(off_gate), 0, off_gate),
            ("evaluations_placed_on_host_backend", host_placed, 0, {}),
        ]

    def close(self):
        self.gc_log.close()
        self.handle.close()
