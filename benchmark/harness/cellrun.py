"""One run of one cell: start the load generator, boot the server, load
and warm (set-up), measure for --seconds, check answers against the
plain reference, reduce to the cell's metrics.

Every metric is a reader file found by name (`end_to_end/<name>.py`,
`layer_metrics/<name>.py`); a reader gets the Measurement below and
returns a number, or None when it finds nothing to read."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import spec

LOADGEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "loadgen.py")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def say(msg: str):
    print(f"[bench t+{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


@dataclasses.dataclass
class Measurement:
    """What the readers read."""

    cell: spec.Cell
    seconds: float
    proc_start_ns: int
    window: tuple = (0, 0)              # (t0, t1) perf_counter_ns
    rec: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    counters0: dict = dataclasses.field(default_factory=dict)
    counters1: dict = dataclasses.field(default_factory=dict)
    span_trees: List[dict] = dataclasses.field(default_factory=list)
    gc_events: List[tuple] = dataclasses.field(default_factory=list)
    ticks: List[tuple] = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    setup: dict = dataclasses.field(default_factory=dict)
    stored_bytes: int = 0
    trace: Optional[object] = None      # trace_reduce.Trace
    device_kind: str = ""
    peak_bytes: List[int] = dataclasses.field(default_factory=list)
    request_timeout_s: float = 0.0
    keep: List[int] = dataclasses.field(default_factory=list)
    t_end: int = 0                      # when the last answer was in

    def trace_span(self):
        """The traced window on the trace's clock: first due request to
        the last answer."""
        return (self.trace.to_trace_ns(self.window[0]),
                self.trace.to_trace_ns(max(self.t_end, self.window[1])))

    def moved(self, key: str) -> float:
        return self.counters1.get(key, 0) - self.counters0.get(key, 0)


class Child:
    """The load generator's pipe."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, LOADGEN], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, bufsize=1)

    def send(self, **msg):
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def recv(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError("the load generator died "
                               f"(exit {self.proc.poll()})")
        reply = json.loads(line)
        if reply.get("jax_imported") or reply.get("program_imported"):
            raise RuntimeError("the load generator imported JAX or the program")
        if "error" in reply:
            raise RuntimeError(f"load generator: {reply['error']}")
        return reply

    def call(self, **msg) -> dict:
        self.send(**msg)
        return self.recv()

    def close(self):
        try:
            if self.proc.poll() is None:
                self.send(op="exit")
                self.proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            pass
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait()
            for f in (self.proc.stdin, self.proc.stdout):
                try:
                    f.close()
                except OSError:
                    pass


def require_chips(chips: int):
    """(platform, kind, count) or NoChip: there is no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"JAX reports {len(devs)} x {devs[0].platform} "
                     f"({devs[0].device_kind}); the cell asks for {chips} TPU "
                     "chip(s)")
    return devs


# ----------------------------------------------------------------------- run


class CellRun:
    """One process's life: set-up once, then one window (a run) or several
    (the rate sweep of benchmark/tools)."""

    def __init__(self, cell: spec.Cell, seed: int, proc_start_ns: int,
                 trace: bool = False, need_chip: bool = True):
        self.cell, self.seed, self.trace = cell, seed, trace
        self.proc_start_ns = proc_start_ns
        self.child = Child()        # before this process touches JAX
        self.server = None
        self.workdir = tempfile.mkdtemp(prefix="m3bench_")
        self.tracer = None
        try:
            import jax

            from m3_tpu.utils import compile_cache

            cache_dir = compile_cache.configure()
            self.devs = require_chips(cell.chips) if need_chip \
                else jax.devices()
            say(f"platform={self.devs[0].platform} "
                f"kind={self.devs[0].device_kind} count={len(self.devs)} "
                f"compile_cache={cache_dir}")
        except BaseException:
            self.close()
            raise

    def setup(self, seconds: float) -> dict:
        """Boot, load from the seed, warm every shape the traffic uses."""
        from . import server as server_mod

        if self.trace:
            from . import spans

            self.tracer = spans.install()
        self.server = server_mod.Server(self.cell, self.seed, self.workdir)
        self._init_child(self.cell, seconds, wait=False)
        setup = self.server.load(say)
        setup["stored_bytes"] = self.server.data_dir_bytes()
        say(f"loaded: {setup}")
        self.child.recv()
        warm = self.child.call(op="warm")
        if not warm["ok"]:
            raise RuntimeError(f"warm-up requests failed: {warm['errors']}")
        log = self.server.compile_log
        say(f"warm; {len(log.ended_ns)} compiles, {log.seconds:.1f}s, in set-up")
        self.setup_facts = setup
        return setup

    def _init_child(self, cell: spec.Cell, seconds: float, wait: bool = True,
                    draw_seed: Optional[int] = None):
        self.child.send(op="init", cell=cell.to_wire(),
                        seed=self.seed if draw_seed is None else draw_seed,
                        seconds=seconds, base=self.server.base,
                        clock_file=self.server.clock_file)
        if wait:
            self.child.recv()

    def window(self, seconds: float,
               traffic_overrides: Optional[dict] = None,
               draw_seed: Optional[int] = None) -> Measurement:
        """One measured window. The overrides and `draw_seed` (other
        request draws over the same data) serve benchmark/tools alone;
        such a window cannot be checked."""
        import jax

        from . import server as server_mod

        cell, server = self.cell, self.server
        if traffic_overrides:
            cell = dataclasses.replace(
                cell, traffic=dict(cell.traffic, **traffic_overrides))
            self._init_child(cell, seconds, draw_seed=draw_seed)
        m = Measurement(cell, seconds, self.proc_start_ns,
                        device_kind=self.devs[0].device_kind,
                        request_timeout_s=float(
                            cell.traffic["request_timeout_s"]),
                        setup=self.setup_facts,
                        stored_bytes=self.setup_facts["stored_bytes"])
        m.keep = spec.load_part("traffic_kinds", cell.traffic["kind"]
                                ).keep_indices(cell, self.seed, seconds)
        trace_dir = os.path.join(self.workdir, "trace")
        m.counters0 = server_mod.counters()
        if self.trace:
            from . import trace_reduce

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(
                    trace_reduce.SYNC, t=str(time.perf_counter_ns())):
                time.sleep(0.001)
        # the window's first instant, on the clock both processes share:
        # the generator starts there, and the mediator's cadence counts
        # from there
        t0 = time.perf_counter_ns() + 200_000_000
        stop_ticker = None
        if cell.traffic.get("mediator_tick_s"):
            stop_ticker = server.start_ticker(
                float(cell.traffic["mediator_tick_s"]), t0)
        try:
            rec = self.child.call(op="run", keep=m.keep, t0=t0,
                                  trace=int(self.trace))
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            if stop_ticker is not None:
                stop_ticker()
        m.t_end = time.perf_counter_ns()
        m.counters1 = server_mod.counters()
        m.window = (int(rec.pop("t0")), int(rec.pop("t1")))
        m.rec = {k: np.asarray(v) for k, v in rec.items()
                 if isinstance(v, list)}
        m.gc_events = list(server.gc_log.events)
        m.ticks = list(server.ticks)
        m.compiles_in_window = server.compile_log.between(m.window[0], m.t_end)
        m.peak_bytes = [int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in self.devs]
        if self.trace:
            from . import spans, trace_reduce

            m.span_trees = spans.collect(self.tracer, m.window[0], m.t_end)
            m.trace = trace_reduce.Trace(trace_reduce.newest_xplane(trace_dir))
            say(f"trace: {m.trace.summary()}")
        say(f"window closed: {len(next(iter(m.rec.values()), []))} records")
        # what JAX traced, lowered or fetched inside the window, though
        # nothing compiled: a shape the warm-up did not meet
        by_event: Dict[str, list] = {}
        for t, event, secs in server.compile_log.events:
            if m.window[0] <= t <= m.t_end:
                by_event.setdefault(event, []).append(secs)
        for event, secs in sorted(by_event.items()):
            say(f"  jax in the window: {event} x{len(secs)}, "
                f"{sum(secs):.3f}s, longest {max(secs):.3f}s")
        if "cls" in m.rec:
            lat = (m.rec["done"] - m.rec["due"]) / 1e6
            for c, cls in enumerate(cell.classes):
                mine = lat[m.rec["cls"] == c]
                if len(mine):
                    say(f"  {cls['name']}: {len(mine)} requests, median "
                        f"{np.median(mine):.1f} ms, max {mine.max():.1f} ms")
        # a stall of the host shows as one long request, with a queue behind it
        took = (m.rec["done"] - m.rec["sent"]) / 1e6
        for j in np.argsort(took)[::-1][:3]:
            say(f"  slowest: request {int(m.rec['i'][j])} sent at "
                f"t0+{(m.rec['sent'][j] - m.window[0]) / 1e9:.2f}s took "
                f"{took[j]:.1f} ms")
        return m

    def check(self, m: Measurement, control: Optional[str] = None):
        """(checks, attempted, failed): each check a number beside its
        limit, from the files the traffic file lists (`checks`; absent,
        its kind's own list) under benchmark/checks/. `control` names the
        control that the check it belongs to puts in the program's place."""
        checks, failed = [], 0
        for name in m.cell.checks:
            rows, bad = spec.load_part("checks", name).check(self, m, control)
            checks += rows
            failed += bad
        checks.append(("compiles_in_window", m.compiles_in_window, 0))
        for e in self.child.call(op="errors")["errors"][:3]:
            say(f"request error: {e}")
        return checks, len(m.rec["status"]), failed

    def result(self, m: Measurement, checks, attempted: int,
               failed: int) -> dict:
        correct = all(value <= limit for _name, value, limit in checks)
        metrics = {}
        wanted = m.cell.per_layer if self.trace else m.cell.end_to_end
        kind = "layer_metrics" if self.trace else "end_to_end"
        for decl in wanted:
            value = spec.load_reader(kind, decl["name"])(m)
            if value is not None:
                metrics[decl["name"]] = {"value": float(value),
                                         "unit": decl["unit"]}
        devs = self.devs
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs),
                  "memory_peak_bytes": max(m.peak_bytes, default=0)}
        result = {"correct": bool(correct), "attempted": int(attempted),
                  "failed": int(failed), "metrics": metrics, "device": device}
        if self.trace:
            from . import breakdown

            lo, hi = m.trace_span()
            device["busy_s"] = m.trace.busy_s(lo, hi)
            device["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = breakdown.breakdown(m, lo, hi)
        # each number compared beside its limit, last in the line
        result["checks"] = {name: [float(value), float(limit)]
                            for name, value, limit in checks}
        return result

    def close(self):
        self.child.close()
        if self.server is not None:
            try:
                self.server.close()
            except Exception as e:  # noqa: BLE001 - teardown must not mask the run's error
                say(f"server close: {e!r}")
            self.server = None
        import shutil

        shutil.rmtree(self.workdir, ignore_errors=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             proc_start_ns: int, need_chip: bool = True,
             control: Optional[str] = None) -> dict:
    """One run: the result line as a dict. `need_chip=False` and
    `control` exist for the tests under benchmark/tests alone."""
    run = CellRun(cell, seed, proc_start_ns, trace, need_chip)
    try:
        run.setup(seconds)
        m = run.window(seconds)
        return run.result(m, *run.check(m, control))
    finally:
        run.close()
