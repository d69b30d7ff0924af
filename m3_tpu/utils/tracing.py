"""Tracing + profiling (reference: the x/instrument + net/http/pprof
surface the reference exposes on every service — opentracing spans via
instrument.Options tracing, goroutine/profile dumps on /debug/pprof).

Spans: context-manager tree with wall-clock timings, thread-local current
span, trace/span ids, and a ring buffer of recent finished roots for
/debug/traces. Cross-process propagation rides request frames as a
compact `"tr"` context (rpc/wire.py TRACE_KEY) exactly like the deadline
`"d"` and priority `"pri"` hints; the server side opens a remote-parented
span and, on success, returns its finished tree in the response frame so
the CLIENT grafts it as a child — one request yields ONE span tree even
when its storage work ran three processes away (the in-process analog of
jaeger's collector assembling spans by trace id; DIVERGENCES.md).

Sampling: root spans are sampled at `M3_TPU_TRACE_SAMPLE` (default 1.0);
an unsampled root is the shared no-op span, children of no span are
no-ops too (`child_span`), and unsampled requests never attach a wire
context — so the hot path's cost when tracing is off is one thread-local
read per call site (PERF.md section 6, PR 24, has what a traced and an
untraced run cost on the chip's host; `benchmark/run.py --trace 0|1`
reads both again).

Detail: a root that was ASKED for — `span_from` with a context (the
`X-M3-Trace` header, a wire `"tr"` field) — or a background root
(`background_span`: the mediator's tick) is `detailed`, and so is every
span below it. Only detailed spans read their thread's CPU time
(`tags["cpu_ns"]`: wall minus CPU is time spent waiting — for the GIL, a
lock, a socket, the device) and only under them do `phase` and the
per-series / per-sample accumulators (`detail()`, then `add_cost`) run.
A head-sampled root (`span`) is not detailed: an untraced request opens
the spans it always opened and pays nothing more.

Phases: `phase(name)` times a stretch of the CURRENT span into its
`costs` (`<name>_ns`, `<name>_n`) instead of opening a child span, so a
span's self time keeps its meaning; given a `stage` it also feeds the
thread's stage sink (query/explain.py's ANALYZE context) from the same
site. Spans never synchronise a device dispatch; ANALYZE does.

Slow queries: a bounded ring of {name, duration, typed reason, costs}
entries (`SLOW_QUERIES`) — reasons are `limit-shed` (ResourceExhausted),
`deadline` (DeadlineExceeded), `cold-cache` (the span's cost tags show
block/grid-cache misses), or plain `slow` past the threshold
(`M3_TPU_SLOW_QUERY_MS`, default 500).

Profiling: a sampling profiler (the statistical CPU profile analog of
/debug/pprof/profile) that samples every thread's Python stack at a fixed
interval and aggregates flattened stack counts, plus an all-threads stack
dump (the goroutine-dump analog of /debug/pprof/goroutine?debug=2).
`PROFILER` runs the sampling loop on ONE shared background thread with a
hard seconds cap (`M3_TPU_PROFILE_MAX_S`) so a /debug/pprof/profile
request can neither stall a serving thread past its deadline nor stack N
concurrent sampling loops.

Runtime: while somebody asks for traces, the tracer runs ONE probe thread
(`RuntimeProbe`, the analog of the Go runtime readings — goroutines, GC,
scheduler — the reference's services report through their `instrument`
scope) that accounts for the one GIL the process's Python threads share:
how late each of its wakes got to run (what a handler thread whose
socket turned readable pays before its first bytecode), CPU and
run-queue time by thread role, and every stall with the thread that
caused it. It starts on an asked-for root and ends itself; with tracing
off no thread exists and nothing is read."""

from __future__ import annotations

import collections
import contextlib
import gc
import os
import random as _random
import resource
import sys
import threading
import time
import traceback
from typing import Dict, List, NamedTuple, Optional

from . import instrument

# ---------------------------------------------------------------- spans

# The spans' clock, for the sites that time phases by hand: CLOCK_MONOTONIC,
# which the benchmark's load generator and profiler annotation share.
clock_ns = time.perf_counter_ns


class SpanContext(NamedTuple):
    """Wire-portable span identity. Only SAMPLED spans ever produce one
    (context presence implies sampled), so the two ids are the whole
    context — the compact `"tr"` frame field."""

    trace_id: int
    span_id: int

    def to_wire(self) -> dict:
        return {"t": self.trace_id, "s": self.span_id}

    @classmethod
    def from_wire(cls, d) -> Optional["SpanContext"]:
        """Parse a frame's trace field; malformed metadata is treated as
        absent — tracing must never be the thing that kills an
        otherwise-valid request (same contract as deadline_from_frame)."""
        if not isinstance(d, dict):
            return None
        t, s = d.get("t"), d.get("s")
        if isinstance(t, bool) or isinstance(s, bool) or \
                not isinstance(t, int) or not isinstance(s, int):
            return None
        return cls(t, s)


_ID_LOCK = threading.Lock()
_ID_RNG = _random.Random()


def _new_id() -> int:
    with _ID_LOCK:
        return _ID_RNG.getrandbits(63) or 1


class Span:
    __slots__ = ("name", "tags", "start_ns", "end_ns", "children", "costs",
                 "trace_id", "span_id", "remote_parent", "detailed", "_cpu0",
                 "_tracer", "_parent")

    sampled = True  # real spans exist only when sampled

    def __init__(self, name: str, tracer: "Tracer", parent: Optional["Span"],
                 tags: Optional[dict] = None,
                 remote: Optional[SpanContext] = None,
                 start_ns: Optional[int] = None,
                 cpu_start_ns: Optional[int] = None):
        """`start_ns` (perf_counter_ns) backdates the span to a stamp
        taken before it could be opened — the accept time of a
        connection; `cpu_start_ns` is the thread's CPU clock at that
        stamp (0 for a thread born with the connection)."""
        self.name = name
        self.tags = dict(tags or {})
        self.start_ns = time.perf_counter_ns() if start_ns is None \
            else start_ns
        self.end_ns: Optional[int] = None
        self.children: List = []  # Span or grafted remote dicts
        self.costs: Dict[str, float] = {}
        if parent is not None:
            self.trace_id = parent.trace_id
        elif remote is not None:
            self.trace_id = remote.trace_id
        else:
            self.trace_id = _new_id()
        self.span_id = _new_id()
        self.remote_parent = remote.span_id if remote is not None else None
        self.detailed = parent.detailed if parent is not None \
            else remote is not None
        self._cpu0 = cpu_start_ns
        self._tracer = tracer
        self._parent = parent

    @property
    def duration_ns(self) -> int:
        return (self.end_ns or time.perf_counter_ns()) - self.start_ns

    def set_tag(self, key: str, value) -> "Span":
        self.tags[key] = value
        return self

    def add_cost(self, kind: str, n: float = 1) -> "Span":
        """Accumulate one QueryScope-style cost tally onto this span
        (docs_matched / bytes_read / block_cache_hit / ...)."""
        self.costs[kind] = self.costs.get(kind, 0) + n
        return self

    def attach(self, child: dict):
        """Graft a REMOTE span tree (a finished to_dict from another
        process, returned in a response frame) as a child. list.append is
        GIL-atomic, so fanout worker threads may attach concurrently."""
        self.children.append(child)

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        if self.detailed and self._cpu0 is None:
            self._cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end_ns = time.perf_counter_ns()
        if self.detailed:
            # in tags, not costs: collect_costs sums costs over a
            # subtree, and nested CPU times would count twice
            self.tags["cpu_ns"] = time.thread_time_ns() - self._cpu0
        if exc_type is not None:
            self.tags["error"] = repr(exc)
        self._tracer._pop(self)
        return False

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "start_ns": self.start_ns,
            "duration_us": round(self.duration_ns / 1000, 1),
            **({"cpu_us": round(self.tags["cpu_ns"] / 1000, 1)}
               if "cpu_ns" in self.tags else {}),
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            **({"remote_parent": self.remote_parent}
               if self.remote_parent is not None else {}),
            **({"tags": self.tags} if self.tags else {}),
            **({"costs": self.costs} if self.costs else {}),
            **({"children": [c if isinstance(c, dict) else c.to_dict()
                             for c in self.children]}
               if self.children else {}),
        }


class _NoopSpan:
    """Shared do-nothing span for unsampled work: every mutator is a
    no-op, so hot paths hold one object test instead of branches."""

    __slots__ = ()
    sampled = False
    detailed = False
    name = ""
    tags: dict = {}
    costs: dict = {}
    children: tuple = ()
    trace_id = 0
    span_id = 0

    def set_tag(self, key, value):
        return self

    def add_cost(self, kind, n=1):
        return self

    def attach(self, child):
        pass

    def context(self) -> Optional[SpanContext]:
        return None

    @property
    def duration_ns(self) -> int:
        return 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def to_dict(self) -> dict:
        return {"name": "", "noop": True}


NOOP_SPAN = _NoopSpan()


def _env_rate() -> float:
    try:
        return min(1.0, max(0.0, float(
            os.environ.get("M3_TPU_TRACE_SAMPLE", "1"))))
    except ValueError:
        return 1.0


# --------------------------------------------------------------- runtime
#
# One GIL serves every Python thread of the process, and nothing else in
# the program measures it. While traces are asked for, the probe below
# wakes on a fixed schedule on the spans' clock and keeps three books:
#
# - each wake's LATENESS: how long after it was due the probe got to
#   run — what any thread that turns runnable (a handler whose socket
#   became readable) pays before its first bytecode. Net of the probe's
#   own run-queue time (the machine's part) it is GIL wait;
# - CPU and run-queue time BY THREAD ROLE, once a second and at every
#   stall. A thread's role is the kind of the last root span it opened
#   (`http.` request, `rpc.` rpc of a node, `mediator.tick` tick,
#   `bootstrap.` bootstrap), else what its name says (accept, fanout,
#   prep, cache-fill, aggregator, m3msg, probe, main), else
#   `python-other`; a thread of the process that is
#   no Python thread is `native` (XLA's, the TPU runtime's). A request's
#   thread lives 10-70 ms, so a root counts its thread's CPU when it
#   ends and the sample counts whatever a live thread has used past
#   that mark: every thread's CPU clock is counted once, up to a mark;
# - every STALL (a wake later than STALL_NS) with the thread whose CPU
#   clock advanced most across it.
#
# What adds up goes to counters under `runtime.` on instrument.ROOT
# (/debug/vars; the benchmark snapshots them around its window); each
# wake and each stall go to rings of the probe's own (/debug/traces,
# key `runtime`).

PROBE_PERIOD_NS = 40_000_000        # a wake, so one forced GIL hand-off
PROBE_SNAPSHOT_WAKES = 3            # Python threads' CPU clocks, every 120 ms
PROBE_SAMPLE_NS = 1_000_000_000     # CPU by role
STALL_NS = 100_000_000              # a wake later than this is a stall
PROBE_IDLE_EXIT_NS = 10_000_000_000  # no asked-for root for this long: end
PROBE_THREAD_NAME = "runtime-probe"

NATIVE = ("native", "")
_ROOT_ROLES = (("http.", "request"), ("rpc.", "rpc"),
               ("mediator.tick", "tick"), ("bootstrap.", "bootstrap"))
_NAME_ROLES = (("accept", "accept"), ("fanout", "fanout"),
               ("tsz-prep", "prep"), ("block-cache-fill", "cache-fill"),
               ("aggregator", "aggregator"), ("m3msg", "m3msg"),
               (PROBE_THREAD_NAME, "probe"),
               ("MainThread", "main"))
_ADDITIVE = ("process_cpu_ns", "probe.wakes", "probe.late_ns",
             "probe.wall_ns", "stalls", "stall_ns", "faults.major",
             "faults.minor", "switches.voluntary", "switches.involuntary")
_RUSAGE = (("faults.major", "ru_majflt"), ("faults.minor", "ru_minflt"),
           ("switches.voluntary", "ru_nvcsw"),
           ("switches.involuntary", "ru_nivcsw"))


def _root_role(span) -> Optional[tuple]:
    """(role, node) of the thread that opened this root, None for a kind
    of root that names no role. An rpc root carries its node (`host`)."""
    name = span.name
    for prefix, role in _ROOT_ROLES:
        if name.startswith(prefix):
            return (role, str(span.tags.get("host", ""))
                    if role == "rpc" else "")
    return None


def _name_role(name: str) -> tuple:
    for prefix, role in _NAME_ROLES:
        if name.startswith(prefix):
            return (role, "")
    return ("python-other", "")


def _role_label(role: tuple) -> str:
    return "@".join(r for r in role if r)


def _frame_label(f) -> str:
    code = f.f_code
    return (f"{code.co_name} "
            f"({code.co_filename.rsplit('/', 1)[-1]}:{f.f_lineno})")


def _thread_cpu_ns(tid: int) -> Optional[int]:
    """A thread's CPU clock by its kernel id (Linux's per-thread clock
    id, what pthread_getcpuclockid computes — without reading a thread
    descriptor that may be gone). No GIL release; None for a dead id."""
    try:
        return time.clock_gettime_ns((~tid << 3) | 6)
    except OSError:
        return None


def _schedstat(tid: int) -> Optional[tuple]:
    """(on-CPU ns, run-queue ns) of a thread of this process, None once
    it has gone (or on a kernel without the file)."""
    try:
        with open(f"/proc/self/task/{tid}/schedstat", "rb") as f:
            cpu, runq = f.read().split()[:2]
        return int(cpu), int(runq)
    except (OSError, ValueError):
        return None


class RuntimeProbe:
    """The tracer's probe thread and its books (section comment above).
    `asked()` starts it; it ends itself PROBE_IDLE_EXIT_NS after the last
    asked-for root. Nothing here runs, and no counter exists, before the
    first `asked()`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.running = False
        self._asked_ns = 0
        # (due_ns, late_ns, the probe's run-queue ns across the wake or
        # None), ~160 s of wakes; and the stalls, as dicts
        self.wakes = collections.deque(maxlen=8192)
        self.stalls = collections.deque(maxlen=256)
        self.threads_by_role: Dict[str, int] = {}  # the newest sample's
        self._role_of: Dict[int, tuple] = {}   # kernel thread id -> role
        self._cpu_seen: Dict[int, int] = {}    # ... -> CPU ns counted
        self._runq_seen: Dict[int, int] = {}
        self._counters: Dict[tuple, instrument.Counter] = {}
        self._has_runq = False
        self._rusage = None

    # ------------------------------------------------------- the tracer's

    def asked(self):
        """Somebody asked for a trace: run, or keep running."""
        with self._lock:
            self._asked_ns = clock_ns()
            if not self.running:
                self._start()

    def root_opened(self, span):
        role = _root_role(span)
        if role is not None:
            with self._lock:
                self._role_of[threading.get_native_id()] = role

    def root_ended(self, span):
        """The root's thread, counted up to here under the root's role:
        a thread a connection is gone before any sample sees it, so a
        request's root reads its thread's run-queue time too (one file,
        after the response's last byte; an rpc handler outlives its
        roots and is left to the samples)."""
        role = _root_role(span)
        if role is None or "cpu_ns" not in span.tags:
            return      # (a root that names a role is detailed)
        cpu = span._cpu0 + span.tags["cpu_ns"]  # the thread's CPU clock
        tid = threading.get_native_id()
        runq = None
        if role[0] == "request" and self._has_runq:
            cpu, runq = _schedstat(tid) or (cpu, None)
        with self._lock:
            self._role_of[tid] = role
            self._count(tid, role, cpu, runq)

    def snapshot(self) -> dict:
        """What /debug/traces serves under `runtime`."""
        late = sorted(w[1] for w in list(self.wakes))
        return {
            "running": self.running,
            "period_ms": PROBE_PERIOD_NS / 1e6,
            "wakes": len(late),
            "late_ms": {q: round(late[min(len(late) - 1,
                                          int(len(late) * f))] / 1e6, 3)
                        for q, f in (("p50", .5), ("p95", .95), ("max", 1))}
            if late else {},
            "threads": dict(self.threads_by_role),
            "stalls": list(self.stalls),
        }

    # ------------------------------------------------------------ the books

    def _counter(self, kind: str, role: tuple = ("", "")):
        c = self._counters.get((kind, role))
        if c is None:
            tags = {k: v for k, v in zip(("role", "node"), role) if v}
            c = self._counters[kind, role] = instrument.ROOT.sub_scope(
                "runtime", **tags).counter(kind)
        return c

    def _count(self, tid: int, role: tuple, cpu: int, runq: Optional[int],
               baseline: bool = False):
        """Move a thread's marks to (cpu, runq), counting what lies past
        them under `role`. A thread without marks was born after the
        baseline (which lists every thread): all of its time counts; a
        clock below its mark is a new thread on a reused id."""
        for seen, kind, now in ((self._cpu_seen, "cpu_ns", cpu),
                                (self._runq_seen, "runq_ns", runq)):
            if now is None:
                continue
            had = seen.get(tid, 0)
            if not baseline and now != had:
                self._counter(kind, role).inc(now - had if now > had else now)
            seen[tid] = now

    def _python_threads(self) -> Dict[int, threading.Thread]:
        return {t.native_id: t for t in threading.enumerate()
                if t.native_id is not None}

    def _read_sample(self) -> tuple:
        """CPU and run-queue time of every thread of the process, and the
        process's faults and context switches. Holds no lock: a file
        read gives the GIL away."""
        py = self._python_threads()
        try:
            tids = [int(d) for d in os.listdir("/proc/self/task")]
        except (OSError, ValueError):
            tids = list(py)
        clocks = {}
        for tid in tids:
            if tid in py and self._has_runq:
                got = _schedstat(tid)
            else:
                cpu = _thread_cpu_ns(tid)
                got = None if cpu is None else (cpu, None)
            if got is not None:
                clocks[tid] = got
        return py, clocks, resource.getrusage(resource.RUSAGE_SELF)

    def _count_sample(self, py, clocks, usage, baseline: bool = False):
        """Under the lock: what `_read_sample` read, counted by role."""
        by_role: Dict[str, int] = {}
        for tid, (cpu, runq) in clocks.items():
            role = self._role_of.get(tid) or (
                _name_role(py[tid].name) if tid in py else NATIVE)
            self._count(tid, role, cpu, runq, baseline)
            label = _role_label(role)
            by_role[label] = by_role.get(label, 0) + 1
        for book in (self._role_of, self._cpu_seen, self._runq_seen):
            for tid in [t for t in book if t not in clocks]:
                del book[tid]
        if not baseline:
            for kind, field in _RUSAGE:
                self._counter(kind).inc(
                    getattr(usage, field) - getattr(self._rusage, field))
        self._rusage = usage
        self.threads_by_role = by_role

    def _sample(self):
        read = self._read_sample()
        with self._lock:
            self._count_sample(*read)

    def _python_cpu(self) -> Dict[int, int]:
        out = {}
        for tid in self._python_threads():
            cpu = _thread_cpu_ns(tid)
            if cpu is not None:
                out[tid] = cpu
        return out

    # ------------------------------------------------------------ the thread

    def _start(self):
        """Under the lock, by the thread that asked: the baseline every
        later count starts from, then the thread."""
        got = _schedstat(threading.get_native_id())
        self._has_runq = got is not None and got[0] > 0
        for kind in _ADDITIVE:
            self._counter(kind)
        self._count_sample(*self._read_sample(), baseline=True)
        self.running = True
        threading.Thread(target=self._run, name=PROBE_THREAD_NAME,
                         daemon=True).start()

    def _run(self):
        me = threading.get_native_id()
        fd = -1
        if self._has_runq:  # one read a wake: the file stays open
            fd = os.open(f"/proc/self/task/{me}/schedstat", os.O_RDONLY)
        try:
            self._loop(me, fd)
        finally:
            if fd >= 0:
                os.close(fd)

    def _loop(self, me: int, fd: int):
        def own_runq():
            return int(os.pread(fd, 64, 0).split()[1]) if fd >= 0 else None

        def books():    # Python threads' CPU clocks, the process's, and
            # the collections so far by generation: who had the CPU
            # across a stall, and whether it was the collector
            return (self._python_cpu(), time.process_time_ns(),
                    [g["collections"] for g in gc.get_stats()])

        # A wake does little: two clock reads, one file read, one ring
        # entry. What adds up is handed to the counters with each
        # sample, so the books close on one instant.
        n = late_sum = 0
        t_books = last = clock_ns()
        due = last + PROBE_PERIOD_NS
        next_sample = last + PROBE_SAMPLE_NS
        runq0 = own_runq()
        before, cpu0, gcs0 = books()
        cpu_counted = cpu0
        while True:
            time.sleep(max(0, due - clock_ns()) / 1e9)
            now = clock_ns()
            late = max(0, now - due)
            runq1 = own_runq()
            runq = None if runq1 is None else runq1 - runq0
            runq0 = runq1
            self.wakes.append((due, late, runq))
            n += 1
            late_sum += late
            stalled = late > STALL_NS
            idle = now - self._asked_ns > PROBE_IDLE_EXIT_NS
            if stalled:
                after, cpu1, gcs1 = books()
                # (a young collection runs every few hundred allocations
                # and takes microseconds: it explains no stall)
                gens = [g for g in range(1, len(gcs1)) if gcs1[g] > gcs0[g]]
                self._stall(due, now, runq, cpu1 - cpu0, before, after, me,
                            max(gens, default=None))
                before, cpu0, gcs0 = after, cpu1, gcs1
            elif n % PROBE_SNAPSHOT_WAKES == 0:
                before, cpu0, gcs0 = books()
            if idle:
                with self._lock:    # `asked` may have come in between
                    idle = now - self._asked_ns > PROBE_IDLE_EXIT_NS
                    if idle:
                        self.running = False
            if idle or stalled or now >= next_sample:
                cpu1 = time.process_time_ns()
                for kind, v in (("probe.wakes", n), ("probe.late_ns", late_sum),
                                ("probe.wall_ns", now - t_books),
                                ("process_cpu_ns", cpu1 - cpu_counted)):
                    self._counter(kind).inc(v)
                n = late_sum = 0
                t_books, cpu_counted = now, cpu1
                self._sample()
                next_sample = now + PROBE_SAMPLE_NS
                if idle:
                    return
            due += PROBE_PERIOD_NS
            now = clock_ns()
            if due <= now:      # what the probe's own work took is no lateness
                due = now + PROBE_PERIOD_NS

    def _stall(self, due: int, now: int, runq: Optional[int], cpu_ns: int,
               before: Dict[int, int], after: Dict[int, int], me: int,
               gc_generation: Optional[int]):
        """One record: who had the CPU across the late wake, by the
        Python threads' CPU clocks `before` (at most 120 ms before the
        wake was due) and `after`; `cpu_ns` is the whole process's over
        the same stretch, `gc_generation` the oldest generation past
        the youngest that the collector finished over it
        (`gc.get_stats`: no callback, so a collection costs nothing
        more while the probe runs). Σ CPU far
        below the stall's length with `runq_ns` high: the machine took
        the CPU; with it low: a thread slept in C holding the GIL."""
        threads = self._python_threads()
        cpu, tid = max(((cpu - before.get(tid, 0), tid)
                        for tid, cpu in after.items() if tid != me),
                       default=(0, 0))
        rec = {"start_ns": due, "end_ns": now, "late_ns": now - due,
               "cpu_ns": cpu_ns, "runq_ns": runq, "held_by": None}
        if cpu > 0:
            th = threads.get(tid)
            with self._lock:
                role = self._role_of.get(tid) or _name_role(
                    th.name if th is not None else "")
            frames, f = [], th is not None and \
                sys._current_frames().get(th.ident)
            while f and len(frames) < 5:
                frames.append(_frame_label(f))
                f = f.f_back
            rec["held_by"] = {"role": _role_label(role),
                              "thread": th.name if th is not None else "?",
                              "cpu_ns": cpu, "frames": frames}
        if gc_generation is not None:
            rec["gc"] = gc_generation
        self.stalls.append(rec)
        self._counter("stalls").inc()
        self._counter("stall_ns").inc(now - due)


class Tracer:
    """Per-process tracer; thread-local span stacks, bounded root history,
    head-based root sampling."""

    def __init__(self, max_traces: int = 128,
                 sample_rate: Optional[float] = None):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._recent = collections.deque(maxlen=max_traces)
        self.sample_rate = _env_rate() if sample_rate is None else sample_rate
        self.runtime = RuntimeProbe()
        # Each finished root is handed to these as it joins the ring (the
        # reference's jaeger reporter: an exporter, or a reader that must
        # not lose a root to the ring's bound). None by default.
        self.reporters: List[Callable[[Span], None]] = []

    def set_sample_rate(self, rate: float):
        self.sample_rate = min(1.0, max(0.0, float(rate)))

    def span(self, name: str, start_ns: Optional[int] = None, **tags):
        """New span: child of the current span when one is active, else a
        sampling-gated new root. Entry points (query execute, session
        calls, rpc dispatch) use this; internals use child_span.
        `start_ns` backdates it to a stamp taken before it could be
        opened (a round whose first phase decides whether it is one)."""
        parent = getattr(self._local, "current", None)
        if parent is None:
            rate = self.sample_rate
            if rate <= 0.0 or (rate < 1.0 and _random.random() >= rate):
                return NOOP_SPAN
        return Span(name, self, parent, tags, start_ns=start_ns)

    def child_span(self, name: str, start_ns: Optional[int] = None,
                   cpu_start_ns: Optional[int] = None, **tags):
        """A span ONLY when sampled work is already in flight — the
        hot-path-safe form for storage/index internals: with no active
        span (benchmarks, bare calls) the cost is one thread-local read."""
        parent = getattr(self._local, "current", None)
        if parent is None:
            return NOOP_SPAN
        return Span(name, self, parent, tags, start_ns=start_ns,
                    cpu_start_ns=cpu_start_ns)

    def span_from(self, ctx: Optional[SpanContext], name: str,
                  start_ns: Optional[int] = None,
                  cpu_start_ns: Optional[int] = None, **tags):
        """Remote-parented root for a propagated wire context (rpc
        dispatch, msg consume, kv ops, the HTTP `X-M3-Trace` header);
        NOOP when the request carried no context (the caller was
        unsampled or untraced). The caller asked for this trace, so the
        root is detailed."""
        if ctx is None:
            return NOOP_SPAN
        self.runtime.asked()
        return Span(name, self, None, tags, remote=ctx, start_ns=start_ns,
                    cpu_start_ns=cpu_start_ns)

    def background_span(self, name: str, start_ns: Optional[int] = None,
                        **tags):
        """Root of background work nobody's request waits on (the
        mediator's tick): sampling-gated like `span`, and detailed — a
        few such roots a minute can afford what a request root cannot.
        A child, and its parent's kind, when some span is active."""
        sp = self.span(name, start_ns=start_ns, **tags)
        if sp.sampled and sp._parent is None:
            sp.detailed = True
        return sp

    def current(self) -> Optional[Span]:
        return getattr(self._local, "current", None)

    @contextlib.contextmanager
    def activate(self, span):
        """Install `span` as this THREAD's current span (restoring the
        previous on exit) without opening a new one — explicit
        propagation into pool workers, where thread-local stacks don't
        follow the submitting thread."""
        prev = getattr(self._local, "current", None)
        self._local.current = span if isinstance(span, Span) else None
        try:
            yield span
        finally:
            self._local.current = prev

    def _push(self, span: Span):
        if span._parent is not None:
            span._parent.children.append(span)
        elif self.runtime.running:
            self.runtime.root_opened(span)
        self._local.current = span

    def _pop(self, span: Span):
        self._local.current = span._parent
        if span._parent is None:
            if self.runtime.running:
                self.runtime.root_ended(span)
            with self._lock:
                self._recent.append(span)
            for report in self.reporters:
                report(span)

    def recent_traces(self, trace_id: Optional[int] = None) -> List[dict]:
        with self._lock:
            roots = list(self._recent)
        out = [s.to_dict() for s in roots]
        if trace_id is not None:
            out = [d for d in out if d.get("trace_id") == trace_id]
        return out


TRACER = Tracer()  # process default, like the global opentracing tracer


def span(name: str, **tags):
    return TRACER.span(name, **tags)


def child_span(name: str, **tags):
    return TRACER.child_span(name, **tags)


def background_span(name: str, start_ns: Optional[int] = None, **tags):
    return TRACER.background_span(name, start_ns=start_ns, **tags)


def detail() -> Optional[Span]:
    """The active span when it is detailed, else None: the ONE flag read
    a per-series or per-sample loop makes, before the loop. The loop
    then hands the span down (`acc`) and its sites `add_cost` on it."""
    cur = getattr(TRACER._local, "current", None)
    return cur if cur is not None and cur.detailed else None


def count_cost(kind: str, n: float = 1):
    """Tally a cost/cache event onto the active span, if any — the
    charge-site hook block/grid caches and QueryScope exits use. One
    thread-local read when no span is active."""
    cur = getattr(TRACER._local, "current", None)
    if cur is not None:
        cur.add_cost(kind, n)


def collect_costs(span) -> Dict[str, float]:
    """Sum cost tallies over a whole span SUBTREE (local Span children
    and grafted remote dicts alike). Cache events accrue on the
    innermost span that saw them — storage.read's child, or a remote
    dbnode span grafted from the response frame — so a root-level
    consumer (the slow-query log's cold-cache classification) must roll
    the subtree up, not read the root's own costs."""
    out: Dict[str, float] = {}

    def walk(node):
        costs = node.get("costs") if isinstance(node, dict) else node.costs
        if costs:
            for k, v in costs.items():
                out[k] = out.get(k, 0) + v
        kids = (node.get("children") or ()) if isinstance(node, dict) \
            else node.children
        for c in kids:
            walk(c)

    walk(span)
    return out


# ---------------------------------------------------------------- phases

# Threads with a stage sink installed, process-wide: a phase site reads
# the thread-local sink only while some ANALYZE runs somewhere, so an
# ordinary request pays one thread-local read per site (`current`).
_SINKS_ACTIVE = 0
_SINK_LOCK = threading.Lock()
_SINK = threading.local()


def current_stage_sink():
    """This thread's stage sink (an object with `add(stage, seconds)`:
    query/explain.py's Analyze), or None."""
    return getattr(_SINK, "sink", None)


@contextlib.contextmanager
def stage_sink(sink):
    """Install `sink` for this thread; the previous one returns on exit."""
    global _SINKS_ACTIVE
    prev = getattr(_SINK, "sink", None)
    _SINK.sink = sink
    with _SINK_LOCK:
        _SINKS_ACTIVE += 1
    try:
        yield sink
    finally:
        with _SINK_LOCK:
            _SINKS_ACTIVE -= 1
        _SINK.sink = prev


class _Phase:
    __slots__ = ("key", "span", "stage", "sink", "t0")

    def __init__(self, key: str, span, stage: str, sink):
        self.key, self.span, self.stage, self.sink = key, span, stage, sink

    def __enter__(self):
        span = self.span
        if span is not None:
            # A phase inside the same phase (guard routes nest:
            # block.decode over codec.decode) is the outer one's time.
            is_open = TRACER._local.__dict__.setdefault("phases", set())
            if self.key in is_open:
                self.span = None
            else:
                is_open.add(self.key)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self.t0
        span = self.span
        if span is not None:
            TRACER._local.phases.discard(self.key)
            costs = span.costs
            costs[self.key + "_ns"] = costs.get(self.key + "_ns", 0) + dt
            costs[self.key + "_n"] = costs.get(self.key + "_n", 0) + 1
        if self.sink is not None:
            self.sink.add(self.stage, dt / 1e9)
        return False


_NOOP_PHASE = contextlib.nullcontext()


def phase(name: str, stage: Optional[str] = None):
    """Time a stretch of the current span WITHOUT a child span: under a
    detailed root, `<name>_ns` and `<name>_n` accumulate in the span's
    costs; with `stage` given and an ANALYZE context active on this
    thread, the same stretch lands there under that stage name. Neither
    active: a shared no-op."""
    cur = getattr(TRACER._local, "current", None)
    span = cur if cur is not None and cur.detailed else None
    sink = getattr(_SINK, "sink", None) \
        if stage is not None and _SINKS_ACTIVE else None
    if span is None and sink is None:
        return _NOOP_PHASE
    return _Phase(name, span, stage, sink)


# ---------------------------------------------------------- slow queries


class SlowQueryLog:
    """Bounded ring of slow/shed query records with typed reasons and
    per-query cost attribution (the dbnode slow-query-log analog).

    `limit-shed` and `deadline` entries record regardless of duration —
    they ARE the interesting events; threshold gating applies only to
    completed work ("slow" / "cold-cache")."""

    REASONS = ("limit-shed", "deadline", "cold-cache", "slow")
    _COLD_KEYS = ("block_cache_miss", "grid_cache_miss")

    def __init__(self, threshold_ms: Optional[float] = None,
                 maxlen: int = 128):
        if threshold_ms is None:
            try:
                threshold_ms = float(
                    os.environ.get("M3_TPU_SLOW_QUERY_MS", "500"))
            except ValueError:
                threshold_ms = 500.0
        self.threshold_ns = int(threshold_ms * 1e6)
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=maxlen)

    def record(self, kind: str, name: str, duration_ns: int, reason: str,
               costs: Optional[dict] = None, trace_id: Optional[int] = None,
               route: Optional[dict] = None):
        entry = {
            "kind": kind,
            "name": name,
            "duration_ms": round(duration_ns / 1e6, 3),
            "reason": reason,
            "costs": dict(costs) if costs else {},
        }
        if trace_id:
            entry["trace_id"] = trace_id
        if route:
            # The executor's route record: a slow INTERPRETED query's
            # entry says WHY it missed the compiled path (typed
            # plan.FallbackReason value), not just that it was slow.
            entry["route"] = route.get("route")
            if route.get("fallback_reason"):
                entry["plan_fallback"] = route["fallback_reason"]
        with self._lock:
            self._ring.append(entry)

    def maybe(self, kind: str, name: str, duration_ns: int,
              costs=None, trace_id: Optional[int] = None,
              reason: Optional[str] = None, route: Optional[dict] = None):
        """Record when `reason` is a typed failure (always) or the
        duration crosses the threshold (reason inferred: cold-cache when
        the costs show cache misses, else slow). `costs` may be a dict
        or a zero-arg callable — callables are only evaluated once the
        entry WILL record, so hot fast queries never pay a subtree
        cost rollup."""
        if reason is None and duration_ns < self.threshold_ns:
            return
        if callable(costs):
            costs = costs()
        if reason is None:
            reason = "cold-cache" if costs and any(
                costs.get(k) for k in self._COLD_KEYS) else "slow"
        self.record(kind, name, duration_ns, reason, costs, trace_id,
                    route=route)

    def entries(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def clear(self):
        with self._lock:
            self._ring.clear()


SLOW_QUERIES = SlowQueryLog()


# ---------------------------------------------------------------- profiling


def thread_stacks() -> str:
    """All-threads stack dump (goroutine-dump analog)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        out.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        out.extend(l.rstrip("\n") for l in traceback.format_stack(frame))
    return "\n".join(out)


def profile(seconds: float = 1.0, hz: int = 100,
            top: int = 40) -> List[dict]:
    """Statistical CPU profile: sample every thread's stack at `hz` for
    `seconds`, aggregate by flattened stack. Returns the hottest stacks
    with sample counts (the /debug/pprof/profile analog; sampling has the
    same bias/overhead profile as pprof's SIGPROF sampling). BLOCKS the
    calling thread for the window — serving endpoints go through
    `PROFILER.run` instead, which runs this on one shared capped
    background thread."""
    counts: Dict[tuple, int] = collections.Counter()
    me = threading.get_ident()
    interval = 1.0 / hz
    deadline = time.perf_counter() + seconds
    total = 0
    while time.perf_counter() < deadline:
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            stack = []
            f = frame
            while f is not None:
                stack.append(_frame_label(f))
                f = f.f_back
            counts[tuple(reversed(stack))] += 1
            total += 1
        time.sleep(interval)
    out = []
    for stack, n in sorted(counts.items(), key=lambda kv: -kv[1])[:top]:
        out.append({"samples": n,
                    "fraction": round(n / max(total, 1), 4),
                    "stack": list(stack)})
    return out


class _ProfileJob:
    __slots__ = ("seconds", "hz", "top", "done", "result")

    def __init__(self, seconds: float, hz: int, top: int):
        self.seconds = seconds
        self.hz = hz
        self.top = top
        self.done = threading.Event()
        self.result: Optional[List[dict]] = None


class ProfileRunner:
    """Background-thread profile driver for the /debug/pprof/profile
    endpoint: the sampling loop runs on ONE daemon thread with a hard
    per-request seconds cap (`M3_TPU_PROFILE_MAX_S`, default 5), and
    concurrent requests SHARE the in-flight window instead of stacking N
    sys._current_frames() loops. The serving thread waits on the result
    with a bounded timeout, so a profile request can never stall it past
    the cap (the pre-fix tracing.profile() blocked for an arbitrary
    caller-chosen window)."""

    def __init__(self, max_seconds: Optional[float] = None):
        if max_seconds is None:
            try:
                max_seconds = float(
                    os.environ.get("M3_TPU_PROFILE_MAX_S", "5"))
            except ValueError:
                max_seconds = 5.0
        self.max_seconds = max(0.05, max_seconds)
        self._lock = threading.Lock()
        self._job: Optional[_ProfileJob] = None
        self.shared = 0  # requests that joined an in-flight window

    def _run_job(self, job: _ProfileJob):
        try:
            job.result = profile(job.seconds, job.hz, job.top)
        except Exception:  # noqa: BLE001 — a failed sample pass must
            job.result = []    # never wedge waiters past their timeout
        finally:
            job.done.set()

    def run(self, seconds: float = 1.0, hz: int = 100,
            top: int = 40) -> List[dict]:
        seconds = min(max(float(seconds), 0.05), self.max_seconds)
        with self._lock:
            job = self._job
            if job is None or job.done.is_set():
                job = self._job = _ProfileJob(seconds, hz, top)
                threading.Thread(target=self._run_job, args=(job,),
                                 name="profile-runner", daemon=True).start()
            else:
                self.shared += 1
        # Bounded wait: cap + slack. A hung sampler yields an empty
        # profile, not a hung serving thread.
        job.done.wait(timeout=self.max_seconds + 2.0)
        return job.result if job.result is not None else []


PROFILER = ProfileRunner()


# ------------------------------------------------- debug endpoint payloads
#
# ONE definition of the /debug response shapes: the coordinator HTTP API
# and the dbnode httpjson server both serve these, and two hand-rolled
# copies would drift (params, keys) the first time either grows a field.


def debug_traces_payload(trace_id: Optional[int] = None) -> dict:
    """/debug/traces body: recent span trees (optionally one trace) +
    the slow-query ring + the runtime probe's ring (its own: a record a
    second would push request trees out of the traces' 128)."""
    return {"traces": TRACER.recent_traces(trace_id=trace_id),
            "slow": SLOW_QUERIES.entries(),
            "runtime": TRACER.runtime.snapshot()}


def debug_profile_payload(seconds: float) -> dict:
    """/debug/pprof/profile body: the shared capped background sampler's
    hottest stacks, plus the cap actually applied to the request."""
    return {"profile": PROFILER.run(seconds=seconds),
            "capped_seconds": min(max(float(seconds), 0.05),
                                  PROFILER.max_seconds)}
