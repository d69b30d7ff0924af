"""The tracer's runtime probe (utils/tracing.py::RuntimeProbe): one
thread, alive while traces are asked for, that accounts for the GIL —
each wake's lateness, CPU by thread role, every stall with the thread
that caused it — and nothing at all with tracing off."""

import ast
import glob
import json
import os
import threading
import time

import pytest

from m3_tpu.utils import instrument, tracing

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CTX = tracing.SpanContext(7, 1)


@pytest.fixture
def books(monkeypatch):
    """Counters of this test's own: another test's probe may still run in
    this process (it ends itself ten seconds after its last trace)."""
    root = instrument.Scope()
    monkeypatch.setattr(instrument, "ROOT", root)
    return root


@pytest.fixture
def tracer(books, monkeypatch):
    t = tracing.Tracer(sample_rate=1.0)
    monkeypatch.setattr(tracing, "TRACER", t)
    yield t
    t.runtime._asked_ns = -tracing.PROBE_IDLE_EXIT_NS  # end with the test


def probes():
    return [t for t in threading.enumerate()
            if t.name == tracing.PROBE_THREAD_NAME]


def wait_for(cond, seconds=0.8):
    deadline = time.perf_counter() + seconds
    while not cond() and time.perf_counter() < deadline:
        time.sleep(0.005)
    return cond()


def runtime_keys(root):
    return {k: v for k, v in root.snapshot().items()
            if k.startswith("runtime.")}


def test_nothing_runs_and_nothing_is_counted_until_a_trace_is_asked_for(
        tracer, books):
    before = set(threading.enumerate())
    assert tracer.span_from(None, "http.GET /x") is tracing.NOOP_SPAN
    with tracer.span("query.execute_range"):
        with tracer.child_span("query.fetch"):
            pass
    with tracer.background_span("mediator.tick"):
        pass
    assert not tracer.runtime.running
    assert not [t for t in set(threading.enumerate()) - before
                if t.name == tracing.PROBE_THREAD_NAME]
    assert runtime_keys(books) == {}
    assert len(tracer.runtime.wakes) == 0


def test_an_asked_for_root_starts_it_and_it_ends_itself(tracer, books,
                                                        monkeypatch):
    # (room for a worker that shares its cores: a first wake 60 ms late
    # would find the probe idle before it had counted five)
    monkeypatch.setattr(tracing, "PROBE_PERIOD_NS", 5_000_000)
    monkeypatch.setattr(tracing, "PROBE_IDLE_EXIT_NS", 300_000_000)
    # (its own thread, not a count by name: an earlier file's probe may
    # still run in this worker, and end itself while this test waits)
    others = set(probes())
    with tracer.span_from(CTX, "http.GET /x"):
        mine = set(probes()) - others
        assert tracer.runtime.running and len(mine) == 1
    # every additive counter exists from the start, and counts from there
    keys = runtime_keys(books)
    assert {"runtime." + k for k in tracing._ADDITIVE} <= set(keys)
    assert wait_for(lambda: not tracer.runtime.running, 5)
    assert wait_for(lambda: not any(t.is_alive() for t in mine), 5)
    keys = runtime_keys(books)
    assert keys["runtime.probe.wakes"] >= 5
    assert keys["runtime.probe.wall_ns"] >= 250_000_000
    assert keys["runtime.cpu_ns{role=probe}"] > 0
    # asked again, it runs again on the counters it had
    with tracer.span_from(CTX, "http.GET /x"):
        assert tracer.runtime.running
    assert wait_for(lambda: runtime_keys(books)["runtime.probe.wakes"]
                    > keys["runtime.probe.wakes"], 5)


def spin_count(ms: float) -> int:
    """An `n` for which sum(range(n)) keeps the GIL for about `ms`: one C
    call, where a pure-Python loop would give the GIL up every 5 ms. By
    the fastest of three timings: on a busy machine the hold is longer,
    never shorter."""
    took = []
    for _ in range(3):
        t0 = time.perf_counter()
        sum(range(1_000_000))
        took.append(time.perf_counter() - t0)
    return int(1_000_000 * ms / 1e3 / min(took))


def test_a_stall_names_the_thread_that_kept_the_gil(tracer, books):
    # (600 ms by the calibration: on a worker that shares its cores the
    # three timings can all be slow, and a hold sized by them has run
    # 110 ms where 300 were meant — under the 150 asserted below)
    n = spin_count(600)
    with tracer.span_from(CTX, "http.GET /x"):
        pass
    assert wait_for(lambda: len(tracer.runtime.wakes) >= 2)
    used = []
    done = threading.Event()

    def hold():
        c0 = time.thread_time_ns()
        sum(range(n))
        used.append(time.thread_time_ns() - c0)
        done.wait(2)    # alive until the sample after the stall has run

    th = threading.Thread(target=hold, name="gil-hog")
    th.start()

    def named():
        return [s for s in list(tracer.runtime.stalls)
                if (s["held_by"] or {}).get("thread") == "gil-hog"]

    assert wait_for(lambda: named() and "runtime.cpu_ns{role=python-other}"
                    in runtime_keys(books), 2.0)
    done.set()
    th.join()
    (stall,) = named()
    assert stall["late_ns"] >= 150_000_000
    assert stall["end_ns"] - stall["start_ns"] == stall["late_ns"]
    held = stall["held_by"]
    assert held["role"] == "python-other"
    assert any("hold" in f for f in held["frames"]) and len(held["frames"]) <= 5
    assert 0.5 * used[0] <= held["cpu_ns"] <= 1.2 * used[0]
    # the whole process's CPU across it is at least the holder's
    assert stall["cpu_ns"] >= held["cpu_ns"]
    keys = runtime_keys(books)
    assert keys["runtime.stalls"] >= 1
    assert keys["runtime.stall_ns"] >= stall["late_ns"]
    # the role's counter moved by about the holder's CPU
    assert 0.8 * used[0] <= keys["runtime.cpu_ns{role=python-other}"] \
        <= 1.5 * used[0] + 50_000_000
    json.dumps(tracer.runtime.snapshot())   # what /debug/traces serves


def test_a_thread_is_counted_under_the_role_of_the_root_it_opened(
        tracer, books):
    with tracer.span_from(CTX, "http.GET /warm"):
        pass    # the probe runs: roots note their threads' roles

    def request():
        with tracer.span_from(CTX, "http.GET /x", cpu_start_ns=0):
            sum(range(400_000))

    def rpc():
        with tracer.span_from(CTX, "rpc.fetch_tagged", host="node-b"):
            sum(range(400_000))

    def tick():
        with tracer.background_span("mediator.tick"):
            sum(range(400_000))

    for fn in (request, rpc, tick):
        th = threading.Thread(target=fn)
        th.start()
        th.join()       # gone before any sample: the root counted it
    keys = runtime_keys(books)
    for key in ("runtime.cpu_ns{role=request}",
                "runtime.cpu_ns{node=node-b,role=rpc}",
                "runtime.cpu_ns{role=tick}"):
        assert keys[key] > 1_000_000, (key, keys)


def test_a_thread_that_exits_between_two_samples_breaks_nothing(
        tracer, books, monkeypatch):
    # (the samples below are this test's alone: a late wake on a loaded
    # worker is a stall, and the probe's own sample at it would put the
    # thread back between the test's sample and its look)
    monkeypatch.setattr(tracing, "PROBE_SAMPLE_NS", 10**12)
    monkeypatch.setattr(tracing, "STALL_NS", 10**12)
    rt = tracer.runtime
    with tracer.span_from(CTX, "http.GET /x"):
        pass
    go = threading.Event()
    th = threading.Thread(target=go.wait, args=(2,), name="fanout_0")
    th.start()
    tid = th.native_id
    rt._sample()
    assert tid in rt._cpu_seen and rt.threads_by_role["fanout"] == 1
    go.set()
    th.join()
    rt._sample()
    assert tid not in rt._cpu_seen and tid not in rt._role_of
    assert "fanout" not in rt.threads_by_role
    # a thread that goes between the listing and the read of its clock
    monkeypatch.setattr(tracing, "_schedstat", lambda tid: None)
    monkeypatch.setattr(tracing, "_thread_cpu_ns", lambda tid: None)
    rt._sample()
    assert rt._cpu_seen == {}
    assert tracing._thread_cpu_ns(2**22 + 12345) is None    # no such id


def test_debug_traces_serves_the_ring_and_keeps_the_request_trees(
        books, monkeypatch):
    monkeypatch.setattr(tracing, "PROBE_PERIOD_NS", 2_000_000)
    monkeypatch.setattr(tracing, "PROBE_SAMPLE_NS", 10_000_000)
    t = tracing.Tracer(max_traces=4, sample_rate=1.0)
    monkeypatch.setattr(tracing, "TRACER", t)
    for i in range(4):
        with t.span_from(tracing.SpanContext(100 + i, 1), "http.GET /x"):
            pass
    assert wait_for(lambda: len(t.runtime.wakes) >= 20)
    t.runtime._asked_ns = -tracing.PROBE_IDLE_EXIT_NS
    body = tracing.debug_traces_payload()
    assert [d["trace_id"] for d in body["traces"]] == [100, 101, 102, 103]
    rt = body["runtime"]
    assert rt["wakes"] >= 20 and rt["period_ms"] == 2.0
    assert set(rt["late_ms"]) == {"p50", "p95", "max"}
    assert rt["threads"]["probe"] >= 1 and isinstance(rt["stalls"], list)
    json.dumps(body["runtime"])


@pytest.mark.parametrize("name,role", [
    ("accept-coordinator-http", "accept"), ("accept-node-rpc", "accept"),
    ("fanout_3", "fanout"), ("tsz-prep_0", "prep"),
    ("block-cache-fill", "cache-fill"),
    (tracing.PROBE_THREAD_NAME, "probe"), ("MainThread", "main"),
    ("mediator", "python-other"),
    ("Thread-7 (process_request_thread)", "python-other")])
def test_a_thread_that_opens_no_root_takes_its_role_from_its_name(name, role):
    assert tracing._name_role(name) == (role, "")


def _thread_sites():
    for path in sorted(glob.glob(os.path.join(ROOT_DIR, "m3_tpu", "**", "*.py"),
                                 recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            called = fn.attr if isinstance(fn, ast.Attribute) else \
                getattr(fn, "id", "")
            if called in ("Thread", "ThreadPoolExecutor"):
                yield (os.path.relpath(path, ROOT_DIR), node.lineno, called,
                       {k.arg for k in node.keywords})


def test_every_thread_is_named_where_it_is_started():
    """What /debug/pprof/threads prints, and what gives a thread that
    opens no root its role."""
    sites = list(_thread_sites())
    assert len(sites) >= 30
    for path, line, called, kwargs in sites:
        want = "name" if called == "Thread" else "thread_name_prefix"
        assert want in kwargs, f"{path}:{line} starts a {called} without {want}="


QUERY_CELLS = ("cpu4k-query-thin", "rf3-query-thin", "cpu4k-query-12h",
               "aggns-query-3d")
INGEST, RF3 = ("cpu4k-ingest",), ("rf3-query-thin",)
# PR 36's readings and the cells that report each. A name with a cell's
# suffix is a twin of its stem: a `benchmark` PR may fold it into the
# stem's `workloads`, and the reading is then found there.
NEW_READINGS = {
    "host_cpu_busy_share": QUERY_CELLS, "host_cpu_busy_share.ingest": INGEST,
    "gil_wait_p95_ms": QUERY_CELLS, "gil_wait_p95_ms.ingest": INGEST,
    "stall_max_ms": QUERY_CELLS, "stall_max_ms.ingest": INGEST,
    "tick_cpu_share.ingest": INGEST,
    "native_cpu_share": QUERY_CELLS, "native_cpu_share.ingest": INGEST,
    "write_decode_cpu_us_per_sample": INGEST,
    "write_append_cpu_us_per_sample": INGEST,
    "node_fetch_cpu_ms_per_replica": RF3, "accept_wait_ms": QUERY_CELLS,
    "node_buffer_ms_per_replica": RF3,
    "buffer_read_us_per_series": QUERY_CELLS[:1] + QUERY_CELLS[2:],
    "decode_layout_ms_per_query": QUERY_CELLS[1:],
    "decode_fetch_ms_per_query": QUERY_CELLS[1:],
    "interp_eval_ms_per_query": QUERY_CELLS[:2] + QUERY_CELLS[3:],
    "tick_encode_prepare_s": INGEST}


@pytest.mark.parametrize("name,cell", [
    pytest.param(name, cell, id=name + "@" + cell)
    for name, cells in NEW_READINGS.items() for cell in cells])
def test_every_metric_of_pr_36_has_its_files_and_its_cells(name, cell):
    """One case a (reading, cell) pair: the entry is the reading's own
    or, once its twin is folded, its stem's, and lists the cell."""
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    decl = by_name.get(name) or by_name[name.rpartition(".")[0]]
    assert cell in decl["workloads"]
    assert cell in {w["name"] for w in bench["workloads"]}
    assert (decl["moves"] == "ingest_samples_per_s") == (
        cell == "cpu4k-ingest")
    d = os.path.join(ROOT_DIR, "benchmark", "layer_metrics")
    with open(os.path.join(d, decl["name"] + ".json")) as f:
        assert json.load(f) == decl
    with open(os.path.join(d, decl["name"] + ".py")) as f:
        assert "def read(m" in f.read()


def test_a_stall_carries_the_generation_of_the_collection_it_overlaps(
        tracer, monkeypatch):
    """A full collection is one C call too: the stall it makes says so,
    from `gc.get_stats` (no `gc.callbacks` hook of the probe's)."""
    import gc

    hooks = list(gc.callbacks)
    with tracer.span_from(CTX, "http.GET /x"):
        pass
    assert gc.callbacks == hooks
    assert wait_for(lambda: len(tracer.runtime.wakes) >= 2)
    n = spin_count(300)
    real = gc.get_stats
    state = {"bump": 0}

    def stats():
        out = real()
        out[2]["collections"] += state["bump"]
        return out

    monkeypatch.setattr(tracing.gc, "get_stats", stats)
    assert wait_for(lambda: len(tracer.runtime.wakes) >= 6)  # a snapshot since

    def collect():      # stands for the collector: counted, keeps the GIL
        state["bump"] = 1
        sum(range(n))

    th = threading.Thread(target=collect, name="collector")
    th.start()
    th.join()
    assert wait_for(lambda: any(s.get("gc") == 2
                                for s in list(tracer.runtime.stalls)), 1.5)
