"""The readers PR 36 added, on hand-built Measurements: each returns
None where the program's spans, counters or rings carry nothing for it
(a program that predates them), and the right number where they do."""

import types

import numpy as np
import pytest

import tiny  # noqa: F401  (puts benchmark/ on the path)
from harness import cellrun, spec

MS = 1_000_000
T0, T1 = 10_000 * MS, 55_000 * MS


def measurement(**kw):
    m = cellrun.Measurement(cell=None, seconds=45.0, proc_start_ns=0,
                            window=(T0, T1), t_end=T1 + 500 * MS)
    m.rec = {"i": np.zeros(0, np.int64), "sent": np.zeros(0, np.int64)}
    for k, v in kw.items():
        setattr(m, k, v)
    return m


def read(name, m):
    return spec.load_reader("layer_metrics", name)(m)


def node(name, start=0, end=0, tags=None, costs=None, children=(),
         trace_id=0):
    return {"name": name, "start": start, "end": end, "tags": tags or {},
            "costs": costs or {}, "trace_id": trace_id,
            "children": list(children)}


NEW = [
    "host_cpu_busy_share", "host_cpu_busy_share.ingest", "gil_wait_p95_ms",
    "gil_wait_p95_ms.ingest",
    "stall_max_ms", "stall_max_ms.ingest", "tick_cpu_share.ingest",
    "native_cpu_share", "native_cpu_share.ingest",
    "write_decode_cpu_us_per_sample", "write_append_cpu_us_per_sample",
    "node_fetch_cpu_ms_per_replica", "accept_wait_ms",
    "node_buffer_ms_per_replica", "buffer_read_us_per_series",
    "decode_layout_ms_per_query", "decode_fetch_ms_per_query",
    "interp_eval_ms_per_query", "tick_encode_prepare_s"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_probe_or_the_costs_reads_nothing(name):
    # what the parent's traced run hands a reader: request trees with
    # their wall times and the counters it had, no `runtime.` key, no ring
    trees = [node("http.GET /api/v1/query_range", T0, T0 + 9 * MS,
                  trace_id=1, children=[
                      node("http.read"),
                      node("query.execute_range", tags={"route": "plan"},
                           children=[node("query.fetch",
                                          costs={"series_n": 5})])]),
             node("rpc.fetch_tagged", costs={"index_ns": 1, "read_ns": 2}),
             node("encode.block")]
    m = measurement(span_trees=trees, counters0={"a": 1}, counters1={"a": 2})
    assert read(name, m) is None


def books():
    c0 = {"runtime.process_cpu_ns": 1_000 * MS,
          "runtime.probe.wall_ns": 2_000 * MS,
          "runtime.cpu_ns{role=native}": 100 * MS,
          "runtime.cpu_ns{role=main}": 0,
          "runtime.cpu_ns{role=tick}": 0,
          "runtime.cpu_ns{role=request}": 500 * MS,
          "runtime.runq_ns{role=request}": 0}
    c1 = {"runtime.process_cpu_ns": 31_000 * MS,     # 30 s of CPU ...
          "runtime.probe.wall_ns": 42_000 * MS,      # ... in 40 s covered
          "runtime.cpu_ns{role=native}": 2_100 * MS,            # 2 s native
          "runtime.cpu_ns{role=main}": 3_000 * MS,     # the harness's driver
          "runtime.cpu_ns{role=tick}": 7_000 * MS,
          "runtime.cpu_ns{role=request}": 14_500 * MS,
          "runtime.cpu_ns{node=a,role=rpc}": 3_000 * MS,   # new in the window
          "runtime.cpu_ns{node=b,role=rpc}": 4_000 * MS,
          "runtime.runq_ns{role=request}": 1_000 * MS,
          "runtime.runq_ns{node=a,role=rpc}": 500 * MS,
          "runtime.runq_ns{role=native}": 9_000 * MS}     # not Python's
    return c0, c1


def test_the_counter_readings():
    c0, c1 = books()
    m = measurement(counters0=c0, counters1=c1)
    for twin in ("", ".ingest"):
        assert read("host_cpu_busy_share" + twin, m) == pytest.approx(
            100 * (30 - 2 - 3) / 40)
        assert read("native_cpu_share" + twin, m) == pytest.approx(
            100 * 2 / 30)
    assert read("tick_cpu_share.ingest", m) == pytest.approx(100 * 7 / 25)


def test_the_ring_readings(capsys):
    wakes = [(T0 - 40 * MS, 900 * MS, 0)]        # due before the window
    wakes += [(T0 + k * 40 * MS, 1 * MS, 0) for k in range(94)]
    wakes += [(T0 + (94 + k) * 40 * MS, 12 * MS, 2 * MS) for k in range(5)]
    wakes += [(T0 + 99 * 40 * MS, 400 * MS, None)]   # no schedstat: late is all
    stall = {"start_ns": T0 + 99 * 40 * MS, "end_ns": T0 + 99 * 40 * MS + 400 * MS,
             "late_ns": 400 * MS, "cpu_ns": 390 * MS, "runq_ns": None, "gc": 2,
             "held_by": {"role": "tick", "thread": "Thread-9 (loop)",
                         "cpu_ns": 380 * MS, "frames": ["write (fs.py:10)"]}}
    before = dict(stall, start_ns=T0 - 900 * MS, end_ns=T0 - 500 * MS,
                  late_ns=700 * MS)
    rt = types.SimpleNamespace(wakes=wakes, stalls=[before, stall])
    m = measurement(runtime=rt)
    for twin in ("", ".ingest"):
        # 100 wakes in the window; the 96th smallest wait is 12 - 2 ms
        assert read("gil_wait_p95_ms" + twin, m) == pytest.approx(10.0)
        assert read("stall_max_ms" + twin, m) == pytest.approx(400.0)
    err = capsys.readouterr().err
    assert "held by tick thread 'Thread-9 (loop)'" in err and "gc 2" in err
    quiet = measurement(runtime=types.SimpleNamespace(wakes=wakes, stalls=[before]))
    assert read("stall_max_ms", quiet) == 0.0
    none = measurement(runtime=types.SimpleNamespace(wakes=wakes[:1], stalls=[]))
    assert read("stall_max_ms", none) is None and \
        read("gil_wait_p95_ms", none) is None


def test_a_stalls_gap_is_named_in_the_breakdown():
    """`breakdown.idle_gaps` gives a stall's stretch to `runtime.stall`,
    not to the spans that stood still under it; the part of it that is a
    full collection stays `gc` (PR 50)."""
    from harness import breakdown

    stall = {"start_ns": T0 + 100 * MS, "end_ns": T0 + 500 * MS,
             "late_ns": 400 * MS}
    fetch = node("query.fetch", T0 + 50 * MS, T0 + 700 * MS)
    m = measurement(
        runtime=types.SimpleNamespace(wakes=[], stalls=[stall]),
        span_trees=[fetch], gc_events=[(T0 + 100 * MS, T0 + 250 * MS, 2)],
        rec={k: np.zeros(0, np.int64) for k in ("i", "sent", "done")},
        trace=types.SimpleNamespace(to_trace_ns=lambda t: t,
                                    busy=lambda lo, hi: {}))
    gaps = breakdown.idle_by_host(m, T0, T0 + 1000 * MS)
    assert gaps == pytest.approx({
        "gc": 0.15, breakdown.STALL: 0.25, "query.fetch": 0.25,
        breakdown.IDLE: 0.35})
    assert breakdown.PRIORITY.index("gc") < breakdown.PRIORITY.index(
        breakdown.STALL) < breakdown.PRIORITY.index("index.query")


def test_the_cpu_twins_of_the_write_path():
    root = node("http.POST /api/v1/prom/remote/write", T0, T0 + 30 * MS,
                tags={"samples": 500, "cpu_ns": 9 * MS}, trace_id=1, children=[
                    node("http.read"),
                    node("http.handler", children=[
                        node("remote_write.decompress", T0, T0 + 4 * MS,
                             tags={"cpu_ns": 1 * MS}),
                        node("remote_write.decode", T0, T0 + 11 * MS,
                             tags={"cpu_ns": 3 * MS}),
                        node("remote_write.append", T0, T0 + 12 * MS,
                             tags={"cpu_ns": 2 * MS},
                             costs={"samples_n": 500})])])
    m = measurement(span_trees=[root])
    assert read("write_decode_cpu_us_per_sample", m) == pytest.approx(8.0)
    assert read("write_append_cpu_us_per_sample", m) == pytest.approx(4.0)
    # their wall twins read the queue: 30 and 24 us a sample here
    assert read("write_decode_us_per_sample", m) == pytest.approx(30.0)
    assert read("write_append_us_per_sample", m) == pytest.approx(24.0)


def test_a_replicas_read_in_cpu_and_in_buffer_reads():
    replicas = [node("rpc.fetch_tagged", 0, 40 * MS, tags={"cpu_ns": cpu * MS},
                     costs={"index_ns": 7 * MS, "read_ns": 24 * MS,
                            "buffer_ns": buf * MS})
                for cpu, buf in ((12, 15), (14, 17), (16, 19))]
    grafted = node("rpc.fetch_tagged")      # the client's copy: no costs
    m = measurement(span_trees=replicas + [grafted])
    assert read("node_fetch_cpu_ms_per_replica", m) == pytest.approx(14.0)
    assert read("node_buffer_ms_per_replica", m) == pytest.approx(17.0)


def test_accept_wait_a_request():
    roots = [node("http.GET /api/v1/query_range", T0 + (10 * i + 1) * MS,
                  T0 + (10 * i + 9) * MS, trace_id=i + 1,
                  children=[node("http.read")]) for i in range(3)]
    m = measurement(span_trees=roots)
    m.rec = {"i": np.arange(4), "sent": np.asarray(
        [T0, T0 + int(10.5 * MS), T0 + 20 * MS, T0 + 30 * MS])}
    # 1, 0.5 and 1 ms; the fourth request has no root (never traced)
    assert read("accept_wait_ms", m) == pytest.approx(2.5 / 3)


def test_the_decode_calls_anatomy_the_buffer_and_the_interpreter():
    def query(route, fetch_costs, under="query.fetch", **ex_costs):
        return node("http.GET /q", children=[node("http.read"), node(
            "query.execute_range", tags={"route": route}, costs=ex_costs,
            children=[node(under, costs=fetch_costs)])])

    trees = [
        query("plan", {"series_n": 10, "buffer_ns": 900_000, "layout_ns": 2 * MS,
                       "device_wait_ns": 1 * MS, "d2h_ns": 3 * MS}),
        query("interpreter", {"series_n": 2, "buffer_ns": 300_000},
              interpreter_eval_ns=4 * MS),
        query("interpreter", {"layout_ns": 1 * MS, "device_wait_ns": 1 * MS,
                              "d2h_ns": 1 * MS}, under="client.fetch_tagged",
              interpreter_eval_ns=6 * MS)]
    m = measurement(span_trees=trees)
    assert read("buffer_read_us_per_series", m) == pytest.approx(100.0)
    assert read("decode_layout_ms_per_query", m) == pytest.approx(1.0)
    assert read("decode_fetch_ms_per_query", m) == pytest.approx(2.0)
    assert read("interp_eval_ms_per_query", m) == pytest.approx(5.0)


def test_the_ticks_prepare():
    tick = node("mediator.tick", children=[node("mediator.snapshot", children=[
        node("encode.block", costs={"prepare_ns": 300 * MS, "pad_ns": 5}),
        node("encode.block", costs={"prepare_ns": 200 * MS})])])
    assert read("tick_encode_prepare_s",
                measurement(span_trees=[tick])) == pytest.approx(0.5)
