"""`buffer_index_hit_share` (PR 38): 100 where every open-buffer read of
the window was answered from a bucket's index by series, the indexed
share where some scanned a tail, None on a program without the counters;
and the tiny thin cell reads it on the CPU."""

import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny  # noqa: E402
from harness import cellrun, spec  # noqa: E402

NAME = "buffer_index_hit_share"
INDEXED, TAIL = "storage.buffer.read.indexed", "storage.buffer.read.tail_scans"


def read(counters0, counters1):
    m = cellrun.Measurement(cell=None, seconds=45.0, proc_start_ns=0,
                            window=(0, 1), t_end=2)
    m.counters0, m.counters1 = counters0, counters1
    return spec.load_reader("layer_metrics", NAME)(m)


@pytest.mark.parametrize("c0, c1, want", [
    ({INDEXED: 40_000, TAIL: 0}, {INDEXED: 62_640, TAIL: 0}, 100.0),
    ({INDEXED: 10, TAIL: 5}, {INDEXED: 40, TAIL: 15}, 75.0),
    ({}, {INDEXED: 7}, 100.0),               # counters born in the window
    ({INDEXED: 3, TAIL: 0}, {INDEXED: 3, TAIL: 9}, 0.0),
])
def test_reads_the_indexed_share_of_the_windows_reads(c0, c1, want):
    assert read(c0, c1) == want


@pytest.mark.parametrize("c0, c1", [
    ({"storage.read.cold_rows": 1}, {"storage.read.cold_rows": 9}),  # a parent
    ({INDEXED: 5, TAIL: 2}, {INDEXED: 5, TAIL: 2}),    # no read in the window
    ({}, {}),
])
def test_reads_nothing_without_the_counters(c0, c1):
    assert read(c0, c1) is None


def test_declared_as_benchmark_json_has_it():
    bench = spec.load_benchmark()
    entry = spec.layer_metric_declarations()[NAME]
    assert entry in bench["per_layer"]
    assert entry["workloads"] == ["cpu4k-query-thin", "rf3-query-thin",
                                  "cpu4k-query-12h", "aggns-query-3d",
                                  "promrw4k-mixed"]
    assert entry["moves"] == "query_p50_ms"


def test_the_tiny_thin_cell_reads_it_on_the_cpu():
    cell = tiny.cell("cpu4k-query-thin")
    run = cellrun.CellRun(cell, 3_800_000_019, time.perf_counter_ns(),
                          trace=True, need_chip=False)
    try:
        run.setup(3.0)
        # on the CPU a decode row bucket compiles where a read first meets
        # it, and after other cells' tests in one process that can be
        # inside this window: meet them all first
        tiny.warm_decode_buckets(run.server.handle)
        m = run.window(3.0)
        result = run.result(m, *run.check(m))
    finally:
        run.close()
    assert result["correct"] is True
    # no write inside the window: every bucket was grouped by the
    # set-up's warm queries and no read scans anything
    assert result["metrics"][NAME]["value"] == 100.0
    assert m.moved(INDEXED) > 0 and m.moved(TAIL) == 0
    assert m.moved("storage.buffer.index.builds") == 0
