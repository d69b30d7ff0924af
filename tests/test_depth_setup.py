"""The benchmark's set-up by filesets (benchmark/setups/filesets-restart.py)
at 24 hosts x 8 blocks on the CPU: the program's own writers leave on
disk what a node that ran for hours leaves there, the node is restarted
over it through its own bootstrap chain, takes live writes, and the
`depth_readback` check reads every block start back exactly over HTTP;
its controls come out not correct. The whole cell, at its own depth and
with its window, is benchmark/tests/test_depth.py (not tier-1)."""

import os
import sys
import time

import pytest

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT_DIR, "benchmark")
BLOCKS = 8
SEED = 2_147_483_659


@pytest.fixture(scope="module")
def run():
    sys.path[:0] = [BENCH_DIR, os.path.join(BENCH_DIR, "tests")]
    try:
        import tiny
        from harness import cellrun, spec

        bench = tiny.bench()
        bench["configs"].append({
            "name": "tsbs-cpu-tiny-16h",
            "file": "benchmark/tests/tsbs-cpu-tiny-16h.json"})
        for w in bench["workloads"]:
            if w["name"] == "cpu4k-query-12h":
                w["config"] = "tsbs-cpu-tiny-16h"
        cell = spec.load_cell("cpu4k-query-12h", bench)
        cell.traffic.update(
            setup={"via": "filesets-restart", "load_steps": BLOCKS * 120 + 12,
                   "sealed_blocks": BLOCKS, "block_steps": 120,
                   "open_steps": 12},
            readback_pairs=200, readback_open_pairs=20)
        r = cellrun.CellRun(cell, SEED, time.perf_counter_ns(),
                            need_chip=False)
        try:
            r.facts = r.setup(1.0)
            r.m = cellrun.Measurement(cell, 1.0, r.proc_start_ns)
            r.spec = spec
            yield r
        finally:
            r.close()
    finally:
        del sys.path[:2]


def test_the_node_was_restarted_over_filesets_the_programs_writers_wrote(run):
    handle = run.server.handle
    ns = handle.db.namespace(handle.namespace)
    assert run.facts["sealed_blocks"] == BLOCKS
    assert run.facts["filesets"] == BLOCKS * len(ns.shards)
    results = handle.node.bootstrap_results
    assert results is not None
    assert not results[handle.namespace].claimed["filesystem"].is_empty()
    assert sum(sh.num_series() for sh in ns.shards.values()) == 240
    assert all(sh.registry.untagged == 0 for sh in ns.shards.values())
    assert run.facts["bootstrap_fs_s"] > 0 and run.facts["fileset_build_s"] > 0
    # the span the fact was read from
    from m3_tpu.utils import tracing

    root = [t for t in tracing.TRACER.recent_traces()
            if t["name"] == "bootstrap.filesystem"][-1]
    assert root["tags"]["filesets"] == BLOCKS * len(ns.shards)
    assert root["tags"]["series"] == 240 and root["tags"]["bytes"] > 0
    assert set(root["costs"]) >= {"verify_ns", "install_ns", "index_ns"}


@pytest.mark.parametrize("control,bad", [
    (None, set()),
    ("unindexed", {"readback_mismatched", "block_starts_not_covered"}),
    ("stale", {"readback_mismatched"}),
])
def test_depth_readback_reads_every_block_start_back_exactly(run, control,
                                                             bad):
    rows, failed = run.spec.load_part("checks", "depth_readback").check(
        run, run.m, control)
    assert [n for n, _v, _l in rows] == [
        "readback_mismatched", "reads_failed", "block_starts_not_covered",
        "readback_pairs_compared_at_least"]
    assert {n for n, v, lim in rows if v > lim} == bad
    assert bool(failed) == bool(bad)
    assert -rows[3][1] >= 200
