"""`cpu4k-query-12h` at a tiny width on the CPU, end to end and at its
own depth: 24 hosts, all 48 sealed blocks written as filesets with the
program's writers, the node restarted over them through its own
bootstrap chain, 12 live scrapes, the mix's four classes at their own
12 h and 8 h ranges over HTTP, and the checks. Most ranges start PAST
the first index-block boundary (step 600 of 5,772): a node that indexed
a series in its first index block only would answer none of those,
which is what the `unindexed` control puts in the program's place."""

import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny  # noqa: E402
from harness import cellrun, spec  # noqa: E402

SEED = 3_000_000_029
CELL = "cpu4k-query-12h"
BLOCKS = 48


def depth_cell(**traffic_overrides):
    bench = tiny.bench()
    bench["configs"].append({"name": "tsbs-cpu-tiny-16h",
                             "file": "benchmark/tests/tsbs-cpu-tiny-16h.json"})
    for w in bench["workloads"]:
        if w["name"] == CELL:
            w["config"] = "tsbs-cpu-tiny-16h"
    cell = spec.load_cell(CELL, bench)
    cell.traffic.update(dict({"rate_per_s": 6.0}, **traffic_overrides))
    return cell


@pytest.fixture(scope="module")
def run():
    r = cellrun.CellRun(depth_cell(), SEED, time.perf_counter_ns(),
                        trace=True, need_chip=False)
    try:
        r.facts = r.setup(3.0)
        tiny.warm_decode_buckets(r.server.handle)
        r.m = r.window(3.0)
        yield r
    finally:
        r.close()


def test_the_cell_is_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "tsbs-cpu-4k-16h", "tsbs-range-12h", 1)
    assert (cell.deployment, cell.setup_via, cell.reference) == (
        "dbnode-restarted", "filesets-restart", "promql_ref_indexed")
    assert cell.checks == ["query_answers", "depth_readback",
                           "served_path_verdict"]
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    assert "warm_first" not in cell.traffic
    assert [(m["class"], m["cards"]) for m in cell.traffic["mix"]] == [
        ("single-groupby-1-1-12", 5), ("single-groupby-5-1-12", 3),
        ("cpu-max-all-1-8h", 3), ("cpu-max-all-8-8h", 1)]
    setup = cell.traffic["setup"]
    assert setup["sealed_blocks"] * setup["block_steps"] \
        + setup["open_steps"] == setup["load_steps"] == 5772
    assert cell.config["reduced"] == ["retention", "replication_factor"]
    assert cell.config["scale"] == 4000 and cell.config["cadence_s"] == 10


def test_the_set_up_is_a_restart_over_the_programs_own_filesets(run):
    facts, handle = run.facts, run.server.handle
    ns = handle.db.namespace(handle.namespace)
    assert facts["sealed_blocks"] == BLOCKS
    assert facts["filesets"] == BLOCKS * len(ns.shards)
    assert handle.node.bootstrap_results is not None      # it was restarted
    claimed = handle.node.bootstrap_results[handle.namespace].claimed
    assert not claimed["filesystem"].is_empty()
    # every block came back, every series got its tags back from the
    # index segments (a fileset carries ids and no tags)
    for sh in ns.shards.values():
        assert len(sh.blocks) == BLOCKS
        assert sh.registry.untagged == 0
    assert sum(sh.num_series() for sh in ns.shards.values()) == 240
    for key in ("fileset_build_s", "bootstrap_fs_s", "verify_s", "install_s",
                "index_s", "restart_s", "live_write_s"):
        assert facts[key] > 0, key


def test_a_run_is_correct_and_every_range_starts_past_the_boundary(run):
    from harness import datagen, schedule

    cell, m = run.cell, run.m
    checks, attempted, failed = run.check(m)
    assert attempted > 10 and failed == 0
    assert all(v <= lim for _n, v, lim in checks), checks
    names = [n for n, _v, _l in checks]
    assert names[6:10] == ["readback_mismatched", "reads_failed",
                           "block_starts_not_covered",
                           "readback_pairs_compared_at_least"]
    ref = spec.load_part("reference", cell.reference)
    t0_s = datagen.T0 // datagen.S
    reqs = schedule.requests_for(cell.to_wire(), run.seed,
                                 schedule.n_requests(cell.traffic, m.seconds))
    past = [ref.starts_past_first_index_block(
        cell.classes[r["cls"]], cell.config, r, t0_s) for r in reqs]
    assert sum(past) > len(past) / 2      # most of the window
    result = run.result(m, checks, attempted, failed)
    assert result["correct"] is True
    new = {"cold_decode_dispatches_per_query", "cold_rows_per_query",
           "cold_decode_ms_per_query", "block_cache_evictions_per_query",
           "blocks_read_per_series", "merge_us_per_series", "bootstrap_fs_s",
           "fileset_build_s"}
    assert new <= set(result["metrics"]), sorted(result["metrics"])
    # the cell's own list (35 readings, each under the one name it has in
    # every cell that reports it): the device trace's two read nothing on
    # the CPU, and three seconds at 6/s may draw no range that ends in the
    # open buffer's two minutes of the last four hours (no query runs on
    # the interpreter: the cell reports no interpreter metric)
    want = {m_["name"] for m_ in cell.per_layer}
    assert len(want) == 35 and not any(n.endswith(".deep") for n in want)
    assert want - set(result["metrics"]) <= {
        "decode_roofline", "device_idle_share.query",
        "buffer_index_hit_share"}


def test_a_folded_reading_reads_what_its_twin_read(run, tmp_path):
    """benchmark/tools/fold_check.py on this window: an older tree in
    which the cell's shared readings were forwarding twins (`<name>.deep`,
    one line: the base's reader) reads, on the one Measurement, what this
    tree's line carries under the folded names; and a twin that reads
    something else is told apart."""
    import json
    import shutil
    import sys

    sys.path.insert(0, os.path.join(spec.BENCH_DIR, "tools"))
    import fold_check

    folded = {old: new for old, new in fold_check.folded_names().items()
              if old.endswith(".deep")}
    assert len(folded) == 18
    bench = spec.load_benchmark()
    by_name = {m_["name"]: m_ for m_ in bench["per_layer"]}
    lm = tmp_path / "benchmark" / "layer_metrics"
    shutil.copytree(os.path.join(spec.BENCH_DIR, "layer_metrics"), lm)
    for old, new in folded.items():
        base = by_name[new]
        assert CELL in base["workloads"]
        base["workloads"] = [w for w in base["workloads"] if w != CELL]
        bench["per_layer"].append(dict(base, name=old, workloads=[CELL]))
        (lm / (old + ".py")).write_text(
            "from harness import spec\n\n"
            f"read = spec.load_reader('layer_metrics', '{new}')\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    m = run.m
    result = run.result(m, *run.check(m))
    rows = fold_check.compare(m, CELL, str(tmp_path), result["metrics"])
    assert len(rows) == 35
    assert {r["old"] for r in rows if r["old"] != r["new"]} == set(folded)
    assert all(r["same"] for r in rows), [r for r in rows if not r["same"]]
    read = [r for r in rows if r["old_value"] is not None]
    assert len(read) >= 32
    (lm / "front_in_ms.deep.py").write_text(
        "def read(m):\n    return -1.0\n")
    rows = fold_check.compare(m, CELL, str(tmp_path), result["metrics"])
    assert [r["old"] for r in rows if not r["same"]] == ["front_in_ms.deep"]
    assert spec.BENCH_DIR == os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))


@pytest.mark.parametrize("control,rows", [
    ("unindexed", {"label_sets_differ", "readback_mismatched",
                   "block_starts_not_covered"}),
    ("stale", {"points_missing_or_extra", "readback_mismatched"}),
])
def test_a_control_comes_out_not_correct(run, control, rows):
    checks, _attempted, _failed = run.check(run.m, control)
    bad = {n for n, v, lim in checks if v > lim}
    assert bad and bad <= rows | {"worst_rel_gap"}, (bad, checks)
    assert bad & rows
