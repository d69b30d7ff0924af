"""`aggns-query-3d` at a tiny width on the CPU, end to end: 40 hosts, a
coordinator with the two-tier namespace list, 10 hours and 12 live
scrapes (the 1-minute namespace's five closed blocks as filesets, its
open block through the commit log, the unaggregated namespace's six),
the restart, the live stretch through the coordinator's writer with the
downsampler flushing, the mix's four classes over HTTP in a traced
window, the checks and every reader of the cell's own per-layer list
(its three `.aggns` readings, and each layer it shares with another cell
under the name that reading has there). The window's ranges end
within the last 7 hours here (54 in the cell), so every query still
resolves to the aggregated namespace. Not tier-1: `tests/
test_aggns_deployment.py` is the set-up and the checks alone."""

import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny  # noqa: E402 - also puts benchmark/ and the repo on sys.path
from harness import cellrun, spec  # noqa: E402

SEED = 3_000_000_061
CELL = "aggns-query-3d"
STEPS = 10 * 360 + 12
LISTED = 40          # the cell's per-layer readings (17 until PR 41)
# no device plane on the CPU; and ten hours of 40 hosts fit the block
# cache, so once the warm-up has read them no decode call is left for the
# window (the call's anatomy reads nothing)
UNREADABLE_ON_CPU = {"decode_roofline", "device_idle_share.query",
                     "decode_layout_ms_per_query",
                     "decode_fetch_ms_per_query"}


def tiny_cell(**traffic_overrides):
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "m3-aggns-tsbs-4k",
                         "file": "benchmark/tests/m3-aggns-tiny.json"}]
    cell = spec.load_cell(CELL, bench)
    cell.traffic["setup"].update(load_steps=STEPS, sealed_blocks=5)
    cell.traffic.update(dict(
        {"rate_per_s": 6.0, "end_within_last_s": 7 * 3600,
         "readback_pairs": 200, "readback_live_series": 40,
         "unagg_readback_pairs": 100,
         "boundary": dict(cell.traffic["boundary"], per_case=3)},
        **traffic_overrides))
    return cell


@pytest.fixture(scope="module")
def run():
    r = cellrun.CellRun(tiny_cell(), SEED, time.perf_counter_ns(),
                        trace=True, need_chip=False)
    try:
        r.facts = r.setup(3.0)
        tiny.warm_decode_buckets(r.server.handle, (
            r.server.handle.namespace,
            r.server.handle.unaggregated_namespace))
        r.m = r.window(3.0)
        yield r
    finally:
        r.close()


def test_the_cell_is_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "m3-aggns-tsbs-4k", "tsbs-range-aggns", 1)
    assert (cell.deployment, cell.setup_via, cell.reference) == (
        "dbnode-aggns", "filesets-aggns", "aggns_ref")
    assert cell.checks == ["query_answers", "aggregated_readback",
                           "unaggregated_readback", "resolver_boundary",
                           "served_path_verdict"]
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    t = cell.traffic
    assert "warm_first" not in t and t["end_within_last_s"] == 194400
    assert (t["max_in_flight"], t["schedule_seed"]) == (1, 20260930)
    assert [(m["class"], m["cards"]) for m in t["mix"]] == [
        ("single-groupby-1-1-12-agg", 5), ("single-groupby-5-1-12-agg", 3),
        ("cpu-max-all-1-8h-agg", 3), ("cpu-max-all-8-8h-agg", 1)]
    assert t["setup"]["load_steps"] == 72 * 360 + 12
    assert cell.config["reduced"] == ["unaggregated_retention",
                                      "replication_factor"]
    assert cell.config["scale"] == 4000 and cell.config["cadence_s"] == 10
    members = cell.config["dbnode"]["coordinator"]["namespaces"]
    assert [(m["namespace"], m["type"], m["retention"]) for m in members] == [
        ("default", "unaggregated", "2h"),
        ("metrics_1m_72h", "aggregated", "72h")]


def test_a_run_is_correct_and_every_query_resolved_to_the_aggregated(run):
    m = run.m
    checks, attempted, failed = run.check(m)
    assert attempted > 10 and failed == 0
    assert all(v <= lim for _n, v, lim in checks), checks
    result = run.result(m, checks, attempted, failed)
    assert result["correct"] is True
    got = result["metrics"]
    assert got["agg_route_share.aggns"]["value"] == 100.0
    assert got["resolve_us_per_query.aggns"]["value"] > 0
    assert got["downsample_live_s.aggns"]["value"] > 0
    # 12 h of 1-minute points in 2-hour blocks: 6-7 a series (8 h: 4-5);
    # fewer here, where a range may reach back past the ten hours held
    assert 2 < got["blocks_read_per_series"]["value"] < 7.5
    # every reading of the cell's own list reads here, those four aside
    want = {m_["name"] for m_ in run.cell.per_layer}
    assert len(want) == LISTED and want - set(got) <= UNREADABLE_ON_CPU, \
        want - set(got)
    assert got["fileset_build_s"]["value"] > 0
    assert got["bootstrap_fs_s"]["value"] > 0
    assert got["query_tail_p95_ms"]["value"] > 0
    assert got["compiles_in_window.query"]["value"] == 0.0


def test_the_reads_say_which_namespace_they_served(run):
    from harness import spans

    fetches = spans.named(run.m.span_trees, "query.fetch")
    assert fetches and all(f["costs"]["namespaces_n"] == 1 for f in fetches)
    assert all("block_n{ns=metrics_1m_72h}" in f["costs"] for f in fetches)
    assert not any("block_n{ns=default}" in f["costs"] for f in fetches)


def test_the_live_stretch_left_downsample_flush_roots(run):
    roots = [s for s in run.tracer._recent if s.name == "downsample.flush"]
    assert len(roots) == 2          # two closed minutes
    for r in roots:
        assert r.costs["rows_n"] == 400 and r.costs["policies_n"] == 1
        assert r.costs["sink_ns"] > 0


@pytest.mark.parametrize("control,rows", [
    ("wrong_namespace", {"points_missing_or_extra", "worst_rel_gap",
                         "label_sets_differ",
                         "agg_readback_mismatched",
                         "agg_block_starts_not_covered",
                         "unagg_readback_mismatched",
                         "unagg_blocks_not_covered",
                         "boundary_answers_differ"}),
    ("stale", {"points_missing_or_extra", "agg_live_mismatched",
               "unagg_readback_mismatched", "unagg_blocks_not_covered",
               "boundary_answers_differ"}),
])
def test_a_control_comes_out_not_correct(run, control, rows):
    """(`bf16` tells nothing apart here, as in the 12-hour cell: every
    answer is a maximum of whole numbers up to 100, which bfloat16 holds
    exactly; the controls differ by series, points and values.)"""
    checks, _attempted, _failed = run.check(run.m, control)
    bad = {n for n, v, lim in checks if v > lim}
    assert bad and bad <= rows, (bad, checks)
