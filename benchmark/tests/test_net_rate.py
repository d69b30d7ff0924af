"""`net4k-query-rate` at a tiny width on the CPU, end to end: 24 hosts of
TSBS `net` counters (the set-up installs `harness/countergen.py`'s
matrix as the truth), the mix's six classes over HTTP as plain range
selectors in a traced window, the checks against
`reference/promql_counter_ref.py`, every reader of the cell's per-layer
list that has something to read without a chip, and the three controls
each failing. Not tier-1: `tests/test_range_selector_raw_samples.py`
holds the program's two routes to the same reference."""

import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import tiny  # noqa: E402,F401 - puts benchmark/ and the repo on sys.path
from harness import cellrun, countergen, schedule, spec  # noqa: E402

SEED = 3_000_000_061
CELL = "net4k-query-rate"
# no device plane on the CPU
UNREADABLE_ON_CPU = {"temporal_roofline", "device_idle_share.query"}


def tiny_cell(**traffic_overrides):
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "tsbs-net-4k",
                         "file": "benchmark/tests/tsbs-net-tiny.json"}]
    cell = spec.load_cell(CELL, bench)
    cell.traffic.update(dict({"rate_per_s": 8.0}, **traffic_overrides))
    return cell


@pytest.fixture(scope="module")
def run():
    r = cellrun.CellRun(tiny_cell(), SEED, time.perf_counter_ns(),
                        trace=True, need_chip=False)
    try:
        r.facts = r.setup(4.0)
        tiny.warm_decode_buckets(r.server.handle)
        r.m = r.window(4.0)
        yield r
    finally:
        r.close()


def test_the_cell_is_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "tsbs-net-4k", "tsbs-net-rate", 1)
    assert (cell.deployment, cell.setup_via, cell.reference) == (
        "dbnode-embedded", "db-write-batch-counters", "promql_counter_ref")
    assert cell.checks == ["query_answers", "counter_readback",
                           "served_path_verdict"]
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    assert len(cell.per_layer) == 16
    t = cell.traffic
    assert (t["kind"], t["loop"], t["max_in_flight"], t["schedule_seed"]) == (
        "query", "open", 1, 20260927)
    assert [(m["class"], m["cards"]) for m in t["mix"]] == [
        ("net-rate-1-1-1", 6), ("net-rate-1-8-1", 3), ("net-rate-5-1-1", 3),
        ("net-rate-5-8-1", 2), ("net-increase-all-1", 2),
        ("net-increase-all-8", 4)]
    assert len(schedule.deck(t["mix"])) == 20
    assert t["warm_first"] == [{"class": "net-rate-all-warm", "count": 1}]
    thin = spec.load_cell("cpu4k-query-thin").traffic
    for key in ("limits", "end_within_last_s", "warm_per_class",
                "check_sample", "request_timeout_s",
                "allowed_runtime_fallbacks"):
        assert t[key] == thin[key], key
    assert dict(t["setup"], via=None) == dict(thin["setup"], via=None)
    for c in cell.classes:
        assert "[1m:" not in c["promql"] and ":10s]" not in c["promql"]
    cfg, cpu = cell.config, spec.load_cell("cpu4k-query-thin").config
    assert (cfg["scale"], cfg["cadence_s"], len(cfg["schema"]["fields"]),
            cfg["series"]) == (4000, 10, 8, 32000)
    assert cfg["dbnode"] == cpu["dbnode"]
    assert cfg["guarantees"] == cpu["guarantees"]
    assert cfg["schema"]["tags"]["order"][-1] == "interface"
    assert cfg["reduced"] == ["measurements", "retention",
                              "replication_factor"]
    assert len(cfg["source"]) <= 200 and cfg["assumed"]


def test_the_generator_is_monotonic_whole_and_the_seeds():
    with open(os.path.join(spec.BENCH_DIR, "tests", "tsbs-net-tiny.json")) as f:
        cfg = json.load(f)
    a = countergen.counters(cfg, SEED, 372)
    assert a.dtype == np.int64 and a.shape == (24 * 8, 372)
    assert (a[:, 1:] >= a[:, :-1]).all() and (a[:, 0] >= 0).all()
    assert (a == countergen.counters(cfg, SEED, 372)).all()
    assert (a != countergen.counters(cfg, SEED + 1, 372)).any()
    # |N(50, 1)| a scrape for the first four fields, |N(5, 1)| for the rest
    per_step = a[:, -1].reshape(24, 8).mean(axis=0) / 372
    assert np.allclose(per_step[:4], 50, atol=1.0)
    assert np.allclose(per_step[4:], 5, atol=0.5)


def test_the_set_up_installed_the_counters_as_the_truth(run):
    vals = run.server.vals
    assert vals.dtype == np.int64
    assert (vals == countergen.counters(run.cell.config, SEED,
                                        vals.shape[1])).all()
    assert {lab["__name__"] for lab in run.server.labels} == {"net"}
    assert {lab["interface"] for lab in run.server.labels} <= {
        "eth0", "eth1", "eth2", "eth3"}


def test_a_run_is_correct_and_every_raw_sample_was_seen(run):
    m = run.m
    checks, attempted, failed = run.check(m)
    assert attempted > 10 and failed == 0
    assert all(v <= lim for _n, v, lim in checks), checks
    result = run.result(m, checks, attempted, failed)
    assert result["correct"] is True
    got = result["metrics"]
    assert got["window_samples_seen_share"]["value"] == 100.0
    assert got["range_window_ms_per_query"]["value"] > 0
    assert got["temporal_device_ms_per_query"]["value"] > 0
    want = {m_["name"] for m_ in run.cell.per_layer}
    assert want - set(got) <= UNREADABLE_ON_CPU | {
        # 192 series: no query reaches the plan's floor of 4,096 cells
        "plan_bind_ms", "plan_device_wait_ms"}, want - set(got)
    assert got["compiles_in_window.query"]["value"] == 0.0


@pytest.mark.parametrize("control,rows", [
    ("gridded", {"points_missing_or_extra", "worst_rel_gap",
                 "label_sets_differ"}),
    ("bf16", {"worst_rel_gap"}),
    ("stale", {"points_missing_or_extra", "worst_rel_gap"}),
    ("drop", {"readback_mismatched"}),
])
def test_a_control_comes_out_not_correct(run, control, rows):
    checks, _attempted, _failed = run.check(run.m, control)
    bad = {n for n, v, lim in checks if v > lim}
    assert bad and bad <= rows, (bad, checks)
