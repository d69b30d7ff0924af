"""`aggtier-query-live` at a tiny width on the CPU, end to end: 40 hosts,
the whole aggregation tier booted by the `aggregator-tier` deployment
(KV service, leader/follower aggregator pair, dbnode with the remote
downsampler and the m3msg ingester), 14 hours of the 1-minute namespace
as filesets and commit-log replay, the restart, the 17 live scrapes
through the tier with the graceful handoff, the warm-up's scrape, a
traced window of 35 s in which a minute closes, is flushed, produced,
consumed and written while the deck reads up to now, the checks, the
cell's eight readings of its own, the three controls put in the
program's place, and two of them planted IN the program (an untraced run
each: a follower that emits the window's minute too, a leader that drops
some shards' windows before the emit)."""

import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny  # noqa: E402 - also puts benchmark/ and the repo on sys.path
from harness import cellrun, spec  # noqa: E402

SEED = 5_100_000_061
CELL = "aggtier-query-live"
OWN = {"agg_client_us_per_sample", "agg_add_us_per_sample", "agg_flush_s",
       "agg_sink_us_per_row", "agg_msg_ack_ms_p95", "agg_staleness_s",
       "agg_redeliveries_in_window", "agg_cpu_share"}
# no device plane on the CPU, and 400 series' 14 hours fit the block
# cache: once the warm-up has read them nothing is read cold
UNREADABLE_ON_CPU = {"device_idle_share.aggtier"}
SECONDS = 35.0


def tiny_cell(**traffic_overrides):
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "m3-aggtier-prom-4k",
                         "file": "benchmark/tests/m3-aggtier-tiny.json"}]
    cell = spec.load_cell(CELL, bench)
    cell.traffic.update(dict(
        {"rate_per_s": 6.0,
         "tier_readback": {"history_pairs": 60, "setup_pairs": 60,
                           "window_pairs": 40, "settle_s": 20}},
        **traffic_overrides))
    return cell


@pytest.fixture(scope="module")
def run():
    r = cellrun.CellRun(tiny_cell(), SEED, time.perf_counter_ns(),
                        trace=True, need_chip=False)
    try:
        r.facts = r.setup(SECONDS)
        tiny.warm_decode_buckets(r.server.handle, (
            r.server.handle.aggregated_namespace,))
        r.m = r.window(SECONDS)
        yield r
    finally:
        r.close()


def test_the_cell_is_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "m3-aggtier-prom-4k", "prom-live-agg-12h", 1)
    assert (cell.deployment, cell.setup_via, cell.reference) == (
        "aggregator-tier", "filesets-aggtier", "aggtier_ref")
    assert cell.checks == ["query_answers_agg_frontier", "tier_readback",
                           "write_pace", "mixed_readback",
                           "served_path_verdict"]
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    t, cfg = cell.traffic, cell.config
    assert (t["kind"], t["max_in_flight"], t["end_within_last_s"]) == (
        "query_under_write", 1, 120)
    thin = spec.load_cell("promrw4k-mixed").traffic
    assert all(t[k] == thin[k] for k in ("senders", "samples_per_send",
                                         "write_why"))
    assert t["mediator_tick_s"] == thin["mediator_tick_s"] == 10
    assert t["mix"] == spec.load_cell("aggns-query-3d").traffic["mix"]
    # the window's first scrape step begins 50 s past a minute boundary
    assert (t["setup"]["load_steps"] + 1) % 6 == 5
    assert (t["setup"]["live_steps"], t["setup"]["agg_sealed_blocks"]) == (
        17, 7)
    assert (cfg["scale"], cfg["cadence_s"], cfg["dbnode"]["num_shards"]) == (
        4000, 10, 64)
    assert [(a["instance_id"], a["num_shards"], a["election_ttl"],
             a["flush_interval"], a["buffer_past"], a["flush_handler"])
            for a in cfg["aggregators"]] == [
        (i, 64, "10s", "1s", "10s", "producer") for i in ("agg0", "agg1")]
    assert cfg["dbnode"]["coordinator"]["downsample"][
        "remote_aggregator"]["replicas"] == 2
    assert {"delivery", "handoff", "lateness"} <= set(cfg["guarantees"])
    assert len(cfg["source"]) <= 200 and cfg["architecture"] is None
    own = {m["name"] for m in cell.per_layer if m["layer"]
           == "aggregation tier"}
    assert own == OWN and len(cell.per_layer) == 24


def test_the_set_up_ran_the_tier_across_a_handoff(run):
    facts, tier = run.facts, run.server.tier_minutes
    assert facts["agg_sealed_blocks"] == 7 and facts["agg_filesets"] == 7 * 4
    assert facts["live_rows_ingested"] == 3 * 400
    assert len(tier["stamps_s"]) == 3
    assert tier["first_leader"] != tier["second_leader"]
    assert {tier["first_leader"], tier["second_leader"]} == {"agg0", "agg1"}


def test_a_run_is_correct_and_reads_all_its_own_readings(run):
    m = run.m
    checks, attempted, failed = run.check(m)
    assert attempted > 10 and failed == 0
    by = {n: (v, lim) for n, v, lim in checks}
    assert all(v <= lim for v, lim in by.values()), checks
    assert by["minutes_closed_in_window"][0] == 1
    assert by["tier_window_pairs_compared_at_least"][0] <= -40
    result = run.result(m, checks, attempted, failed)
    assert result["correct"] is True
    got = result["metrics"]
    want = {m_["name"] for m_ in run.cell.per_layer}
    assert want - set(got) <= UNREADABLE_ON_CPU, want - set(got)
    for name in OWN - {"agg_redeliveries_in_window"}:
        assert got[name]["value"] > 0, name
    assert got["agg_redeliveries_in_window"]["value"] >= 0
    # a minute's rows became readable buffer_past (10 s) after its end,
    # plus the wait for the leader's flush check, the flush, the
    # produce, the consume and the write
    assert 10.0 <= got["agg_staleness_s"]["value"] < 20.0


def test_the_window_fetched_from_the_aggregated_namespace_alone(run):
    from harness import spans

    fetches = spans.named(run.m.span_trees, "query.fetch")
    assert fetches and all(f["costs"]["namespaces_n"] == 1 for f in fetches)
    assert not any("block_n{ns=default}" in f["costs"] for f in fetches)
    roots = spans.named(run.m.span_trees, "aggregator.flush")
    assert sum(r["costs"]["rows_n"] for r in roots) == 400


@pytest.mark.parametrize("control,rows", [
    ("lost_window", {"tier_readback_mismatched", "windows_missing"}),
    ("both_flush", {"windows_emitted_by_both"}),
    ("stale", {"tier_readback_mismatched", "points_missing_or_extra",
               "worst_rel_gap", "label_sets_differ"}),
])
def test_a_control_comes_out_not_correct(run, control, rows):
    checks, _attempted, _failed = run.check(run.m, control)
    bad = {n for n, v, lim in checks if v > lim}
    assert bad and bad <= rows, (bad, checks)


# ------------------------------------------- the faults, IN the program


def _follower_emits(handle):
    """Split brain: each instance believes it leads (its campaigns win
    without the lease) and has lost the flush times in KV from sight,
    reading and writing (were the usurper to write them, the leader
    would find the minute flushed and discard it: one emitter again,
    whichever of the two comes first in that second). Planted in both,
    since the lease can change hands where the clock steps into the
    window: whichever follows then emits as well."""
    from m3_tpu.aggregator.election import ElectionState

    for handle_ in handle.aggregators.values():
        agg = handle_.aggregator

        def campaign(election=agg._election):
            election._set(ElectionState.LEADER)
            return ElectionState.LEADER

        agg._election.campaign = campaign
        agg._flush_times.get_many = lambda sids: {sid: {} for sid in sids}
        agg._flush_times.store_many = lambda pending: None


def _leader_drops_shards(handle):
    """An instance collects the closed windows of its first shards that
    hold a series and emits none of them (in both, whichever leads)."""
    from m3_tpu.aggregator.list import FlushBatch

    for handle_ in handle.aggregators.values():
        agg = handle_.aggregator
        for sid in sorted(agg._shards)[:8]:
            for lst in agg._shards[sid].lists.lists():
                def into_nothing(target, batch, already=0,
                                 _collect=lst.collect_into):
                    return _collect(target, FlushBatch(), already=already)

                lst.collect_into = into_nothing


@pytest.fixture(scope="module", params=[
    ("follower_emits", _follower_emits, "windows_emitted_by_both", set()),
    ("leader_drops_shards", _leader_drops_shards, "windows_missing",
     {"tier_readback_mismatched", "points_missing_or_extra",
      "worst_rel_gap"}),
], ids=lambda p: p[0])
def faulty(request):
    _name, plant, row, may_also = request.param
    r = cellrun.CellRun(tiny_cell(), SEED + 1, time.perf_counter_ns(),
                        trace=False, need_chip=False)
    try:
        r.setup(SECONDS)
        plant(r.server.handle)
        yield r, r.window(SECONDS), row, may_also
    finally:
        r.close()


def test_a_fault_planted_in_the_program_comes_out_not_correct(faulty):
    run_, m, row, may_also = faulty
    checks, _attempted, _failed = run_.check(m)
    by = {n: v for n, v, _lim in checks}
    bad = {n for n, v, lim in checks if v > lim}
    # the row that covers every window of the minute names the fault
    assert row in bad and bad <= {row} | may_also, (
        bad, run_.server.handle.tier_log.flushes, checks)
    assert by["minutes_closed_in_window"] == 1
