"""`promrw4k-mixed` at a tiny width on the CPU, end to end: 24 hosts,
each at its seeded millisecond offset, three sealed millisecond-unit
blocks and 72 open steps loaded by `setups/db-write-batch-offsets.py`,
then one window in which `traffic_kinds/query_under_write.py` runs both
generators on one clock: the fleet's remote-writes at their offsets and
the mix's six classes as plain range selectors ending at a moving now.
The checks (`query_answers_frontier` against
`reference/promql_offset_ref.py`, `mixed_readback`, `write_pace`,
`served_path_verdict`), the cell's two readers on a traced run, and what
has to come out not correct: the `stale` control, a reference fed
aligned timestamps, a read-back that misses a sample, and a server that
drops one sample it acknowledged. Not tier-1:
`tests/test_offset_scrapes.py` holds the program's two routes to the
same reference."""

import dataclasses
import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import tiny  # noqa: E402,F401 - puts benchmark/ and the repo on sys.path
from harness import cellrun, datagen, promoffsets, schedule, spec  # noqa: E402

SEED = 3_000_000_061
CELL = "promrw4k-mixed"
SECONDS = 12.0
# no device plane on the CPU: neither of the cell's two readers needs one
TINY = {"rate_per_s": 8.0, "samples_per_send": 30}


def tiny_cell(**traffic_overrides):
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "prom-rw-cpu-4k",
                         "file": "benchmark/tests/prom-rw-tiny.json"}]
    cell = spec.load_cell(CELL, bench)
    cell.traffic.update(dict(TINY, **traffic_overrides))
    return cell


def one_run(trace: bool, before_window=None):
    r = cellrun.CellRun(tiny_cell(), SEED, time.perf_counter_ns(),
                        trace=trace, need_chip=False)
    try:
        r.facts = r.setup(SECONDS)
        tiny.warm_decode_buckets(r.server.handle)
        if before_window is not None:
            before_window(r)
        r.m = r.window(SECONDS)
        yield r
    finally:
        r.close()


@pytest.fixture(scope="module")
def run():
    yield from one_run(trace=True)


def failing(checks):
    return {n for n, v, lim in checks if v > lim}


def test_the_cell_is_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "prom-rw-cpu-4k", "prom-mixed-thin", 1)
    assert (cell.deployment, cell.setup_via, cell.reference) == (
        "dbnode-embedded", "db-write-batch-offsets", "promql_offset_ref")
    assert cell.checks == ["query_answers_frontier", "mixed_readback",
                           "served_path_verdict", "write_pace"]
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["read_offcpu_share",
                                                   "window_packed_share"]
    assert len(spec.load_benchmark()["per_layer"]) == 128
    t = cell.traffic
    thin = spec.load_cell("cpu4k-query-thin").traffic
    assert (t["kind"], t["loop"], t["max_in_flight"]) == (
        "query_under_write", "open", 1)
    for key in ("rate_per_s", "schedule_seed", "end_within_last_s",
                "warm_per_class", "check_sample", "request_timeout_s",
                "allowed_runtime_fallbacks", "warm_first"):
        assert t[key] == thin[key], key
    assert [(m["class"], m["cards"]) for m in t["mix"]] == [
        (m["class"] + "-plain", m["cards"]) for m in thin["mix"]]
    assert {k: t["limits"][k] for k in thin["limits"]} == thin["limits"]
    for c in cell.classes:
        assert ":10s]" not in c["promql"] and "[1" in c["promql"]
    assert (t["senders"], t["samples_per_send"], t["mediator_tick_s"]) == (
        8, 500, 10)
    assert (t["setup"]["load_steps"], t["setup"]["sealed_blocks"],
            t["setup"]["open_steps"]) == (432, 3, 72)
    assert t["setup"]["final_clock_advance_s"] <= 10
    cfg, cpu = cell.config, spec.load_cell("cpu4k-query-thin").config
    for key in ("scale", "cadence_s", "schema", "dbnode", "series"):
        assert cfg[key] == cpu[key], key
    assert cfg["reduced"] == ["retention", "query_range_hours",
                              "replication_factor"]
    assert len(cfg["source"]) <= 200 and cfg["assumed"]
    assert cfg["scrape"]["timestamp_tolerance_ms"] == 2
    assert {"read_your_writes", "no_loss", "commitlog",
            "replication"} <= set(cfg["guarantees"])
    # the fleet's rate: 4,000 samples/s = 8 requests/s of 500
    groups = promoffsets.send_groups(cfg, SEED, 50)
    assert len(groups) == 80 and all(len(h) == 50 for h, _ in groups)
    assert len(groups) * 500 / cfg["cadence_s"] == 4000
    due = [d for _, d in groups]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 10_000


def test_offsets_are_whole_milliseconds_and_the_seeds():
    cfg = tiny_cell().config
    a = promoffsets.offsets_ms(cfg, SEED)
    assert a.dtype == np.int64 and a.shape == (24,)
    assert (0 <= a).all() and (a < 10_000).all()
    assert (a == promoffsets.offsets_ms(cfg, SEED)).all()
    assert (a != promoffsets.offsets_ms(cfg, SEED + 1)).any()
    big = promoffsets.offsets_ms(dict(cfg, scale=4000), SEED)
    assert len(set(big.tolist())) > 3000        # no shared grid
    assert abs(big.mean() - 5000) < 200         # uniform over the interval
    ns = promoffsets.series_offsets_ns(cfg, SEED)
    assert (ns.reshape(24, 10) == (a * promoffsets.MS)[:, None]).all()


def test_a_request_ends_at_the_clocks_now_when_it_is_due():
    cell = tiny_cell()
    kind = spec.load_part("traffic_kinds", "query_under_write")
    reqs = kind.requests_for(cell.to_wire(), SEED, SECONDS)
    due = schedule.arrivals(cell.traffic, SECONDS)
    assert len(reqs) == len(due) > 50
    now0 = int(datagen.step_ts(cell.config, 433)) // datagen.S
    back = np.array([now0 + int(d) - r["end_s"] for r, d in zip(reqs, due)])
    assert (back >= 0).all() and (back <= 120).all() and back.std() > 20
    assert [r["cls"] for r in reqs] == list(
        schedule.class_sequence(cell.traffic, len(due)))
    again = kind.requests_for(cell.to_wire(), SEED, SECONDS)
    assert [r["path"] for r in again] == [r["path"] for r in reqs]
    later = kind.requests_for(cell.to_wire(), SEED, SECONDS, base_step=440)
    assert [r["end_s"] - q["end_s"] for r, q in zip(later, reqs)] == \
        [70] * len(reqs)


def test_the_set_up_sealed_millisecond_blocks_at_the_hosts_offsets(run):
    handle = run.server.handle
    ns = handle.db.namespace(handle.namespace)
    units = {blk.time_unit.name for sh in ns.shards.values()
             for blk in sh.blocks.values()}
    assert units == {"MILLISECOND"}
    assert run.facts["sealed_blocks"] == 3


def test_a_run_is_correct_and_both_generators_ran(run):
    m = run.m
    checks, attempted, failed = run.check(m)
    assert attempted > 50 and failed == 0
    assert not failing(checks), checks
    by = {n: v for n, v, _lim in checks}
    assert by["writes_sent_at_least"] <= -8 and by["writes_failed"] == 0
    assert by["window_samples_read_at_least"] <= -8 * 30
    assert by["commitlog_samples_short"] == 0
    assert by["sealed_decode_series_blocks_at_least"] == -300
    assert by["compiles_in_window"] == 0
    # the readings the full per-layer list has no place for, as rows
    for name in ("write_ack_p50_ms", "write_ack_p99_ms",
                 "buffer_tail_rows_per_read", "buffer_regroups_in_window",
                 "fill_quiet_timeouts_in_window",
                 "read_lock_wait_us_per_query", "frontier_pairs",
                 "answers_took_in_flight", "reading.index_query_ms",
                 "reading.range_window_ms_per_query"):
        assert name in by, name
    result = run.result(m, checks, attempted, failed)
    assert result["correct"] is True
    json.loads(json.dumps(result))         # every limit is a JSON number
    got = result["metrics"]
    assert set(got) == {"read_offcpu_share", "window_packed_share"}
    assert 0 <= got["read_offcpu_share"]["value"] <= 100
    # 9 of 20 cards touch eight hosts: eight grids, the packed layout
    assert 25 <= got["window_packed_share"]["value"] <= 65
    # reads and writes both left roots, with trace ids apart
    ids = {t["trace_id"] for t in m.span_trees
           if t["name"].startswith("http.")}
    assert any(i < 1_000_000 for i in ids) and any(i > 1_000_000 for i in ids)
    rec = m.rec
    assert len(rec["w_i"]) != len(rec["i"]) and int(rec["base_step"][0]) == 433
    assert ((rec["w_sent"] - rec["w_due"]) < 1e9).all()


def test_a_program_without_the_counters_reads_nothing(run):
    m = dataclasses.replace(
        run.m, span_trees=[],
        counters0={k: v for k, v in run.m.counters0.items()
                   if "layouts" not in k and "tail_rows" not in k},
        counters1={k: v for k, v in run.m.counters1.items()
                   if "layouts" not in k and "tail_rows" not in k})
    for name in ("read_offcpu_share", "window_packed_share"):
        assert spec.load_reader("layer_metrics", name)(m) is None
    rows, _ = spec.load_part("checks", "write_pace").check(run, m)
    assert "buffer_tail_rows_per_read" not in {n for n, _v, _l in rows}


def test_an_answer_that_took_an_in_flight_sample_is_sound(run):
    """Put every write of the window in flight for every read that it
    was sent before (its acknowledgement moved past the window's end):
    the (row, step) pairs they reach become pairs of the second kind,
    the served values (which do hold them) stay sound, and are counted;
    `tests/test_offset_scrapes.py` holds the comparison itself to each
    way an answer can be wrong there."""
    m = run.m
    rec = dict(m.rec)
    rec["w_done"] = np.full_like(rec["w_done"], m.t_end + 10**9)
    m2 = dataclasses.replace(m, rec=rec)
    rows, failed = spec.load_part("checks", "query_answers_frontier").check(
        run, m2)
    by = {n: v for n, v, _lim in rows}
    assert failed == 0 and not failing(rows), rows
    assert by["frontier_pairs"] > 0
    assert 0 <= by["answers_took_in_flight"] <= by["frontier_pairs"]


@pytest.mark.parametrize("control,rows", [
    ("stale", {"points_missing_or_extra", "worst_rel_gap",
               "label_sets_differ"}),
    ("aligned", {"points_missing_or_extra", "worst_rel_gap"}),
    ("drop", {"readback_mismatched", "window_samples_missing",
              "window_samples_read_at_least"}),
])
def test_a_control_comes_out_not_correct(run, control, rows):
    checks, _attempted, _failed = run.check(run.m, control)
    bad = failing(checks)
    assert bad and bad <= rows, (bad, checks)


def test_a_server_that_drops_an_acknowledged_sample_is_not_correct():
    def drop_one(r):
        db = r.server.handle.db
        real, state = db.write_batch, {"dropped": 0}

        def write_batch(ns, ids, ts, vals, tags=None, shard_ids=None, **kw):
            if shard_ids is not None and not state["dropped"]:
                state["dropped"] = 1   # acknowledged in full all the same
                ids, ts, vals = ids[:-1], ts[:-1], vals[:-1]
                tags = tags[:-1] if tags is not None else None
                shard_ids = shard_ids[:-1]
            return real(ns, ids, ts, vals, tags=tags, shard_ids=shard_ids,
                        **kw)

        db.write_batch = write_batch

    for r in one_run(trace=False, before_window=drop_one):
        checks, _attempted, failed = r.check(r.m)
        bad = failing(checks)
        assert "window_samples_missing" in bad, checks
        assert bad <= {"window_samples_missing", "commitlog_samples_short",
                       "window_samples_read_at_least",
                       "readback_mismatched", "points_missing_or_extra",
                       "worst_rel_gap"}, bad
        assert failed >= 1
        assert r.result(r.m, checks, 1, failed)["correct"] is False
