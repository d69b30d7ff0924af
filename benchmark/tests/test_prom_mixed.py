"""`promrw4k-mixed` at a tiny width on the CPU, end to end: 24 hosts,
each at its seeded millisecond offset, three sealed millisecond-unit
blocks and 72 open steps loaded by `setups/db-write-batch-offsets.py`,
then one window in which `traffic_kinds/query_under_write.py` runs both
generators on one clock: the fleet's remote-writes at their offsets and
the mix's six classes as plain range selectors ending at a moving now.
The checks (`query_answers_frontier` against
`reference/promql_offset_ref.py`, `mixed_readback`, `write_pace`,
`served_path_verdict`), the cell's per-layer readers on a traced run (its
own five and the 30 it shares with `cpu4k-query-thin` and
`net4k-query-rate`, under their one name each), and what
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
# no device plane on the CPU, and 240 series never clear the plan's floor
# of 4,096 cells
UNREADABLE_ON_CPU = {"device_idle_share.query", "temporal_roofline",
                     "plan_bind_ms", "plan_device_wait_ms"}
OWN = ["read_offcpu_share", "window_packed_share",
       "window_pack_ms_per_packed_query", "read_lock_wait_us_per_query",
       "buffer_tail_rows_per_read"]
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
    # its own five, and every reading of its control (thin's 26) and of
    # the range selector's layout (net's four) under the one name each has
    listed = [m["name"] for m in cell.per_layer]
    assert [n for n in listed if n in OWN] == OWN
    thin = {m["name"] for m in spec.load_cell("cpu4k-query-thin").per_layer}
    assert len(thin) == 26 and thin <= set(listed)
    assert set(listed) - thin - set(OWN) == {
        "range_window_ms_per_query", "window_samples_seen_share",
        "temporal_device_ms_per_query", "temporal_roofline"}
    assert len(spec.load_benchmark()["per_layer"]) == 91
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
    # the write side's readings stay rows; what the reads pay is the
    # cell's per-layer readings, and a number stands in one place
    for name in ("write_ack_p50_ms", "write_ack_p99_ms", "write_late_max_ms",
                 "buffer_regroups_in_window", "fill_quiet_timeouts_in_window",
                 "frontier_pairs", "answers_took_in_flight"):
        assert name in by, name
    assert not [n for n in by if n.startswith("reading.") or n in OWN]
    result = run.result(m, checks, attempted, failed)
    assert result["correct"] is True
    json.loads(json.dumps(result))         # every limit is a JSON number
    got = result["metrics"]
    want = {d["name"] for d in run.cell.per_layer}
    assert set(got) <= want and want - set(got) <= UNREADABLE_ON_CPU, \
        want - set(got)
    assert got["window_pack_ms_per_packed_query"]["value"] > 0
    assert got["read_lock_wait_us_per_query"]["value"] >= 0
    assert got["buffer_tail_rows_per_read"]["value"] >= 0
    assert got["window_samples_seen_share"]["value"] == 100.0
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
    for name in OWN:
        assert spec.load_reader("layer_metrics", name)(m) is None, name


def test_a_row_of_pr_46_reads_what_its_entry_reads(run, tmp_path):
    """benchmark/tools/fold_check.py --old-rows on this window: an older
    tree whose `write_pace` carried the cell's readings as rows
    (`reading.<name>` read by that tree's reader, and the cell's own
    under their names) reads, on the one Measurement, what this tree's
    line carries as entries; a row that reads something else is told
    apart, and a row that is no reading of the cell is left alone."""
    import shutil
    import sys

    sys.path.insert(0, os.path.join(spec.BENCH_DIR, "tools"))
    import fold_check

    old = tmp_path / "benchmark"
    shutil.copytree(os.path.join(spec.BENCH_DIR, "layer_metrics"),
                    old / "layer_metrics")
    (old / "checks").mkdir()
    (old / "checks" / "write_pace.py").write_text(
        "from harness import spec\n\n\n"
        "def check(run, m, control=None):\n"
        "    rows = [('writes_failed', 0, 0)]\n"
        "    for name in %r:\n"
        "        v = spec.load_reader('layer_metrics', name)(m)\n"
        "        if v is not None:\n"
        "            rows.append(('reading.' + name, float(v), 1e18))\n"
        "    for name in %r:\n"
        "        rows.append((name, float(spec.load_reader(\n"
        "            'layer_metrics', name)(m)), 1e18))\n"
        "    return rows, 0\n" % (
            ["index_query_ms", "range_window_ms_per_query",
             "device_idle_share.query"], OWN[2:]))
    m = run.m
    result = run.result(m, *run.check(m))
    listed = {d["name"] for d in run.cell.per_layer}
    was = fold_check.old_rows(run, m, str(tmp_path), "write_pace")
    rows = fold_check.compare_rows(was, listed, result["metrics"])
    assert [r["new"] for r in rows] == [
        "index_query_ms", "range_window_ms_per_query"] + OWN[2:]
    assert all(r["same"] for r in rows), rows
    assert spec.BENCH_DIR == os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) and spec.ROOT_DIR == os.path.dirname(
        spec.BENCH_DIR)
    was["reading.index_query_ms"] += 1.0
    rows = fold_check.compare_rows(was, listed, result["metrics"])
    assert [r["old"] for r in rows if not r["same"]] == [
        "checks:reading.index_query_ms"]


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


def test_a_window_the_host_held_up_is_late_and_not_wrong(run):
    """The driver's check of PR 50, seed 869126231: the machine stood
    still for 2.3 s, the 11 of 358 writes that came due in its first
    1.3 s went out more than a second late (0.031 against the limit 0.01
    the row had), every one of them acknowledged in full and read back.
    A write that is sent late is late: the row shows it and has no
    limit, and `correct` is for what the writes and the reads say."""
    m = run.m
    rec = dict(m.rec)
    late = np.arange(len(rec["w_sent"])) % 8 == 0
    held = np.where(late, int(2.3e9), 0)
    rec["w_sent"], rec["w_done"] = rec["w_sent"] + held, rec["w_done"] + held
    m2 = dataclasses.replace(m, rec=rec)
    rows, failed = spec.load_part("checks", "write_pace").check(run, m2)
    by = {n: (v, lim) for n, v, lim in rows}
    assert by["writes_late_share"][0] >= 0.1
    assert by["write_late_max_ms"][0] >= 2300
    assert failed == 0 and not failing(rows), rows
    assert {n for n, (_v, lim) in by.items() if lim < 1e18} == {
        "writes_failed", "writes_not_acknowledged_in_full",
        "writes_sent_at_least"}
    # a write that was not acknowledged in full is still what fails it
    rec = dict(m.rec)
    rec["w_samples"] = rec["w_samples"].copy()
    rec["w_samples"][0] -= 1
    rows, failed = spec.load_part("checks", "write_pace").check(
        run, dataclasses.replace(m, rec=rec))
    assert failing(rows) == {"writes_not_acknowledged_in_full"}
    assert failed == 1
    assert "writes_late_share" not in run.cell.traffic["limits"]


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
