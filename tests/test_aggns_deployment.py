"""M3's two-tier namespace layout as a deployment, at 40 hosts on the
CPU: a dbnode started by `services.run_dbnode` from a configuration whose
coordinator has the NAMESPACE LIST (`default` unaggregated 10 s / 2 h,
`metrics_1m_72h` aggregated 1 m / 72 h, `downsample.all`), filled by the
benchmark's own set-up (`benchmark/setups/filesets-aggns.py`: both
namespaces' filesets with the program's writers, a restart through the
node's bootstrap, the newest scrapes through
`DownsamplerAndWriter.write_batch` with the downsampler flushing), then
held to the plain reference (`benchmark/reference/aggns_ref.py`) by the
cell's own checks: PromQL over HTTP for ranges inside and beyond the
unaggregated retention, every flushed aggregate read back exactly, one
series one identity in both namespaces, the retention's edge, and the
node restarted once more with both namespaces answering as before. The
checks' controls come out not correct."""

import json
import os
import sys
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT_DIR, "benchmark")
SEED = 2_147_483_693
HOURS = 10
STEPS = HOURS * 360 + 12
S = 1_000_000_000


@pytest.fixture(scope="module")
def run():
    sys.path[:0] = [BENCH_DIR, os.path.join(BENCH_DIR, "tests")]
    try:
        from harness import cellrun, spec

        bench = spec.load_benchmark()
        bench["configs"] = [{"name": "m3-aggns-tsbs-4k",
                             "file": "benchmark/tests/m3-aggns-tiny.json"}]
        cell = spec.load_cell("aggns-query-3d", bench)
        # ten hours and twelve live scrapes: from datagen.T0 the 1-minute
        # namespace's first block holds 99 points and four hold 120
        # (filesets); the last, open at the restart, 21 (commit log)
        cell.traffic["setup"].update(load_steps=STEPS, sealed_blocks=5)
        cell.traffic.update(readback_pairs=200, readback_live_series=40,
                            unagg_readback_pairs=100,
                            boundary=dict(cell.traffic["boundary"],
                                          per_case=3))
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


def _rows(run, name, control=None):
    rows, _failed = run.spec.load_part("checks", name).check(
        run, run.m, control)
    return {n for n, value, limit in rows if value > limit}


def test_both_namespaces_came_back_through_the_nodes_own_bootstrap(run):
    handle, facts = run.server.handle, run.facts
    assert handle.namespace == b"metrics_1m_72h"
    assert handle.unaggregated_namespace == b"default"
    assert facts["sealed_blocks"] == 5 and facts["agg_sealed_blocks"] == 5
    assert facts["filesets"] == 5 * 4
    assert facts["agg_sealed_points"] == 400 * 579
    assert facts["unagg_sealed_blocks"] == 6 and facts["unagg_filesets"] == 24
    for ns in (b"default", b"metrics_1m_72h"):
        claimed = handle.node.bootstrap_results[ns].claimed["filesystem"]
        assert not claimed.is_empty()
    assert facts["agg_points"] == 400 * (STEPS // 6)
    for fact in ("walk_s", "fileset_build_s", "bootstrap_fs_s",
                 "downsample_live_s"):
        assert facts[fact] > 0
    # the configuration's index block reached the namespace
    assert handle.db.namespace(b"metrics_1m_72h").index.block_size_ns == \
        24 * 3600 * S


def test_a_series_is_one_series_in_either_namespace_whichever_way_it_came(run):
    """The set-up's filesets carry a series under the id its rule makes;
    the live downsampler sank the same (host, field) through
    `_on_flushed_columnar`: one registry entry a series a namespace, one
    tag set, and its points from both ways in one run."""
    from m3_tpu.parallel import scope as dscope
    from m3_tpu.query.model import Matcher, MatchType
    from m3_tpu.query.storage import LocalStorage

    handle = run.server.handle
    db = handle.db
    for ns in (b"default", b"metrics_1m_72h"):
        assert sum(sh.num_series()
                   for sh in db.namespace(ns).shards.values()) == 400
    match = (Matcher(MatchType.EQUAL, b"__name__", b"cpu"),
             Matcher(MatchType.EQUAL, b"hostname", b"host_7"))
    now = db.clock()
    with dscope.entered(db.scope):
        raw = LocalStorage(db, b"default").fetch_raw(match, 0, now + 1)
        agg = LocalStorage(db, b"metrics_1m_72h").fetch_raw(match, 0, now + 1)
    assert len(raw) == 10 and set(raw) == set(agg)
    for sid in raw:
        assert raw[sid]["tags"] == agg[sid]["tags"]
        assert b".last" not in sid and raw[sid]["tags"][b"__name__"] == b"cpu"
        # 579 windows from filesets, 21 from the commit log's replay and
        # the two the live downsampler closed
        assert len(agg[sid]["t"]) == STEPS // 6
        assert np.all(np.diff(np.asarray(agg[sid]["t"])) == 60 * S)


@pytest.mark.parametrize("check,control,bad", [
    ("aggregated_readback", None, set()),
    ("aggregated_readback", "wrong_namespace", {"agg_readback_mismatched",
                                                "agg_block_starts_not_covered"}),
    ("aggregated_readback", "stale", {"agg_live_mismatched"}),
    ("unaggregated_readback", None, set()),
    ("unaggregated_readback", "wrong_namespace",
     {"unagg_readback_mismatched", "unagg_blocks_not_covered"}),
    ("unaggregated_readback", "stale", {"unagg_readback_mismatched",
                                        "unagg_blocks_not_covered"}),
    ("resolver_boundary", None, set()),
    ("resolver_boundary", "wrong_namespace", {"boundary_answers_differ"}),
])
def test_the_cells_checks_hold_the_deployment_to_the_reference(
        run, check, control, bad):
    assert _rows(run, check, control) == bad


def _range(run, query, start_s, end_s, step_s):
    url = run.server.base + "/api/v1/query_range?" + urllib.parse.urlencode(
        {"query": query, "start": start_s, "end": end_s,
         "step": "%ds" % step_s})
    with urllib.request.urlopen(url, timeout=120) as r:
        return json.loads(r.read())["data"]["result"]


def test_a_fetch_that_starts_at_the_retentions_edge_finds_every_point(run):
    """`now - 2h` falls two minutes into the unaggregated namespace's
    oldest block: the rule's first case trusts the retention, and the
    node serves the straddling block whole."""
    from m3_tpu.parallel import scope as dscope
    from m3_tpu.query.model import Matcher, MatchType
    from m3_tpu.utils import instrument

    coord = run.server.handle.node.coordinator
    now = run.server.handle.db.clock()
    match = (Matcher(MatchType.EQUAL, b"__name__", b"cpu"),
             Matcher(MatchType.EQUAL, b"hostname", b"host_3"),
             Matcher(MatchType.EQUAL, b"field", b"usage_user"))
    raw = instrument.ROOT.sub_scope("query.resolve").counter("unaggregated")
    before = raw.value()
    with dscope.entered(run.server.handle.db.scope):
        got = coord.engine.storage.fetch_raw(match, now - 7200 * S, now + 1)
        older = coord.engine.storage.fetch_raw(match, now - 7200 * S - 1,
                                               now + 1)
    assert raw.value() == before + 1        # the edge: case 1; past it: not
    (entry,) = got.values()
    t = np.asarray(entry["t"])
    assert t[0] == now - 7200 * S and len(t) == 720
    assert np.all(np.diff(t) == 10 * S)
    want = run.server.vals[3 * 10, STEPS - 720:STEPS].astype(float)
    assert np.asarray(entry["v"]).tolist() == want.tolist()
    (entry,) = older.values()
    assert np.all(np.diff(np.asarray(entry["t"])) == 60 * S)


def test_restarted_once_more_both_namespaces_answer_as_before(run):
    """The live stretch is in the commit log and the open buffers'
    snapshots, not in filesets: a second restart brings it back through
    the bootstrap chain, and the boundary's three cases (10 s points,
    1-minute points, the live downsampler's newest) still equal the
    reference."""
    before = _range(run, 'cpu{hostname="host_5",field="usage_idle"}',
                    run.server.clock[0] // S - 600, run.server.clock[0] // S,
                    10)
    run.server.handle.restart()
    after = _range(run, 'cpu{hostname="host_5",field="usage_idle"}',
                   run.server.clock[0] // S - 600, run.server.clock[0] // S,
                   10)
    assert before == after and len(before) == 1
    assert _rows(run, "resolver_boundary") == set()
    assert _rows(run, "aggregated_readback") == set()
