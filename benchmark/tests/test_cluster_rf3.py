"""The cluster cell at a tiny size on the CPU (24 hosts, 4 shards, three
nodes and a coordinator on virtual devices 1-3 and 0): boot, set-up
through the direct load and the cluster write path, warm, window,
checks, readers and a traced run; every per-layer metric that needs no
device plane reads a number; the set-up's open buffer went through one
RPC a host a batch; and both controls come out not correct."""

import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8")
# the CPU keeps no encoded device buffers unless told to: the check of
# where a node's resident arrays sit needs some
os.environ.setdefault("M3_TPU_BLOCK_CACHE_RETAIN", "1")

import pytest  # noqa: E402

import tiny  # noqa: E402,F401 - puts benchmark/ on the path
from harness import cellrun, spec  # noqa: E402

SEED = 3_000_000_037
CELL = "rf3-query-thin"
# no device plane on the CPU; and 24 hosts never clear the plan floor.
# By the reading's stem, whatever suffix the cell's own list has it under
UNREADABLE_ON_CPU = {"decode_roofline", "device_idle_share", "plan_bind_ms",
                     "plan_device_wait_ms"}


def readable_on_cpu(c) -> set:
    return {d["name"] for d in c.per_layer
            if d["name"].split(".")[0] not in UNREADABLE_ON_CPU}


def cell(**traffic_overrides):
    real = spec.load_benchmark()
    bench = dict(
        real,
        configs=[{"name": "m3-rf3-tiny",
                  "file": "benchmark/tests/m3-rf3-tiny.json"}],
        workloads=[dict(next(w for w in real["workloads"]
                             if w["name"] == CELL),
                        config="m3-rf3-tiny", chips=1)])
    c = spec.load_cell(CELL, bench)
    c.traffic.update(traffic_overrides)
    return c


@pytest.fixture(scope="module", autouse=True)
def session_decode_buckets_warm_as_on_a_chip():
    """On the CPU the session compiles a stacked decode's row bucket where
    a fetch first meets it, which on a cold `.jax_cache` is inside the
    window (`compiles_in_window` 1 since PR 35); on an accelerator a
    geometry's first decode brings every bucket through its compile, in
    the warm-up. The tests run the program's own warm-up (the embedded
    cells' tests warm a node's buckets by hand: `tiny.warm_decode_buckets`)."""
    from m3_tpu.ops import decode_rows

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode_rows, "_compiles_are_dear", lambda: True)
        yield


@pytest.fixture(scope="module")
def traced():
    run = cellrun.CellRun(cell(), SEED, time.perf_counter_ns(), trace=True,
                          need_chip=False)
    try:
        run.setup(3.0)
        m = run.window(3.0)
        checks = run.check(m)
        yield run, m, run.result(m, *checks)
    finally:
        run.close()


def test_the_cell_is_what_the_issue_names():
    c = cell()
    assert (c.deployment, c.setup_via, c.reference) == (
        "cluster-rf3", "cluster-replicas", "replica_ref")
    assert c.checks == ["query_answers", "replica_readback",
                        "served_path_verdict"]
    assert {m["name"] for m in c.end_to_end} == {"query_p50_ms", "setup_s"}
    # the cell's own list: the cluster's readings and every layer it
    # shares with the embedded cells
    assert len(c.per_layer) == 41


def test_a_traced_run_is_correct_and_every_reader_reads(traced):
    _run, _m, result = traced
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = readable_on_cpu(cell())
    assert len(want) == 41 - len(UNREADABLE_ON_CPU)
    assert want <= set(result["metrics"]), want - set(result["metrics"])
    for name in want:
        v = result["metrics"][name]["value"]
        assert v == v and v >= 0, (name, v)
    v = {k: x["value"] for k, x in result["metrics"].items()}
    assert 2 <= v["replicas_merged_per_query"] <= 3
    # the parts of a clustered read lie inside the session's span
    assert (v["tile_decode_ms_per_query"] + v["replica_merge_ms_per_query"]
            <= v["session_fetch_ms_per_query"])
    assert v["fanout_wait_ms_per_query"] <= v["session_fetch_ms_per_query"]
    # and a replica's parts inside the replica's span
    assert (v["node_index_ms_per_replica"] + v["node_read_ms_per_replica"]
            + v["node_tile_ms_per_replica"]
            <= v["node_fetch_ms_per_replica"])
    for row in ("acked_on_fewer_than_2_replicas", "replicas_not_identical",
                "coordinator_readback_mismatched",
                "arrays_off_their_service_device"):
        assert result["checks"][row] == [0.0, 0.0], row


def test_the_open_buffer_went_through_one_rpc_a_host_a_batch(traced):
    run, _m, _result = traced
    c = cell()
    setup = c.traffic["setup"]
    n = c.config["scale"] * len(c.config["schema"]["fields"])
    batches = int(setup["open_steps"]) * -(-n // int(setup["batch_samples"]))
    facts = run.setup_facts
    assert facts["cluster_write_samples"] == n * int(setup["open_steps"])
    from harness import server as server_mod

    moved = {k: v - run.server.counters0.get(k, 0)
             for k, v in server_mod.counters().items()}
    assert moved["client.write_batch.rpcs"] == 3 * batches
    assert moved["client.write_batch.samples"] == \
        facts["cluster_write_samples"]


def test_an_untraced_run_reports_the_two_end_to_end_metrics():
    result = cellrun.run_cell(cell(), SEED + 1, 3.0, False,
                              time.perf_counter_ns(), need_chip=False)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"query_p50_ms", "setup_s"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("control,row", [
    ("drop_replica_write", "acked_on_fewer_than_2_replicas"),
    ("one_replica", "points_missing_or_extra"),
])
def test_a_control_comes_out_not_correct(traced, control, row):
    run, m, _result = traced
    checks, _attempted, _failed = run.check(m, control)
    by_name = {n: (v, lim) for n, v, lim in checks}
    assert by_name[row][0] > by_name[row][1], by_name
    # and by that check alone: every other row holds
    other = "coordinator_readback_mismatched" \
        if control == "drop_replica_write" else None
    for name, (v, lim) in by_name.items():
        if name not in (row, other, "replicas_not_identical",
                        "worst_rel_gap" if control == "one_replica" else row):
            assert v <= lim, (control, name, v, lim)


def test_new_readers_find_nothing_on_a_program_without_the_spans(traced):
    """The parent's trees: no costs on client.fetch_tagged, no
    rpc.fetch_tagged phases, no set-up facts of the cluster."""
    import dataclasses

    _run, m, _result = traced

    def strip(node):
        return dict(node, children=[strip(c) for c in node["children"]],
                    tags={k: v for k, v in node["tags"].items()
                          if k not in ("replicas_merged", "host", "device")},
                    costs={})

    old = dataclasses.replace(
        m, span_trees=[strip(t) for t in m.span_trees
                       if t["name"] != "rpc.fetch_tagged"],
        setup={"series": 1, "samples": 1})
    for name in ("node_fetch_ms_per_replica", "rpc_wire_ms_per_query",
                 "rpc_bytes_per_query", "tile_decode_ms_per_query",
                 "decode_dispatches_per_query", "replica_merge_ms_per_query",
                 "replicas_merged_per_query", "cluster_write_us_per_sample",
                 "replica_load_s", "fanout_wait_ms_per_query",
                 "node_index_ms_per_replica", "node_read_ms_per_replica",
                 "node_tile_ms_per_replica"):
        assert spec.load_reader("layer_metrics", name)(old) is None, name


def test_declarations_mirror_benchmark_json():
    bench = spec.load_benchmark()
    declared = spec.layer_metric_declarations()
    for entry in bench["per_layer"]:
        assert declared[entry["name"]] == entry
