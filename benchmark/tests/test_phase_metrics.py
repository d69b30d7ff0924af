"""The per-layer metrics that read the program's own phase accounting
(PR 24), at a tiny size on the CPU: a traced run of each cell returns
every one of them as a number, beside every per-layer metric the cell
already had; and on a program that has no such spans the new readers
return None and do not raise."""

import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny  # noqa: E402
from harness import cellrun, spec  # noqa: E402

SEED = 3_000_000_019

NEW = {
    "cpu4k-query-thin": [
        "front_in_ms", "front_out_ms", "query_offcpu_share",
        "fetch_us_per_series", "block_read_us", "plan_bind_ms",
        "plan_device_wait_ms"],
    "cpu4k-ingest": [
        "write_offcpu_share", "write_decode_us_per_sample",
        "write_append_us_per_sample", "commitlog_us_per_sample",
        "ingest_rate_in_tick_share", "tick_snapshot_s", "tick_encode_s",
        "tick_persist_s", "accept_wait_us_per_sample", "snapshot_lag_s"],
}
# no device plane on the CPU; the tiny store warms every block it reads
UNREADABLE_ON_CPU = {"block_cache_hit_share", "encode_roofline",
                     "device_idle_share.query"}


@pytest.fixture(scope="module", params=sorted(NEW))
def traced(request):
    overrides = {}
    if request.param == "cpu4k-ingest":
        overrides["mediator_tick_s"] = 1.0    # several ticks in 6 s
    cell = tiny.cell(request.param, **overrides)
    seconds = 6.0 if request.param == "cpu4k-ingest" else 3.0
    run = cellrun.CellRun(cell, SEED, time.perf_counter_ns(), trace=True,
                          need_chip=False)
    try:
        run.setup(seconds)
        m = run.window(seconds)
        yield cell, m, run.result(m, *run.check(m))
    finally:
        run.close()


def test_every_new_metric_is_a_number(traced):
    cell, _m, result = traced
    if cell.name != "cpu4k-ingest":
        # a 1 s tick may meet a snapshot shape the set-up did not warm:
        # the ingest cell's `correct` is test_reference.py's to judge
        assert result["correct"] is True
    for name in NEW[cell.name]:
        assert name in result["metrics"], (name, sorted(result["metrics"]))
        value = result["metrics"][name]["value"]
        assert value == value and value >= 0, (name, value)


def test_the_metrics_the_cell_had_still_read(traced):
    cell, _m, result = traced
    had = {d["name"] for d in cell.per_layer} - set(NEW[cell.name])
    assert had - set(result["metrics"]) <= UNREADABLE_ON_CPU


def test_shares_and_parts_are_consistent(traced):
    cell, m, result = traced
    v = {k: x["value"] for k, x in result["metrics"].items()}
    if cell.name == "cpu4k-query-thin":
        assert 0 <= v["query_offcpu_share"] <= 100
        # a block read is one part of one series' read
        assert v["block_read_us"] <= v["fetch_us_per_series"]
        # the front's two ends lie inside what the generator saw of it
        lat_ms = float((m.rec["done"] - m.rec["sent"]).mean()) / 1e6
        assert v["front_in_ms"] + v["front_out_ms"] < lat_ms
    else:
        assert 0 <= v["write_offcpu_share"] <= 100
        assert v["commitlog_us_per_sample"] < v["write_append_us_per_sample"]
        # a sender's cycle holds the wait for accept(), the decode and
        # the append, one after the other
        cycle_us = float(((m.rec["done"] - m.rec["sent"])
                          / m.rec["want"]).mean()) / 1e3
        assert (v["accept_wait_us_per_sample"]
                + v["write_decode_us_per_sample"]
                + v["write_append_us_per_sample"]) <= cycle_us * 1.001
        # the tick's parts lie inside the benchmark's own stamps around it
        t0, t1 = m.window
        in_ticks = sum(min(b, t1) - max(a, t0) for _asked, a, b in m.ticks
                       if b > t0 and a < t1) / 1e9
        assert v["tick_snapshot_s"] <= in_ticks * 1.001
        assert v["tick_encode_s"] + v["tick_persist_s"] <= in_ticks * 1.001
        # the first tick is asked for one interval into the window
        first = min(asked for asked, _a, _b in m.ticks if asked >= t0)
        assert first - t0 == int(cell.traffic["mediator_tick_s"] * 1e9)
        assert 0 < v["snapshot_lag_s"] <= m.seconds


def test_new_readers_find_nothing_in_an_older_programs_spans(traced):
    """The parent commit's trees: a root around the handler call alone,
    no CPU time, no phase costs, no tick span."""
    cell, m, _result = traced

    def strip(node, root=False):
        kids = node["children"]
        if root and node["name"].startswith("http."):
            handler = next(c for c in kids if c["name"] == "http.handler")
            kids = handler["children"]
        kids = [strip(c) for c in kids
                if not c["name"].startswith(("remote_write.", "http."))]
        return dict(node, children=kids,
                    tags={k: v for k, v in node["tags"].items()
                          if k not in ("cpu_ns", "samples", "status",
                                       "bytes_out")},
                    costs={k: v for k, v in node["costs"].items()
                           if not k.endswith(("_ns", "_n"))})

    import dataclasses

    old = dataclasses.replace(m, span_trees=[
        strip(t, root=True) for t in m.span_trees
        if t["name"] != "mediator.tick"])
    for name in NEW[cell.name]:
        assert spec.load_reader("layer_metrics", name)(old) is None, name
    # and what the benchmark already read reads on
    if cell.name == "cpu4k-query-thin":
        assert spec.load_reader("layer_metrics",
                                "fetch_ms_per_query")(old) is not None


def test_declarations_mirror_benchmark_json():
    bench = spec.load_benchmark()
    declared = spec.layer_metric_declarations()
    for entry in bench["per_layer"]:
        assert declared[entry["name"]] == entry
    for names in NEW.values():
        for name in names:
            assert name in declared
