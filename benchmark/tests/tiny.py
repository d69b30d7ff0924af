"""A tiny stand-in for BENCHMARK.json: the real mixes, classes and
readers over 24 hosts, so the whole of a run fits a CPU test."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

from harness import spec  # noqa: E402


def bench() -> dict:
    real = spec.load_benchmark()
    cells = [dict(w, config="tsbs-cpu-tiny", chips=1)
             for w in real["workloads"]]
    # the fat mix is no cell yet (PERF.md, Open questions); its classes and
    # the reference's shapes for them are tested all the same
    thin = next(w for w in cells if w["traffic"] == "tsbs-thin")
    cells.append(dict(thin, name="cpu4k-query-fat", traffic="tsbs-fat"))
    for m in real["end_to_end"] + real["per_layer"]:
        if thin["name"] in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["cpu4k-query-fat"]
    # a stand-in deployment: the coordinator configured by the
    # configuration's own block, the store filled through its writer
    ingest = next(w for w in cells if w["traffic"] == "tsbs-remote-write")
    cells.append(dict(ingest, name="tiny-via-coordinator",
                      config="tsbs-cpu-tiny-coordinator",
                      traffic="tiny-via-coordinator"))
    for m in real["end_to_end"] + real["per_layer"]:
        if ingest["name"] in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["tiny-via-coordinator"]
    return dict(real, workloads=cells, configs=[
        {"name": name, "file": "benchmark/tests/%s.json" % name}
        for name in ("tsbs-cpu-tiny", "tsbs-cpu-tiny-coordinator")])


def cell(workload: str, **traffic_overrides):
    c = spec.load_cell(workload, bench())
    c.traffic.update(traffic_overrides)
    return c


def warm_decode_buckets(handle, namespaces=None):
    """On the CPU the program compiles a decode shape where a read first
    meets it (on an accelerator a geometry's first cold read brings every
    row bucket through its compile); which bucket a read needs depends on
    what the cache holds, so the test meets them all before the window,
    in the handle's namespace or in each of `namespaces`."""
    from m3_tpu.storage import block, block_cache

    cache = block_cache.active()
    for name in namespaces or (handle.namespace,):
        ns = handle.db.namespace(name)
        blk = next(iter(next(iter(ns.shards.values())).blocks.values()))
        for rows in block.ROW_BUCKETS:
            at = [0] * rows
            block.decode_rows(blk.words[at], blk.npoints[at], blk.window,
                              blk.time_unit.nanos)
        # where the cache retains a block's encode on the devices
        # (M3_TPU_BLOCK_CACHE_RETAIN=1, which test_cluster_rf3.py sets for
        # the whole session while it is collected, with 8 virtual devices),
        # a rung-sized encode is decoded where it lies: a program of its
        # own a sharding, which would else compile in a session's first
        # window that reads such a block
        seen = set()
        for sh in ns.shards.values():
            for b in sh.blocks.values():
                enc = cache.encoded(b) if cache is not None else None
                key = enc and (enc[0].shape, str(enc[0].sharding))
                if enc and enc[0].shape[0] in block.ROW_BUCKETS \
                        and key not in seen:
                    seen.add(key)
                    block.decode_rows(enc[0], enc[1], b.window,
                                      b.time_unit.nanos)
