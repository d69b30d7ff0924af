#!/usr/bin/env python
"""Observability smoke: the check_all tier for the tracing / /debug /
self-scrape plane. ONE 2-node clustered run (real RPC between the
coordinator's session and both dbnodes) drives traffic and asserts the
headline guarantees:

  1. ONE cross-process span tree per query: a PromQL fetch shows the
     client -> coordinator/fanout -> dbnode-storage chain (>= 3 hops)
     in /debug/traces, with the dbnode hop GRAFTED from the response
     frame (endpoint-tagged) and carrying storage child spans;
  2. per-span cost attribution: the rpc span carries the QueryScope's
     charges (docs_matched / series_fetched / bytes_read);
  3. a slow-query log entry with cost attribution (threshold forced to
     0 for the run);
  4. self-scrape round trip: instrument counters incremented by REAL
     traffic (query.executed, health state, rpc gate depth) are written
     through the coordinator ingest path into its own dbnodes and read
     back via the PromQL HTTP API;
  5. JAX telemetry: non-empty jit-compile counters after a rate() query
     (the lru_cache jit-builder instrumentation).

The full matrix lives in tests/test_observability.py.

Usage: python scripts/obs_smoke.py [--seed N]
Wall budget: OBS_SMOKE_BUDGET_S (default 10 seconds; the first cold run
pays one-time XLA compiles, persisted to .jax_cache for later runs).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Deterministic tracing for the assertions below, BEFORE m3_tpu imports
# freeze the defaults.
os.environ.setdefault("M3_TPU_TRACE_SAMPLE", "1")
os.environ.setdefault("M3_TPU_SLOW_QUERY_MS", "0")


def _get(url: str):
    with urllib.request.urlopen(url) as r:
        return json.load(r)


def _chain_depth(node: dict) -> int:
    kids = node.get("children") or []
    return 1 + max((_chain_depth(c) for c in kids), default=0)


def _find(node: dict, name: str):
    if node.get("name") == name:
        return node
    for c in node.get("children") or []:
        hit = _find(c, name)
        if hit is not None:
            return hit
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="observability smoke")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    budget_s = float(os.environ.get("OBS_SMOKE_BUDGET_S", "10.0"))
    t_start = time.monotonic()

    # Persist kernel compiles across runs.
    from m3_tpu.utils import compile_cache

    compile_cache.configure()

    from m3_tpu.client.session import Session, SessionOptions
    from m3_tpu.coordinator import SelfScraper, run_clustered
    from m3_tpu.testing.cluster import ClusterHarness

    S = 1_000_000_000
    failures = []

    def check(name, ok, detail=""):
        print(f"  {name:52s} {'ok' if ok else 'FAIL'}"
              f"{('  ' + detail) if detail else ''}")
        if not ok:
            failures.append(name)

    harness = ClusterHarness(n_nodes=2, replica_factor=2, num_shards=4)
    session = Session(harness.topology, SessionOptions(timeout_s=10.0))
    coord = run_clustered(session, kv_store=harness.kv,
                          clock=harness.clock)
    try:
        t0 = harness.clock.now_ns

        # ---- traffic: writes via the ingest path, reads via PromQL HTTP
        for i in range(8):
            coord.writer.write(
                {b"__name__": b"obs_metric", b"host": b"h%d" % (i % 2)},
                t0 - (8 - i) * 10 * S, float(i))
        rng = _get(f"{coord.endpoint}/api/v1/query_range?query=obs_metric"
                   f"&start={t0 // S - 120}&end={t0 // S}&step=10")
        n_series = len(rng["data"]["result"])
        check("query served over HTTP", n_series >= 2,
              f"series={n_series}")

        # rate() exercises the temporal jit builders (telemetry pt. 5)
        _get(f"{coord.endpoint}/api/v1/query_range?"
             f"query=rate(obs_metric%5B1m%5D)"
             f"&start={t0 // S - 120}&end={t0 // S}&step=10")

        # ---- 1+2: one cross-process span tree, >= 3 hops, cost-tagged
        traces = _get(f"{coord.endpoint}/debug/traces")
        roots = [t for t in traces["traces"]
                 if t["name"] == "query.execute_range"]
        check("query trace recorded", bool(roots), f"roots={len(roots)}")
        tree = roots[-1] if roots else {}
        client_sp = _find(tree, "client.fetch_tagged")
        check("client fanout span in tree", client_sp is not None)
        rpc_sp = _find(client_sp or {}, "rpc.fetch_tagged")
        check("dbnode span GRAFTED under client span", rpc_sp is not None)
        check("grafted span endpoint-tagged (cross-process)",
              bool((rpc_sp or {}).get("tags", {}).get("endpoint")),
              str((rpc_sp or {}).get("tags")))
        check("dbnode storage child under rpc span",
              _find(rpc_sp or {}, "index.query") is not None)
        depth = _chain_depth(tree) if roots else 0
        check("span tree >= 3 hops", depth >= 3, f"depth={depth}")
        one_trace = {tree.get("trace_id")} == {
            s.get("trace_id")
            for s in (tree, client_sp or tree, rpc_sp or tree)}
        check("ONE trace id across all hops", one_trace)
        costs = (rpc_sp or {}).get("costs", {})
        check("per-span QueryScope cost attribution",
              any(k in costs for k in ("docs_matched", "series_fetched",
                                       "bytes_read")), str(costs))

        # ---- 3: slow-query entry with cost attribution
        slow = traces.get("slow", [])
        with_costs = [e for e in slow if e.get("costs")]
        check("slow-query entry with costs", bool(with_costs),
              f"entries={len(slow)}")

        # ---- 4: self-scrape round trip via PromQL against own dbnodes
        scraper = SelfScraper(coord.writer, clock=harness.clock)
        wrote = scraper.scrape_once()
        check("self-scrape wrote samples", wrote > 0, f"samples={wrote}")
        qt = t0 // S + 1
        for metric in ("query_executed", "health_state",
                       "admission_rpc_node_depth"):
            inst = _get(f"{coord.endpoint}/api/v1/query?query={metric}"
                        f"&time={qt}")
            got = inst["data"]["result"]
            check(f"self-scraped {metric} queryable via PromQL",
                  len(got) >= 1, f"series={len(got)}")

        # ---- 5: jit telemetry counters
        dvars = _get(f"{coord.endpoint}/debug/vars")["metrics"]
        compiles = dvars.get("telemetry.jit.compiles", 0)
        builds = dvars.get("telemetry.jit.misses", 0)
        check("jit builder counters non-empty", builds > 0 or compiles > 0,
              f"misses={builds} compiles={compiles}")
    finally:
        coord.close()
        session.close()
        harness.close()

    total = time.monotonic() - t_start
    check("wall budget", total < budget_s, f"{total:.2f}s/{budget_s:.0f}s")
    print(f"obs smoke: {len(failures)} failure(s) in {total:.1f}s "
          f"(seed {args.seed})")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
