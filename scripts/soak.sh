#!/usr/bin/env bash
# Sustained-load soak of one dbnode+coordinator process: continuous HTTP
# writes + a rotating query mix (instant, range, rate, subquery, labels)
# for SOAK_SECONDS (default 30), asserting at the end that
#   * every write succeeded and every query returned success,
#   * the process RSS grew by less than SOAK_MAX_RSS_GROWTH_MB (default
#     256MB) between the post-warmup and final samples — catches
#     unbounded caches, span buffers, or leaked sockets/threads.
# (reference: the long-haul dtests; this is the single-process analog)
# SOAK_TARGET=aggregator soaks the aggregator tier instead: a real
# `services aggregator` process under sustained rawtcp timed-metric
# ingest, asserting continuous flush progress and bounded child RSS.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
if [ "${SOAK_TARGET:-dbnode}" = aggregator ]; then
  exec python scripts/_soak_aggregator.py "$@"
fi
exec python - "$@" <<'PY'
import gc
import json
import os
import resource
import sys
import threading
import time
import urllib.parse
import urllib.request

from m3_tpu.services import load_dict, run_dbnode

SECONDS = float(os.environ.get("SOAK_SECONDS", "30"))
MAX_GROWTH_MB = float(os.environ.get("SOAK_MAX_RSS_GROWTH_MB", "256"))

handle = run_dbnode(load_dict({"coordinator": {}}, "dbnode"))
ep = handle.coordinator.api.endpoint
stop = threading.Event()
stats = {"writes": 0, "write_errs": 0, "queries": 0, "query_errs": 0}
lock = threading.Lock()


def writer(widx):
    i = 0
    while not stop.is_set():
        now = int(time.time())
        body = json.dumps({
            "tags": {"__name__": "soak_metric", "host": f"h{widx}",
                     "core": str(i % 8)},
            "timestamp": now, "value": float(i)}).encode()
        req = urllib.request.Request(ep + "/api/v1/json/write", data=body,
                                     method="POST")
        req.add_header("Content-Type", "application/json")
        try:
            urllib.request.urlopen(req, timeout=10).read()
            with lock:
                stats["writes"] += 1
        except Exception:
            with lock:
                stats["write_errs"] += 1
        i += 1


QUERIES = [
    ("query", "soak_metric"),
    ("query", "scalar(sum(soak_metric))"),
    ("query_range", "rate(soak_metric[1m])"),
    ("query_range", "sum by (host) (soak_metric)"),
    ("query_range", "avg_over_time(soak_metric[2m:30s])"),
]


def querier():
    i = 0
    while not stop.is_set():
        kind, q = QUERIES[i % len(QUERIES)]
        now = int(time.time())
        if kind == "query":
            url = (ep + "/api/v1/query?" + urllib.parse.urlencode(
                {"query": q, "time": now}))
        else:
            url = (ep + "/api/v1/query_range?" + urllib.parse.urlencode(
                {"query": q, "start": now - 120, "end": now, "step": 10}))
        try:
            out = json.load(urllib.request.urlopen(url, timeout=15))
            assert out["status"] == "success"
            with lock:
                stats["queries"] += 1
        except Exception:
            with lock:
                stats["query_errs"] += 1
        i += 1
        time.sleep(0.02)


def rss_mb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


threads = [threading.Thread(target=writer, args=(w,), daemon=True)
           for w in range(3)] + [threading.Thread(target=querier, daemon=True)]
for t in threads:
    t.start()

time.sleep(min(5.0, SECONDS / 3))  # warmup: caches fill, compiles land
gc.collect()
rss_start = rss_mb()
time.sleep(SECONDS)
stop.set()
for t in threads:
    t.join(timeout=10)
gc.collect()
rss_end = rss_mb()
handle.close()

growth = rss_end - rss_start
print(f"soak: {stats['writes']} writes ({stats['write_errs']} errs), "
      f"{stats['queries']} queries ({stats['query_errs']} errs), "
      f"rss {rss_start:.0f} -> {rss_end:.0f} MB (+{growth:.0f})")
assert stats["writes"] > 0 and stats["queries"] > 0
assert stats["write_errs"] == 0, stats
assert stats["query_errs"] == 0, stats
assert growth < MAX_GROWTH_MB, f"RSS grew {growth:.0f}MB > {MAX_GROWTH_MB}MB"
print("SOAK PASS")
PY
