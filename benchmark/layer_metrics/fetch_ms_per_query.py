"""Self time of the query.fetch and storage.read spans (the per-series read
loop, block reads and decode dispatch), per query.

In `aggns-query-3d`: the resolve, the routed sweep over 6-7 two-hour
blocks a series, cache lookups, cold decode, merges."""

from harness import spans


def read(m):
    n = len(spans.named(m.span_trees, "query.execute_range"))
    t = sum(spans.self_time(x) for name in ("query.fetch", "storage.read")
            for x in spans.named(m.span_trees, name))
    return t / n / 1e6 if n else None
