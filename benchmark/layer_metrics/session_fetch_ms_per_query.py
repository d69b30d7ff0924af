"""The session's part of a query: the `client.fetch_tagged` spans' wall
time (fan-out and the wait for coverage, then frame decode, tile decodes
and merges on the calling thread), summed, per query."""

from harness import spans


def read(m):
    n = len(spans.named(m.span_trees, "query.execute_range"))
    found = spans.named(m.span_trees, "client.fetch_tagged")
    if not n or not found:
        return None
    return sum(spans.duration(x) for x in found) / n / 1e6
