"""The engine to the last byte: mean of query.execute_range's end to the
request root's end. Render (with the device wait of a lazily read plan
result), serialisation and the socket write.

In `rf3-query-thin` (until PR 50 `front_out_ms.rf3`) this is the
dedicated coordinator's front, after a clustered fetch."""

from harness import phases


def read(m):
    d = [root["end"] - ex["end"] for root in phases.request_roots(m)
         for ex in [phases.descendant(root, "query.execute_range")] if ex]
    return sum(d) / len(d) / 1e6 if d else None
