"""Accept to the engine: mean of the request root's start (the accept stamp)
to its query.execute_range span's start. The handler thread's start, the
header parse, the body read and the route match.

In `rf3-query-thin` (until PR 50 `front_in_ms.rf3`) this is the
dedicated coordinator's front, before a clustered fetch."""

from harness import phases


def read(m):
    d = [ex["start"] - root["start"] for root in phases.request_roots(m)
         for ex in [phases.descendant(root, "query.execute_range")] if ex]
    return sum(d) / len(d) / 1e6 if d else None
