"""Self time of query.execute_range on the queries the span says ran on the
interpreter (below the plan floor, or a static fallback), per such query.

In `rf3-query-thin` the session's fetch is a child span and is not in
it."""

from harness import spans


def read(m):
    d = [spans.self_time(n)
         for n in spans.named(m.span_trees, "query.execute_range")
         if n["tags"].get("route") == "interpreter"]
    return sum(d) / len(d) / 1e6 if d else None
