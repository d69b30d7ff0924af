"""Plan bind per compiled-route query: mean `bind_ns` cost of the
query.execute_range spans tagged route=plan. The bind fetches and grids its
selectors, so it holds those queries' query.fetch spans.

In `rf3-query-thin` that is those queries' whole clustered fetch; in
`aggns-query-3d` a bind over 1-minute points."""

from harness import phases


def read(m):
    d = [ex["costs"]["bind_ns"] for _root, ex in phases.plan_queries(m)
         if "bind_ns" in ex["costs"]]
    return sum(d) / len(d) / 1e6 if d else None
