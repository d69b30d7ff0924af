"""The resolver's own time a query: `resolve_ns` on `query.fetch` (the clock read and
the rule over the namespace list) over the window's queries. Nothing on a program
whose fetch carries no such cost."""

from harness import phases, spans


def read(m):
    fetches = spans.named(m.span_trees, "query.fetch")
    if not any("resolve_ns" in f["costs"] for f in fetches):
        return None
    n = len(spans.named(m.span_trees, "query.execute_range"))
    return phases.cost(fetches, "resolve_ns") / n / 1e3 if n else None
