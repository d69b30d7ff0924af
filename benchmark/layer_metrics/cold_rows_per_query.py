"""(series, block) rows a query decoded cold, because the block cache did
not hold their block's planes: `cold_rows_n` on `query.fetch` over the
window's queries."""

from harness import phases, spans


def read(m):
    fetches = spans.named(m.span_trees, "query.fetch")
    if not any("cold_rows_n" in f["costs"] for f in fetches):
        return None
    n = len(spans.named(m.span_trees, "query.execute_range"))
    return phases.cost(fetches, "cold_rows_n") / n if n else None
