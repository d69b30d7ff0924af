"""Device dispatches of the embedded read path's cold decode per query:
`cold_dispatch_n` on `query.fetch` (one a geometry a fetch, and one more
for every 1,024 rows past the first; storage/read_batch.py), over the
window's queries. None on a program whose fetch carries no such cost."""

from harness import phases, spans


def read(m):
    fetches = spans.named(m.span_trees, "query.fetch")
    if not any("cold_dispatch_n" in f["costs"] for f in fetches):
        return None
    n = len(spans.named(m.span_trees, "query.execute_range"))
    return phases.cost(fetches, "cold_dispatch_n") / n if n else None
