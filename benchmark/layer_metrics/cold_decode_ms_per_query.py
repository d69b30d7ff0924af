"""Host time inside the cold decode's calls per query (upload, dispatch,
the device's work and the planes back): `cold_decode_ns` on
`query.fetch` over the window's queries.

In `aggns-query-3d` the cold rows are the 1-minute namespace's."""

from harness import phases, spans


def read(m):
    fetches = spans.named(m.span_trees, "query.fetch")
    if not any("cold_decode_ns" in f["costs"] for f in fetches):
        return None
    n = len(spans.named(m.span_trees, "query.execute_range"))
    return phases.cost(fetches, "cold_decode_ns") / n / 1e6 if n else None
