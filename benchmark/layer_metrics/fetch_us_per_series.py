"""The per-series read loop, per series: query.fetch's `read_ns` cost (the
whole loop of LocalStorage.fetch_raw) over its `series_n`."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "query.fetch"),
                      "read_ns", "series_n", 1e3)
