"""Making one run of a series' parts (clip, concatenate, order, dedup),
mean: `merge_ns` over `series_n` on `query.fetch`.

In `aggns-query-3d` a series' run is 6-7 parts of 120 one-minute points."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "query.fetch"),
                      "merge_ns", "series_n", 1e3)
