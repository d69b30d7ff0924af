"""Sealed blocks a fetched series was read from, mean: `block_n` over
`series_n` on `query.fetch` (3 an hour of range; 37 behind a 12-hour
range).

In `aggns-query-3d`: 6-7 two-hour blocks of the aggregated namespace
behind a 12-hour range, where the 10 s namespace would read 36."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "query.fetch"),
                      "block_n", "series_n", 1)
