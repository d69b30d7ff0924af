"""Sealed blocks a fetched series was read from, mean: `block_n` over
`series_n` on `query.fetch` (3 an hour of range; 37 behind a 12-hour
range)."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "query.fetch"),
                      "block_n", "series_n", 1)
