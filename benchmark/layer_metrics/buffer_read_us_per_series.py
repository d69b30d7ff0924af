"""The open buffer's read per series (ROADMAP A2: `ShardBuffer.read`, a
scan of the open bucket's series column under the shard lock):
query.fetch's `buffer_ns` cost over its `series_n`."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "query.fetch"),
                      "buffer_ns", "series_n", 1e3)
