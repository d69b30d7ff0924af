"""One SealedBlock.read (block-cache hit, decode or disk), mean: the
`block_ns` cost over `block_n` on query.fetch and storage.read spans.

In `aggns-query-3d` a block is a two-hour block of 1-minute points
(resolve, cache lookup, gather, cold decode)."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "query.fetch")
                      + spans.named(m.span_trees, "storage.read"),
                      "block_ns", "block_n", 1e3)
