"""What the m3msg ingester's storage write costs an aggregated row:
`coordinator.m3msg.ingest` spans' `write_ns` over their `rows_n`
(`write_batch` a consumed message; PR 27 read 207 us a row from the
embedded sink's write a row)."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "coordinator.m3msg.ingest"),
                      "write_ns", "rows_n", 1e3)
