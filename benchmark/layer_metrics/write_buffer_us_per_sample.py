"""The shard appends of a request's one batch, per sample: the
`buffer_ns` cost of the remote_write.append spans (routing the batch to
its shards and every Shard.write_batch, `lock_wait_ns` inside it) over
their `samples_n`. The body of write_append_us_per_sample."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "remote_write.append"),
                      "buffer_ns", "samples_n", 1e3)
