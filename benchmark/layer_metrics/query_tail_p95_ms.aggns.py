"""The two-tier deployment's tail: `query_tail_p95_ms`'s reading (one request
in flight, open loop: a stall or a backlog in the window shows here and in the
lag, not in the median)."""

from harness import spec

read = spec.load_reader("layer_metrics", "query_tail_p95_ms")
