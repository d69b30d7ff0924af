"""Of the QUERY requests' wall time (root spans of /api/v1/query_range
and /api/v1/query, accept to last byte; the write requests' roots left
out), the part their threads were not on a CPU (root wall minus the
`cpu_ns` tag): waiting for the GIL, the shard lock, a socket or the
device, while a fleet's remote-writes run beside them.
`query_offcpu_share` is the same over every request root of a cell that
only reads."""

from harness import phases


def read(m):
    return phases.offcpu_share(
        [r for r in phases.request_roots(m, "http.GET /api/v1/query")])
