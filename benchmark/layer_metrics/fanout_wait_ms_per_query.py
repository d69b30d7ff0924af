"""The session's wait for enough responders per query: `fanout_wait_ns` on
`client.fetch_tagged`, from the fan-out's submit to coverage met (the
nodes' reads and the frames' decode on the workers run inside it)."""

from harness import clusterspans


def read(m):
    return clusterspans.per_query(m, "fanout_wait_ns", 1e6)
