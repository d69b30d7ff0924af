"""Host-side merging per query (`merge_ns` on `client.fetch_tagged`):
each responder's blocks and buffer into one run a series, then the
responders' runs into one by timestamp."""

from harness import clusterspans


def read(m):
    return clusterspans.per_query(m, "merge_ns", 1e6)
