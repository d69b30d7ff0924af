"""The dashboard tail in front of a cluster: the 95th percentile of the
window's latencies, from when each request was due."""

from harness import reduce


def read(m):
    return reduce.latency_percentile(m, 95)
