"""Median latency of every query due in the window, on the load
generator's clock, from when the request was due; a request that failed
counts as having taken at least its timeout."""

from harness import reduce


def read(m):
    return reduce.latency_percentile(m, 50)
