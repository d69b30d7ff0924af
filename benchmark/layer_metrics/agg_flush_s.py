"""Seconds inside the `aggregator.flush` rounds that emitted in the
window (collect, reduce, the producer's publishes, the flush times to
KV), every instance's together."""

from harness import phases


def read(m):
    return phases.seconds_in_window(m, "aggregator.flush")
