"""Seconds inside encode.block spans within the window: pad, prepare, the
device encode's dispatch, and the wait for its words (seal and snapshot)."""

from harness import phases


def read(m):
    return phases.seconds_in_window(m, "encode.block")
