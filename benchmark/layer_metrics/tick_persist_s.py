"""Seconds inside persist.write spans within the window: the files of every
snapshot and flush fileset (data, index, summaries, bloom, digests)."""

from harness import phases


def read(m):
    return phases.seconds_in_window(m, "persist.write")
