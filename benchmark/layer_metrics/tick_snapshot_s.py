"""Seconds inside mediator.snapshot spans within the window: every open
bucket densified, encoded and written as a snapshot fileset, each tick."""

from harness import phases


def read(m):
    return phases.seconds_in_window(m, "mediator.snapshot")
