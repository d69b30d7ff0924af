"""Of the remote-write request threads' wall time (http.POST roots, accept to
last byte), the part their threads were not on a CPU: with 8 senders on one
GIL, mostly waiting for it."""

from harness import phases


def read(m):
    return phases.offcpu_share(phases.request_roots(m, "http.POST"))
