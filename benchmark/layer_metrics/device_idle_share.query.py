"""1 minus the union of device-operation intervals over the traced
window, on the BUSIEST device. On one chip that is the chip. In
`rf3-query-thin` each service has a chip of its own, so an average over
chips would hide the one that works (until PR 50 that cell's reading was
`device_idle_share.rf3`, and this one averaged over the devices used:
the same number wherever one device is used)."""

from harness import trace_reduce


def read(m):
    lo, hi = m.trace_span()
    per = [trace_reduce.total(iv) for iv in m.trace.busy(lo, hi).values()]
    if not per:
        return None
    return 100.0 * (1.0 - max(per) / (hi - lo))
