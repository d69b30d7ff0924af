"""1 minus the union of device-operation intervals over the traced window,
averaged over the devices used."""



def read(m):
    lo, hi = m.trace_span()
    return 100.0 * (1.0 - m.trace.busy_s(lo, hi) * 1e9 / (hi - lo))
