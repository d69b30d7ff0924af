"""Samples acknowledged through Prometheus remote-write inside the
window, over the window."""

import numpy as np


def read(m):
    ok = (m.rec["status"] == 200) & (m.rec["done"] <= m.window[1])
    return float(np.sum(m.rec["samples"][ok])) / m.seconds
