"""How late the generator ran: sent minus due, 95th percentile. A starved
generator must not be read as a fast server.

With one request in flight a due request waits for the one in service,
so this holds the queueing the open loop puts before a slow answer."""

import numpy as np


def read(m):
    lag = (m.rec["sent"] - m.rec["due"]) / 1e6
    return float(np.percentile(lag, 95)) if len(lag) else None
