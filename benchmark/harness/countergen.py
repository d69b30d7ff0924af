"""TSBS's monotonic counters from a seed: the `net` measurement's fields
as TSBS's `MWD` (monotonic random walk) makes them. Each series starts
at 0 and grows every scrape by the absolute value of a normal draw whose
mean and deviation the configuration states per field
(`schema.field_steps`); what is written is the state truncated to a
whole number, as `int64(state)` is. numpy only, from `--seed` alone:
the same seed gives the same matrix.

Series order is `datagen`'s, host-major: series i is field i % F of
host i // F. A set-up installs the matrix as `server.vals` in place of
`datagen.walk`'s gauges (`setups/db-write-batch-counters.py`); every
check and reference reads the truth there."""

from __future__ import annotations

import numpy as np


def counters(cfg: dict, seed: int, steps: int) -> np.ndarray:
    """Values [series, steps] as int64, each row non-decreasing."""
    fields = cfg["schema"]["fields"]
    spec = cfg["schema"]["field_steps"]
    hosts = int(cfg["scale"])
    mean = np.array([spec[f]["mean"] for f in fields], np.float64)
    dev = np.array([spec[f]["stddev"] for f in fields], np.float64)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 31])
    out = np.empty((hosts * len(fields), steps), np.int64)
    chunk = max(1, (1 << 22) // max(steps, 1))     # rows a pass: ~32 MB
    for lo in range(0, len(out), chunk):
        hi = min(lo + chunk, len(out))
        f = np.arange(lo, hi) % len(fields)
        inc = np.abs(rng.standard_normal((hi - lo, steps)) * dev[f, None]
                     + mean[f, None])
        out[lo:hi] = np.cumsum(inc, axis=1)    # truncation, as int64(float)
    return out
