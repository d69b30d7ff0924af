"""Prometheus's scrape timing from a seed: each target's offset inside
the scrape interval, and what follows from it. numpy only: the server
process stamps the truth with it, the load-generator child the payloads,
the checks the reference, each from the seed alone.

Prometheus scrapes a target at `offset + k * interval`, the offset a
hash of the target's labels and the server's seed modulo the interval
(`scrape/target.go`, `offset()`), and stamps a scrape that began within
its timestamp tolerance (2 ms) of that schedule with the scheduled time:
a target's timestamps are exactly `offset + k * interval`, in whole
milliseconds on the remote-write wire. Here the offset is drawn per host
from `--seed`, uniform over the interval either way; a host's series
share it, as one scrape's samples do."""

from __future__ import annotations

import numpy as np

from . import datagen

MS = 1_000_000


def offsets_ms(cfg: dict, seed: int) -> np.ndarray:
    """int64 [hosts]: whole milliseconds in [0, interval)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 41])
    return rng.integers(0, int(cfg["cadence_s"]) * 1000, int(cfg["scale"]),
                        dtype=np.int64)


def series_offsets_ns(cfg: dict, seed: int) -> np.ndarray:
    """int64 [series]: each series' offset (its host's), in ns; series
    are host-major (`datagen.series_labels`)."""
    return np.repeat(offsets_ms(cfg, seed) * MS,
                     len(cfg["schema"]["fields"]))


def sample_ts_ns(cfg: dict, off_ms, k) -> np.ndarray:
    """Timestamp (ns) of scrape k of a target at offset `off_ms`."""
    return datagen.step_ts(cfg, k) + np.asarray(off_ms, np.int64) * MS


def send_groups(cfg: dict, seed: int, hosts_per_send: int):
    """The fleet's remote-write requests of one scrape cycle: hosts in
    offset order, `hosts_per_send` a request, so that a request holds
    the scrapes that came due together. [(hosts int64[], due_ms)]: a
    request is due when the last of its scrapes is."""
    off = offsets_ms(cfg, seed)
    order = np.argsort(off, kind="stable")
    return [(order[lo:lo + hosts_per_send],
             int(off[order[lo:lo + hosts_per_send]].max()))
            for lo in range(0, len(order), hosts_per_send)]
