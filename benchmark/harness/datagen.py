"""TSBS `cpu-only` data from a seed: hosts, their ten tags, ten `cpu`
fields per host on a 10-second cadence, values by TSBS's clamped random
walk in [0, 100] (normal(0, 1) steps from a uniform start, emitted as
whole numbers, as TSBS writes `usage_user=58i`). numpy only: the server
process makes the truth from it and the load-generator child the
payloads, each from the seed alone.

Series order is host-major: series i is field i % F of host i // F."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

S = 1_000_000_000
T0 = 1_700_000_400 * S   # aligned to the 20-minute block


def host_tags(cfg: dict, seed: int) -> List[Dict[str, str]]:
    """The ten TSBS host tags, drawn per host from the config's choices."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    tags = cfg["schema"]["tags"]
    n = cfg["scale"]
    out = [{"hostname": "host_%d" % h} for h in range(n)]
    region = rng.integers(0, len(tags["region"]), n)
    for h in range(n):
        r = tags["region"][region[h]]
        out[h]["region"] = r
        out[h]["datacenter"] = r + "abc"[rng.integers(0, 3)]
    for key in tags["order"]:
        if key in ("hostname", "region", "datacenter"):
            continue
        choice = tags[key]
        draw = rng.integers(0, len(choice), n)
        for h in range(n):
            out[h][key] = str(choice[draw[h]])
    return out


def series_labels(cfg: dict, seed: int) -> List[Dict[str, str]]:
    """One label set per series: __name__, field and the host's tags."""
    fields = cfg["schema"]["fields"]
    name = cfg["schema"]["measurement"]
    out = []
    for t in host_tags(cfg, seed):
        for f in fields:
            out.append({"__name__": name, "field": f, **t})
    return out


def wire_tags(labels: List[Dict[str, str]]) -> List[Dict[bytes, bytes]]:
    """The label sets as the program's write paths take them."""
    return [{k.encode(): v.encode() for k, v in lab.items()}
            for lab in labels]


def walk(cfg: dict, seed: int, steps: int) -> np.ndarray:
    """Values [series, steps] as uint8 (whole numbers in [0, 100])."""
    n = cfg["scale"] * len(cfg["schema"]["fields"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 13])
    state = rng.random(n, np.float32) * 100.0
    out = np.empty((n, steps), np.uint8)
    chunk = 64
    for lo in range(0, steps, chunk):
        hi = min(lo + chunk, steps)
        inc = rng.standard_normal((hi - lo, n), np.float32)
        for k in range(lo, hi):
            state += inc[k - lo]
            np.clip(state, 0.0, 100.0, out=state)
            out[:, k] = state   # truncation, as int64(float) does
    return out


def step_ts(cfg: dict, k) -> np.ndarray:
    """Timestamp (ns) of step k: every series is scraped on the cadence."""
    return T0 + np.asarray(k, np.int64) * int(cfg["cadence_s"]) * S
