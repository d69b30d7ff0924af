"""One general generator of requests from a traffic file and the seed.

What is fixed by the traffic file's `schedule_seed` and identical in
every run: the arrival offsets (Poisson at the mix's rate) and the class
of every request, dealt from whole shuffled decks that hold each class
in exactly its share. What `--seed` draws: each request's hosts, fields
and end time (and, elsewhere, the data). So parent and change are
offered the same bursts of the same classes at the same instants."""

from __future__ import annotations

import urllib.parse
from typing import Dict, List

import numpy as np

from . import datagen

S = datagen.S


def deck(mix: List[dict]) -> List[int]:
    """Class indices of one deck: `cards` of each class of the mix."""
    out: List[int] = []
    for i, m in enumerate(mix):
        out += [i] * int(m["cards"])
    return out


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Offsets (s) of the open loop's arrivals inside [0, seconds): the
    same prefix of one Poisson stream for any `seconds`."""
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 1])
    rate = float(traffic["rate_per_s"])
    out = []
    t = 0.0
    while True:
        gaps = rng.exponential(1.0 / rate, 4096)
        ts = t + np.cumsum(gaps)
        out.append(ts)
        t = float(ts[-1])
        if t >= seconds:
            break
    ts = np.concatenate(out)
    return ts[ts < seconds]


def class_sequence(traffic: dict, n: int) -> np.ndarray:
    """Class index of each of n requests: whole decks, each shuffled."""
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 2])
    d = np.array(deck(traffic["mix"]), np.int64)
    out = []
    while sum(len(o) for o in out) < n:
        out.append(rng.permutation(d))
    return np.concatenate(out)[:n] if out else np.zeros(0, np.int64)


def n_requests(traffic: dict, seconds: float) -> int:
    """Open loop: the arrivals inside the window. Closed loop: the
    replay list, cycled until the window ends."""
    if traffic["loop"] == "open":
        return len(arrivals(traffic, seconds))
    return int(traffic["replay_len"])


def _draw(rng, cls: dict, cfg: dict, hold_end_s: int, traffic: dict) -> dict:
    fields = cfg["schema"]["fields"]
    want = cls.get("draw", {})
    p: Dict[str, object] = {}
    nh = want.get("hosts", 0)
    p["hosts"] = (sorted(int(h) for h in rng.choice(cfg["scale"], nh,
                                                    replace=False))
                  if nh else None)        # None: every host
    nf = want.get("fields", 0)
    if "fixed_fields" in cls:
        p["fields"] = list(cls["fixed_fields"])
    else:
        p["fields"] = (sorted(int(f) for f in rng.choice(len(fields), nf,
                                                         replace=False))
                       if nf else None)   # None: every field
    p["end_s"] = int(hold_end_s - rng.integers(
        0, int(traffic["end_within_last_s"]) + 1))
    return p


def build_request(cls: dict, cfg: dict, p: dict) -> dict:
    """Fill the class's PromQL template and make the HTTP path."""
    fields = cfg["schema"]["fields"]
    q = cls["promql"]
    if p["hosts"] is not None:
        q = q.replace("$hosts", "|".join("host_%d" % h for h in p["hosts"]))
    if p["fields"] is not None:
        q = q.replace("$fields", "|".join(fields[f] for f in p["fields"]))
    end = p["end_s"]
    if cls["endpoint"] == "query_range":
        start = end - int(cls["range_s"])
        step = int(cls["step_s"])
        path = "/api/v1/query_range?" + urllib.parse.urlencode(
            {"query": q, "start": start, "end": end, "step": "%ds" % step})
    else:
        start, step = end, 1
        path = "/api/v1/query?" + urllib.parse.urlencode(
            {"query": q, "time": end})
    return {"query": q, "path": path, "start_s": start, "end_s": end,
            "step_s": step, **p}


def requests_for(cell: dict, seed: int, n: int, salt: int = 0) -> List[dict]:
    """The cell's first n requests: class from the schedule (open loop)
    or the equal-share cycle (closed-loop replay), parameters from the
    seed. `salt` keeps warm-up draws apart from the window's."""
    traffic, cfg, classes = cell["traffic"], cell["config"], cell["classes"]
    if traffic["loop"] == "open":
        seq = class_sequence(traffic, n)
    else:
        d = deck(traffic["mix"])
        seq = np.array([d[i % len(d)] for i in range(n)], np.int64)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17, salt])
    hold_end_s = int(datagen.step_ts(
        cfg, int(traffic["setup"]["load_steps"]) - 1) // S)
    out = []
    for i in range(n):
        cls = classes[int(seq[i])]
        r = build_request(cls, cfg, _draw(rng, cls, cfg, hold_end_s, traffic))
        r["cls"] = int(seq[i])
        out.append(r)
    return out
