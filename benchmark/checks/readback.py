"""Every write of the window acknowledged in full, and a seeded sample
of acknowledged (series, timestamp) pairs read back over HTTP, half from
the set-up's load (sealed block and buffer), half from the window, each
exactly: the guarantee the configuration states (`read_your_writes`).
The truth is the seed's data itself, so no reference file is loaded.
Control `drop`: one sample of each read not stored."""

import json
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen


def read_back(run, m, drop: bool = False) -> dict:
    cell, cfg, server, seed = m.cell, m.cell.config, run.server, run.seed
    t = cell.traffic
    nf = len(cfg["schema"]["fields"])
    per = int(t["samples_per_send"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 29])
    ok = (m.rec["status"] == 200) & (m.rec["samples"] > 0)
    acked = np.stack([m.rec["step"][ok], m.rec["group"][ok]], 1)
    pairs = int(t["readback_reads"])
    picks = []
    load_steps = int(t["setup"]["load_steps"])
    for _ in range(pairs // 2):
        picks.append((int(rng.integers(0, load_steps)),
                      int(rng.integers(0, cfg["scale"]))))
    if len(acked):
        for j in rng.choice(len(acked), min(pairs - len(picks), len(acked)),
                            replace=False):
            step, group = (int(x) for x in acked[j])
            lo = group * per // nf
            hi = min((group + 1) * per // nf, cfg["scale"])
            picks.append((step, int(rng.integers(lo, max(hi, lo + 1)))))
    out = {"pairs": 0, "readback_mismatched": 0, "reads_failed": 0}
    cadence = int(cfg["cadence_s"])
    name = cfg["schema"]["measurement"]
    for step, host in picks:
        ts = int(datagen.step_ts(cfg, step) // datagen.S)
        q = 'max_over_time(%s{hostname="host_%d"}[%ds])' % (name, host, cadence)
        url = (server.base + "/api/v1/query?"
               + urllib.parse.urlencode({"query": q, "time": ts}))
        try:
            with urllib.request.urlopen(url, timeout=60) as r:
                res = json.loads(r.read())["data"]["result"]
        except (OSError, ValueError, KeyError):
            out["reads_failed"] += 1
            continue
        got = {s["metric"].get("field"): float(s["value"][1]) for s in res}
        for f, fname in enumerate(cfg["schema"]["fields"]):
            want = float(server.vals[host * nf + f, step])
            if drop and f == 0:          # the control: one sample not stored
                got.pop(fname, None)
            out["pairs"] += 1
            if got.get(fname) != want:
                out["readback_mismatched"] += 1
    return out


def check(run, m, control=None):
    cell = m.cell
    bad = int(((m.rec["status"] != 200)
               | (m.rec["samples"] != m.rec["want"])).sum())
    rb = read_back(run, m, drop=(control == "drop"))
    rows = [("writes_not_acknowledged_in_full", bad, 0),
            ("readback_mismatched", rb["readback_mismatched"], 0),
            ("readback_reads_failed", rb["reads_failed"], 0),
            ("readback_pairs_compared_at_least", -rb["pairs"],
             -int(cell.traffic["readback_reads"]) * len(
                 cell.config["schema"]["fields"]) // 2)]
    return rows, bad + rb["readback_mismatched"] + rb["reads_failed"]
