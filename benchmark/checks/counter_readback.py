"""A seeded sample of the set-up's acknowledged (host, scrape) pairs
read back over HTTP, each field's counter exactly as it was written:
the guarantee the configuration states (`read_your_writes`), from sealed
blocks and the open buffer alike. `readback.py` holds a write cell to
the same and reads the generator's records of acknowledged writes; a
query cell has none, and its truth is what its set-up installed
(`server.vals`), so this reads that. `traffic.readback_reads` pairs, a
quarter of them from the open buffer's scrapes. Control `drop`: one
field of each read not stored."""

import json
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen


def read_back(run, m, drop: bool = False) -> dict:
    cell, cfg, server, seed = m.cell, m.cell.config, run.server, run.seed
    setup = cell.traffic["setup"]
    fields = cfg["schema"]["fields"]
    nf = len(fields)
    steps = int(setup["load_steps"])
    buffered = int(setup.get("open_steps", 0))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 37])
    reads = int(cell.traffic["readback_reads"])
    picks = [(int(rng.integers(0, steps)), int(rng.integers(0, cfg["scale"])))
             for _ in range(reads - reads // 4)]
    picks += [(int(rng.integers(steps - max(buffered, 1), steps)),
               int(rng.integers(0, cfg["scale"])))
              for _ in range(reads // 4)]
    out = {"pairs": 0, "readback_mismatched": 0, "reads_failed": 0}
    for step, host in picks:
        ts = int(datagen.step_ts(cfg, step) // datagen.S)
        q = 'last_over_time(%s{hostname="host_%d"}[%ds])' % (
            cfg["schema"]["measurement"], host, int(cfg["cadence_s"]))
        url = (server.base + "/api/v1/query?"
               + urllib.parse.urlencode({"query": q, "time": ts}))
        try:
            with urllib.request.urlopen(url, timeout=60) as r:
                res = json.loads(r.read())["data"]["result"]
        except (OSError, ValueError, KeyError):
            out["reads_failed"] += 1
            continue
        got = {s["metric"].get("field"): float(s["value"][1]) for s in res}
        for f, fname in enumerate(fields):
            if drop and f == 0:          # the control: one sample not stored
                got.pop(fname, None)
            out["pairs"] += 1
            if got.get(fname) != float(server.vals[host * nf + f, step]):
                out["readback_mismatched"] += 1
    return out


def check(run, m, control=None):
    rb = read_back(run, m, drop=(control == "drop"))
    rows = [("readback_mismatched", rb["readback_mismatched"], 0),
            ("readback_reads_failed", rb["reads_failed"], 0),
            ("readback_pairs_compared_at_least", -rb["pairs"],
             -int(m.cell.traffic["readback_reads"])
             * len(m.cell.config["schema"]["fields"]))]
    return rows, rb["readback_mismatched"] + rb["reads_failed"]
