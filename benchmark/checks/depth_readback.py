"""The restarted node's guarantee, as far as a run can show it: a sealed
block read back from its fileset answers exactly as before the restart,
at every depth. A seeded sample of (series, timestamp) pairs, at least
`readback_pairs_per_block` from EVERY sealed block start the set-up
wrote and `readback_open_pairs` from the open buffer, `readback_pairs`
in all: each pick is one host at one scrape, read over HTTP as an
instant query (its ten series are ten pairs), and every pair compared
exactly with the seed's value. The truth is the seed's data itself, so
no reference file is loaded.

Rows, each with a limit of 0: `readback_mismatched`, `reads_failed`,
`block_starts_not_covered` (a block start with fewer pairs compared than
it was to have). Controls, put in the program's place: `unindexed` (a
node that indexes a series in its first index block only finds no series
at a timestamp past the first index-block boundary); `stale` (a read
that misses the open buffer)."""

import json
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen, spec
from harness.cellrun import say


def picks(cell, seed: int):
    """[(block index or -1 for the open buffer, step, host)]"""
    t, cfg = cell.traffic, cell.config
    setup = t["setup"]
    nf = len(cfg["schema"]["fields"])
    per, sealed = int(setup["block_steps"]), int(setup["sealed_blocks"])
    steps, open_steps = int(setup["load_steps"]), int(setup["open_steps"])
    want = int(t.get("readback_pairs", 1000))
    open_reads = -(-int(t.get("readback_open_pairs", 100)) // nf)
    per_block = max(-(-int(t.get("readback_pairs_per_block", 15)) // nf),
                    -(-(want - open_reads * nf) // (nf * sealed)))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 37])
    out = []
    for b in range(sealed):
        for _ in range(per_block):
            out.append((b, int(rng.integers(b * per, (b + 1) * per)),
                        int(rng.integers(0, cfg["scale"]))))
    for _ in range(open_reads):
        out.append((-1, int(rng.integers(steps - open_steps, steps)),
                    int(rng.integers(0, cfg["scale"]))))
    return out, per_block * nf


def read_back(run, m, control=None) -> dict:
    cell, cfg, server = m.cell, m.cell.config, run.server
    fields = cfg["schema"]["fields"]
    nf = len(fields)
    name = cfg["schema"]["measurement"]
    cadence = int(cfg["cadence_s"])
    t0_s = datagen.T0 // datagen.S
    boundary_s = None
    if control == "unindexed":
        ref = spec.load_part("reference", "promql_ref_indexed")
        boundary_s = ref.first_index_boundary_s(cfg, t0_s)
    chosen, block_pairs = picks(cell, run.seed)
    sealed = int(cell.traffic["setup"]["sealed_blocks"])
    compared = [0] * sealed
    out = {"pairs": 0, "readback_mismatched": 0, "reads_failed": 0}
    shown = 0
    for b, step, host in chosen:
        ts = int(datagen.step_ts(cfg, step) // datagen.S)
        q = 'max_over_time(%s{hostname="host_%d"}[%ds])' % (name, host,
                                                            cadence)
        url = (server.base + "/api/v1/query?"
               + urllib.parse.urlencode({"query": q, "time": ts}))
        try:
            with urllib.request.urlopen(url, timeout=60) as r:
                res = json.loads(r.read())["data"]["result"]
        except (OSError, ValueError, KeyError):
            out["reads_failed"] += 1
            continue
        got = {s["metric"].get("field"): float(s["value"][1]) for s in res}
        if boundary_s is not None and ts - cadence >= boundary_s:
            got = {}        # the control: nobody indexed past the boundary
        if control == "stale" and b < 0:
            got = {}        # the control: the open buffer missed
        for f, fname in enumerate(fields):
            want = float(server.vals[host * nf + f, step])
            out["pairs"] += 1
            if got.get(fname) != want:
                out["readback_mismatched"] += 1
                if shown < 5 and control is None:
                    shown += 1
                    say(f"pair (host {host}, step {step}, {fname}): want "
                        f"{want}, read {got.get(fname)}")
            elif b >= 0:
                compared[b] += 1
    out["block_starts_not_covered"] = sum(
        1 for c in compared if c < block_pairs)
    return out


def check(run, m, control=None):
    rb = read_back(run, m, control)
    say(f"depth read-back: {rb}")
    rows = [("readback_mismatched", rb["readback_mismatched"], 0),
            ("reads_failed", rb["reads_failed"], 0),
            ("block_starts_not_covered", rb["block_starts_not_covered"], 0),
            ("readback_pairs_compared_at_least", -rb["pairs"],
             -int(m.cell.traffic.get("readback_pairs", 1000)))]
    return rows, rb["readback_mismatched"] + rb["reads_failed"]
