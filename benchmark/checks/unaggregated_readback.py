"""`depth_readback` for the UNAGGREGATED namespace of a deployment whose
coordinator has a namespace list: every acknowledged sample that the
namespace's retention still covers is read back exactly, from its sealed
blocks (read back from filesets by the restart) and from its open buffer
(the live stretch, written through the coordinator's writer) alike.

A seeded sample of (host, scrape) picks from the steps whose one-cadence
window starts inside `now - retention`, so that the resolver has to
answer each from the unaggregated namespace: at least two picks from
every sealed block and two from the open buffer,
`unagg_readback_pairs` pairs in all, each an instant query over HTTP
(its ten series are ten pairs) compared exactly with the seed's value.

Rows, each with a limit of 0: `unagg_readback_mismatched`,
`unagg_reads_failed`, `unagg_blocks_not_covered`. Controls:
`wrong_namespace` (answered from the aggregated namespace, which holds
one scrape in six) and `stale` (a read that misses the open buffer)."""

import json
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen, spec
from harness.cellrun import say


def read_back(run, m, control=None) -> dict:
    cell, cfg, server = m.cell, m.cell.config, run.server
    handle = server.handle
    ref = spec.load_part("reference", "aggns_ref")
    setup = cell.traffic["setup"]
    fields = cfg["schema"]["fields"]
    nf = len(fields)
    name = cfg["schema"]["measurement"]
    cadence = int(cfg["cadence_s"])
    steps, live = int(setup["load_steps"]), int(setup["live_steps"])
    per = int(setup["block_steps"])
    win = handle.resolution_ns // datagen.S // cadence
    raw = next(ns for ns in ref.namespaces(cfg) if not ns["aggregated"])
    # a pick's fetch is (ts - cadence, ts]: it has to start inside the
    # retention for the rule to name the unaggregated namespace
    first = max(steps - live - int(setup["unagg_sealed_blocks"]) * per,
                steps + 1 - raw["retention_s"] // cadence)
    groups = [np.arange(max(lo, first), lo + per)
              for lo in range(steps - live - int(setup["unagg_sealed_blocks"])
                              * per, steps - live, per)]
    groups.append(np.arange(steps - live, steps))       # the open buffer
    want = int(cell.traffic.get("unagg_readback_pairs", 300))
    per_group = max(2, -(-want // (nf * len(groups))))
    rng = np.random.default_rng([run.seed & 0xFFFFFFFF, run.seed >> 32, 43])
    out = {"pairs": 0, "unagg_readback_mismatched": 0,
           "unagg_reads_failed": 0}
    compared = [0] * len(groups)
    shown = 0
    for g, members in enumerate(groups):
        for _ in range(per_group):
            step = int(rng.choice(members))
            host = int(rng.integers(0, cfg["scale"]))
            ts = int(datagen.step_ts(cfg, step) // datagen.S)
            q = 'max_over_time(%s{hostname="host_%d"}[%ds])' % (name, host,
                                                                cadence)
            url = (server.base + "/api/v1/query?"
                   + urllib.parse.urlencode({"query": q, "time": ts}))
            try:
                with urllib.request.urlopen(url, timeout=120) as r:
                    res = json.loads(r.read())["data"]["result"]
            except (OSError, ValueError, KeyError):
                out["unagg_reads_failed"] += 1
                continue
            got = {s["metric"].get("field"): float(s["value"][1])
                   for s in res}
            if control == "wrong_namespace" and step % win:
                got = {}    # the 1-minute namespace has no point there
            if control == "stale" and g == len(groups) - 1:
                got = {}
            for f, fname in enumerate(fields):
                truth = float(server.vals[host * nf + f, step])
                out["pairs"] += 1
                if got.get(fname) != truth:
                    out["unagg_readback_mismatched"] += 1
                    if shown < 5 and control is None:
                        shown += 1
                        say(f"sample (host {host}, step {step}, {fname}): "
                            f"want {truth}, read {got.get(fname)}")
                else:
                    compared[g] += 1
    out["unagg_blocks_not_covered"] = sum(1 for c in compared if c < 2 * nf)
    return out


def check(run, m, control=None):
    rb = read_back(run, m, control)
    say(f"unaggregated read-back: {rb}")
    rows = [("unagg_readback_mismatched", rb["unagg_readback_mismatched"], 0),
            ("unagg_reads_failed", rb["unagg_reads_failed"], 0),
            ("unagg_blocks_not_covered", rb["unagg_blocks_not_covered"], 0),
            ("unagg_readback_pairs_compared_at_least", -rb["pairs"],
             -int(m.cell.traffic.get("unagg_readback_pairs", 300)))]
    return rows, rb["unagg_readback_mismatched"] + rb["unagg_reads_failed"]
