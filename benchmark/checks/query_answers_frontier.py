"""`query_answers` for reads under writes: every read of the window
answered, and a seeded sample of the answers equal to what the plain
reference (`reference/promql_offset_ref.py`: per series, over each
series' own timestamps) computes, with the write frontier held exactly.

What a read may have seen is decided by the generator's own stamps, all
on one clock: a write request acknowledged in full before the read was
SENT is acknowledged to it (every one of its samples must be in the
answer); one sent before the read was DONE and not acknowledged before
it was sent is in flight (each of its samples may be in the answer or
not); one sent later cannot be in it. An output (row, step) that no
in-flight sample reaches must equal the reference over the acknowledged
samples; one that in-flight samples reach (at most one a series: 10 s
between scrapes against milliseconds to acknowledge) must equal the
reference over the acknowledged samples joined with SOME subset of them
— for these MAX classes the acknowledged maximum, or an in-flight value
above it — and nothing else. No step is skipped. The set-up's scrapes
and the warm-up's are acknowledged to every read.

Rows beyond `query_answers`' own: `frontier_pairs` ((row, step) pairs of
the second kind) and `answers_took_in_flight` (pairs whose served value
was an in-flight sample's), without a limit. Controls, put in the
program's place: `bf16`, `stale` (a read that misses the open buffer)
and `aligned` (a reference fed the shared grid, offset 0)."""

import numpy as np

from harness import datagen, promoffsets, spec
from harness.cellrun import say

NO_LIMIT = 1e18


def compare_answers(run, m, control=None) -> dict:
    ref = spec.load_part("reference", m.cell.reference)
    if control not in ref.CONTROLS:     # another check's control
        control = None
    kind = spec.load_part("traffic_kinds", m.cell.traffic["kind"])
    cell, server, keep = m.cell, run.server, m.keep
    cfg, setup = cell.config, cell.traffic["setup"]
    t0_s = datagen.T0 // datagen.S
    reqs = kind.requests_for(cell.to_wire(), run.seed, m.seconds,
                             int(m.rec["base_step"][0]))
    vals = server.vals
    off = promoffsets.offsets_ms(cfg, run.seed)
    # the set-up's scrapes and the warm-up's: acknowledged to every read
    base = np.zeros((int(cfg["scale"]), vals.shape[1]), bool)
    base[:, :int(setup["load_steps"]) + 1] = True
    writes = kind.window_writes(cell.to_wire(), run.seed, m.rec)
    at = {int(i): j for j, i in enumerate(m.rec["i"])}
    gap_limit = float(cell.traffic["limits"]["worst_rel_gap"])
    sealed_steps = int(setup["load_steps"]) - int(setup.get("open_steps", 0))
    agg = {"answers": 0, "values": 0, "label_sets_differ": 0,
           "points_missing_or_extra": 0, "worst_rel_gap": 0.0,
           "unanswered": 0, "frontier_pairs": 0, "took_in_flight": 0}
    for lo in range(0, len(keep), 50):
        bodies = run.child.call(op="bodies",
                                indices=keep[lo:lo + 50])["bodies"]
        for i in keep[lo:lo + 50]:
            got = bodies.get(str(i))
            if got is None or got[0] != 200 or i not in at:
                agg["unanswered"] += 1
                continue
            sent, done = (int(m.rec[k][at[i]]) for k in ("sent", "done"))
            visible = base.copy()
            in_flight = []
            for hosts, k, w_sent, w_done, full in writes:
                if full and w_done <= sent:
                    visible[hosts, k] = True
                elif w_sent < done:
                    in_flight += [(int(h), k) for h in hosts]
            req = reqs[i]
            cls = cell.classes[req["cls"]]
            want = ref.evaluate(cls, cfg, server.labels, vals, req, t0_s,
                                offsets_ms=off, visible=visible)
            cands = ref.candidates(cls, cfg, server.labels, vals, req, t0_s,
                                   off, in_flight)
            if control is None:
                have = ref.parse_response(got[1], req)
            else:   # the control, put in the program's place
                have = ref.evaluate(cls, cfg, server.labels, vals, req, t0_s,
                                    control=control,
                                    open_steps=vals.shape[1] - sealed_steps,
                                    offsets_ms=off, visible=visible)
            c = ref.compare_frontier(have, want, cands)
            if (c["worst_rel_gap"] > gap_limit or c["label_sets_differ"]
                    or c["points_missing_or_extra"]):
                say(f"answer {i} differs from the reference: {c}; "
                    f"{len(in_flight)} samples in flight; "
                    f"{req['path'][:300]}")
            agg["answers"] += 1
            for key in ("values", "label_sets_differ",
                        "points_missing_or_extra", "frontier_pairs",
                        "took_in_flight"):
                agg[key] += c[key]
            agg["worst_rel_gap"] = max(agg["worst_rel_gap"],
                                       c["worst_rel_gap"])
    return agg


def check(run, m, control=None):
    bad = int((m.rec["status"] != 200).sum())
    agg = compare_answers(run, m, control)
    limits = m.cell.traffic["limits"]
    gap_limit = float(limits["worst_rel_gap"])
    rows = [
        ("requests_failed", bad, 0),
        ("answers_unanswered", agg["unanswered"], 0),
        ("label_sets_differ", agg["label_sets_differ"], 0),
        ("points_missing_or_extra", agg["points_missing_or_extra"], 0),
        ("worst_rel_gap", agg["worst_rel_gap"], gap_limit),
        ("answers_compared_at_least", -agg["answers"],
         -min(len(m.keep), len(m.rec["status"]),
              int(limits["answers_compared_at_least"]))),
        ("frontier_pairs", agg["frontier_pairs"], NO_LIMIT),
        ("answers_took_in_flight", agg["took_in_flight"], NO_LIMIT),
    ]
    failed = (bad + agg["unanswered"] + agg["label_sets_differ"]
              + agg["points_missing_or_extra"]
              + int(agg["worst_rel_gap"] > gap_limit))
    return rows, failed
