"""Every request of the window answered, and the sampled (open loop) or
every replayed (closed loop) answer equal to what the plain reference
computes from the seed's data: the reference is the file the traffic
file names (`reference`, absent `promql_ref`) under
`benchmark/reference/`. Controls, put in the program's place: `bf16`
(the reference's arithmetic in bfloat16) and `stale` (a read that misses
the open buffer)."""

from harness import datagen, schedule, spec
from harness.cellrun import say


def compare_answers(run, m, control=None) -> dict:
    ref = spec.load_part("reference", m.cell.reference)
    cell, server, keep = m.cell, run.server, m.keep
    t0_s = datagen.T0 // datagen.S
    reqs = schedule.requests_for(
        cell.to_wire(), run.seed, schedule.n_requests(cell.traffic, m.seconds))
    held = server.vals[:, :int(cell.traffic["setup"]["load_steps"])]
    gap_limit = float(cell.traffic["limits"]["worst_rel_gap"])
    agg = {"answers": 0, "values": 0, "label_sets_differ": 0,
           "points_missing_or_extra": 0, "worst_rel_gap": 0.0,
           "unanswered": 0}
    open_steps = int(cell.traffic["setup"].get("open_steps", 0))
    for lo in range(0, len(keep), 50):
        bodies = run.child.call(op="bodies",
                                indices=keep[lo:lo + 50])["bodies"]
        for i in keep[lo:lo + 50]:
            got = bodies.get(str(i))
            if got is None and cell.traffic["loop"] != "open":
                continue        # a replay entry the window never reached
            if got is None or got[0] != 200:
                agg["unanswered"] += 1
                continue
            req = reqs[i]
            cls = cell.classes[req["cls"]]
            want = ref.evaluate(cls, cell.config, server.labels, held, req,
                                t0_s)
            if control is None:
                have = ref.parse_response(got[1], req)
            else:   # the control, put in the program's place
                have = ref.evaluate(cls, cell.config, server.labels, held,
                                    req, t0_s, control=control,
                                    open_steps=open_steps)
            c = ref.compare(have, want)
            if (c["worst_rel_gap"] > gap_limit or c["label_sets_differ"]
                    or c["points_missing_or_extra"]):
                say(f"answer {i} differs from the reference: {c}; "
                    f"{req['path'][:300]}")
            agg["answers"] += 1
            agg["values"] += c["values"]
            agg["label_sets_differ"] += c["label_sets_differ"]
            agg["points_missing_or_extra"] += c["points_missing_or_extra"]
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
    ]
    failed = (bad + agg["unanswered"] + agg["label_sets_differ"]
              + agg["points_missing_or_extra"]
              + int(agg["worst_rel_gap"] > gap_limit))
    return rows, failed
