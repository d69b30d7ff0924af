"""Both branches of the resolver and the edge between them, as part of
`correct` though only one branch is timed: after the window, over HTTP,
`boundary.per_case` queries of each of three cases from the traffic
file's `boundary` block, each answer compared with the plain reference
(`aggns_ref`, which resolves the namespace by its own copy of the rule),
and the program's own `query.resolve.*` counters held to the branch the
rule names:

- `recent`: a 1-hour class ending in the last two minutes: the rule's
  first case, 10-second points from the unaggregated namespace;
- `beyond`: the same hour ending between 2 and 3 hours ago: the second
  case, 1-minute points;
- `whole`: a 12-hour class ending at the newest held instant: the second
  case, its last points the ones the live downsampler flushed in set-up.

Rows, each with a limit of 0: `boundary_answers_differ` (an answer whose
labels, points or values are not the reference's),
`boundary_reads_failed`, `boundary_routed_elsewhere` (queries of a case
whose fetch the program's counters show on another branch). Control:
`wrong_namespace` (each case answered from the other namespace)."""

import urllib.request

import numpy as np

from harness import datagen, schedule, spec
from harness import server as server_mod
from harness.cellrun import say

BRANCH = {"recent": "unaggregated", "beyond": "aggregated",
          "whole": "aggregated"}


def requests_for(cell, seed: int, case: str, salt: int):
    """`per_case` requests of one case: the class's own draws of hosts
    and fields, the end drawn where the case says."""
    t, cfg = cell.traffic, cell.config
    spec_ = t["boundary"][case]
    cls = spec.load_class(spec_["class"])
    hold_end_s = int(datagen.step_ts(
        cfg, int(t["setup"]["load_steps"]) - 1) // datagen.S)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 47, salt])
    lo, hi = spec_.get("end_between_s", [0, spec_.get("end_within_last_s", 0)])
    out = []
    for _ in range(int(t["boundary"]["per_case"])):
        p = schedule._draw(rng, cls, cfg, hold_end_s,
                           {"end_within_last_s": 0})
        p["end_s"] = int(hold_end_s - rng.integers(int(lo), int(hi) + 1))
        out.append((cls, schedule.build_request(cls, cfg, p)))
    return out


def check(run, m, control=None):
    cell, server = m.cell, run.server
    ref = spec.load_part("reference", cell.reference)
    held = server.vals[:, :int(cell.traffic["setup"]["load_steps"])]
    t0_s = datagen.T0 // datagen.S
    gap_limit = float(cell.traffic["limits"]["worst_rel_gap"])
    differ = failed = elsewhere = compared = 0
    for salt, case in enumerate(("recent", "beyond", "whole")):
        c0 = server_mod.counters()
        reqs = requests_for(cell, run.seed, case, salt)
        for cls, req in reqs:
            want = ref.evaluate(cls, cell.config, server.labels, held, req,
                                t0_s)
            if control is None:
                try:
                    with urllib.request.urlopen(server.base + req["path"],
                                                timeout=120) as r:
                        have = ref.parse_response(r.read().decode(), req)
                except (OSError, ValueError, KeyError) as e:
                    failed += 1
                    say(f"boundary {case}: {e!r}; {req['path'][:200]}")
                    continue
            else:
                have = ref.evaluate(cls, cell.config, server.labels, held,
                                    req, t0_s, control=control)
            c = ref.compare(have, want)
            compared += 1
            if (c["worst_rel_gap"] > gap_limit or c["label_sets_differ"]
                    or c["points_missing_or_extra"] or not c["values"]):
                differ += 1
                say(f"boundary {case} differs from the reference: {c}; "
                    f"{req['path'][:300]}")
        if control is None:
            c1 = server_mod.counters()
            moved = {k: c1.get("query.resolve." + k, 0)
                     - c0.get("query.resolve." + k, 0)
                     for k in ("unaggregated", "aggregated", "partial")}
            stray = sum(v for k, v in moved.items() if k != BRANCH[case])
            short = max(0, len(reqs) - moved[BRANCH[case]])
            if stray or short:
                say(f"boundary {case}: the program resolved {moved}, the "
                    f"rule names {BRANCH[case]} for all {len(reqs)}")
            elsewhere += stray + short
    say(f"resolver boundary: {compared} answers compared, {differ} differ, "
        f"{failed} failed, {elsewhere} routed elsewhere")
    rows = [("boundary_answers_differ", differ, 0),
            ("boundary_reads_failed", failed, 0),
            ("boundary_routed_elsewhere", elsewhere, 0),
            ("boundary_compared_at_least", -compared,
             -3 * int(cell.traffic["boundary"]["per_case"]))]
    return rows, differ + failed + elsewhere
