"""chip_smoke's served-path verdict over the window and the checks that
followed it, as numbers beside limits of 0 (`Server.verdict`): no
compute-fault counter moved, no runtime plan fallback but those the
traffic file allows, every breaker closed, no codec dispatch off its
gate, no evaluation on the host backend. It has no control: each number
is a count of faults."""

from harness import server as server_mod
from harness.cellrun import say


def check(run, m, control=None):
    allow = tuple(m.cell.traffic.get("allowed_runtime_fallbacks",
                                     ["below-floor"]))
    rows = []
    for name, value, limit, detail in run.server.verdict(
            m.counters0, server_mod.counters(), allow):
        rows.append((name, value, limit))
        if value > limit:
            say(f"verdict {name}: {detail}")
    return rows, 0
