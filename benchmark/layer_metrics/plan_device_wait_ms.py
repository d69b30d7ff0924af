"""Enqueue plus wait per compiled-route query: mean of `dispatch_ns` (the
guarded dispatch, which returns before the device is done) and
`device_wait_ns` (where the result is read: at render, so under
http.handler) over the requests whose span says route=plan.

In `rf3-query-thin` the device is the coordinator's own."""

from harness import phases, spans


def read(m):
    d = []
    for root, ex in phases.plan_queries(m):
        if "dispatch_ns" not in ex["costs"]:
            continue
        d.append(ex["costs"]["dispatch_ns"] + phases.cost(
            (n for n in spans.walk(root)
             if n["name"] in ("http.handler", "query.execute_range")),
            "device_wait_ns"))
    return sum(d) / len(d) / 1e6 if d else None
