"""The window program's enqueue plus wait, per query that laid a range
selector out: `dispatch_ns` (the guarded dispatch, which returns before
the device is done) and `device_wait_ns` (where the result is read: in
the interpreter's evaluation, or at render under http.handler on the
compiled route) of the requests whose query.execute_range carries
`window_ns`. A program before PR 42 carries none and gives nothing to
read."""

from harness import phases, spans


def read(m):
    d = []
    for root in phases.request_roots(m):
        ex = phases.descendant(root, "query.execute_range")
        if ex is None or "window_ns" not in ex["costs"]:
            continue
        nodes = [n for n in spans.walk(root)
                 if n["name"] in ("http.handler", "query.execute_range")]
        d.append(phases.cost(nodes, "dispatch_ns")
                 + phases.cost(nodes, "device_wait_ns"))
    return sum(d) / len(d) / 1e6 if d else None
