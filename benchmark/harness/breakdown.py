"""The traced run's breakdown: the device operations that took most
time, and the device's idle time by what the host was doing."""

from __future__ import annotations

from typing import List, Tuple

from . import phases, spans, trace_reduce

# Several requests are in flight at once, beside the mediator's tick; an
# idle instant goes to the first of these that is active. A full
# collection, then a stall the runtime probe recorded (a wake more than
# 100 ms late: whoever held the CPU, every span under it stood still, so
# the gap is the stall's and not theirs), then the deepest program span
# first (the parts of a tick and of a write before `mediator.tick` and
# `http`, which enclose them), the benchmark's own intervals after.
STALL = "runtime.stall"
PRIORITY = ["gc", STALL, "index.query", "storage.read", "query.fetch",
            "query.parse", "query.execute_range", "persist.write",
            "encode.block", "mediator.snapshot", "storage.write_batch",
            "remote_write.append", "remote_write.decode", "mediator.tick",
            "render", "http"]
IDLE = "loadgen-wait"


def host_intervals(m) -> List[Tuple[float, float, str]]:
    """Everything the host is known to have been doing, on the trace's clock."""
    tr = m.trace
    out = []

    def add(lo, hi, name):
        out.append((tr.to_trace_ns(lo), tr.to_trace_ns(hi), name))

    for a, b, _gen in m.gc_events:
        add(a, b, "gc")
    rt = phases.runtime_probe(m)
    for s in list(rt.stalls) if rt is not None else ():
        add(s["start_ns"], s["end_ns"], STALL)
    for _asked, a, b in m.ticks:
        add(a, b, "mediator.tick")
    trees = spans.by_trace_id(m.span_trees)
    for tree in m.span_trees:
        for node in spans.walk(tree):
            if not node["name"].startswith("http."):
                add(node["start"], node["end"], node["name"])
    # a request in flight outside its handler's spans: `http` before the
    # engine starts and after the handler returns, `render` between the
    # engine's end and the handler's
    for i, sent, done in zip(m.rec["i"], m.rec["sent"], m.rec["done"]):
        root = trees.get(int(i) + 1)
        ex = root and phases.descendant(root, "query.execute_range")
        if not ex:
            add(sent, done, "http")
            continue
        add(sent, ex["start"], "http")
        add(ex["end"], root["end"], "render")
        add(root["end"], done, "http")
    return out


def idle_by_host(m, lo: float, hi: float) -> dict:
    busy = trace_reduce.union(iv for per in m.trace.busy(lo, hi).values()
                              for iv in per)
    return trace_reduce.attribute_gaps(busy, lo, hi, host_intervals(m),
                                       PRIORITY, IDLE)


def breakdown(m, lo: float, hi: float) -> dict:
    ops = sorted(m.trace.op_seconds(lo, hi).items(), key=lambda kv: -kv[1])
    gaps = sorted(idle_by_host(m, lo, hi).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}
