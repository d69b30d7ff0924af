"""The program's spans, as the benchmark reads them.

The program's tracer keeps a ring of its last 128 finished root spans
and `/debug/traces` renders them without start times; a traced run
swaps in a tracer of the same class with a ring that holds the whole
window, and reads the Span objects (start_ns / end_ns are
time.perf_counter_ns, the clock the load generator stamps with)."""

from __future__ import annotations

from typing import Dict, Iterator, List


def install(capacity: int = 400_000):
    """Before the server boots. Returns the tracer to read after the window."""
    from m3_tpu.utils import tracing

    tracing.TRACER = tracing.Tracer(max_traces=capacity, sample_rate=1.0)
    return tracing.TRACER


def _tree(span) -> dict:
    kids = [_tree(c) for c in span.children if not isinstance(c, dict)]
    return {"name": span.name, "start": span.start_ns,
            "end": span.end_ns or span.start_ns, "tags": dict(span.tags),
            "costs": dict(span.costs), "trace_id": span.trace_id,
            "children": kids}


def collect(tracer, t0: int, t1: int) -> List[dict]:
    """Finished root spans that started inside [t0, t1], as plain trees."""
    with tracer._lock:
        roots = list(tracer._recent)
    return [_tree(s) for s in roots if t0 <= s.start_ns <= t1]


def walk(tree: dict) -> Iterator[dict]:
    yield tree
    for c in tree["children"]:
        yield from walk(c)


def duration(node: dict) -> int:
    return node["end"] - node["start"]


def self_time(node: dict) -> int:
    """Duration minus the part of it that child spans cover."""
    covered = 0
    last = node["start"]
    for c in sorted(node["children"], key=lambda c: c["start"]):
        lo, hi = max(c["start"], last), min(c["end"], node["end"])
        if hi > lo:
            covered += hi - lo
            last = hi
    return duration(node) - covered


def named(trees: List[dict], name: str) -> List[dict]:
    return [n for t in trees for n in walk(t) if n["name"] == name]


def by_trace_id(trees: List[dict]) -> Dict[int, dict]:
    return {t["trace_id"]: t for t in trees}
