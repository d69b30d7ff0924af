"""The phases the program accounts on its own spans (PR 24), as the
readers under layer_metrics/ take them: the front's sub-spans under a
request's root, thread CPU time in a span's tags (`cpu_ns`), phase
accumulators in its costs (`<phase>_ns`, `<phase>_n`), and the
mediator's tick tree. Every helper finds nothing, and says so with an
empty list or None, on a program that predates them."""

from __future__ import annotations

from typing import Iterable, List, Optional

from . import reduce, spans


def request_roots(m, prefix: str = "http.") -> List[dict]:
    """Roots of traced requests that the program spans from accept to
    last byte (they hold an `http.read` child; an older program's root
    wraps the handler call alone and is left out)."""
    return [t for t in m.span_trees if t["name"].startswith(prefix)
            and any(c["name"] == "http.read" for c in t["children"])]


def runtime_probe(m):
    """The runtime probe's rings (PR 36: wakes and stalls, on the spans'
    clock): a test's hand-built `m.runtime`, else the program's tracer's;
    None on a program without one."""
    rt = getattr(m, "runtime", None)
    if rt is None:
        from m3_tpu.utils import tracing

        rt = getattr(tracing.TRACER, "runtime", None)
    return rt


def descendant(node: dict, name: str) -> Optional[dict]:
    return next((n for n in spans.walk(node) if n["name"] == name), None)


def cost(nodes: Iterable[dict], key: str) -> float:
    return sum(n["costs"].get(key, 0) for n in nodes)


def per(nodes: List[dict], total_key: str, count_key: str, scale: float):
    """Sum of one cost over the sum of another, or None when the spans
    carry neither."""
    nodes = [n for n in nodes if total_key in n["costs"]]
    count = cost(nodes, count_key)
    return cost(nodes, total_key) / count / scale if count else None


def offcpu_share(roots: List[dict]):
    """Wall time the request threads spent off the CPU (waiting for the
    GIL, a lock, a socket, the device), over their wall time."""
    roots = [r for r in roots if "cpu_ns" in r["tags"]]
    wall = sum(spans.duration(r) for r in roots)
    return reduce.share(wall - sum(r["tags"]["cpu_ns"] for r in roots), wall)


def plan_queries(m) -> List[dict]:
    """(root, query.execute_range) of the traced requests that ran on
    the compiled plan route."""
    out = []
    for root in request_roots(m):
        ex = descendant(root, "query.execute_range")
        if ex is not None and ex["tags"].get("route") == "plan":
            out.append((root, ex))
    return out


def seconds_in_window(m, name: str):
    """Seconds inside spans of this name, clipped to the window; None
    when the program opened none."""
    t0, t1 = m.window
    found = spans.named(m.span_trees, name)
    if not found:
        return None
    return sum(max(0, min(n["end"], t1) - max(n["start"], t0))
               for n in found) / 1e9
