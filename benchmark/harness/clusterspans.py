"""What the cluster cell's readers share: a cost of the session's
`client.fetch_tagged` spans, summed over the window, per query, and a
cost of the nodes' `rpc.fetch_tagged` spans, per replica's read. None
where the program's spans carry no such cost (a program that predates
it), so the metric is left out of the line."""

from __future__ import annotations

from . import phases, spans


def per_query(m, key: str, scale: float = 1.0):
    n = len(spans.named(m.span_trees, "query.execute_range"))
    found = [x for x in spans.named(m.span_trees, "client.fetch_tagged")
             if key in x["costs"]]
    if not n or not found:
        return None
    return phases.cost(found, key) / n / scale


def per_replica(m, key: str, scale: float = 1.0):
    """The mean of a cost of the server-side `rpc.fetch_tagged` spans: one
    replica's read of one query (in one process the node's span is a
    root of its own; the copy grafted under the client's carries no
    costs)."""
    found = [x for x in spans.named(m.span_trees, "rpc.fetch_tagged")
             if key in x["costs"]]
    if not found:
        return None
    return phases.cost(found, key) / len(found) / scale
