"""The part of a request's latency outside the engine: client latency (sent
to answered) minus its query.execute_range span, over the latency. Holds
connection set-up, the server thread's start, parsing and render."""

from harness import reduce, spans


def read(m):
    trees = spans.by_trace_id(m.span_trees)
    lat = inside = 0
    for i, sent, done in zip(m.rec["i"], m.rec["sent"], m.rec["done"]):
        root = trees.get(int(i) + 1)
        ex = root and next((n for n in spans.walk(root)
                            if n["name"] == "query.execute_range"), None)
        if ex:
            lat += done - sent
            inside += spans.duration(ex)
    return reduce.share(lat - inside, lat)
