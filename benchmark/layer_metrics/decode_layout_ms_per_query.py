"""Host copies after a decode call's fetches, per query: the `layout_ns`
stretch of `tsz.decode_plane` (the `ascontiguousarray` copies of the two
pair planes out of the device layout's strides, and the k > 0 fix-up),
summed over the spans a decode runs under. `decode_fetch_ms_per_query`
loads this reader and names other stretches."""

from harness import phases, spans

# where a decode call runs: the session's span, or the embedded fetch's
UNDER = ("client.fetch_tagged", "query.fetch", "storage.read")


def read(m, keys=("layout_ns",)):
    found = [x for name in UNDER for x in spans.named(m.span_trees, name)
             if keys[0] in x["costs"]]
    n = len(spans.named(m.span_trees, "query.execute_range"))
    if not found or not n:
        return None
    return sum(phases.cost(found, k) for k in keys) / n / 1e6
