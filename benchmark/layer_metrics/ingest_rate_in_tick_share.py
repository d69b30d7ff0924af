"""Whether writers make progress while the mediator ticks: samples
acknowledged per second while a mediator.tick span is open, over the rate
while none is (100 = a tick costs the writers nothing, 0 = they stand
still). A request counts where its root span ends, with the root's `samples`
tag; ticks and requests are clipped to the window."""

from harness import phases, spans


def read(m):
    t0, t1 = m.window
    ticks = sorted((max(n["start"], t0), min(n["end"], t1))
                   for n in spans.named(m.span_trees, "mediator.tick")
                   if n["end"] > t0 and n["start"] < t1)
    in_tick_ns = sum(b - a for a, b in ticks)
    acked = [(r["end"], r["tags"]["samples"])
             for r in phases.request_roots(m, "http.POST")
             if "samples" in r["tags"] and t0 <= r["end"] <= t1]
    if not ticks or not acked or in_tick_ns >= t1 - t0:
        return None
    inside = sum(n for end, n in acked
                 if any(a <= end <= b for a, b in ticks))
    outside = sum(n for _end, n in acked) - inside
    if not outside:
        return None
    return 100.0 * (inside / in_tick_ns) / (outside / (t1 - t0 - in_tick_ns))
