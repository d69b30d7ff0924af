"""How far the open buffer's durability trails its cadence: from the instant
the traffic file's `mediator_tick_s` asked for a tick (the benchmark's stamp)
to the end of that tick's mediator.snapshot span, the largest in the window.
A tick that is still running when the window ends, or has not reached its
snapshot's end, counts up to the window's end."""

from harness import phases, spans


def read(m):
    t0, t1 = m.window
    roots = spans.named(m.span_trees, "mediator.tick")
    lags = []
    for asked, start, end in m.ticks:
        if not t0 <= asked < t1:
            continue
        root = next((r for r in roots if start <= r["start"] <= end), None)
        snap = root and phases.descendant(root, "mediator.snapshot")
        if not snap:
            continue
        lags.append((min(snap["end"], t1) - asked) / 1e9)
    return max(lags) if lags else None
