"""Of the raw samples Prometheus' windows hold, the share that reached a
window's arithmetic: `window_samples_n` (samples in the laid-out windows,
each counted once a window that covers it) over `window_samples_due_n`
(the fetched samples of (T - range, T], summed over the output steps) on
query.execute_range. 100 when every raw sample is seen; one sample a
gcd(step, range) cell, the program before PR 42, would read 16.7 at
[1m] and at [5m] over 10-second scrapes. A program without those costs
gives nothing to read."""

from harness import phases, reduce, spans


def read(m):
    nodes = [n for n in spans.named(m.span_trees, "query.execute_range")
             if "window_samples_due_n" in n["costs"]]
    if not nodes:
        return None
    return reduce.share(phases.cost(nodes, "window_samples_n"),
                        phases.cost(nodes, "window_samples_due_n"))
