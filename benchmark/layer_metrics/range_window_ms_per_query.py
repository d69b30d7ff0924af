"""Laying a range selector's fetched samples onto its window layout
(query/window.py: the cadence's dense plane, or the packed one), per
query that evaluated one: mean `window_ns` cost of query.execute_range,
on the interpreter and under the compiled route's bind alike. A program
without that phase (before PR 42) gives nothing to read."""

from harness import spans


def read(m):
    d = [n["costs"]["window_ns"]
         for n in spans.named(m.span_trees, "query.execute_range")
         if "window_ns" in n["costs"]]
    return sum(d) / len(d) / 1e6 if d else None
