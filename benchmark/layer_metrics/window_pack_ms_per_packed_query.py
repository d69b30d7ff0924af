"""The packed layout's own share of a range selector's `window` phase
(query/window.py: samples on no common grid, eight hosts at eight scrape
offsets, gathered window by window on the host), per query that paid it:
mean `window_pack_ns` cost of the query.execute_range spans that carry
one, on the interpreter and under the compiled route's bind alike. A
query whose selector was laid out dense carries none; a program without
the cost (before PR 46) gives nothing to read. Until PR 50 a row of
`checks/write_pace.py`."""

from harness import spans


def read(m):
    d = [n["costs"]["window_pack_ns"]
         for n in spans.named(m.span_trees, "query.execute_range")
         if "window_pack_ns" in n["costs"]]
    return sum(d) / len(d) / 1e6 if d else None
