"""The interpreter's evaluation alone, per interpreted query (ROADMAP A5):
the `interpreter_eval_ns` cost of query.execute_range, which holds the
fetch it drives; `interp_ms_per_query` is the span's self time, which
leaves the fetch out and takes parse-to-route overhead in."""

from harness import spans


def read(m):
    d = [n["costs"]["interpreter_eval_ns"]
         for n in spans.named(m.span_trees, "query.execute_range")
         if "interpreter_eval_ns" in n["costs"]]
    return sum(d) / len(d) / 1e6 if d else None
