"""Mean duration of the index.query span (tag query to series ids)."""

from harness import spans


def read(m):
    d = [spans.duration(n) for n in spans.named(m.span_trees, "index.query")]
    return sum(d) / len(d) / 1e6 if d else None
