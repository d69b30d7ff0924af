"""Mean duration of the index.query span (tag query to series ids).

In `rf3-query-thin` the span opens on the nodes: every replica asked
runs the tag query on its own index, so a clustered read opens up to
three. In `aggns-query-3d` a range overlaps one or two 24-hour index
blocks of the aggregated namespace."""

from harness import spans


def read(m):
    d = [spans.duration(n) for n in spans.named(m.span_trees, "index.query")]
    return sum(d) / len(d) / 1e6 if d else None
