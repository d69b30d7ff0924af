"""Fetches of the window that the resolver answered from an aggregated namespace
alone, over all it resolved (`query.resolve.aggregated` over the three
`query.resolve.*` counters moved in the window): 100 where every panel is older
than the unaggregated retention. Nothing on a program without the counters."""

from harness import reduce


def read(m):
    moved = [m.moved("query.resolve." + k)
             for k in ("aggregated", "unaggregated", "partial")]
    return reduce.share(moved[0], sum(moved)) if sum(moved) else None
