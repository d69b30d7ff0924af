"""Of the rows the request path routed through the shard memo
(ShardSet.lookup_memo) in the window, the share that were known series:
the rest were first sightings, hashed on the host. A program without the
memo moves neither counter, and nothing is read."""

from harness import reduce


def read(m):
    hits = m.moved("sharding.memo.hits")
    return reduce.share(hits, hits + m.moved("sharding.memo.misses"))
