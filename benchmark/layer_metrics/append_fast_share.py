"""Of the shard appends the routed write batches made in the window
(Database.write_batch: one per shard a batch touches), the share that
were fast: the rows shared one block, every id was known and no series
got its tags from them, so the append was a lookup, the shard lock and
three slice stores. A program without the counters moves neither, and
nothing is read."""

from harness import reduce


def read(m):
    return reduce.share(m.moved("storage.write_batch.fast_appends"),
                        m.moved("storage.write_batch.shard_appends"))
