"""Of the open buffer's (series, bucket) reads in the window, the share
answered from the bucket's index by series alone: `storage.buffer.read.
indexed` over it and `.tail_scans` (a read that had to scan the rows
appended since the index was built). A program without the counters
moves neither, and nothing is read."""

from harness import reduce


def read(m):
    indexed = m.moved("storage.buffer.read.indexed")
    return reduce.share(indexed,
                        indexed + m.moved("storage.buffer.read.tail_scans"))
