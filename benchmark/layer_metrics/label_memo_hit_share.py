"""Of the series the remote-write decode saw in the window, the share
whose label block was known (promremote.LabelMemo): those cost one dict
probe; the rest were first sightings, or in a layout the walk leaves to
the full decoder, and paid a decode per label and an id. A program
without the memo moves neither counter, and nothing is read."""

from harness import reduce


def read(m):
    hits = m.moved("coordinator.remote_write.label_memo.hits")
    return reduce.share(
        hits, hits + m.moved("coordinator.remote_write.label_memo.misses"))
