"""Rows a read of an open bucket looked through past the bucket's index
by series, per (series, bucket) read of the window: an open bucket that
is being appended to keeps a tail the index has not taken in yet, and
every read scans it. `storage.buffer.read.tail_rows` over
`storage.buffer.read.indexed` + `.tail_scans` moved in the window. A
program without the counter (before PR 46) gives nothing to read. Until
PR 50 a row of `checks/write_pace.py`."""

TAIL_ROWS = "storage.buffer.read.tail_rows"


def read(m):
    if TAIL_ROWS not in m.counters1:
        return None
    reads = (m.moved("storage.buffer.read.indexed")
             + m.moved("storage.buffer.read.tail_scans"))
    return m.moved(TAIL_ROWS) / max(reads, 1)
