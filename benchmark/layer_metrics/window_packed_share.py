"""Of the range-selector layouts built in the window
(query/window.py), the share that were packed: samples on no common
grid, eight hosts at eight scrape offsets, gathered window by window on
the host. `query.range_selector.layouts{layout=packed}` over it and
`{layout=dense}`. A program without the counters gives nothing to
read."""

from harness import reduce

PACKED = "query.range_selector.layouts{layout=packed}"
DENSE = "query.range_selector.layouts{layout=dense}"


def read(m):
    if PACKED not in m.counters1 and DENSE not in m.counters1:
        return None
    packed = m.moved(PACKED)
    return reduce.share(packed, packed + m.moved(DENSE))
