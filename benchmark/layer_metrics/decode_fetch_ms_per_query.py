"""A decode call's waits and fetches per query: `tsz.decode_plane`'s
`device_wait_ns` (the device's work and the first output's fetch) plus
`d2h_ns` (the other outputs' fetches), summed over the spans a decode
runs under (`decode_layout_ms_per_query` holds the reading)."""

from harness import spec

_stretches = spec.load_reader("layer_metrics", "decode_layout_ms_per_query")


def read(m):
    return _stretches(m, ("device_wait_ns", "d2h_ns"))
