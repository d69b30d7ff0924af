"""Host time inside the cold decode's calls per query over the 1-minute namespace:
`cold_decode_ms_per_query`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "cold_decode_ms_per_query")
