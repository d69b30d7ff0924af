"""Result to last byte at depth (721 points a series): `front_out_ms`'s
reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "front_out_ms")
