"""Mean duration of the index.query span on the nodes: every replica asked
runs the tag query on its own index, so a clustered read opens up to
three. `index_query_ms`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "index_query_ms")
