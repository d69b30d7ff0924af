"""Seconds the set-up took to encode and write both namespaces' filesets and
index segments with the program's writers: `fileset_build_s`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "fileset_build_s")
