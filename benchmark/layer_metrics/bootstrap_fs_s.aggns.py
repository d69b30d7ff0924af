"""Seconds of the restarted node's filesystem bootstrap over both namespaces
(2,688 filesets, 42 block starts): `bootstrap_fs_s`'s reading."""

from harness import spec

read = spec.load_reader("layer_metrics", "bootstrap_fs_s")
