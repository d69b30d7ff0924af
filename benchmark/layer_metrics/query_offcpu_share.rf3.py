"""Of the coordinator's request threads' wall time, the part they were not
on a CPU: `query_offcpu_share`'s reading. In the cluster cell that is
mostly the wait for the fan-out's workers and the nodes' handler
threads, which share the one GIL."""

from harness import spec

read = spec.load_reader("layer_metrics", "query_offcpu_share")
