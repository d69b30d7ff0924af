"""What the mediator's tick takes of the CPU the Python threads had (ROADMAP
A6), beside `ingest_rate_in_tick_share`: `runtime.cpu_ns{role=tick}` over
`host_cpu_busy_share`'s numerator (which holds the reading)."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "tick_cpu_share")
