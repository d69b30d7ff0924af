"""How busy the one GIL's threads kept one core while the senders wrote:
`host_cpu_busy_share`'s reading. ROADMAP A1 says the ingest rate is one
over the GIL time a sample costs: that holds if this reads 90-100."""

from harness import spec

_runtime = spec.load_reader("layer_metrics", "host_cpu_busy_share")


def read(m):
    return _runtime(m, "host_cpu_busy_share")
