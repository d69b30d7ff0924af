"""What the aggregation tier took of the CPU the Python threads had:
the runtime probe's `aggregator` role (the rawtcp connections' threads
and the flush loops) and `m3msg` role (the producers' ack readers and
retry scans, the consumer's connection threads, which run the ingester)
over `host_cpu_busy_share`'s numerator (process CPU less the native
threads' and the main thread's). The coordinator's own share of the
tier (match, encode, send) is on the request threads:
`agg_client_us_per_sample`."""

PREFIX = "runtime."


def _role(m, role: str) -> float:
    total = 0.0
    for key in m.counters1:
        if key.startswith(PREFIX + "cpu_ns{"):
            if "role=" + role in key[key.index("{") + 1:-1].split(","):
                total += m.moved(key)
    return total


def read(m):
    if not m.moved(PREFIX + "probe.wall_ns"):
        return None
    python = m.moved(PREFIX + "process_cpu_ns") - _role(m, "native") \
        - _role(m, "main")
    tier = _role(m, "aggregator") + _role(m, "m3msg")
    return 100.0 * tier / python if python > 0 and tier > 0 else None
