"""The dashboard tail: the 95th percentile of the window's latencies, from
when each request was due. Not judged end to end yet: over 15 same-code
runs it spread by 12% (a few full collections and host stalls a window
decide which of the heaviest class's requests rank 49th of 976), more
than a bound of 25% admits (PERF.md, Open questions).

With one request in flight and an open loop (every cell that reports
it), a stall or a backlog in the window shows here and in
`loadgen_lag_p95_ms`, not in the median."""

from harness import reduce


def read(m):
    return reduce.latency_percentile(m, 95)
