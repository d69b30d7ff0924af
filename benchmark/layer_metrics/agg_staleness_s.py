"""`staleness_s`, which PERF.md section 1 lists among what users pay
for: how long after its window's end an aggregate became readable, on
the injected clock (which runs a second a second with the fleet's
writes) — the worst `staleness_ns` of the window's
`coordinator.m3msg.ingest` spans: buffer_past, the wait for the
leader's next flush check, the flush, the produce, the consume and the
write of the batch."""

from harness import spans


def read(m):
    stale = [x["costs"]["staleness_ns"]
             for x in spans.named(m.span_trees, "coordinator.m3msg.ingest")
             if "staleness_ns" in x["costs"]]
    return max(stale) / 1e9 if stale else None
