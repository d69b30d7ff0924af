"""What a query's connection waits before the server has it: from the
generator's `sent` stamp (just before connect) to the start of the
request's root span (the accept stamp), mean over the traced queries;
`accept_wait_us_per_sample`'s arithmetic a request. The root span and
every `front_*` metric leave this stretch out."""

from harness import phases, spans


def read(m):
    roots = spans.by_trace_id(phases.request_roots(m))
    waits = [root["start"] - int(sent)
             for i, sent in zip(m.rec["i"], m.rec["sent"])
             for root in (roots.get(int(i) + 1),) if root is not None]
    return sum(waits) / len(waits) / 1e6 if waits else None
