"""What a connection waits before the server has it: from the generator's
`sent` stamp (just before connect) to the start of the request's root span
(the accept stamp), summed over the traced requests and divided by the
samples they acknowledged. The server's one accepting thread needs the GIL
for every connection; the root span and everything under it leave this out."""

from harness import phases, spans


def read(m):
    roots = spans.by_trace_id(phases.request_roots(m, "http.POST"))
    wait = n = 0
    for i, sent, samples in zip(m.rec["i"], m.rec["sent"], m.rec["samples"]):
        root = roots.get(int(i) + 1)
        if root is not None and samples > 0:
            wait += root["start"] - int(sent)
            n += int(samples)
    return wait / 1e3 / n if n else None
