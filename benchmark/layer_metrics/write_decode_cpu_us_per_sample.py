"""Thread CPU time of snappy decompress plus protobuf decode per sample:
the `cpu_ns` tags of the remote_write.decompress and remote_write.decode
spans over the samples their requests acknowledged.
`write_decode_us_per_sample` times the same spans' wall, which under a
contended GIL measures the queue; this one should not move with it."""

from harness import phases, spans


def read(m):
    roots = [r for r in phases.request_roots(m, "http.POST")
             if "samples" in r["tags"]]
    n = sum(r["tags"]["samples"] for r in roots)
    found = [x for name in ("remote_write.decompress", "remote_write.decode")
             for x in spans.named(roots, name) if "cpu_ns" in x["tags"]]
    if not n or not found:
        return None
    return sum(x["tags"]["cpu_ns"] for x in found) / 1e3 / n
