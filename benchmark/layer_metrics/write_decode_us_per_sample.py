"""Snappy decompress plus protobuf decode per sample: the
remote_write.decompress and remote_write.decode spans over the samples their
requests acknowledged (the root's `samples` tag)."""

from harness import phases, spans


def read(m):
    roots = [r for r in phases.request_roots(m, "http.POST")
             if "samples" in r["tags"]]
    n = sum(r["tags"]["samples"] for r in roots)
    t = sum(spans.duration(x) for name in ("remote_write.decompress",
                                           "remote_write.decode")
            for x in spans.named(roots, name))
    return t / 1e3 / n if n else None
