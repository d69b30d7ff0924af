"""Server-side handling per sample: the remote-write handler's span (snappy
and protobuf decode, id encode, per-sample append and commit log) over the
samples it acknowledged. The path has no storage.write_batch span: it
writes sample by sample."""

from harness import spans


def read(m):
    d = [spans.duration(t) for t in m.span_trees
         if t["name"].startswith("http.POST")]
    n = int(m.rec["samples"].sum())
    return sum(d) / 1e3 / n if d and n else None
