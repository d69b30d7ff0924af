"""The sample-by-sample append loop per sample: remote_write.append spans
over their `samples_n` cost (id encode, shard hash, buffer append under the
shard lock, commit log, admission gate)."""

from harness import spans


def read(m):
    found = spans.named(m.span_trees, "remote_write.append")
    n = sum(x["costs"].get("samples_n", 0) for x in found)
    return sum(spans.duration(x) for x in found) / 1e3 / n if n else None
