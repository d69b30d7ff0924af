"""Thread CPU time of the routed append per sample: the `cpu_ns` tags of
the remote_write.append spans over their `samples_n` cost; the CPU twin
of `write_append_us_per_sample`."""

from harness import spans


def read(m):
    found = [x for x in spans.named(m.span_trees, "remote_write.append")
             if "cpu_ns" in x["tags"]]
    n = sum(x["costs"].get("samples_n", 0) for x in found)
    return sum(x["tags"]["cpu_ns"] for x in found) / 1e3 / n if n else None
