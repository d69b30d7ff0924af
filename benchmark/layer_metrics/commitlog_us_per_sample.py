"""commitlog.write per sample: the `commitlog_ns` cost of the
remote_write.append spans over their `samples_n`."""

from harness import phases, spans


def read(m):
    return phases.per(spans.named(m.span_trees, "remote_write.append"),
                      "commitlog_ns", "samples_n", 1e3)
