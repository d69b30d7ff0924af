"""The cluster write path, per sample: the seconds the set-up spent
writing its open buffer through the coordinator's writer, the session
and one node RPC a host a batch at the configured consistency level (the
drain of the stragglers included), over the samples it wrote. One
thread, 500-row batches: beside `write_append_us_per_sample`, the
embedded path's."""


def read(m):
    secs, n = (m.setup.get(k) for k in ("cluster_write_s",
                                        "cluster_write_samples"))
    return 1e6 * secs / n if secs is not None and n else None
