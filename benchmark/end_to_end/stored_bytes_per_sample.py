"""Bytes of every file under the node's data_dir (filesets, index,
snapshots, commit log) after the set-up's fixed load, over the samples
loaded: taken before the window, because the number of seals inside it
depends on the rate."""


def read(m):
    return m.stored_bytes / m.setup["samples"]
