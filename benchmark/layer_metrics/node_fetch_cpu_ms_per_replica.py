"""Thread CPU time of one replica's part of a clustered read: the mean
`cpu_ns` tag of the server-side `rpc.fetch_tagged` spans, beside the
wall `node_fetch_ms_per_replica` reads. The difference is what a
replica's handler waited for the other two replicas' handlers (and the
coordinator's threads) on the one GIL."""

from harness import spans


def read(m):
    found = [x for x in spans.named(m.span_trees, "rpc.fetch_tagged")
             if "index_ns" in x["costs"] and "cpu_ns" in x["tags"]]
    if not found:
        return None
    return sum(x["tags"]["cpu_ns"] for x in found) / len(found) / 1e6
