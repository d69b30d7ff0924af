"""One replica's part of a clustered read: the mean wall time of the
server-side `rpc.fetch_tagged` spans (index query, per-series identity
and buffer reads, tile gathers, and the result's wire encode). In one
process the replicas' spans of one query overlap on one GIL."""

from harness import spans


def read(m):
    found = [x for x in spans.named(m.span_trees, "rpc.fetch_tagged")
             if "index_ns" in x["costs"]]
    if not found:
        return None
    return sum(spans.duration(x) for x in found) / len(found) / 1e6
