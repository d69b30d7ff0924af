"""Responders whose frames a read merged, mean over the window's
`client.fetch_tagged` spans (tag `replicas_merged`): 2 where the quorum
returned before the third replica, 3 where all were in."""

from harness import spans


def read(m):
    got = [x["tags"]["replicas_merged"]
           for x in spans.named(m.span_trees, "client.fetch_tagged")
           if "replicas_merged" in x["tags"]]
    return sum(got) / len(got) if got else None
