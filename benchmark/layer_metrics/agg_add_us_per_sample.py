"""What the aggregators' ingest costs a sample of the fleet on THIS
host: the `aggregator.rawtcp.frame` spans' `decode_ns` + `add_ns` of
every instance (leader's and follower's frames together: one process
holds both) over the samples one instance was sent."""

from harness import spans


def read(m):
    found = spans.named(m.span_trees, "aggregator.rawtcp.frame")
    instances = {x["tags"].get("instance") for x in found}
    n = sum(x["costs"].get("samples_n", 0) for x in found)
    if not n or not instances:
        return None
    spent = sum(x["costs"].get(k, 0) for x in found
                for k in ("decode_ns", "add_ns"))
    return spent / 1e3 / (n / len(instances))
