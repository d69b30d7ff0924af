"""What the coordinator's remote downsample leg costs a sample: the
`aggregator.client.write_batch` spans' match, encode and send (one
`Matcher.match_batch` pass, one `tbatch` encode a replica set, the same
bytes to each replica) over their `samples_n`. None where the program
opens no such span."""

from harness import spans


def read(m):
    found = spans.named(m.span_trees, "aggregator.client.write_batch")
    n = sum(x["costs"].get("samples_n", 0) for x in found)
    spent = sum(x["costs"].get(k, 0) for x in found
                for k in ("match_ns", "encode_ns", "send_ns"))
    return spent / 1e3 / n if n else None
