"""p95 of an m3msg message's wait for its acknowledgement (first frame
write to the ack frame read): `msg.produce` roots' `ack_wait_ns`. It
holds the consumer's decode and the ingester's write of the message and
of those queued before it on the connection."""

import numpy as np

from harness import spans


def read(m):
    waits = [x["costs"]["ack_wait_ns"]
             for x in spans.named(m.span_trees, "msg.produce")
             if "ack_wait_ns" in x["costs"]]
    return float(np.percentile(waits, 95)) / 1e6 if waits else None
