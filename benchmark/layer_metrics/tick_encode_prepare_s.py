"""Seconds of the encodes' host-side `prepare` (time unit, the u32-pair
inputs, the boundary metadata: ROADMAP A6) inside the ticks the window
holds: the `prepare_ns` cost of the encode.block spans, a part of
`tick_encode_s`."""

from harness import phases, spans


def read(m):
    found = [x for x in spans.named(m.span_trees, "encode.block")
             if "prepare_ns" in x["costs"]]
    return phases.cost(found, "prepare_ns") / 1e9 if found else None
