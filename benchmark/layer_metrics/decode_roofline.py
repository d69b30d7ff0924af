"""The M3TSZ decode kernel's share of its roofline: the bytes its calls
had to move (the shapes on each call's own HLO line: the [words, lanes]
u32 stream plane and the point counts in, five [window, lanes] planes
out) over the chip's HBM bandwidth, against the device time of its trace
events. A bit-unpacker does no floating-point work, so memory bounds it.
The kernel is the Pallas `tpu_custom_call` inside `jit(run)`, whose result
is the decode's five-plane tuple (s32 ticks, then the timestamp and value
pairs as u32); the program gives it no name of its own."""

from harness import trace_reduce

# the jitted decode program is `run` (ops/tsz._decode_fused_jit): its
# Pallas call is device operation `run(.N)`, a custom call whose result
# tuple opens with the s32 tick plane
OP = r"^run(\.\d+)?$"
HLO = r'= \(s32\[\d+,\d+\].*custom_call_target="tpu_custom_call"'


def read(m):
    lo, hi = m.trace_span()
    calls, seconds, nbytes = m.trace.kernel(lo, hi, OP, HLO)
    if not calls:
        return None
    return trace_reduce.roofline_share(seconds, 0.0, nbytes,
                                       m.device_kind)["share"]
