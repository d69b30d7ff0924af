"""The M3TSZ pack kernel's share of its roofline: the bytes its calls had
to move (the shapes on each call's own HLO line: four [window, rows] u32
planes in, [max_words, rows] u32 out) over the chip's HBM bandwidth,
against the device time of its trace events. A bit-packer does no
floating-point work, so memory bounds it. The kernel is the Pallas
`tpu_custom_call` inside `jit(_encode_batch)`; the program gives it no
name of its own yet."""

from harness import trace_reduce


def read(m):
    lo, hi = m.trace_span()
    calls, seconds, nbytes = m.trace.kernel(
        lo, hi, r"^_encode_batch(\.\d+)?$", r'custom_call_target="tpu_custom_call"')
    if not calls:
        return None
    return trace_reduce.roofline_share(seconds, 0.0, nbytes,
                                       m.device_kind)["share"]
