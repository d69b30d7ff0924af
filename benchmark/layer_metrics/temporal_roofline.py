"""The windowed reductions' share of their roofline: the bytes the
window programs' `reduce-window` operations had to move (the shapes on
each operation's own HLO line: the [series, lanes] plane in, the
[series, steps] plane out; `trace_reduce.hlo_bytes`) over the chip's HBM
bandwidth, against the device time of their trace events. Sums, first
and last indices over a window do a flop an element at most, so memory
bounds them. XLA names them `reduce_window(.N)` / `reduce_window_sum(.N)`
(ops/temporal.py::_wsum, _first_abs, _last_abs); an hour's window over
an hour's lanes (W == lanes) it rewrites as a plain reduce, which is not
counted."""

from harness import trace_reduce

OP = r"^reduce[_-]window"
HLO = r"\breduce-window\("


def read(m):
    lo, hi = m.trace_span()
    calls, seconds, nbytes = m.trace.kernel(lo, hi, OP, HLO)
    if not calls:
        return None
    return trace_reduce.roofline_share(seconds, 0.0, nbytes,
                                       m.device_kind)["share"]
