"""From a profiler trace (.xplane.pb) to numbers: the device's busy
union and idle share, time per device operation and per kernel, a
kernel's share of its roofline, and every idle gap named by what the
host was doing in it. Kept with the benchmark so that every PR computes
the same number the same way; checked against a small recorded trace in
benchmark/tests/.

Clocks: trace events count nanoseconds from the start of the trace. The
harness writes one `bench.sync` annotation whose stat `t` is
time.perf_counter_ns() at its start; the difference maps the program's
spans and the load generator's stamps onto the trace."""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from . import spec

SYNC = "bench.sync"
OPS_LINE = "XLA Ops"
Interval = Tuple[float, float]


def peaks(device_kind: str) -> dict:
    """Published peaks by device_kind; a device not in the table is an
    error, never a default."""
    with open(os.path.join(spec.BENCH_DIR, "harness", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       "benchmark/harness/peaks.json")
    return table["devices"][device_kind]


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(hi - lo for lo, hi in intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def op_name(text: str) -> str:
    """An `XLA Ops` event is named by its whole HLO line,
    `%fusion.1 = f32[...] fusion(...)`: the stable name is what stands
    before the equals sign."""
    return text.split(" = ", 1)[0].lstrip("%")


_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_BYTES = {"pred": 1, "bf16": 2}


def hlo_bytes(text: str) -> int:
    """Bytes an operation reads and writes, from the shapes its HLO line
    states: the result (a tuple's members all count) and every operand."""
    total = 0
    for dtype, dims in _SHAPE.findall(text.split(", custom_call_target")[0]):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES.get(dtype, int(dtype[1:]) // 8 if
                                dtype[1:].isdigit() else 4)
    return total


class Trace:
    """Device operations per device plane, and the sync offset."""

    def __init__(self, path: str, device_plane: str = r"^/device:TPU:\d+$"):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.devices: Dict[str, List[tuple]] = {}   # plane -> (lo, hi, name, hlo)
        self.sync_offset: Optional[float] = None    # perf_counter_ns - trace ns
        pat = re.compile(device_plane)
        for plane in data.planes:
            if pat.match(plane.name):
                lines = {ln.name: ln for ln in plane.lines}
                ops = lines.get(OPS_LINE)
                if ops is None:
                    continue
                evs = []
                for e in ops.events:
                    evs.append((float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                op_name(e.name), e.name))
                self.devices[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name == SYNC and self.sync_offset is None:
                            t = dict(e.stats).get("t")
                            if t is not None:
                                self.sync_offset = float(t) - float(e.start_ns)

    def summary(self) -> str:
        return (f"{len(self.devices)} device plane(s) "
                f"{ {d: len(e) for d, e in self.devices.items()} } ops, "
                f"sync offset {self.sync_offset}")

    def to_trace_ns(self, perf_ns: float) -> float:
        if self.sync_offset is None:
            raise ValueError("the trace holds no bench.sync annotation")
        return perf_ns - self.sync_offset

    def busy(self, lo: float, hi: float) -> Dict[str, List[Interval]]:
        """Per device: the union of the intervals in which an operation ran,
        clipped to [lo, hi] (trace ns)."""
        return {d: clip(union((a, b) for a, b, _n, _m in evs), lo, hi)
                for d, evs in self.devices.items()}

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds busy, averaged over the devices that ran anything."""
        per = [total(iv) for iv in self.busy(lo, hi).values()]
        used = [p for p in per if p > 0]
        return float(np.mean(used)) / 1e9 if used else 0.0

    def op_seconds(self, lo: float, hi: float) -> Dict[str, float]:
        """Seconds per operation name, summed over devices."""
        out: Dict[str, float] = {}
        for evs in self.devices.values():
            for a, b, name, _m in evs:
                if b > lo and a < hi:
                    out[name] = out.get(name, 0.0) + (min(b, hi) - max(a, lo)) / 1e9
        return out

    def kernel(self, lo: float, hi: float, op_regex: str,
               hlo_regex: str = "") -> Tuple[int, float, int]:
        """(calls, seconds, bytes) of the operations whose name, and HLO
        line where given, match: a kernel's device time, and the bytes
        its calls had to move by their own shapes."""
        op, hlo = re.compile(op_regex), re.compile(hlo_regex)
        n, secs, nbytes = 0, 0.0, 0
        for evs in self.devices.values():
            for a, b, name, text in evs:
                if a >= lo and b <= hi and op.search(name) and hlo.search(text):
                    n += 1
                    secs += (b - a) / 1e9
                    nbytes += hlo_bytes(text)
        return n, secs, nbytes


def roofline_share(seconds: float, flops: float, nbytes: float,
                   device_kind: str) -> dict:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the time the kernel
    took, in %, and which of the two bounds it."""
    p = peaks(device_kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return {"share": 100.0 * least / seconds if seconds > 0 else None,
            "bound": "compute" if t_flops > t_bytes else "memory"}


def attribute_gaps(busy: List[Interval], lo: float, hi: float,
                   host: List[Tuple[float, float, str]],
                   priority: List[str], idle_label: str) -> Dict[str, float]:
    """Seconds of device idle time inside [lo, hi] by what the host was
    doing: every instant with no device operation goes to the active host
    interval whose label comes first in `priority` (several requests are
    in flight at once), or to `idle_label` when none is active."""
    rank = {name: i for i, name in enumerate(priority)}
    events = []
    for a, b in busy:
        events.append((a, 1, -1))
        events.append((b, -1, -1))
    for a, b, name in host:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            r = rank.setdefault(name, len(rank))
            events.append((a, 1, r))
            events.append((b, -1, r))
    names = sorted(rank, key=rank.get)
    events.sort()
    active = np.zeros(len(names) + 1, np.int64)   # last slot: device busy
    out: Dict[str, float] = {}
    t = lo
    for when, delta, r in events:
        when = min(max(when, lo), hi)
        if when > t and active[-1] == 0:
            on = np.flatnonzero(active[:-1])
            label = names[on[0]] if len(on) else idle_label
            out[label] = out.get(label, 0.0) + (when - t) / 1e9
        t = max(t, when)
        active[r] += delta
    if hi > t and active[-1] == 0:
        on = np.flatnonzero(active[:-1])
        label = names[on[0]] if len(on) else idle_label
        out[label] = out.get(label, 0.0) + (hi - t) / 1e9
    return out
