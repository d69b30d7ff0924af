"""What the program's runtime probe says of the one GIL (PR 36;
`m3_tpu/utils/tracing.py::RuntimeProbe`): this file holds every reading
of it, and the other readers of the layer load this one
(`spec.load_reader`) and name theirs. The probe runs while traces are
asked for; what adds up is in counters under `runtime.` on
instrument.ROOT, which the harness snapshots around the window (a key
the first snapshot lacks counts from 0: the window's first traced
request starts the probe), and a share divides by `probe.wall_ns`, the
wall the probe covered. Each wake's lateness and each stall are in rings
on the program's tracer. A program without the probe has neither, and
every reading is None.

`host_cpu_busy_share` itself: CPU of the process less its native
threads' (XLA's, the TPU runtime's) and its main thread's (in a service
it waits; here it is the benchmark's own driver, which stops the
profiler inside the counted stretch), over the wall the probe covered,
as a share of one core: how busy the one GIL's threads kept one core.
Above 100 where numpy and JAX calls that release the GIL overlap.

No reading of run-queue time: the chip's host (gVisor) has no
`/proc/<pid>/task/<tid>/schedstat`, so `runtime.runq_ns` stays absent
there and a `runq_share` would have nothing to read (PERF.md section 7)."""

import sys

from harness import phases, reduce

PREFIX = "runtime."


def role_moved(m, kind: str, role: str) -> float:
    """What the counters `runtime.<kind>{role=<role>}` moved in the
    window (an rpc role has a counter a node: summed)."""
    total = 0.0
    for key in m.counters1:
        if key.startswith(PREFIX + kind + "{"):
            tags = key[key.index("{") + 1:-1].split(",")
            if "role=" + role in tags:
                total += m.moved(key)
    return total


def covered(m):
    """The wall the probe covered, None on a program without it."""
    return m.moved(PREFIX + "probe.wall_ns") or None


def wakes_in_window(m):
    rt = phases.runtime_probe(m)
    if rt is None:
        return None
    t0, t1 = m.window[0], max(m.t_end, m.window[1])
    return [w for w in list(rt.wakes) if t0 <= w[0] <= t1] or None


def python_cpu(m) -> float:
    """CPU of the threads that serve: all but the native and the main."""
    return m.moved(PREFIX + "process_cpu_ns") - sum(
        role_moved(m, "cpu_ns", role) for role in ("native", "main"))


def host_cpu_busy_share(m):
    wall = covered(m)
    if wall is None:
        return None
    say_books(m, wall)
    return reduce.share(python_cpu(m), wall)


def gil_wait_p95_ms(m):
    """p95 of what a wake of the probe waited past its due time, net of
    the probe's own run-queue time: what a thread that turns runnable
    waits for the GIL."""
    wakes = wakes_in_window(m)
    if wakes is None:
        return None
    # (where the host has no run-queue reading the lateness stands whole)
    waits = sorted(max(0, late - (runq or 0)) for _due, late, runq in wakes)
    return waits[min(len(waits) - 1, int(0.95 * len(waits)))] / 1e6


def stall_max_ms(m):
    """The longest stall (a wake more than 100 ms late) that overlaps the
    window, 0 when none; each with who held the CPU, on standard error."""
    if wakes_in_window(m) is None:
        return None
    t0, t1 = m.window[0], max(m.t_end, m.window[1])
    longest = 0.0
    for s in list(phases.runtime_probe(m).stalls):
        if s["end_ns"] < t0 or s["start_ns"] > t1:
            continue
        longest = max(longest, s["late_ns"] / 1e6)
        held = s.get("held_by") or {}
        print("[bench] stall at t0+%.2fs: %.1f ms late, cpu %.1f ms, probe runq "
              "%s ms, gc %s, held by %s thread %r (cpu %.1f ms): %s" % (
                  (s["start_ns"] - t0) / 1e9, s["late_ns"] / 1e6,
                  s["cpu_ns"] / 1e6,
                  "n/a" if s.get("runq_ns") is None
                  else "%.1f" % (s["runq_ns"] / 1e6),
                  s.get("gc", "-"), held.get("role"), held.get("thread"),
                  held.get("cpu_ns", 0) / 1e6,
                  " < ".join(held.get("frames", []))),
              file=sys.stderr, flush=True)
    return longest


def tick_cpu_share(m):
    """What the mediator's tick took of the CPU the Python threads had."""
    if covered(m) is None:
        return None
    return reduce.share(role_moved(m, "cpu_ns", "tick"), python_cpu(m))


def native_cpu_share(m):
    """Host CPU spent below Python, in threads the interpreter never
    made (numpy's and jax's stretches on a Python thread are not in it)."""
    if covered(m) is None:
        return None
    return reduce.share(role_moved(m, "cpu_ns", "native"),
                        m.moved(PREFIX + "process_cpu_ns"))


def say_books(m, wall: float):
    """The window's books on standard error, once a run: PERF.md's
    section 5 is written from these lines."""
    if getattr(m, "_runtime_said", False):
        return
    m._runtime_said = True
    keys = sorted(k for k in m.counters1 if k.startswith(PREFIX))
    line = ", ".join("%s %.6g" % (k[len(PREFIX):], m.moved(k)) for k in keys)
    print("[bench] runtime over %.2f s the probe covered: %s"
          % (wall / 1e9, line), file=sys.stderr, flush=True)


READINGS = {f.__name__: f for f in (
    host_cpu_busy_share, gil_wait_p95_ms, stall_max_ms,
    tick_cpu_share, native_cpu_share)}


def read(m, reading: str = "host_cpu_busy_share"):
    return READINGS[reading](m)
