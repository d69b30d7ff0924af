"""Seconds inside the mediator's ticks (seal, device encode, fileset flush,
snapshot, clean-up) within the window: the benchmark's own stamps around
each Mediator.run_once it drives, since the program has only counters there."""



def read(m):
    t0, t1 = m.window
    return sum(min(b, t1) - max(a, t0) for a, b in m.ticks
               if b > t0 and a < t1) / 1e9
