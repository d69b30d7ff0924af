"""Time the server process spent in full (generation-2) collections inside
the window, over the window: gc.callbacks, watched and never tuned.

`rf3-query-thin` is one process that holds the coordinator and all three
nodes: their collections are this one reading."""



def read(m):
    t0, t1 = m.window
    full = sum(min(b, t1) - max(a, t0) for a, b, gen in m.gc_events
               if gen == 2 and b > t0 and a < t1)
    return 100.0 * full / (t1 - t0)
