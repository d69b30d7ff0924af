"""Process start to the first timed request: loading, warming up and,
in a run that compiles, compilation."""


def read(m):
    return (m.window[0] - m.proc_start_ns) / 1e9
