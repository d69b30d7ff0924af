#!/usr/bin/env python3
"""Builder's tool, not part of a run: one set-up, then several windows
of one open-loop cell at the rates given (each rate `--repeat` times),
to find the knee and the same-code spread of the tail at a rate.

    python3 benchmark/tools/sweep.py --workload W --seed N --seconds S --rates 10,20,30 [--repeat 1]
"""

import time

_PROC_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeat", type=int, default=1)
    args = ap.parse_args()
    from harness import cellrun, reduce, spec

    run = cellrun.CellRun(spec.load_cell(args.workload), args.seed,
                          _PROC_START_NS)
    try:
        run.setup(args.seconds)
        k = 0
        for rate in (float(r) for r in args.rates.split(",")):
            for rep in range(args.repeat):
                k += 1    # fresh hosts in every window: first touches, as a run sees
                m = run.window(args.seconds, {"rate_per_s": rate},
                               draw_seed=args.seed + 7919 * k)
                lat = reduce.latencies_ms(m)
                lag = (m.rec["sent"] - m.rec["due"]) / 1e6
                # a growing backlog: the last fifth waits longer than the first
                order = np.argsort(m.rec["due"])
                fifth = max(1, len(order) // 5)
                print(json.dumps({
                    "rate": rate, "rep": rep, "n": len(lat),
                    "p50": float(np.percentile(lat, 50)),
                    "p95": float(np.percentile(lat, 95)),
                    "p99": float(np.percentile(lat, 99)),
                    "lag_p95": float(np.percentile(lag, 95)),
                    "first_fifth_p50": float(np.median(lat[order[:fifth]])),
                    "last_fifth_p50": float(np.median(lat[order[-fifth:]])),
                    "failed": int((m.rec["status"] != 200).sum()),
                    "gen2": len([e for e in m.gc_events if e[2] == 2
                                 and e[1] > m.window[0]]),
                }), flush=True)
    finally:
        run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
