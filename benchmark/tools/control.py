#!/usr/bin/env python3
"""Builder's tool, not part of a run: the readings a limit is set from.
One set-up and one short window of a cell at its own size, then the
comparison that decides `correct` twice over the same window: on what
the program served (sound), and with each control put in the program's
place (`bf16`: the reference's arithmetic in bfloat16; `stale`: a read
that misses the open buffer; `drop`: one sample of each read-back not
stored). Prints one JSON line per reading.

    python3 benchmark/tools/control.py --workload W --seed N --seconds S --controls bf16,stale
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", required=True)
    args = ap.parse_args()
    from harness import cellrun, spec

    run = cellrun.CellRun(spec.load_cell(args.workload), args.seed,
                          _PROC_START_NS)
    try:
        run.setup(args.seconds)
        m = run.window(args.seconds)
        for control in [None] + args.controls.split(","):
            t0 = time.perf_counter()
            checks, attempted, failed = run.check(m, control)
            print(json.dumps({
                "workload": args.workload, "seed": args.seed,
                "control": control or "sound", "attempted": attempted,
                "correct": all(v <= lim for _n, v, lim in checks),
                "check_s": round(time.perf_counter() - t0, 2),
                "checks": {n: v for n, v, _lim in checks}}), flush=True)
    finally:
        run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
