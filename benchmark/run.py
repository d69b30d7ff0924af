#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process boots the served path (run_dbnode + embedded coordinator)
on the chip, loads and warms it from the seed (set-up), lets a
load-generator child drive it over localhost HTTP for --seconds, checks
the answers against the plain reference, and prints one JSON line.
Exits nonzero, printing no result, without a TPU or outside a checkout
that holds the program."""

import time

_PROC_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import cellrun, spec

    cell = spec.load_cell(args.workload)
    try:
        result = cellrun.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), _PROC_START_NS)
    except cellrun.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    # every number compared beside its limit: the last lines on standard
    # error, and the last key of the result's line
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value!r} (limit {limit!r}) "
              f"{'ok' if value <= limit else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
