#!/usr/bin/env python3
"""Builder's tool, not part of a run: a reading that changed its name reads
what it read before. One traced run of a cell from this tree, then, on
that run's one `Measurement`, every per-layer reading the cell reported
in an older tree (`--old DIR`, a `git archive` of it) read by that
tree's own reader file and held against the number this tree's line
carries under the reading's name of today (`folded_names.json`: old name
-> the entry it was folded into; a name not listed there kept its own).

    python3 benchmark/tools/fold_check.py --workload W --seed N --seconds S --old DIR

`--old-rows CHECK` (PR 50) also runs that one check of the older tree on
the same `Measurement` and holds the rows it carried for readings that
are entries today (`reading.<name>`, or a row named as an entry of this
cell is: PR 46's `write_pace` in `promrw4k-mixed`) against the line.

Prints one JSON line a reading of the older tree, a summary, and last
the run's result line as `run.py --trace 1` prints it. Exits 1 where a
reading differs or is missing from the line. The older tree's reader
files import this tree's `harness`: the comparison is of the readers, on
the spans, counters and trace that one harness took."""

import time

_PROC_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def folded_names() -> dict:
    with open(os.path.join(HERE, "folded_names.json")) as f:
        return json.load(f)


def old_readings(m, workload: str, old_dir: str) -> dict:
    """{old name: value or None}: what the older tree's readers read on `m`
    for the per-layer entries `workload` reported there."""
    from harness import spec

    with open(os.path.join(old_dir, "BENCHMARK.json")) as f:
        old_cell = spec.load_cell(workload, json.load(f))
    here = spec.BENCH_DIR
    out = {}
    try:
        # a forwarding reader loads its target while it is imported, from
        # the tree it lies in
        spec.BENCH_DIR = os.path.join(old_dir, "benchmark")
        for decl in old_cell.per_layer:
            out[decl["name"]] = spec.load_reader("layer_metrics",
                                                 decl["name"])(m)
    finally:
        spec.BENCH_DIR = here
    return out


def old_rows(run, m, old_dir: str, check: str) -> dict:
    """{row: value} of the older tree's check `check` on `m`, read by
    that tree's file, its readers and its BENCHMARK.json."""
    from harness import spec

    here = spec.BENCH_DIR, spec.ROOT_DIR
    try:
        spec.BENCH_DIR, spec.ROOT_DIR = (os.path.join(old_dir, "benchmark"),
                                         old_dir)
        rows, _failed = spec._load_module("checks", check).check(run, m)
    finally:
        spec.BENCH_DIR, spec.ROOT_DIR = here
    return {name: value for name, value, _limit in rows}


def compare_rows(rows: dict, listed: set, metrics: dict) -> list:
    """As `compare`, for the rows of `old_rows` that are readings of the
    cell today (`listed`: the names of its per-layer entries)."""
    out = []
    for old, was in rows.items():
        new = old[len("reading."):] if old.startswith("reading.") else old
        if new in listed:
            now = metrics.get(new, {}).get("value")
            out.append({"old": "checks:" + old, "new": new,
                        "old_value": was, "new_value": now,
                        "same": float(was) == now})
    return out


def compare(m, workload: str, old_dir: str, metrics: dict) -> list:
    """Rows {old, new, old_value, new_value, same}; `metrics` is the
    `metrics` of this tree's result line for the same `m`."""
    names = folded_names()
    rows = []
    for old, was in old_readings(m, workload, old_dir).items():
        new = names.get(old, old)
        now = metrics.get(new, {}).get("value")
        rows.append({"old": old, "new": new, "old_value": was,
                     "new_value": now,
                     "same": (None if was is None else float(was)) == now})
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--old", required=True)
    ap.add_argument("--old-rows", default=None)
    args = ap.parse_args()
    from harness import cellrun, spec

    try:
        run = cellrun.CellRun(spec.load_cell(args.workload), args.seed,
                              _PROC_START_NS, trace=True)
    except cellrun.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    try:
        run.setup(args.seconds)
        m = run.window(args.seconds)
        result = run.result(m, *run.check(m))
        old_dir = os.path.abspath(args.old)
        rows = compare(m, args.workload, old_dir, result["metrics"])
        if args.old_rows:
            rows += compare_rows(
                old_rows(run, m, old_dir, args.old_rows),
                {d["name"] for d in run.cell.per_layer}, result["metrics"])
    finally:
        run.close()
    for row in rows:
        print(json.dumps(row), flush=True)
    bad = [r["old"] for r in rows if not r["same"]]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "old_readings": len(rows), "renamed": sum(
            r["old"] != r["new"] for r in rows),
        "read_nothing_in_both": [r["old"] for r in rows
                                 if r["same"] and r["old_value"] is None],
        "differ_or_missing": bad,
        "new_in_this_tree": sorted(set(result["metrics"])
                                   - {r["new"] for r in rows})}), flush=True)
    print(json.dumps(result), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
