#!/usr/bin/env python
"""Record the compiled-vs-oracle property corpus through the REAL
serving path into a coverage corpus (artifacts/query_corpus_rNN.jsonl).

Drives every query of tests/test_plan_compile.py's corpus (the grown
~90-query compiled + fallback lists) through an Engine with the opt-in
corpus recorder installed at sample=1.0, so each record carries the
route the query ACTUALLY took plus its typed fallback reason — the
input `scripts/coverage_report.py` computes the ROADMAP item 4 coverage
number from.

Usage: python scripts/record_corpus.py artifacts/query_corpus_r16.jsonl

The PLAN_MIN_CELLS floor is DISABLED for the recording (the same
no_floor fixture the property tests use): the corpus measures the
LOWERING surface — which query shapes can take the compiled route —
over a test-sized storage that would otherwise record below-floor for
every shape. Data-size routing is telemetry's job in production
(`plan_fallback{scope=runtime}`), not this instrument's; the r15
baseline was recorded under the same convention, so the coverage
numbers compare like for like.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"


def main(argv) -> int:
    if len(argv) != 1:
        print(__doc__)
        return 2
    out_path = argv[0]
    if os.path.exists(out_path):
        print(f"refusing to append to existing corpus {out_path}")
        return 2

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests"))
    import test_plan_compile as tpc

    from m3_tpu.query import Engine
    from m3_tpu.query import corpus as qcorpus
    from m3_tpu.query import plan as qplan

    # Dashboard-sized storage: enough series x cells that compilable
    # queries clear the production floor (the corpus measures lowering
    # coverage, not the small-data routing policy).
    qplan.PLAN_MIN_CELLS = 1
    eng = Engine(tpc.make_storage(0, n_m=24, n_b=11, n_c=6))
    qcorpus.install(qcorpus.CorpusRecorder(out_path, sample=1.0))
    try:
        for q in tpc.COMPILED_QUERIES + tpc.FALLBACK_QUERIES:
            eng.execute_range(q, tpc.START, tpc.END, tpc.STEP).values
    finally:
        qcorpus.install(None)
    records = qcorpus.read_corpus(out_path)
    cov = qcorpus.coverage(records)
    print(f"recorded {len(records)} queries -> {out_path}; "
          f"coverage {cov['coverage']:.1%} recorded / "
          f"{cov['structural_coverage']:.1%} structural")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
