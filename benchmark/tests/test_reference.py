"""The comparison that decides `correct`, at a tiny size on the CPU:
every query class of both query mixes through the booted server agrees
with the plain reference; each control (arithmetic in bfloat16, a read
that misses the open buffer, a sample not stored) is rejected by the
same comparison; a run whose timed path is broken underneath comes out
not correct; and the measuring path refuses to report without a TPU."""

import json
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny  # noqa: E402
from harness import cellrun  # noqa: E402

SEED = 3_000_000_019
ROOT = os.path.dirname(tiny.BENCH)


def _run(workload, seconds, **overrides):
    run = cellrun.CellRun(tiny.cell(workload, **overrides), SEED,
                          time.perf_counter_ns(), need_chip=False)
    run.setup(seconds)
    return run


def _correct(run, m, control=None):
    return run.result(m, *run.check(m, control))["correct"]


def _failing(run, m, control):
    checks, _n, _f = run.check(m, control)
    return {name for name, value, limit in checks if value > limit}


@pytest.fixture(scope="module")
def thin():
    run = _run("cpu4k-query-thin", 3.0)
    yield run, run.window(3.0)
    run.close()


@pytest.fixture(scope="module")
def fat():
    run = _run("cpu4k-query-fat", 3.0)
    yield run, run.window(3.0)
    run.close()


def test_thin_classes_agree_with_the_reference(thin):
    run, m = thin
    assert {int(c) for c in m.rec["cls"]} == set(range(len(m.cell.classes)))
    assert _correct(run, m)


def test_fat_classes_agree_with_the_reference(fat):
    run, m = fat
    assert len(m.keep) == int(m.cell.traffic["replay_len"])
    assert _correct(run, m)


def test_bfloat16_arithmetic_is_rejected(fat):
    run, m = fat
    assert "worst_rel_gap" in _failing(run, m, "bf16")


def test_a_read_that_misses_the_open_buffer_is_rejected(thin, fat):
    for run, m in (thin, fat):
        assert _failing(run, m, "stale") & {"points_missing_or_extra",
                                            "worst_rel_gap"}


def test_an_answer_altered_where_it_is_produced_is_not_correct(thin,
                                                                monkeypatch):
    """The rest of a run, the timed path broken underneath: the range
    renderer serves every value a hundredth too high."""
    from m3_tpu.query import render

    real = render.prom_matrix_bytes

    def off_by_a_hundredth(block):
        import dataclasses

        import numpy as np

        vals = np.asarray(block.values, np.float64) * 1.01
        try:
            return real(dataclasses.replace(block, values=vals))
        except TypeError:
            block.values = vals
            return real(block)

    run, _m = thin
    monkeypatch.setattr(render, "prom_matrix_bytes", off_by_a_hundredth)
    m = run.window(2.0)
    result = run.result(m, *run.check(m))
    assert result["correct"] is False and result["failed"] > 0


def test_ingest_reads_back_and_a_lost_sample_is_rejected():
    run = _run("cpu4k-ingest", 3.0)
    try:
        m = run.window(3.0)
        assert _correct(run, m)
        assert "readback_mismatched" in _failing(run, m, "drop")
    finally:
        run.close()


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         "cpu4k-query-thin", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, None)
    assert not p.stdout.strip(), p.stdout[-500:]
    assert "TPU" in p.stderr


def test_traced_run_reads_every_layer_metric_it_can_on_a_cpu():
    """No device plane exists on the CPU, so the busiest device's idle
    share has nothing to read; every span, counter and clock reader still
    finds its input and every idle instant gets a name."""
    from harness import breakdown

    cell = tiny.cell("cpu4k-query-thin")
    result = cellrun.run_cell(cell, SEED, 2.0, True, time.perf_counter_ns(),
                              need_chip=False)
    assert result["correct"] is True
    want = {m["name"] for m in cell.per_layer}
    assert want - set(result["metrics"]) <= {"block_cache_hit_share",
                                             "device_idle_share.query"}
    assert result["device"]["window_s"] >= 2.0
    names = {name for name, _s in result["breakdown"]["idle_gaps"]}
    assert names <= set(breakdown.PRIORITY) | {breakdown.IDLE}
    assert "query.fetch" in names and "loadgen-wait" in names
