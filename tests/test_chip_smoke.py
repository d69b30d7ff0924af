"""chip_smoke.py off the chip: its phases run tiny on the CPU test
platform (this is also how a builder debugs before spending chip time),
and the script itself refuses any platform that is not a TPU."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def test_phases_run_tiny_on_cpu(tmp_path, monkeypatch):
    import chip_smoke
    from m3_tpu.parallel import guard
    from m3_tpu.query import plan as qplan

    # tiny grids sit under the 4096-cell plan floor; the floor is not
    # what this test is about
    monkeypatch.setattr(qplan, "PLAN_MIN_CELLS", 1)
    guard.reset()
    sizes = chip_smoke.Sizes(
        series=160, hosts=10, sealed_blocks=2, open_steps=12,
        http_series=24, http_steps=3, timer_groups=6, timer_per_group=5,
        timer_samples=3, codec_sample=8, num_shards=2)
    ctx = chip_smoke.run_phases(sizes, seed=7, workdir=str(tmp_path))
    assert not ctx.cuts
    assert ctx.facts["seal"]["sealed_block_starts"] >= 2
    assert ctx.facts["queries"]["second_pass_compiles"] == 0
    assert ctx.facts["fileset"]["retriever"]["seeks"] > 0
    assert set(ctx.results) >= {"sum_by_host_rate", "bare_rate",
                                "instant_sum_by_host", "instant_http_leg",
                                "fileset_read", "timer_p99"}
    digest = chip_smoke.results_digest(ctx)
    assert len(digest["bare_rate"]["labels"]) == 20  # dc0: 160 / 8
    # --compare: a run equals its own results after the JSON round trip
    results = {"device": {}, "sealed_sha256": "x", "queries": digest}
    ref = tmp_path / "ref.json"
    ref.write_text(json.dumps(results))
    chip_smoke.compare_runs(results, str(ref))


def test_script_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
