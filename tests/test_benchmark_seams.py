"""Every name the benchmark ships resolves: each cell of BENCHMARK.json,
and each configuration file under benchmark/configs paired with each
traffic file under benchmark/traffic, finds its deployment, set-up,
traffic kind, checks and reference as files that define their functions.

The tier-1 copy of benchmark/tests/test_seams.py::
test_every_shipped_name_resolves_to_a_file_with_its_functions (which
tier-1 does not run). No JAX: `benchmark/harness/spec.py` and the part
files import neither JAX nor the program while they are imported."""

import glob
import json
import os

import pytest

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT_DIR, "benchmark")


def _shipped():
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        yield pytest.param(w["name"], bench, id=w["name"])
    configs = sorted(glob.glob(os.path.join(BENCH_DIR, "configs", "*.json")))
    traffic = sorted(glob.glob(os.path.join(BENCH_DIR, "traffic", "*.json")))
    for c in configs:
        for t in traffic:
            cname, tname = (os.path.basename(p)[:-5] for p in (c, t))
            name = cname + "." + tname
            yield pytest.param(name, dict(
                bench,
                configs=[{"name": cname,
                          "file": os.path.relpath(c, ROOT_DIR)}],
                workloads=[{"name": name, "config": cname, "traffic": tname,
                            "chips": 1}]), id=name)


@pytest.mark.parametrize("workload,bench", list(_shipped()))
def test_every_shipped_name_resolves_to_a_file_with_its_functions(
        workload, bench, monkeypatch):
    monkeypatch.syspath_prepend(BENCH_DIR)  # the part files import `harness`
    from harness import spec

    cell = spec.load_cell(workload, bench)
    parts = [("deployments", cell.deployment), ("setups", cell.setup_via),
             ("traffic_kinds", cell.traffic["kind"]),
             ("reference", cell.reference)]
    parts += [("checks", c) for c in cell.checks]
    for kind, name in parts:
        mod = spec.load_part(kind, name)
        assert os.path.dirname(mod.__file__) == os.path.join(BENCH_DIR, kind)
        for attr in spec.PARTS[kind]:
            assert hasattr(mod, attr), (mod.__file__, attr)
