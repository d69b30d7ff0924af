"""Finds everything that belongs to one cell by the names in BENCHMARK.json.

A later PR adds a configuration, a mix, a query class, a cell, a layer
metric, a deployment kind, a set-up, a traffic kind, a check or a
reference by adding files and entries; nothing here is edited for it."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT_DIR = os.path.dirname(BENCH_DIR)

# The parts of a run that are files found by name: directory -> what a
# file there defines (functions; CHECKS is a kind's default list of checks).
PARTS = {
    "deployments": ("boot",),
    "setups": ("load",),
    "traffic_kinds": ("init", "warm", "run", "keep_indices", "CHECKS"),
    "checks": ("check",),
    "reference": ("evaluate", "parse_response", "compare"),
}


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _load_json(os.path.join(ROOT_DIR, "BENCHMARK.json"))


def load_class(name: str) -> dict:
    cls = _load_json(os.path.join(BENCH_DIR, "classes", name + ".json"))
    cls["name"] = name
    return cls


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict           # benchmark/configs/<config>.json
    traffic_name: str
    traffic: dict          # benchmark/traffic/<traffic>.json
    classes: List[dict]    # benchmark/classes/<class>.json, in the mix's order
    deployment: str        # benchmark/deployments/<name>.py
    setup_via: str         # benchmark/setups/<name>.py
    checks: List[str]      # benchmark/checks/<name>.py, in order
    reference: str         # benchmark/reference/<name>.py
    end_to_end: List[dict]  # the BENCHMARK.json entries this cell reports
    per_layer: List[dict]

    def to_wire(self) -> dict:
        return dataclasses.asdict(self)


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {[w['name'] for w in bench['workloads']]})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load_json(os.path.join(ROOT_DIR, cfg_entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      entry["traffic"] + ".json"))
    classes = [load_class(c["class"]) for c in traffic.get("mix", [])]
    # the four parts a run is made of, each a file found by its name; a
    # name with no file, or a file without its functions, fails here
    deployment = config.get("deployment_kind", "dbnode-embedded")
    load_part("deployments", deployment)
    setup_via = traffic["setup"].get("via", "db-write-batch")
    load_part("setups", setup_via)
    kind = load_part("traffic_kinds", traffic["kind"])
    checks = list(traffic.get("checks", kind.CHECKS))  # absent: the kind's own
    for c in checks:
        load_part("checks", c)
    reference = traffic.get("reference", "promql_ref")
    load_part("reference", reference)
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _in_cell(m, workload) and m["moves"] in reported]
    return Cell(workload, int(entry["chips"]), entry["config"], config,
                entry["traffic"], traffic, classes, deployment, setup_via,
                checks, reference, e2e, layer)


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no file {path} (a {kind} named "
                         f"{name!r} was asked for)")
    spec = importlib.util.spec_from_file_location(
        kind + "_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(kind: str, metric_name: str
                ) -> Callable[[object], Optional[float]]:
    """benchmark/<kind>/<name>.py defines read(m) -> value | None; `kind`
    is `end_to_end` or `layer_metrics`."""
    return _load_module(kind, metric_name).read


_parts: Dict[tuple, object] = {}


def load_part(kind: str, name: str):
    """The module benchmark/<kind>/<name>.py, `kind` a key of PARTS. None
    of them imports JAX or the program while it is imported: the
    load-generator child loads its traffic kind this way too."""
    if (kind, name) not in _parts:
        mod = _load_module(kind, name)
        missing = [f for f in PARTS[kind] if not hasattr(mod, f)]
        if missing:
            raise SystemExit(f"benchmark: {mod.__file__} does not define "
                             f"{', '.join(missing)}")
        _parts[kind, name] = mod
    return _parts[kind, name]


def layer_metric_declarations() -> Dict[str, dict]:
    out = {}
    d = os.path.join(BENCH_DIR, "layer_metrics")
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            out[fn[:-5]] = _load_json(os.path.join(d, fn))
    return out
