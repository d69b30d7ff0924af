"""The four parts of a run that are files found by name: what boots
(`deployments/`), how the store is filled (`setups/`), what the
generator sends (`traffic_kinds/`), and what decides `correct`
(`checks/`, `reference/`). At a tiny size on the CPU: both cells through
the seams give the checks and the metric names they gave before; a
stand-in deployment whose configuration carries a `coordinator` block
and whose traffic file loads through the coordinator's writer boots,
loads, serves and checks; a name with no file fails in `load_cell` with
the path, before anything is started; and every name in every file the
benchmark ships resolves."""

import glob
import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import tiny  # noqa: E402
from harness import cellrun, spec  # noqa: E402

SEED = 3_000_000_029

VERDICT = ["compute_fault_counters_moved",
           "runtime_plan_fallbacks_not_allowed", "breakers_not_closed",
           "codec_dispatches_off_gate", "evaluations_placed_on_host_backend",
           "compiles_in_window"]
CHECKS = {
    "cpu4k-query-thin": [
        "requests_failed", "answers_unanswered", "label_sets_differ",
        "points_missing_or_extra", "worst_rel_gap",
        "answers_compared_at_least"] + VERDICT,
    "cpu4k-ingest": [
        "writes_not_acknowledged_in_full", "readback_mismatched",
        "readback_reads_failed", "readback_pairs_compared_at_least"] + VERDICT,
}
CHECKS["tiny-via-coordinator"] = CHECKS["cpu4k-ingest"]
END_TO_END = {
    "cpu4k-query-thin": {"query_p50_ms", "setup_s"},
    "cpu4k-ingest": {"ingest_samples_per_s", "stored_bytes_per_sample",
                     "setup_s"},
}
END_TO_END["tiny-via-coordinator"] = END_TO_END["cpu4k-ingest"]


@pytest.mark.parametrize("workload", sorted(CHECKS))
def test_a_run_through_the_seams(workload):
    cell = tiny.cell(workload)
    result = cellrun.run_cell(cell, SEED, 3.0, False, time.perf_counter_ns(),
                              need_chip=False)
    assert result["correct"] is True, result["checks"]
    assert list(result["checks"]) == CHECKS[workload]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == END_TO_END[workload]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_the_stand_in_is_set_up_by_the_ingest_path():
    """The coordinator serves the namespace its own block names (the
    parent's boot wrote `{}` over the block: the writer then had no such
    namespace), and a store filled through its writer has seen every
    series before the window: the shard memo misses nothing in it."""
    cell = tiny.cell("tiny-via-coordinator")
    assert (cell.deployment, cell.setup_via) == (
        "dbnode-embedded", "coordinator-write-batch")
    run = cellrun.CellRun(cell, SEED, time.perf_counter_ns(), need_chip=False)
    try:
        facts = run.setup(2.0)
        handle = run.server.handle
        assert handle.namespace == b"tsbs"
        assert facts["samples"] == 240 * int(
            cell.traffic["setup"]["load_steps"])
        m = run.window(2.0)
        assert m.moved("sharding.memo.hits") > 0
        assert m.moved("sharding.memo.misses") == 0
        checks, attempted, failed = run.check(m)
        assert attempted > 0 and failed == 0
        assert all(v <= lim for _n, v, lim in checks), checks
        # and the control of the check it belongs to still comes out wrong
        bad = {n for n, v, lim in run.check(m, "drop")[0] if v > lim}
        assert bad == {"readback_mismatched"}
    finally:
        run.close()


def test_the_ingest_window_starts_one_scrape_after_the_warm_scrape():
    cell = tiny.cell("cpu4k-ingest")
    run = cellrun.CellRun(cell, SEED, time.perf_counter_ns(), need_chip=False)
    try:
        run.setup(2.0)
        m = run.window(2.0)
        first = int(cell.traffic["setup"]["load_steps"])
        assert int(m.rec["step"].min()) == first + 1
        # the warm scrape was the first sighting of every series
        assert m.moved("sharding.memo.misses") == 0
        # and is stored: read it back, every series of it
        import urllib.parse
        import urllib.request

        from harness import datagen

        ts = int(datagen.step_ts(cell.config, first) // datagen.S)
        url = run.server.base + "/api/v1/query?" + urllib.parse.urlencode(
            {"query": "max_over_time(cpu[10s])", "time": ts})
        with urllib.request.urlopen(url, timeout=60) as r:
            res = json.loads(r.read())["data"]["result"]
        assert len(res) == 240
        nf = len(cell.config["schema"]["fields"])
        fields = cell.config["schema"]["fields"]
        for s in res:
            host = int(s["metric"]["hostname"].split("_")[1])
            row = host * nf + fields.index(s["metric"]["field"])
            assert float(s["value"][1]) == float(run.server.vals[row, first])
    finally:
        run.close()


MISSING = [
    ("traffic", {"kind": "no-such-kind"}, "traffic_kinds/no-such-kind.py"),
    ("traffic", {"setup": {"via": "no-such-setup", "load_steps": 1}},
     "setups/no-such-setup.py"),
    ("traffic", {"checks": ["readback", "no-such-check"]},
     "checks/no-such-check.py"),
    ("traffic", {"reference": "no-such-reference"},
     "reference/no-such-reference.py"),
    ("config", {"deployment_kind": "no-such-deployment"},
     "deployments/no-such-deployment.py"),
]


@pytest.mark.parametrize("where,override,path", MISSING,
                         ids=[m[2] for m in MISSING])
def test_a_name_with_no_file_fails_in_load_cell_with_the_path(
        where, override, path, monkeypatch):
    real = spec._load_json

    def edited(p):
        d = real(p)
        if os.sep + where + os.sep in p or (
                where == "config" and p.endswith("tsbs-cpu-tiny.json")):
            d.update(override)
        return d

    monkeypatch.setattr(spec, "_load_json", edited)
    with pytest.raises(SystemExit) as e:
        spec.load_cell("cpu4k-ingest", tiny.bench())
    assert os.path.join(spec.BENCH_DIR, path) in str(e.value)


def test_a_file_without_its_functions_fails_with_its_path(tmp_path,
                                                          monkeypatch):
    os.makedirs(tmp_path / "checks")
    (tmp_path / "checks" / "empty.py").write_text("ROWS = []\n")
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_part("checks", "empty")
    assert "empty.py does not define check" in str(e.value)


def _shipped():
    """(workload, BENCHMARK.json) for every cell, and for every
    configuration file under benchmark/configs paired with every traffic
    file under benchmark/traffic (cells later PRs may add as entries)."""
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        yield pytest.param(w["name"], bench, id=w["name"])
    configs = sorted(glob.glob(os.path.join(spec.BENCH_DIR, "configs",
                                            "*.json")))
    traffic = sorted(glob.glob(os.path.join(spec.BENCH_DIR, "traffic",
                                            "*.json")))
    for c in configs:
        for t in traffic:
            cname, tname = (os.path.basename(p)[:-5] for p in (c, t))
            name = cname + "." + tname
            yield pytest.param(name, dict(
                bench,
                configs=[{"name": cname,
                          "file": os.path.relpath(c, spec.ROOT_DIR)}],
                workloads=[{"name": name, "config": cname, "traffic": tname,
                            "chips": 1}]), id=name)


@pytest.mark.parametrize("workload,bench", list(_shipped()))
def test_every_shipped_name_resolves_to_a_file_with_its_functions(workload,
                                                                  bench):
    cell = spec.load_cell(workload, bench)
    parts = [("deployments", cell.deployment), ("setups", cell.setup_via),
             ("traffic_kinds", cell.traffic["kind"]),
             ("reference", cell.reference)]
    parts += [("checks", c) for c in cell.checks]
    for kind, name in parts:
        mod = spec.load_part(kind, name)
        for attr in spec.PARTS[kind]:
            assert hasattr(mod, attr), (mod.__file__, attr)


def _pairs():
    """(kind, entry, cell) for every metric of BENCHMARK.json, once for
    each cell that reports it (an entry with no list: every cell)."""
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    for kind, key in (("end_to_end", "end_to_end"),
                      ("layer_metrics", "per_layer")):
        for m in bench[key]:
            for c in m.get("workloads", cells):
                yield pytest.param(kind, m, c, id=m["name"] + "@" + c)


@pytest.mark.parametrize("kind,decl,workload", list(_pairs()))
def test_every_reading_of_every_cell_has_its_reader_and_its_declaration(
        kind, decl, workload):
    """One case a (metric, cell) pair, so a reading folded into its base's
    `workloads` keeps its case: the reader loads, the cell exists and
    reports what the reading moves, `load_cell` hands the entry to the
    cell's run, and a per-layer entry's declaration beside the reader is
    the entry (its `workloads` too, while tier-1's
    tests/test_benchmark_seams.py and test_runtime_probe.py hold that
    mirror: PERF.md section 7)."""
    bench = spec.load_benchmark()
    assert callable(spec.load_reader(kind, decl["name"]))
    assert workload in {w["name"] for w in bench["workloads"]}
    cell = spec.load_cell(workload, bench)
    if kind == "end_to_end":
        assert decl in cell.end_to_end
        return
    assert decl in cell.per_layer
    assert decl["moves"] in {m["name"] for m in cell.end_to_end}
    assert spec.layer_metric_declarations()[decl["name"]] == decl


def test_the_per_layer_list_fits_the_drivers_limit():
    """`per_layer`: 1 to 128 metrics (the builder's instructions; README:
    "A layer metric")."""
    assert 1 <= len(spec.load_benchmark()["per_layer"]) <= 128


def test_a_folded_name_is_no_entry_and_its_base_lists_its_cells():
    """One entry a reading: a twin that a `benchmark` PR folded into its
    base (tools/folded_names.json: PR 41's 18 `.deep`, PR 50's 14 `.rf3`,
    14 `.aggns` and 12 `.net`) has no entry, declaration or reader any
    more, and the base it names is an entry that lists the twin's cell."""
    with open(os.path.join(spec.BENCH_DIR, "tools",
                           "folded_names.json")) as f:
        folded = json.load(f)
    by_name = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    names = set(by_name)
    assert folded and not set(folded) & names
    assert set(folded.values()) <= names
    cell_of = {"deep": "cpu4k-query-12h", "rf3": "rf3-query-thin",
               "aggns": "aggns-query-3d", "net": "net4k-query-rate"}
    by_suffix = {}
    for old, new in folded.items():
        suffix = old.rpartition(".")[2]
        by_suffix[suffix] = by_suffix.get(suffix, 0) + 1
        assert cell_of[suffix] in by_name[new]["workloads"], (old, new)
    assert by_suffix == {"deep": 18, "rf3": 14, "aggns": 14, "net": 12}
    for old in folded:
        for ext in (".json", ".py"):
            assert not os.path.exists(os.path.join(
                spec.BENCH_DIR, "layer_metrics", old + ext)), old + ext


def test_a_twin_still_to_fold_reads_with_its_bases_code():
    """A twin (an entry whose `moves`, unit, source and layer are another
    entry's and whose name is that entry's stem plus a cell's suffix)
    stays safe to fold: a forwarder to its base's reader or a copy of
    its body. PR 50 folded the last 40, so none is held until a PR that
    is no `benchmark` PR brings a cell that shares a reading (README,
    "One entry a reading")."""
    import ast

    def body(name):
        path = os.path.join(spec.BENCH_DIR, "layer_metrics", name + ".py")
        with open(path) as f:
            tree = ast.parse(f.read())
        return [ast.dump(n) for n in tree.body
                if not (isinstance(n, ast.Expr)
                        and isinstance(n.value, ast.Constant))]

    by_name = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name, m in by_name.items():
        stem, _, suffix = name.rpartition(".")
        base = by_name.get(stem) or by_name.get(stem + ".query")
        if not suffix or base is None or base is m or any(
                base[k] != m[k] for k in ("unit", "better", "source",
                                          "layer", "moves")):
            continue
        read = spec.load_reader("layer_metrics", name)
        forwarded = os.path.basename(read.__code__.co_filename) \
            == base["name"] + ".py"
        assert forwarded or body(name) == body(base["name"]), name
