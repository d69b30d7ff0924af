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


def _named_classes():
    """Every query class a traffic file names outside its mix (the mix's
    own are loaded by `load_cell`): `warm_first` entries and the cases
    of a `boundary` block."""
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "traffic",
                                              "*.json"))):
        with open(path) as f:
            traffic = json.load(f)
        named = [w["class"] for w in traffic.get("warm_first", [])]
        named += [case["class"] for case in traffic.get("boundary",
                                                        {}).values()
                  if isinstance(case, dict)]
        for name in named:
            yield pytest.param(name, id=os.path.basename(path)[:-5] + "."
                               + name)


@pytest.mark.parametrize("name", list(_named_classes()))
def test_every_class_a_traffic_file_names_is_a_class_file(name, monkeypatch):
    monkeypatch.syspath_prepend(BENCH_DIR)
    from harness import spec

    cls = spec.load_class(name)
    assert {"endpoint", "promql", "reference"} <= set(cls)


def test_the_namespace_list_cell_ships_what_it_names():
    """`aggns-query-3d`: its configuration's deployment kind, its mix's
    set-up, its checks and its reference are the files this PR added, and
    the configuration states what the issue asks of it."""
    with open(os.path.join(BENCH_DIR, "configs", "m3-aggns-tsbs-4k.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "tsbs-range-aggns.json")) as f:
        traffic = json.load(f)
    assert cfg["deployment_kind"] == "dbnode-aggns"
    assert traffic["setup"]["via"] == "filesets-aggns"
    assert traffic["reference"] == "aggns_ref"
    assert set(traffic["checks"]) >= {"aggregated_readback",
                                      "unaggregated_readback",
                                      "resolver_boundary"}
    for kind, name in (("deployments", "dbnode-aggns"),
                       ("setups", "filesets-aggns"),
                       ("reference", "aggns_ref"),
                       ("checks", "aggregated_readback"),
                       ("checks", "unaggregated_readback"),
                       ("checks", "resolver_boundary")):
        assert os.path.isfile(os.path.join(BENCH_DIR, kind, name + ".py"))
    for key in ("source", "reduced", "assumed", "guarantees", "held",
                "resolver_rule", "aggregation", "run_values",
                "source_values"):
        assert cfg[key], key
    assert len(cfg["source"]) <= 200 and cfg["architecture"] is None
    assert {"aggregation", "resolution"} <= set(cfg["guarantees"])
    # the published shapes are uncut
    assert (cfg["scale"], cfg["cadence_s"], cfg["dbnode"]["num_shards"]) == (
        4000, 10, 64)
    sizes = {ns["name"]: ns["block_size"]
             for ns in cfg["dbnode"]["namespaces"]}
    assert sizes == {"default": "20m", "metrics_1m_72h": "2h"}


def test_the_aggregation_tier_cell_ships_what_it_names():
    """`aggtier-query-live` (PR 51): its configuration's deployment kind,
    its mix's set-up, its checks and its reference are files with their
    functions, the traffic kind is the accepted one, and the
    configuration states the topology and the guarantees the issue asks
    of it."""
    with open(os.path.join(BENCH_DIR, "configs",
                           "m3-aggtier-prom-4k.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           "prom-live-agg-12h.json")) as f:
        traffic = json.load(f)
    assert cfg["deployment_kind"] == "aggregator-tier"
    assert traffic["kind"] == "query_under_write"
    assert traffic["setup"]["via"] == "filesets-aggtier"
    assert traffic["reference"] == "aggtier_ref"
    assert traffic["checks"] == ["query_answers_agg_frontier",
                                 "tier_readback", "write_pace",
                                 "mixed_readback", "served_path_verdict"]
    for kind, name in (("deployments", "aggregator-tier"),
                       ("setups", "filesets-aggtier"),
                       ("reference", "aggtier_ref"),
                       ("checks", "query_answers_agg_frontier"),
                       ("checks", "tier_readback")):
        assert os.path.isfile(os.path.join(BENCH_DIR, kind, name + ".py"))
    with open(os.path.join(BENCH_DIR, "reference", "aggtier_ref.py")) as f:
        src = f.read()
    assert "import m3_tpu" not in src and "from m3_tpu" not in src
    for key in ("source", "reduced", "assumed", "guarantees", "held",
                "resolver_rule", "aggregation", "run_values",
                "source_values", "kv", "aggregators"):
        assert cfg[key], key
    assert len(cfg["source"]) <= 200 and cfg["architecture"] is None
    assert {"aggregation", "delivery", "handoff", "lateness",
            "resolution"} <= set(cfg["guarantees"])
    assert cfg["reduced"] == [
        "aggregated_history_held", "unaggregated_retention",
        "unaggregated_history_held", "replication_factor",
        "aggregator_shard_sets"]
    # never cut: the fleet, the front, the placement, the rule
    assert (cfg["scale"], cfg["cadence_s"], cfg["dbnode"]["num_shards"],
            len(cfg["schema"]["fields"]), len(cfg["schema"]["tags"]["order"])
            ) == (4000, 10, 64, 10, 10)
    assert [(a["instance_id"], a["num_shards"], a["shard_set_id"])
            for a in cfg["aggregators"]] == [("agg0", 64, "shardset-0"),
                                             ("agg1", 64, "shardset-0")]
    assert (traffic["senders"], traffic["samples_per_send"]) == (8, 500)
    # the window's first scrape step begins 50 s past a minute boundary
    assert (traffic["setup"]["load_steps"] + 1) * cfg["cadence_s"] % 60 == 50
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "aggtier-query-live")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "m3-aggtier-prom-4k", "prom-live-agg-12h", 1)
    own = [m["name"] for m in bench["per_layer"]
           if m["layer"] == "aggregation tier"]
    assert own == ["agg_client_us_per_sample", "agg_add_us_per_sample",
                   "agg_flush_s", "agg_sink_us_per_row",
                   "agg_msg_ack_ms_p95", "agg_staleness_s",
                   "agg_redeliveries_in_window", "agg_cpu_share"]
    assert len(bench["per_layer"]) <= 128


def _readings():
    """(kind, entry, cell) for every metric of BENCHMARK.json, once for
    each cell that reports it (an entry with no list: every cell): a
    reading folded into its base's `workloads` keeps its case."""
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    for kind, key in (("end_to_end", "end_to_end"),
                      ("layer_metrics", "per_layer")):
        for m in bench[key]:
            for c in m.get("workloads", cells):
                yield pytest.param(kind, m, c, bench, id=m["name"] + "@" + c)


@pytest.mark.parametrize("kind,decl,workload,bench", list(_readings()))
def test_every_metric_has_its_reader_and_its_declaration(
        kind, decl, workload, bench, monkeypatch):
    """One case a (metric, cell) pair (the tier-1 copy of
    benchmark/tests/test_seams.py::
    test_every_reading_of_every_cell_has_its_reader_and_its_declaration).
    A metric of BENCHMARK.json is a reader file found by its name
    (`read(m)`), and a per-layer one a declaration beside it that mirrors
    the entry; the cell exists, `load_cell` hands the entry to its run,
    and the cell is one the end-to-end metric the reading moves lists."""
    monkeypatch.syspath_prepend(BENCH_DIR)
    from harness import spec

    assert callable(spec.load_reader(kind, decl["name"]))
    assert workload in {w["name"] for w in bench["workloads"]}
    cell = spec.load_cell(workload, bench)
    if kind == "end_to_end":
        assert decl in cell.end_to_end
        return
    assert decl in cell.per_layer
    with open(os.path.join(BENCH_DIR, kind, decl["name"] + ".json")) as f:
        assert json.load(f) == decl
    assert decl["moves"] in {m["name"] for m in cell.end_to_end}


def _per_layer():
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def test_a_folded_name_is_no_entry_and_its_base_lists_its_cells():
    """One entry a reading: a twin that a `benchmark` PR folded into its
    base (benchmark/tools/folded_names.json) has no entry, declaration
    or reader any more, and the base it names is an entry."""
    with open(os.path.join(BENCH_DIR, "tools", "folded_names.json")) as f:
        folded = json.load(f)
    names = set(_per_layer())
    assert folded and not set(folded) & names
    assert set(folded.values()) <= names
    for old in folded:
        for ext in (".json", ".py"):
            assert not os.path.exists(os.path.join(
                BENCH_DIR, "layer_metrics", old + ext)), old + ext


def test_a_twin_still_to_fold_reads_with_its_bases_code(monkeypatch):
    """The twins this tree still holds (an entry whose `moves`, unit,
    source and layer are another entry's and whose name is that entry's
    stem plus a cell's suffix) stay safe to fold: each is a forwarder to
    its base's reader or a copy of its body, `device_idle_share.rf3`
    alone apart (the busiest device, which on one chip is the chip)."""
    import ast

    monkeypatch.syspath_prepend(BENCH_DIR)
    from harness import spec

    def body(name):
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               name + ".py")) as f:
            tree = ast.parse(f.read())
        return [ast.dump(n) for n in tree.body
                if not (isinstance(n, ast.Expr)
                        and isinstance(n.value, ast.Constant))]

    by_name = _per_layer()
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
        assert forwarded or body(name) == body(base["name"]) \
            or name == "device_idle_share.rf3", name
