"""M3DB's own cluster in one process (ISSUE 32): three dbnodes at RF 3
behind a coordinator's replicating session, each service on a device of
its own. At a tiny size on the CPU's 8 virtual devices: the benchmark's
six thin query classes through the cluster equal the plain reference
exactly; the reference's replica merge equals the program's; the
columnar cluster write leaves on every replica what single writes
leave, and a commit log that replays to the same state; it acknowledges
at Majority, survives one node and fails typed without two; and every
service's device work sits on the device its configuration names, while
a service given none keeps the process-wide meshes."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

os.environ.setdefault("M3_TPU_BLOCK_CACHE_RETAIN", "1")

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT_DIR, "benchmark")
S = 1_000_000_000
T0 = 1_700_000_400 * S
NS = b"default"
SEED = 3_000_000_041
CLASSES = ["single-groupby-1-1-1", "single-groupby-1-8-1",
           "single-groupby-5-1-1", "single-groupby-5-8-1", "cpu-max-all-1",
           "cpu-max-all-8"]


# ------------------------------------------------- the benchmark's own cluster


@pytest.fixture(scope="module")
def served():
    """The benchmark's tiny cluster cell, booted and set up by the files
    the real cell uses (deployments/cluster-rf3.py,
    setups/cluster-replicas.py), without the load generator."""
    import sys
    import tempfile

    sys.path.insert(0, BENCH_DIR)
    try:
        from harness import server as server_mod, spec

        real = spec.load_benchmark()
        cell = spec.load_cell("rf3-query-thin", dict(
            real,
            configs=[{"name": "m3-rf3-tiny",
                      "file": "benchmark/tests/m3-rf3-tiny.json"}],
            workloads=[dict(next(w for w in real["workloads"]
                                 if w["name"] == "rf3-query-thin"),
                            config="m3-rf3-tiny", chips=1)]))
        with tempfile.TemporaryDirectory(prefix="m3rf3_") as td:
            srv = server_mod.Server(cell, SEED, td)
            try:
                facts = srv.load(lambda _msg: None)
                yield cell, srv, facts
            finally:
                srv.close()
    finally:
        sys.path.remove(BENCH_DIR)


@pytest.mark.parametrize("name", CLASSES)
def test_a_thin_class_through_the_cluster_equals_the_reference(served, name):
    from harness import datagen, schedule, spec

    cell, srv, _facts = served
    ref = spec.load_part("reference", "promql_ref")
    cls = spec.load_class(name)
    one = dict(cell.to_wire(), classes=[cls], traffic=dict(
        cell.traffic, loop="closed", mix=[{"class": name, "cards": 1}]))
    held = srv.vals[:, :int(cell.traffic["setup"]["load_steps"])]
    for req in schedule.requests_for(one, SEED, 2, salt=5):
        with urllib.request.urlopen(srv.base + req["path"], timeout=60) as r:
            got = ref.parse_response(r.read().decode(), req)
        want = ref.evaluate(cls, cell.config, srv.labels, held, req,
                            datagen.T0 // datagen.S)
        c = ref.compare(got, want)
        assert c["values"] > 0
        assert (c["label_sets_differ"], c["points_missing_or_extra"],
                c["worst_rel_gap"]) == (0, 0, 0.0), (c, req["path"])


def test_the_set_up_wrote_its_open_buffer_through_the_cluster(served):
    cell, srv, facts = served
    setup = cell.traffic["setup"]
    n = len(srv.labels)
    assert facts["cluster_write_samples"] == n * int(setup["open_steps"])
    # every replica holds the open buffer's every point
    last = int(setup["load_steps"]) - 1
    from harness import datagen

    ts = int(datagen.step_ts(cell.config, last))
    for node in srv.handle.nodes:
        held = sum(len(sh.buffer.read(i, ts, ts + 1)[0])
                   for sh in node.db.namespace(NS).shards.values()
                   for i in range(len(sh.registry)))
        assert held == n


def test_each_services_device_work_sits_on_its_own_device(served):
    import jax

    from m3_tpu.parallel import ingest as par_ingest, scope as dscope
    from m3_tpu.storage import block_cache
    from m3_tpu.utils import instrument

    _cell, srv, _facts = served
    devs = jax.devices()
    handle = srv.handle
    caches = []
    for i, node in enumerate(handle.nodes, start=1):
        assert node.db.scope.devices == (devs[i],)
        with node.db.scope:
            cache = block_cache.get_cache()
            assert par_ingest.flush_mesh() is None     # one chip: plain jit
            assert dscope.device_tag() == str(devs[i].id)
        caches.append(cache)
        held = [e.encoded[0] for e in cache._entries.values()
                if e.encoded is not None]
        assert held, "the seal kept no encoded buffer"
        assert all(a.devices() == {devs[i]} for a in held)
    assert len({id(c) for c in caches}) == 3
    assert len({id(c.budget) for c in caches}) == 3
    assert block_cache.get_cache() not in caches        # the process's own
    # the coordinator decodes what it fetched on device 0
    assert handle.coordinator.api.device_scope.devices == (devs[0],)
    assert handle.coordinator.engine.mesh is None
    ran_on = {k for k, v in instrument.ROOT.snapshot().items()
              if k.startswith("client.decode_tile.dispatches{")
              and v != srv.counters0.get(k, 0)}   # since this cluster booted
    assert ran_on == {"client.decode_tile.dispatches{device=%d}" % devs[0].id}


def test_a_service_given_no_devices_owns_them_all():
    import jax

    from m3_tpu.parallel import ingest as par_ingest, scope as dscope
    from m3_tpu.query import executor

    assert dscope.current() is dscope.DEFAULT
    assert dscope.from_config([], "x") is None
    mesh = par_ingest.flush_mesh()
    assert mesh is not None and mesh.devices.size == len(jax.devices())
    assert mesh.devices.shape == (len(jax.devices()) // 2, 2)
    assert executor._default_query_mesh().devices.size == len(jax.devices())
    two = dscope.DeviceScope([2, 5], "pair")
    with two:
        assert dscope.current() is two
        assert par_ingest.flush_mesh().devices.size == 2
        assert executor._default_query_mesh().devices.size == 2
        with dscope.DeviceScope([4], "one"):
            assert par_ingest.flush_mesh() is None
            assert executor._default_query_mesh() is None
        assert dscope.current() is two
    assert dscope.current() is dscope.DEFAULT
    seen = []
    t = threading.Thread(target=lambda: seen.append(dscope.current()))
    with two:
        t.start()
        t.join()
    assert seen == [dscope.DEFAULT]     # a scope is its thread's alone


# --------------------------------------------------------- the reference


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_references_merge_is_the_programs(seed):
    import importlib.util

    from m3_tpu.client.decode import merge_replica_points

    spec_ = importlib.util.spec_from_file_location(
        "replica_ref_under_test",
        os.path.join(BENCH_DIR, "reference", "replica_ref.py"))
    ref = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(ref)
    rng = np.random.default_rng(seed)
    base = T0 + np.arange(60, dtype=np.int64) * 10 * S
    parts_t, parts_v = [], []
    for _k in range(3):       # overlapping replicas that disagree in places
        keep = np.sort(rng.choice(60, int(rng.integers(20, 60)),
                                  replace=False))
        parts_t.append(base[keep])
        parts_v.append(rng.integers(0, 100, len(keep)).astype(np.float64))
    parts_t.append(np.zeros(0, np.int64))
    parts_v.append(np.zeros(0, np.float64))
    t, v = ref.merge_replicas(parts_t, parts_v)
    pt, pv = merge_replica_points(parts_t, parts_v)
    assert t.tolist() == pt.tolist() and v.tolist() == pv.tolist()
    assert len(t) == len(set(np.concatenate(parts_t).tolist()))
    # and its murmur3 is the sharding's
    from m3_tpu.utils.hashing import murmur3_32

    for sid in (b"", b"a", b"ab", b"abc", b"cpu{host=h1}", bytes(range(37))):
        assert ref.murmur3_32(sid) == murmur3_32(sid)
    assert ref.holders([b"abc"], {murmur3_32(b"abc") % 4: ["n2", "n1"]},
                       4) == [("n1", "n2")]


# ----------------------------------------------------- the cluster write


class Cluster:
    """Three dbnodes, a placement and a session, by the entry points the
    deployment uses; no coordinator."""

    def __init__(self, tmp_path, name, shards=8, scoped=False, **opts):
        from m3_tpu.client.session import Session, SessionOptions
        from m3_tpu.cluster import kv as cluster_kv
        from m3_tpu.cluster.placement import Instance, PlacementService
        from m3_tpu.cluster.topology import DynamicTopology
        from m3_tpu.query.storage import SessionStorage
        from m3_tpu.services import load_dict, run_dbnode

        self.now = {"t": T0 + 60 * S}
        self.dirs, self.nodes = [], []
        for i in (1, 2, 3):
            d = str(tmp_path / name / ("n%d" % i))
            self.dirs.append(d)
            self.nodes.append(run_dbnode(load_dict({
                "host_id": "node%d" % i, "data_dir": d, "num_shards": shards,
                "replication_factor": 3,
                "devices": [i] if scoped else [],
                "namespaces": [{"name": "default", "block_size": "20m",
                                "retention": "12h"}]}, "dbnode"),
                clock=lambda: self.now["t"]))
        svc = PlacementService(cluster_kv.MemStore())
        svc.init([Instance(n.server.service.host_id, n.endpoint,
                           isolation_group="g%d" % i)
                  for i, n in enumerate(self.nodes)], shards, 3)
        self.session = Session(DynamicTopology(svc),
                               SessionOptions(timeout_s=5.0, **opts))
        self.storage = SessionStorage(self.session, NS)

    def stored(self):
        """Per node, every shard's registry and open buckets, rows in
        stored order."""
        out = []
        for node in self.nodes:
            per = {}
            for shard_id, shard in node.db.namespace(NS).shards.items():
                reg = shard.registry
                per[shard_id] = (
                    reg.all_ids(), [reg.tags_of(i) for i in range(len(reg))],
                    {bs: tuple(col.tolist() for col in b.cols.view())
                     for bs, b in sorted(shard.buffer.buckets.items())})
            out.append(per)
        return out

    def replayed(self):
        """Per node, what its commit log replays to: a series' tags and
        its points in log order."""
        from m3_tpu.persist import commitlog

        out = []
        for node, d in zip(self.nodes, self.dirs):
            node.db.commitlog.close()
            state = {}
            for b in commitlog.replay_batches(os.path.join(d, "commitlog")):
                for sid, t, v, tg in zip(b.ids, b.t_ns, b.values, b.tags):
                    e = state.setdefault(sid, {"tags": None, "points": []})
                    e["tags"] = e["tags"] or (dict(tg) if tg else None)
                    e["points"].append((int(t), float(v)))
            out.append(state)
        return out

    def close(self):
        self.session.close()
        for n in self.nodes:
            n.close()


def _rows(hosts, t_ns, first=0, value=1.0):
    from m3_tpu.coordinator.ingest import _series_id

    out = []
    for i in range(first, first + hosts):
        tags = {b"__name__": b"cpu", b"host": b"h%03d" % i,
                b"dc": b"d%d" % (i % 3)}
        out.append((_series_id(tags), tags, t_ns, value + i))
    return out


def _batch(cluster, rows):
    cluster.storage.write_batch([r[0] for r in rows], [r[1] for r in rows],
                                [r[2] for r in rows], [r[3] for r in rows])


def _writes():
    first = _rows(120, T0)
    again = [(sid, tags, T0 + 10 * S, v + 0.5) for sid, tags, _t, v in first]
    mixed = [(sid, tags, T0 + 20 * S, v) for sid, tags, _t, v in first[:40]] \
        + _rows(30, T0 + 20 * S, first=500)
    return [first, again, mixed]


def test_a_cluster_batch_stores_what_single_writes_store(tmp_path):
    from m3_tpu.utils.instrument import ROOT

    batch = Cluster(tmp_path, "batch")
    single = Cluster(tmp_path, "single")
    try:
        c0 = ROOT.snapshot()
        for rows in _writes():
            _batch(batch, rows)
            for sid, tags, t, v in rows:
                single.storage.write(sid, tags, t, v)
        assert batch.session.drain() and single.session.drain()
        c1 = ROOT.snapshot()
        moved = {k: c1[k] - c0.get(k, 0) for k in c1
                 if k.startswith("client.write_batch.")}
        # one RPC a host a batch; nothing per datapoint
        assert moved["client.write_batch.rpcs"] == 3 * len(_writes())
        assert moved["client.write_batch.samples"] == sum(
            len(r) for r in _writes())
        got, want = batch.stored(), single.stored()
        assert got == want
        assert got[0] == got[1] == got[2]
        assert sum(len(ids) for ids, _t, _b in got[0].values()) == 150
        assert all(tags for _ids, tg, _b in got[0].values() for tags in tg)
        # a series' tags crossed the wire once a host: the second and
        # third batches carried them for first sightings alone
        for known in batch.session._tagged.values():
            assert len(known) == 150
        assert batch.replayed() == single.replayed()
        replayed = batch.replayed()[0]
        assert len(replayed) == 150 and all(
            e["tags"] for e in replayed.values())
    finally:
        batch.close()
        single.close()


def test_tags_travel_once_a_series_a_host(tmp_path, monkeypatch):
    from m3_tpu.rpc.node_server import NodeService

    seen = []
    real = NodeService.rpc_write_batch

    def spy(self, ns, ids, ts, vals, tags=None, shards=None):
        seen.append((self.host_id, None if tags is None
                     else sum(1 for t in tags if t), shards is not None))
        return real(self, ns, ids, ts, vals, tags, shards)

    monkeypatch.setattr(NodeService, "rpc_write_batch", spy)
    c = Cluster(tmp_path, "tags")
    try:
        for rows in _writes():
            _batch(c, rows)
        assert c.session.drain()
        for host in ("node1", "node2", "node3"):
            mine = [(n, routed) for h, n, routed in seen if h == host]
            assert mine == [(120, True), (None, True), (30, True)]
        # an attempt that failed under a batch whose tags were withheld
        # (the host may have restarted between the send and the ack): the
        # session forgets what it believed of that host and the batch
        # goes again with every tag
        victim = c.session._client(c.session._map().hosts["node2"])
        real_call = victim.call

        def call_after_a_failure(method, **kw):
            victim.call = real_call
            victim.epoch += 1
            return real_call(method, **kw)

        victim.call = call_after_a_failure
        del seen[:]
        _batch(c, _writes()[1])
        assert c.session.drain()
        assert sorted(seen, key=lambda r: (r[0], r[1] or 0)) == [
            ("node1", None, True), ("node2", None, True),
            ("node2", 120, True), ("node3", None, True)]
        assert len(c.session._tagged["node2"]) == 120
    finally:
        c.close()


def test_a_batch_is_acknowledged_at_majority(tmp_path, monkeypatch):
    """Two hosts' acks return the call; the third's share lands behind
    it."""
    from m3_tpu.rpc.node_server import NodeService

    gate = threading.Event()
    real = NodeService.rpc_write_batch

    def slow_on_node3(self, *a, **kw):
        if self.host_id == "node3":
            gate.wait(10)
        return real(self, *a, **kw)

    monkeypatch.setattr(NodeService, "rpc_write_batch", slow_on_node3)
    c = Cluster(tmp_path, "quorum")
    try:
        rows = _rows(40, T0)
        t0 = time.monotonic()
        _batch(c, rows)
        assert time.monotonic() - t0 < 5
        held = [sum(len(sh.registry) for sh in
                    n.db.namespace(NS).shards.values()) for n in c.nodes]
        assert held == [40, 40, 0]
        assert not c.session.drain(0.05)
        gate.set()
        assert c.session.drain()
        assert c.stored()[2] == c.stored()[0]
    finally:
        gate.set()
        c.close()


@pytest.mark.parametrize("down,ok", [(1, True), (2, False)])
def test_nodes_down(tmp_path, down, ok):
    from m3_tpu.client.session import ConsistencyError
    from m3_tpu.query.model import Matcher, MatchType

    c = Cluster(tmp_path, "down%d" % down)
    try:
        _batch(c, _rows(30, T0))
        assert c.session.drain()
        for node in c.nodes[-down:]:
            node.server.close()
            for cl in c.session._clients.values():
                cl.close()      # pooled connections die with their node
        rows = [(sid, tags, T0 + 10 * S, v)
                for sid, tags, _t, v in _rows(30, T0)]
        if not ok:
            with pytest.raises(ConsistencyError):
                _batch(c, rows)
            return
        _batch(c, rows)
        c.session.drain()
        got = c.storage.fetch_raw(
            (Matcher(MatchType.EQUAL, b"__name__", b"cpu"),), T0 - S,
            T0 + 60 * S)
        assert len(got) == 30
        assert all(e["t"].tolist() == [T0, T0 + 10 * S]
                   for e in got.values())
    finally:
        c.close()


def test_a_batch_one_node_refuses_counts_as_that_hosts_failure(tmp_path):
    """A node whose acceptance window refuses the batch refuses it whole;
    the other two acknowledge it."""
    c = Cluster(tmp_path, "window")
    try:
        # node 3's clock runs an hour ahead: the rows are too old for it
        c.nodes[2].db.clock = lambda: c.now["t"] + 3600 * S
        _batch(c, _rows(20, T0))
        c.session.drain()
        held = [sum(len(sh.registry) for sh in
                    n.db.namespace(NS).shards.values()) for n in c.nodes]
        assert held == [20, 20, 0]
    finally:
        c.close()


def test_the_flush_encode_of_a_scoped_node_runs_on_its_device(tmp_path):
    import jax

    from m3_tpu.storage import block_cache
    from m3_tpu.storage.mediator import Mediator
    from m3_tpu.utils import tracing

    c = Cluster(tmp_path, "scoped", scoped=True)
    try:
        for k in range(6):
            _batch(c, [(sid, tags, T0 + k * 10 * S, v)
                       for sid, tags, _t, v in _rows(64, T0)])
        assert c.session.drain()
        c.now["t"] = T0 + 3600 * S
        devs = jax.devices()
        for i, node in enumerate(c.nodes, start=1):
            stats = Mediator(node.db, node.persist).run_once()
            assert stats["sealed"] > 0
            tick = [t for t in tracing.TRACER.recent_traces()
                    if t["name"] == "mediator.tick"][-1]
            assert tick["tags"]["device"] == str(devs[i].id)
            blocks = [g for ch in tick["children"]
                      for g in ch.get("children", [])
                      if g["name"] == "encode.block"]
            assert blocks and all(b["tags"]["device"] == str(devs[i].id)
                                  for b in blocks)
            with node.db.scope:
                cache = block_cache.get_cache()
            held = [e.encoded[0] for e in cache._entries.values()
                    if e.encoded is not None]
            assert held and all(a.devices() == {devs[i]} for a in held)
    finally:
        c.close()
