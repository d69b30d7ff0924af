"""Coordinator tests: HTTP API end-to-end (json write -> PromQL query_range),
embedded downsampler with rule-matched aggregation written back to storage,
admin endpoints (reference: src/query/api/v1 + m3coordinator ingest and
downsample packages; docker-integration-tests/simple is the model for the
HTTP round trip)."""

import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from m3_tpu.cluster import kv as cluster_kv
from m3_tpu.coordinator import run_embedded
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.metrics import aggregation as magg
from m3_tpu.metrics.filters import TagsFilter
from m3_tpu.metrics.matcher import RuleSetStore
from m3_tpu.metrics.policy import StoragePolicy
from m3_tpu.metrics.rules import MappingRuleSnapshot, Rule, RuleSet
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.query import NamespaceAttrs
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions

S = 1_000_000_000
T0 = 1_600_000_000 * S
TEN_S = StoragePolicy.of("10s", "2d")


def http(method, url, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


@pytest.fixture
def coord():
    now = {"t": T0}
    db = Database(ShardSet(8), clock=lambda: now["t"])
    db.create_namespace(b"default", NamespaceOptions(),
                        index=NamespaceIndex(clock=lambda: now["t"]))
    db.create_namespace(b"agg_10s", NamespaceOptions(),
                        index=NamespaceIndex(clock=lambda: now["t"]))
    store = cluster_kv.MemStore()
    rs = RuleSet(
        b"default", 1,
        mapping_rules=[Rule([MappingRuleSnapshot(
            "downsample-api", 0, TagsFilter({"service": "api"}),
            magg.AggID.compress([magg.AggType.MAX]), (TEN_S,))])])
    RuleSetStore(store).publish(rs)
    c = run_embedded(db, kv_store=store, cluster_namespaces=[
        NamespaceAttrs(b"default", retention_ns=TEN_S.retention_ns),
        NamespaceAttrs(b"agg_10s", True, TEN_S.retention_ns,
                       TEN_S.resolution.window_ns, complete=False)],
        clock=lambda: now["t"])
    yield c, db, now
    c.close()


class TestHTTPReadWrite:
    def test_json_write_then_query_range(self, coord):
        c, db, now = coord
        base = c.endpoint
        for i in range(20):
            now["t"] = T0 + i * 15 * S
            http("POST", f"{base}/api/v1/json/write", {
                "tags": {"__name__": "cpu_percent", "host": "a"},
                "timestamp": (T0 + i * 15 * S) / S,
                "value": 50.0 + i,
            })
        q = urllib.parse.urlencode({
            "query": "cpu_percent", "start": (T0 + 60 * S) / S,
            "end": (T0 + 240 * S) / S, "step": "30s"})
        out = http("GET", f"{base}/api/v1/query_range?{q}")
        assert out["status"] == "success"
        result = out["data"]["result"]
        assert len(result) == 1
        assert result[0]["metric"]["host"] == "a"
        ts, v = result[0]["values"][0]
        assert float(v) >= 50.0

    def test_promql_function_over_http(self, coord):
        c, db, now = coord
        base = c.endpoint
        for i in range(30):
            now["t"] = T0 + i * 15 * S
            http("POST", f"{base}/api/v1/json/write", {
                "tags": {"__name__": "reqs_total", "job": "a"},
                "timestamp": (T0 + i * 15 * S) / S, "value": 10.0 * i})
        q = urllib.parse.urlencode({
            "query": "rate(reqs_total[2m])", "start": (T0 + 240 * S) / S,
            "end": (T0 + 420 * S) / S, "step": "60s"})
        out = http("GET", f"{base}/api/v1/query_range?{q}")
        vals = [float(v) for _, v in out["data"]["result"][0]["values"]]
        np.testing.assert_allclose(vals, 10 / 15, rtol=1e-6)

    def test_labels_series_label_values(self, coord):
        c, db, now = coord
        base = c.endpoint
        http("POST", f"{base}/api/v1/json/write", {
            "tags": {"__name__": "m1", "dc": "east"},
            "timestamp": T0 / S, "value": 1.0})
        http("POST", f"{base}/api/v1/json/write", {
            "tags": {"__name__": "m1", "dc": "west"},
            "timestamp": T0 / S, "value": 2.0})
        q = urllib.parse.urlencode({"match[]": "m1", "start": T0 / S - 60,
                                    "end": T0 / S + 60})
        labels = http("GET", f"{base}/api/v1/labels?{q}")
        assert "dc" in labels["data"]
        vals = http("GET", f"{base}/api/v1/label/dc/values?{q}")
        assert vals["data"] == ["east", "west"]
        series = http("GET", f"{base}/api/v1/series?{q}")
        assert len(series["data"]) == 2

    def test_instant_query(self, coord):
        c, db, now = coord
        base = c.endpoint
        http("POST", f"{base}/api/v1/json/write", {
            "tags": {"__name__": "g1"}, "timestamp": T0 / S, "value": 7.0})
        q = urllib.parse.urlencode({"query": "g1", "time": (T0 + 30 * S) / S})
        out = http("GET", f"{base}/api/v1/query?{q}")
        assert out["data"]["resultType"] == "vector"
        assert float(out["data"]["result"][0]["value"][1]) == 7.0

    def test_health_and_routes(self, coord):
        c, _, _ = coord
        assert http("GET", f"{c.endpoint}/health")["ok"]
        assert any("query_range" in r for r in
                   http("GET", f"{c.endpoint}/routes")["routes"])

    def test_debug_vars_serves_the_documented_keys(self, coord):
        """Operators watching /debug/vars see the process counters
        (`metrics`) and the compute guard's per-route state (`compute`),
        and nothing else."""
        c, _, _ = coord
        q = urllib.parse.urlencode({"query": "g1", "time": T0 / S})
        http("GET", f"{c.endpoint}/api/v1/query?{q}")
        v = http("GET", f"{c.endpoint}/debug/vars")
        assert set(v) == {"metrics", "compute"}
        assert v["metrics"]["query.executed"] >= 1
        for route, state in v["compute"].items():
            assert "state" in state, route


class TestDownsampler:
    def test_rule_matched_writes_aggregate_back(self, coord):
        c, db, now = coord
        # service=api matches the MAX/10s rule; others don't.
        for i in range(12):
            now["t"] = T0 + i * 2 * S
            c.writer.write({b"__name__": b"lat", b"service": b"api"},
                           T0 + i * 2 * S, float(i))
            c.writer.write({b"__name__": b"lat", b"service": b"web"},
                           T0 + i * 2 * S, float(i))
        now["t"] = T0 + 40 * S
        c.flush_downsampler()
        assert c.downsampler.samples_matched == 12
        # Aggregated namespace holds the 10s MAX series (suffix .upper).
        from m3_tpu.index import query as iq
        ids = db.query_ids(b"agg_10s", iq.new_term(b"service", b"api"))
        assert len(ids) == 1
        assert b".upper" in ids[0] or b"lat" in ids[0]
        ns = db.namespace(b"agg_10s")
        shard = ns.shards[db.shard_set.lookup(ids[0])]
        t, v = shard.read(ids[0], T0, T0 + 60 * S)
        # windows [T0,T0+10): max=4; [T0+10,T0+20): max=9; [T0+20,..): max=11
        np.testing.assert_array_equal(v, [4.0, 9.0, 11.0])
        # Unaggregated write always lands in the default namespace too.
        ids_unagg = db.query_ids(b"default", iq.new_term(b"service", b"web"))
        assert len(ids_unagg) == 1


    def test_a_sink_that_fails_loses_no_closed_window(self, coord,
                                                      monkeypatch):
        """The aggregator gives a window up when it is collected: a round
        whose sink raises keeps its rows for the next round, the flush
        thread counts and logs it and lives on."""
        from m3_tpu.utils import instrument

        c, db, now = coord
        for i in range(12):
            now["t"] = T0 + i * 2 * S
            c.writer.write({b"__name__": b"lat", b"service": b"api"},
                           T0 + i * 2 * S, float(i))
        ns = db.namespace(b"agg_10s")
        real, fails = db.write_batch, [2]

        def failing(namespace, *a, **kw):
            if namespace == b"agg_10s" and fails[0]:
                fails[0] -= 1
                raise OSError("disk full")
            return real(namespace, *a, **kw)

        monkeypatch.setattr(db, "write_batch", failing)
        now["t"] = T0 + 40 * S
        with pytest.raises(OSError):
            c.flush_downsampler()        # the caller's own flush hears
        assert len(c.downsampler._held) == 3
        errors = instrument.ROOT.counter("coordinator.downsample.flush_errors")
        before = errors.value()
        c.start_downsample_flush()       # a round a second
        deadline = time.monotonic() + 10
        while c.downsampler._held and time.monotonic() < deadline:
            time.sleep(0.01)
        c.close()
        assert errors.value() - before == 1 and not c.downsampler._held
        from m3_tpu.index import query as iq
        (sid,) = db.query_ids(b"agg_10s", iq.new_term(b"service", b"api"))
        t, v = ns.shards[db.shard_set.lookup(sid)].read(sid, T0, T0 + 60 * S)
        np.testing.assert_array_equal(v, [4.0, 9.0, 11.0])


class TestAdmin:
    def test_database_create_quickstart(self, coord):
        c, db, now = coord
        base = c.endpoint
        out = http("POST", f"{base}/api/v1/database/create", {
            "type": "local", "namespaceName": "quickstart", "retentionTime": "12h"})
        assert "quickstart" in out["namespace"]["registry"]["namespaces"]
        assert out["placement"]["placement"]["instances"]
        assert b"quickstart" in db.namespaces
        got = http("GET", f"{base}/api/v1/namespace")
        assert "quickstart" in got["registry"]["namespaces"]
        p = http("GET", f"{base}/api/v1/services/m3db/placement")
        assert p["placement"]["num_shards"] == 64

    def test_topic_admin(self, coord):
        c, _, _ = coord
        base = c.endpoint
        out = http("POST", f"{base}/api/v1/topic/init", {
            "name": "aggregated_metrics", "numberOfShards": 4,
            "consumerServices": [{"serviceId": "coordinator"}]})
        assert out["topic"]["num_shards"] == 4
        got = http("GET", f"{base}/api/v1/topic?name=aggregated_metrics")
        assert got["topic"]["consumer_services"][0]["service_id"] == "coordinator"


def test_buildinfo_and_metadata_compat(coord):
    """Grafana probes these prometheus-compat endpoints during datasource
    setup; both must return the prom success envelope."""
    c, _, _ = coord
    r = http("GET", c.api.endpoint + "/api/v1/status/buildinfo")
    assert r["status"] == "success" and "version" in r["data"]
    r = http("GET", c.api.endpoint + "/api/v1/metadata")
    assert r["status"] == "success" and r["data"] == {}


def test_instant_scalar_result_type(coord):
    """prom API: instant queries of scalar-typed expressions return
    resultType "scalar" with Go-style shortest number formatting ("2",
    not "2.0"); vector-typed stay "vector"."""
    c, _, _ = coord
    base = c.endpoint
    r = http("GET", base + "/api/v1/query?query=1%2B1&time=1700000000")
    assert r["data"]["resultType"] == "scalar"
    assert r["data"]["result"][1] == "2"
    r = http("GET", base + "/api/v1/query?query=vector(42)&time=1700000000")
    assert r["data"]["resultType"] == "vector"
    assert r["data"]["result"][0]["value"][1] == "42"


def test_a_flush_beside_the_first_writes_of_new_series_loses_nothing(coord):
    """The flush thread drains the aggregator's lists while handler
    threads add the elems of series they have not seen: every closed
    window is emitted once, and no round raises."""
    c, db, now = coord
    ds = c.downsampler
    errors, stop = [], threading.Event()

    def flusher():
        while not stop.is_set():
            try:
                ds.flush()
            except Exception as e:     # what the flush thread would log
                errors.append(e)

    t = threading.Thread(target=flusher)
    t.start()
    n = 4000
    for i in range(n):
        c.writer.write({b"__name__": b"lat", b"service": b"api",
                        b"inst": b"%05d" % i}, T0 + S, float(i))
    stop.set()
    t.join()
    assert not errors
    now["t"] = T0 + 40 * S
    ds.flush()
    from m3_tpu.index import query as iq
    ids = db.query_ids(b"agg_10s", iq.new_term(b"service", b"api"))
    assert len(ids) == n
