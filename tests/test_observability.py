"""End-to-end observability plane: distributed tracing (ids, sampling,
wire propagation, cross-process graft), per-span cost attribution from
QueryScope charges, the slow-query log's typed reasons, the self-scrape
pipeline (instrument snapshot -> own ingest -> PromQL), JAX runtime
telemetry, and the /debug surface satellites (snapshot-outside-lock,
capped background profiler)."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from m3_tpu.utils import tracing
from m3_tpu.utils.tracing import (NOOP_SPAN, PROFILER, SLOW_QUERIES,
                                  ProfileRunner, SlowQueryLog, SpanContext,
                                  Tracer)

T0 = 1_700_000_000 * 1_000_000_000
S = 1_000_000_000


# ---------------------------------------------------------------- tracer core


class TestSpanIdentity:
    def test_root_gets_trace_and_span_ids(self):
        tr = Tracer(sample_rate=1.0)
        with tr.span("root") as root:
            assert root.trace_id > 0 and root.span_id > 0
            with tr.span("child") as c:
                assert c.trace_id == root.trace_id
                assert c.span_id != root.span_id
        d = tr.recent_traces()[-1]
        assert d["trace_id"] == root.trace_id
        assert d["children"][0]["trace_id"] == root.trace_id

    def test_context_wire_roundtrip_and_malformed(self):
        ctx = SpanContext(123, 456)
        assert SpanContext.from_wire(ctx.to_wire()) == ctx
        for bad in (None, 7, {"t": "x", "s": 1}, {"t": 1}, {"s": 2},
                    {"t": True, "s": 1}, []):
            assert SpanContext.from_wire(bad) is None

    def test_sampling_zero_yields_noop(self):
        tr = Tracer(sample_rate=0.0)
        sp = tr.span("never")
        assert sp is NOOP_SPAN
        with sp:
            assert tr.current() is None
        assert tr.recent_traces() == []

    def test_child_span_without_parent_is_noop(self):
        tr = Tracer(sample_rate=1.0)
        assert tr.child_span("bare") is NOOP_SPAN
        with tr.span("root"):
            real = tr.child_span("inner")
            assert real is not NOOP_SPAN
            with real:
                pass

    def test_span_from_remote_context(self):
        tr = Tracer(sample_rate=1.0)
        ctx = SpanContext(99, 11)
        with tr.span_from(ctx, "rpc.x") as sp:
            assert sp.trace_id == 99
            assert sp.remote_parent == 11
        d = tr.recent_traces(trace_id=99)
        assert d and d[-1]["remote_parent"] == 11
        assert tr.span_from(None, "rpc.x") is NOOP_SPAN

    def test_activate_propagates_across_threads(self):
        tr = Tracer(sample_rate=1.0)
        seen = {}

        with tr.span("root") as root:
            def worker():
                with tr.activate(root):
                    seen["cur"] = tr.current()
                    with tr.span("in-pool"):
                        pass
                seen["after"] = tr.current()

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["cur"] is root
        assert seen["after"] is None
        d = tr.recent_traces()[-1]
        assert [c["name"] for c in d["children"]] == ["in-pool"]

    def test_attach_grafts_remote_dict(self):
        tr = Tracer(sample_rate=1.0)
        with tr.span("root") as root:
            root.attach({"name": "rpc.fetch", "trace_id": root.trace_id,
                         "tags": {"endpoint": "h:1"}})
        d = tr.recent_traces()[-1]
        assert d["children"][0]["name"] == "rpc.fetch"

    def test_collect_costs_rolls_up_subtree_and_grafts(self):
        """Review fix: cache events accrue on the INNERMOST span (a
        storage child, or a grafted remote dict) — the slow-query log's
        cold-cache classification reads the subtree rollup."""
        tr = Tracer(sample_rate=1.0)
        with tr.span("root") as root:
            root.add_cost("docs_matched", 5)
            with tr.span("child") as c:
                c.add_cost("block_cache_miss", 2)
            root.attach({"name": "rpc", "costs": {"bytes_read": 7},
                         "children": [{"name": "x",
                                       "costs": {"block_cache_miss": 1}}]})
        assert tracing.collect_costs(root) == {
            "docs_matched": 5, "block_cache_miss": 3, "bytes_read": 7}

    def test_slow_log_lazy_costs_only_evaluated_on_record(self):
        log = SlowQueryLog(threshold_ms=1.0)
        calls = []

        def expensive():
            calls.append(1)
            return {"block_cache_miss": 1}

        log.maybe("query", "fast", duration_ns=100, costs=expensive)
        assert calls == []  # under threshold: rollup never ran
        log.maybe("query", "slow", duration_ns=5_000_000, costs=expensive)
        assert calls == [1]
        assert log.entries()[-1]["reason"] == "cold-cache"

    def test_costs_accumulate(self):
        tr = Tracer(sample_rate=1.0)
        with tr.span("root") as root:
            root.add_cost("bytes_read", 10)
            root.add_cost("bytes_read", 5)
        assert tr.recent_traces()[-1]["costs"] == {"bytes_read": 15}


# ------------------------------------------------------------ slow-query log


class TestSlowQueryLog:
    def test_threshold_and_typed_reasons(self):
        log = SlowQueryLog(threshold_ms=1.0, maxlen=8)
        log.maybe("query", "fast", duration_ns=10_000)  # under threshold
        log.maybe("query", "slow_one", duration_ns=5_000_000)
        log.maybe("query", "shed", duration_ns=10, reason="limit-shed")
        log.maybe("query", "dead", duration_ns=10, reason="deadline")
        entries = log.entries()
        assert [e["name"] for e in entries] == ["slow_one", "shed", "dead"]
        assert [e["reason"] for e in entries] == ["slow", "limit-shed",
                                                 "deadline"]

    def test_cold_cache_reason_from_costs(self):
        log = SlowQueryLog(threshold_ms=0.0)
        log.maybe("query", "q", duration_ns=1,
                  costs={"block_cache_miss": 2, "bytes_read": 5})
        log.maybe("query", "warm", duration_ns=1, costs={"bytes_read": 5})
        assert log.entries()[0]["reason"] == "cold-cache"
        assert log.entries()[1]["reason"] == "slow"

    def test_ring_bounded(self):
        log = SlowQueryLog(threshold_ms=0.0, maxlen=4)
        for i in range(10):
            log.maybe("rpc", f"m{i}", duration_ns=1)
        assert len(log.entries()) == 4
        assert log.entries()[-1]["name"] == "m9"


# --------------------------------------------------- cross-process span trees


def _node_with_data():
    from m3_tpu.parallel.sharding import ShardSet
    from m3_tpu.rpc import NodeServer, NodeService
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.namespace import NamespaceOptions

    db = Database(ShardSet(2), clock=lambda: T0)
    db.mark_bootstrapped()
    db.ensure_namespace(b"obs", NamespaceOptions(index_enabled=True,
                                                 writes_to_commitlog=False))
    for i in range(6):
        db.write(b"obs", b"s-%02d" % i, T0 - (6 - i) * S, float(i),
                 tags={b"__name__": b"m", b"host": b"h%02d" % i})
    return NodeServer(NodeService(db), port=0).start()


class TestCrossProcessTrace:
    def test_rpc_span_grafted_with_costs_and_storage_children(self):
        from m3_tpu.client.session import HostClient
        from m3_tpu.index import query as iq
        from m3_tpu.rpc import wire

        srv = _node_with_data()
        hc = HostClient(srv.endpoint, timeout=5)
        try:
            with tracing.TRACER.span("test.root") as root:
                r = hc.call("fetch_tagged", ns=b"obs",
                            query=wire.query_to_wire(iq.AllQuery()),
                            start_ns=0, end_ns=2**62)
                assert len(r["series"]) == 6
                grafted = [c for c in root.children if isinstance(c, dict)]
            assert grafted, "no server span grafted"
            sp = grafted[0]
            assert sp["name"] == "rpc.fetch_tagged"
            assert sp["trace_id"] == root.trace_id
            assert sp["remote_parent"] == root.span_id
            assert sp["tags"]["endpoint"] == srv.endpoint
            # per-span QueryScope cost attribution rode the graft
            assert sp["costs"]["series_fetched"] == 6
            assert sp["costs"]["docs_matched"] >= 6
            assert sp["costs"]["bytes_read"] > 0
            # dbnode-side storage child (index query) inside the rpc span
            names = [c["name"] for c in sp.get("children", [])]
            assert "index.query" in names
        finally:
            hc.close()
            srv.close()

    def test_unsampled_request_attaches_no_context(self):
        from m3_tpu.client.session import HostClient

        srv = _node_with_data()
        hc = HostClient(srv.endpoint, timeout=5)
        try:
            before = len(tracing.TRACER.recent_traces())
            assert hc.call("health")["ok"]  # no active span -> no "tr"
            after = [d for d in tracing.TRACER.recent_traces()[before:]
                     if d["name"].startswith("rpc.")]
            assert after == []
        finally:
            hc.close()
            srv.close()

    def test_session_fetch_tagged_one_tree_three_hops(self):
        from m3_tpu.client.session import Session, SessionOptions
        from m3_tpu.index import query as iq
        from m3_tpu.testing.cluster import ClusterHarness

        harness = ClusterHarness(n_nodes=2, replica_factor=2, num_shards=4)
        session = Session(harness.topology, SessionOptions(timeout_s=10))
        try:
            t0 = harness.clock.now_ns
            session.write_batch(
                b"default", [b"a", b"b"], np.array([t0 - S] * 2, np.int64),
                np.array([1.0, 2.0]),
                tags=[{b"__name__": b"mm"}, {b"__name__": b"mm"}])
            with tracing.TRACER.span("test.query") as root:
                out = session.fetch_tagged(b"default", iq.AllQuery(),
                                           0, 2**62)
                assert len(out) == 2
            d = root.to_dict()
            client = d["children"][0]
            assert client["name"] == "client.fetch_tagged"
            grafts = [c for c in client.get("children", [])
                      if c.get("name") == "rpc.fetch_tagged"]
            assert grafts, "no dbnode spans under the client fanout span"
            # one trace id across client + every grafted dbnode span
            assert {g["trace_id"] for g in grafts} == {root.trace_id}
            endpoints = {g["tags"]["endpoint"] for g in grafts}
            assert len(endpoints) == 2  # both replicas traced
        finally:
            session.close()
            harness.close()

    def test_gate_shed_logs_empty_costs_not_previous_requests(self):
        """Review fix: a request shed by the admission gate BEFORE its
        QueryScope runs must log empty costs — not the previous
        request's totals left on this reused serving thread."""
        from m3_tpu.index import query as iq
        from m3_tpu.rpc import wire
        from m3_tpu.rpc.node_server import NodeService
        from m3_tpu.parallel.sharding import ShardSet
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.namespace import NamespaceOptions
        from m3_tpu.utils.health import AdmissionGate, HealthTracker
        from m3_tpu.utils.limits import ResourceExhausted

        db = Database(ShardSet(2), clock=lambda: T0)
        db.mark_bootstrapped()
        db.ensure_namespace(b"obs", NamespaceOptions(index_enabled=True))
        db.write(b"obs", b"g-0", T0, 1.0, tags={b"__name__": b"m"})
        gate = AdmissionGate(capacity=2, name="",
                             tracker=HealthTracker())
        svc = NodeService(db, gate=gate)
        q = wire.query_to_wire(iq.AllQuery())
        # Request A charges real costs on this thread.
        svc.dispatch("fetch_tagged",
                     {"ns": b"obs", "query": q, "start_ns": 0,
                      "end_ns": 2**62})
        # Fill the gate so request B sheds BEFORE its scope runs.
        gate.admit(2)
        SLOW_QUERIES.clear()
        try:
            with pytest.raises(ResourceExhausted):
                svc.dispatch("fetch_tagged",
                             {"ns": b"obs", "query": q, "start_ns": 0,
                              "end_ns": 2**62})
        finally:
            gate.release(2)
        sheds = [e for e in SLOW_QUERIES.entries()
                 if e["reason"] == "limit-shed"]
        assert sheds and sheds[-1]["costs"] == {}

    def test_slow_log_limit_shed_reason_from_rpc(self):
        from m3_tpu.client.session import HostClient
        from m3_tpu.index import query as iq
        from m3_tpu.rpc import NodeServer, NodeService, wire
        from m3_tpu.parallel.sharding import ShardSet
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.namespace import NamespaceOptions
        from m3_tpu.utils.limits import (LimitOptions, QueryLimits,
                                         ResourceExhausted)

        db = Database(ShardSet(2), clock=lambda: T0)
        db.mark_bootstrapped()
        db.ensure_namespace(b"obs", NamespaceOptions(index_enabled=True))
        for i in range(20):
            db.write(b"obs", b"x-%02d" % i, T0, 1.0,
                     tags={b"__name__": b"m"})
        limits = QueryLimits(docs_matched=LimitOptions(per_second=5))
        srv = NodeServer(NodeService(db, limits=limits), port=0).start()
        hc = HostClient(srv.endpoint, timeout=5)
        SLOW_QUERIES.clear()
        try:
            with pytest.raises(ResourceExhausted):
                hc.call("fetch_tagged", ns=b"obs",
                        query=wire.query_to_wire(iq.AllQuery()),
                        start_ns=0, end_ns=2**62)
            sheds = [e for e in SLOW_QUERIES.entries()
                     if e["reason"] == "limit-shed"]
            assert sheds and sheds[-1]["kind"] == "rpc"
        finally:
            hc.close()
            srv.close()


# ------------------------------------------------------- scope cost tagging


class TestScopeCostTagging:
    def test_scope_exit_annotates_active_span(self):
        from m3_tpu.utils import limits as xlimits

        ql = xlimits.QueryLimits()
        with tracing.TRACER.span("q") as sp:
            with ql.scope("test"):
                xlimits.charge("docs_matched", 7)
                xlimits.charge("bytes_read", 100)
                xlimits.charge("docs_matched", 3)
        assert sp.costs["docs_matched"] == 10
        assert sp.costs["bytes_read"] == 100
        # thread-local totals readable after exit (slow-log source)
        assert xlimits.last_scope_totals()["docs_matched"] == 10


# ----------------------------------------------------------- self-scrape


def _embedded():
    from m3_tpu.cluster import kv as cluster_kv
    from m3_tpu.coordinator import run_embedded
    from m3_tpu.index.namespace_index import NamespaceIndex
    from m3_tpu.parallel.sharding import ShardSet
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.namespace import NamespaceOptions

    now = {"t": T0}
    db = Database(ShardSet(4), clock=lambda: now["t"])
    db.create_namespace(b"default", NamespaceOptions(),
                        index=NamespaceIndex(clock=lambda: now["t"]))
    coord = run_embedded(db, kv_store=cluster_kv.MemStore(),
                         clock=lambda: now["t"])
    return coord, now


class TestSelfScrape:
    def test_traffic_counter_round_trip_via_promql(self):
        """THE acceptance criterion: an instrument counter incremented
        by real traffic is readable back through the PromQL query path
        against the platform's own storage."""
        from m3_tpu.coordinator.selfscrape import SelfScraper
        from m3_tpu.utils.instrument import ROOT

        coord, now = _embedded()
        try:
            coord.writer.write({b"__name__": b"real"}, T0 - 30 * S, 1.0)
            coord.engine.execute_range("real", T0 - 60 * S, T0, 10 * S)
            executed = ROOT.snapshot()["query.executed"]
            scraper = SelfScraper(coord.writer, clock=lambda: now["t"])
            assert scraper.scrape_once() > 0
            blk = coord.engine.execute_instant("query_executed", T0 + 1)
            assert blk.n_series == 1
            assert blk.values[0][-1] >= executed
            # constant labels identify the scraped process
            assert blk.series_tags[0].get(b"role") == b"coordinator"
        finally:
            coord.close()

    def test_snapshot_delta_skips_unchanged(self):
        from m3_tpu.coordinator.selfscrape import SelfScraper

        coord, now = _embedded()
        try:
            coord.writer.write({b"__name__": b"real"}, T0 - 30 * S, 1.0)
            scraper = SelfScraper(coord.writer, clock=lambda: now["t"])
            first = scraper.scrape_once()
            assert first > 0
            # a second immediate scrape only re-emits what the FIRST
            # scrape itself moved (its own ingest counters), a strict
            # subset of the full registry
            second = scraper.scrape_once()
            assert second < first
        finally:
            coord.close()

    def test_histogram_emits_le_buckets(self):
        from m3_tpu.coordinator.selfscrape import SelfScraper

        coord, now = _embedded()
        try:
            coord.writer.write({b"__name__": b"real"}, T0 - 30 * S, 1.0)
            coord.engine.execute_range("real", T0 - 60 * S, T0, 10 * S)
            SelfScraper(coord.writer,
                        clock=lambda: now["t"]).scrape_once()
            blk = coord.engine.execute_instant(
                'query_latency_s_bucket{le="+Inf"}', T0 + 1)
            assert blk.n_series >= 1
            cnt = coord.engine.execute_instant("query_latency_s_count",
                                               T0 + 1)
            assert cnt.n_series == 1 and cnt.values[0][-1] >= 1
        finally:
            coord.close()

    def test_shed_value_reemits_next_pass(self):
        """Review fix: a value whose write was shed must NOT be marked
        done — if it then stays flat, the next pass re-emits it (the
        'levels, nothing is lost' contract)."""
        from m3_tpu.coordinator.selfscrape import SelfScraper
        from m3_tpu.utils.instrument import Scope

        root = Scope()
        root.counter("stuck").inc(5)

        class FlakyWriter:
            def __init__(self):
                self.fail_first = True
                self.names = []

            def write(self, tags, t_ns, value):
                if self.fail_first:
                    self.fail_first = False
                    raise ConnectionError("down")
                self.names.append(tags[b"__name__"])

        w = FlakyWriter()
        scraper = SelfScraper(w, clock=lambda: T0, scope=root)
        scraper.scrape_once()
        assert b"stuck" not in w.names  # first emit was shed
        scraper.scrape_once()           # value unchanged — must re-emit
        assert b"stuck" in w.names

    def test_shed_scrape_survives(self):
        """A writer that sheds (Backpressure) must not kill the scrape:
        errors count, the pass completes, levels re-emit next pass."""
        from m3_tpu.coordinator.selfscrape import SelfScraper
        from m3_tpu.utils.limits import Backpressure

        class SheddingWriter:
            def __init__(self):
                self.n = 0

            def write(self, tags, t_ns, value):
                self.n += 1
                if self.n % 2:
                    raise Backpressure("shed")

        w = SheddingWriter()
        scraper = SelfScraper(w, clock=lambda: T0)
        scraper.scrape_once()
        assert scraper.errors > 0
        assert w.n > 0


# ------------------------------------------------------------- telemetry


class TestTelemetry:
    def test_jit_builder_counts_and_times_compiles(self):
        import functools

        from m3_tpu.parallel import telemetry
        from m3_tpu.utils.instrument import ROOT

        calls = []

        @telemetry.jit_builder("obs_test")
        @functools.lru_cache(maxsize=8)
        def build(w: int):
            calls.append(w)
            return lambda x: x * w

        before = ROOT.snapshot()
        f = build(3)
        assert f(2) == 6  # first call -> compile timed
        assert f(2) == 6
        g = build(3)      # hit: raw fn, same result
        assert g(2) == 6
        build(4)
        snap = ROOT.snapshot()
        key_m = "telemetry.jit.misses{builder=obs_test}"
        key_h = "telemetry.jit.hits{builder=obs_test}"
        assert snap[key_m] - before.get(key_m, 0) == 2
        assert snap[key_h] - before.get(key_h, 0) == 1
        assert calls == [3, 4]
        assert snap["telemetry.jit.compile_s"]["count"] >= 1

    def test_jit_builder_rejects_unwrapped(self):
        from m3_tpu.parallel import telemetry

        with pytest.raises(TypeError):
            telemetry.jit_builder("bad")(lambda: None)

    def test_shape_bucket_hit_miss(self):
        from m3_tpu.parallel import telemetry
        from m3_tpu.utils.instrument import ROOT

        key = ("test-path", (64, 32, int(time.monotonic_ns())))
        before = ROOT.snapshot().get("telemetry.shape_bucket.misses", 0)
        telemetry.record_bucket(*key)
        telemetry.record_bucket(*key)
        snap = ROOT.snapshot()
        assert snap["telemetry.shape_bucket.misses"] == before + 1
        assert snap["telemetry.shape_bucket.hits"] >= 1

    def test_transfer_counters_and_span_costs(self):
        from m3_tpu.parallel import telemetry
        from m3_tpu.utils.instrument import ROOT

        before = ROOT.snapshot().get("telemetry.transfer.h2d_bytes", 0)
        with tracing.TRACER.span("xfer") as sp:
            telemetry.count_h2d(1024)
            telemetry.count_d2h(2048)
        snap = ROOT.snapshot()
        assert snap["telemetry.transfer.h2d_bytes"] == before + 1024
        assert sp.costs == {"h2d_bytes": 1024, "d2h_bytes": 2048}

    def test_decode_records_bucket(self):
        from m3_tpu.ops import tsz
        from m3_tpu.ops.decode_rows import decode_stacked
        from m3_tpu.utils.instrument import ROOT

        ts = np.arange(T0, T0 + 5 * S, S, np.int64)
        vals = np.arange(5, dtype=np.float64)
        inp = tsz.prepare_encode_inputs(ts[None, :], vals[None, :],
                                        np.array([5], np.int32))
        words, nbits = tsz.encode_batch(
            inp["dt"], inp["t0"], inp["vhi"], inp["vlo"], inp["int_mode"],
            inp["k"], inp["npoints"], inp["ts_regular"], inp["delta0"],
            max_words=64)
        tile = {"bs": T0, "words": np.asarray(words[:1]),
                "nbits": np.asarray(nbits[:1]), "npoints": [5], "window": 8,
                "time_unit": 4}
        before = ROOT.snapshot().get("telemetry.shape_bucket.misses", 0)
        (_tile, _ks, _ts, out), = decode_stacked([tile])
        np.testing.assert_array_equal(out[0, :5], vals)
        after = ROOT.snapshot()["telemetry.shape_bucket.misses"]
        assert after >= before  # first geometry may or may not be new
        snap = ROOT.snapshot()
        path = "{path=block.decode_plane}"
        assert (snap.get("telemetry.shape_bucket.misses" + path, 0)
                + snap.get("telemetry.shape_bucket.hits" + path, 0)) >= 1


# ------------------------------------------------- /debug surface satellites


class TestInstrumentSnapshotLock:
    def test_snapshot_does_not_hold_root_lock_over_metric_snapshots(self):
        """Satellite: Scope.snapshot copies refs under the registry lock
        and snapshots outside it — a Histogram whose snapshot itself
        touches the registry (nested root-lock acquisition, guaranteed
        deadlock pre-fix on the non-reentrant Lock) must complete."""
        from m3_tpu.utils.instrument import Scope

        root = Scope()
        h = root.histogram("lat")
        h.record(0.5)
        orig = h.snapshot

        def reentrant_snapshot():
            root.counter("probe").inc()  # takes the root registry lock
            return orig()

        h.snapshot = reentrant_snapshot
        done = {}

        def run():
            done["snap"] = root.snapshot()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(timeout=5)
        assert "snap" in done, "snapshot deadlocked on the registry lock"
        assert done["snap"]["lat"]["count"] == 1

    def test_histogram_snapshot_consistent_under_writes(self):
        from m3_tpu.utils.instrument import Histogram

        h = Histogram()
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                h.record(0.01)

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        try:
            for _ in range(200):
                snap = h.snapshot()
                assert sum(snap["buckets"].values()) == snap["count"]
        finally:
            stop.set()
            t.join()


class TestProfileRunner:
    def test_hard_cap_bounds_the_request(self):
        runner = ProfileRunner(max_seconds=0.3)
        t0 = time.perf_counter()
        out = runner.run(seconds=30.0, hz=50)
        assert time.perf_counter() - t0 < 2.0
        assert isinstance(out, list)

    def test_concurrent_requests_share_one_window(self):
        runner = ProfileRunner(max_seconds=0.5)
        results = []

        def req():
            results.append(runner.run(seconds=0.4, hz=100))

        threads = [threading.Thread(target=req) for _ in range(4)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # 4 requests of 0.4s each sharing one window: far under 4x serial
        assert time.perf_counter() - t0 < 1.5
        assert runner.shared >= 1
        assert len(results) == 4

    def test_default_runner_profiles(self):
        stop = threading.Event()

        def hot_loop_for_runner():
            x = 0
            while not stop.is_set():
                x += 1

        t = threading.Thread(target=hot_loop_for_runner)
        t.start()
        try:
            out = PROFILER.run(seconds=0.3, hz=200)
        finally:
            stop.set()
            t.join()
        assert "hot_loop_for_runner" in json.dumps(out)


# ---------------------------------------------------- msg / kv propagation


class TestMsgKvPropagation:
    def test_producer_consumer_joins_trace(self):
        from m3_tpu.msg.consumer import Consumer
        from m3_tpu.msg.producer import Producer
        from m3_tpu.msg.topic import ConsumerService, ConsumptionType, Topic
        from m3_tpu.cluster.placement import Instance, initial_placement

        got = threading.Event()
        consumer = Consumer(lambda shard, val: got.set(), ack_batch=1)
        consumer.start()
        placement = initial_placement(
            [Instance(id="c0", endpoint=consumer.endpoint)], num_shards=1,
            replica_factor=1)
        topic = Topic("t", 1, [ConsumerService("svc",
                                               ConsumptionType.SHARED)])
        producer = Producer(topic, {"svc": lambda: placement})
        try:
            with tracing.TRACER.span("publish.root") as root:
                producer.publish(0, b"payload")
            assert got.wait(5.0)
            deadline = time.monotonic() + 5.0
            consumed = []
            while time.monotonic() < deadline and not consumed:
                consumed = [d for d in tracing.TRACER.recent_traces(
                    trace_id=root.trace_id) if d["name"] == "msg.consume"]
                time.sleep(0.01)
            assert consumed, "consumer span did not join the trace"
            assert consumed[-1]["remote_parent"] == root.span_id
        finally:
            producer.close()
            consumer.close()

    def test_kv_ops_graft_server_span(self):
        from m3_tpu.cluster.kv_service import KVServer, RemoteStore

        srv = KVServer().start()
        store = RemoteStore(srv.endpoint)
        try:
            with tracing.TRACER.span("kv.root") as root:
                store.set("k", b"v")
                assert store.get("k").data == b"v"
            grafted = [c for c in root.children if isinstance(c, dict)]
            names = {g["name"] for g in grafted}
            assert "kv.set" in names and "kv.get" in names
            assert all(g["trace_id"] == root.trace_id for g in grafted)
        finally:
            store.close()
            srv.close()


# -------------------------------------------------------- HTTP debug surface


class TestHTTPSurface:
    def test_coordinator_debug_traces_slow_and_trace_filter(self):
        coord, now = _embedded()
        try:
            old = SLOW_QUERIES.threshold_ns
            SLOW_QUERIES.threshold_ns = 0
            try:
                coord.writer.write({b"__name__": b"real"}, T0 - 30 * S, 1.0)
                coord.engine.execute_range("real", T0 - 60 * S, T0, 10 * S)
            finally:
                SLOW_QUERIES.threshold_ns = old
            d = json.load(urllib.request.urlopen(
                coord.endpoint + "/debug/traces"))
            assert "slow" in d
            entry = [e for e in d["slow"] if e["name"] == "real"][-1]
            assert entry["reason"] in ("slow", "cold-cache")
            assert entry["costs"].get("datapoints_decoded", 0) >= 1
            roots = [t for t in d["traces"]
                     if t["name"] == "query.execute_range"]
            tid = roots[-1]["trace_id"]
            filtered = json.load(urllib.request.urlopen(
                coord.endpoint + f"/debug/traces?trace_id={tid}"))
            assert all(t["trace_id"] == tid for t in filtered["traces"])
        finally:
            coord.close()

    def test_http_trace_header_ingress(self):
        coord, now = _embedded()
        try:
            req = urllib.request.Request(coord.endpoint + "/health")
            req.add_header("X-M3-Trace", "777:42")
            urllib.request.urlopen(req)
            # the root spans accept to last byte: it closes just after
            # the client has its answer
            deadline = time.time() + 5
            while not (spans := tracing.TRACER.recent_traces(trace_id=777)) \
                    and time.time() < deadline:
                time.sleep(0.01)
            assert spans and spans[-1]["name"].startswith("http.GET")
            assert spans[-1]["remote_parent"] == 42
        finally:
            coord.close()

    def test_dbnode_httpjson_debug_surface(self):
        from m3_tpu.rpc.httpjson import HTTPJSONServer
        from m3_tpu.rpc.node_server import NodeService
        from m3_tpu.parallel.sharding import ShardSet
        from m3_tpu.storage.database import Database

        db = Database(ShardSet(2), clock=lambda: T0)
        db.mark_bootstrapped()
        srv = HTTPJSONServer(NodeService(db)).start()
        try:
            dvars = json.load(urllib.request.urlopen(
                srv.endpoint + "/debug/vars"))
            assert "metrics" in dvars
            traces = json.load(urllib.request.urlopen(
                srv.endpoint + "/debug/traces"))
            assert "traces" in traces and "slow" in traces
            prof = json.load(urllib.request.urlopen(
                srv.endpoint + "/debug/pprof/profile?seconds=0.1"))
            assert "profile" in prof
            # malformed params answer a typed 400, never a dropped conn
            try:
                urllib.request.urlopen(
                    srv.endpoint + "/debug/pprof/profile?seconds=abc")
                assert False, "expected HTTP 400"
            except urllib.error.HTTPError as e:
                assert e.code == 400
            stacks = urllib.request.urlopen(
                srv.endpoint + "/debug/pprof/threads").read().decode()
            assert "--- thread" in stacks
        finally:
            srv.close()


# ------------------------- spans where the host time goes (ISSUE 24)
#
# What benchmark/harness/spans.py and the layer-metric readers take from
# the program (the contract), that an untraced request pays what it paid
# (Rule B), that spans whose self time a reader takes got phases as
# costs and no new child (Rule A), and the mediator's tick tree.


def _walk(span):
    yield span
    for c in span.children:
        if not isinstance(c, dict):
            yield from _walk(c)


def _names(span):
    """The span tree as nested (name, [children]) tuples."""
    return (span.name, [_names(c) for c in span.children])


def _http(coord, path, trace_id=None, body=None):
    req = urllib.request.Request(
        coord.endpoint + path, data=body,
        headers={"Content-Type": "application/x-protobuf"})
    if trace_id is not None:
        req.add_header("X-M3-Trace", "%d:1" % trace_id)
    with urllib.request.urlopen(req) as r:
        return r.read()


def _remote_write_body(now_ns, hosts=20, steps=3):
    from m3_tpu.coordinator import promremote

    t_ms = now_ns // 1_000_000
    return promremote.snappy_compress(promremote.encode_write_request([
        ({b"__name__": b"cpu", b"host": b"h%d" % i},
         [(t_ms - 10_000 * k, float(i + k)) for k in range(steps)])
        for i in range(hosts)]))


QUERY = ("/api/v1/query_range?query=max_over_time(cpu[1m:10s])"
         "&start=%d&end=%d&step=10")


@pytest.fixture
def served(monkeypatch):
    """An embedded coordinator holding one sealed block and an open
    buffer of 20 series, under a tracer installed the way the
    benchmark's traced run installs it; thread_time_ns calls counted."""
    tracer = tracing.Tracer(max_traces=10_000, sample_rate=1.0)
    monkeypatch.setattr(tracing, "TRACER", tracer)
    cpu_reads = []
    real = time.thread_time_ns
    monkeypatch.setattr(tracing.time, "thread_time_ns",
                        lambda: cpu_reads.append(1) or real())
    coord, now = _embedded()
    db = coord.engine.storage._db
    _http(coord, "/api/v1/prom/remote/write", body=_remote_write_body(T0))
    now["t"] = T0 + 3 * 3600 * S            # past the block: it seals
    assert db.tick(now["t"])["sealed"] >= 1
    _http(coord, "/api/v1/prom/remote/write", body=_remote_write_body(now["t"]))
    with tracer._lock:
        tracer._recent.clear()
    del cpu_reads[:]
    yield coord, now, tracer, cpu_reads
    coord.close()


def _roots(tracer, wait_for=1):
    deadline = time.time() + 5      # the root closes after the last byte
    while time.time() < deadline:
        with tracer._lock:
            roots = list(tracer._recent)
        if len(roots) >= wait_for:
            return roots
        time.sleep(0.01)
    return roots


def _sealed_query(now):
    return QUERY % ((T0 - 60 * S) // S, T0 // S)


class TestBenchmarkContract:
    """benchmark/harness/spans.py copies exactly these fields of a Span
    and swaps the tracer exactly this way; the readers find spans by
    these names. Renaming any of them blinds the benchmark."""

    @pytest.mark.parametrize("field", [
        "name", "start_ns", "end_ns", "tags", "costs", "trace_id",
        "children"])
    def test_span_fields_the_collector_copies(self, field):
        tracer = tracing.Tracer(max_traces=400_000, sample_rate=1.0)
        with tracer.span("root") as root:
            with tracer.child_span("child"):
                pass
        assert hasattr(root, field)
        assert isinstance(root.start_ns, int) and root.end_ns >= root.start_ns
        assert isinstance(root.tags, dict) and isinstance(root.costs, dict)
        with tracer._lock:
            assert list(tracer._recent) == [root]
        assert root.children[0].trace_id == root.trace_id

    @pytest.mark.parametrize("name", [
        "query.execute_range", "query.fetch", "query.parse", "index.query"])
    def test_span_names_the_readers_look_for(self, served, name):
        coord, now, tracer, _ = served
        _http(coord, _sealed_query(now), trace_id=41)
        (root,) = _roots(tracer)
        assert root.name.startswith("http.GET")
        assert root.trace_id == 41          # the header's trace id
        found = [s for s in _walk(root) if s.name == name]
        assert found, _names(root)
        if name == "query.execute_range":
            assert found[0].tags["route"] in ("interpreter", "plan")

    def test_storage_read_span_and_its_costs(self, served):
        from m3_tpu.index.query import AllQuery

        coord, now, tracer, _ = served
        db = coord.engine.storage._db
        sid = next(iter(db.query_ids(b"default", AllQuery())))
        with tracer.span_from(SpanContext(5, 1), "rpc.read") as root:
            db.read(b"default", sid, T0 - 60 * S, T0 + S)
        (read,) = root.children
        assert read.name == "storage.read" and read.children == []
        assert read.costs["block_n"] == 1
        assert (read.costs["lock_wait_ns"] + read.costs["buffer_ns"]
                + read.costs["block_ns"] + read.costs["merge_ns"]
                <= read.duration_ns)

    def test_one_root_per_trace_id(self, served):
        coord, now, tracer, _ = served
        for tid in (51, 52, 53):
            _http(coord, _sealed_query(now), trace_id=tid)
        roots = _roots(tracer, 3)
        assert sorted(r.trace_id for r in roots) == [51, 52, 53]
        assert all(r.name.startswith("http.") for r in roots)

    @pytest.mark.parametrize("counter", [
        "query.executed", "query.plan.executed", "storage.block_cache.hits",
        "storage.block_cache.misses", "hbm.bytes"])
    def test_counters_the_readers_look_for(self, served, monkeypatch,
                                           counter):
        from m3_tpu.query import plan as qplan
        from m3_tpu.utils.instrument import ROOT

        coord, now, _tracer, _ = served
        monkeypatch.setattr(qplan, "PLAN_MIN_CELLS", 1)
        before = ROOT.snapshot().get("query.plan.executed", 0)
        _http(coord, _sealed_query(now))
        snap = ROOT.snapshot()
        assert counter in snap
        if counter == "query.plan.executed":
            assert snap[counter] == before + 1

    @pytest.mark.parametrize("needle,path", [
        ('("pallas_" if pallas else "xla_") + kernel',
         "m3_tpu/parallel/telemetry.py"),
        ('sub_scope("telemetry")', "m3_tpu/parallel/telemetry.py"),
        ("def _encode_batch(", "m3_tpu/ops/tsz.py")])
    def test_names_only_a_chip_run_exercises(self, needle, path):
        """The codec route counters (`telemetry.codec.pallas_<kernel>` is
        the default route only on a TPU; `codec_dispatches_off_gate` reads
        both spellings), the telemetry scope and the pack kernel's jit
        name (`_encode_batch(.N)` in the device trace, read by
        encode_roofline) cannot be driven from a CPU test; their spelling
        in the source is pinned instead."""
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, path)) as f:
            assert needle in f.read()


class TestRuleBUntracedPaysWhatItPaid:
    def test_untraced_query_opens_the_same_spans_and_no_cpu_clock(self,
                                                                  served):
        coord, now, tracer, cpu_reads = served
        _http(coord, _sealed_query(now))
        (root,) = _roots(tracer)
        assert _names(root) == ("query.execute_range", [
            ("query.parse", []),
            ("query.fetch", [("index.query", [])])])
        assert not root.detailed and not cpu_reads
        for sp in _walk(root):
            assert "cpu_ns" not in sp.tags
            assert not any(k.endswith("_ns") for k in sp.costs), sp.costs

    def test_untraced_remote_write_opens_no_span_at_all(self, served):
        coord, now, tracer, cpu_reads = served
        out = json.loads(_http(coord, "/api/v1/prom/remote/write",
                               body=_remote_write_body(now["t"] + S)))
        assert out["wrote"] == 60
        time.sleep(0.05)
        assert _roots(tracer, 0) == [] and not cpu_reads

    def test_the_accept_stamp_is_kept_for_no_longer_than_the_connection(
            self, served):
        coord, now, _tracer, _ = served
        _http(coord, "/health")
        _http(coord, "/health", trace_id=9)
        deadline = time.time() + 5
        while coord.api._server.accepted_ns and time.time() < deadline:
            time.sleep(0.01)
        assert coord.api._server.accepted_ns == {}


class TestTracedRequestTree:
    def test_front_spans_from_accept_to_last_byte(self, served):
        coord, now, tracer, _ = served
        t_before = time.perf_counter_ns()
        body = _http(coord, _sealed_query(now), trace_id=61)
        (root,) = _roots(tracer)
        assert [c.name for c in root.children] == [
            "http.read", "http.handler", "http.serialize", "http.write"]
        read, handler, _ser, write = root.children
        assert t_before <= root.start_ns == read.start_ns   # the accept stamp
        assert handler.children[0].name == "query.execute_range"
        assert root.end_ns >= write.end_ns
        assert root.tags["status"] == 200
        assert root.tags["bytes_out"] == len(body)
        assert root.tags["cpu_ns"] <= root.duration_ns
        covered = sum(c.duration_ns for c in root.children)
        assert covered <= root.duration_ns

    def test_remote_write_tree_and_per_sample_costs(self, served):
        coord, now, tracer, _ = served
        _http(coord, "/api/v1/prom/remote/write", trace_id=62,
              body=_remote_write_body(now["t"] + S))
        (root,) = _roots(tracer)
        assert root.name == "http.POST /api/v1/prom/remote/write"
        assert root.tags["samples"] == 60
        handler = root.children[1]
        assert [c.name for c in handler.children] == [
            "remote_write.decompress", "remote_write.decode",
            "remote_write.append"]
        append = handler.children[2]
        c = append.costs
        assert c["samples_n"] == 60
        assert c["lock_wait_ns"] <= c["buffer_ns"]
        assert c["buffer_ns"] + c["commitlog_ns"] <= append.duration_ns
        # the memo probe has no stretch of its own (0.28 us a sample):
        # shard_memo_hit_share is its reading
        assert "id_ns" not in c

    def test_error_answers_keep_their_status(self, served):
        coord, _now, tracer, _ = served
        with pytest.raises(urllib.error.HTTPError) as e:
            _http(coord, "/api/v1/query_range?query=cpu", trace_id=63)
        assert e.value.code == 400
        (root,) = _roots(tracer)
        assert root.tags["status"] == 400
        assert "error" in root.children[1].tags     # http.handler


class TestRuleANoChildWhereSelfTimeIsRead:
    @pytest.mark.parametrize("route", ["interpreter", "plan"])
    def test_same_children_phases_as_costs(self, served, monkeypatch, route):
        from m3_tpu.query import plan as qplan

        coord, now, tracer, _ = served
        if route == "plan":
            monkeypatch.setattr(qplan, "PLAN_MIN_CELLS", 1)
        _http(coord, _sealed_query(now), trace_id=71)
        (root,) = _roots(tracer)
        handler = root.children[1]
        (ex,) = [c for c in handler.children]
        assert ex.tags["route"] == route
        assert _names(ex) == ("query.execute_range", [
            ("query.parse", []),
            ("query.fetch", [("index.query", [])])])
        fetch = ex.children[1]
        c = fetch.costs
        assert c["series_n"] == 20 and c["block_n"] == 20
        parts = (c["lock_wait_ns"] + c["buffer_ns"] + c["block_ns"]
                 + c["merge_ns"])
        assert parts <= c["read_ns"] <= fetch.duration_ns
        # the sealed block's 20 rows: slices of its planes where the
        # cache admitted it, else one cold dispatch for them all
        assert (c["cold_rows_n"], c["cold_dispatch_n"]) in ((0, 0), (20, 1))
        assert c["cold_decode_ns"] <= c["block_ns"]
        # the decode call's anatomy, from inside tsz.decode_plane (the
        # cold rows' call, or the admission's whole-block one): five
        # stretches of this span
        anatomy = ("h2d_ns", "launch_ns", "device_wait_ns", "d2h_ns",
                   "layout_ns")
        assert all(k in c for k in anatomy)
        assert sum(c[k] for k in anatomy) <= c["block_ns"]
        assert ex.costs["bind_n"] == 1 and ex.costs["bind_ns"] <= ex.duration_ns
        if route == "plan":
            assert ex.costs["dispatch_n"] >= 1
            # the result is read where it is rendered: under http.handler
            waited = tracing.collect_costs(handler)
            assert waited["device_wait_n"] >= 1 and waited["d2h_bytes"] > 0
        else:
            assert ex.costs["interpreter_eval_n"] == 1

    def test_analyze_and_span_from_one_request(self, served, monkeypatch):
        from m3_tpu.query import plan as qplan

        coord, now, tracer, _ = served
        monkeypatch.setattr(qplan, "PLAN_MIN_CELLS", 1)
        out = json.loads(_http(
            coord, _sealed_query(now) + "&explain=true&analyze=true",
            trace_id=72))
        stages = out["data"]["explain"]["analyze"]["stages_ms"]
        assert "bind" in stages and "result_materialize" in stages
        assert any(k.startswith("device_program[") for k in stages)
        (root,) = _roots(tracer)
        costs = tracing.collect_costs(root)
        assert costs["bind_ns"] / 1e6 == pytest.approx(stages["bind"],
                                                       abs=1e-3)


class TestMediatorTickTree:
    def test_run_once_opens_the_tick_tree(self, served, tmp_path):
        from m3_tpu.persist.fs import PersistManager
        from m3_tpu.storage.mediator import Mediator

        coord, now, tracer, _ = served
        db = coord.engine.storage._db
        mediator = Mediator(db, PersistManager(str(tmp_path)))
        stats = mediator.run_once()
        (tick,) = _roots(tracer)
        assert tick.name == "mediator.tick" and tick.detailed
        assert [c.name for c in tick.children] == [
            "mediator.seal", "mediator.flush", "mediator.snapshot",
            "mediator.cleanup"]
        for k in ("sealed", "flushed", "snapshotted", "cleaned"):
            assert tick.tags[k] == stats[k]
        assert stats["flushed"] >= 1 and stats["snapshotted"] >= 1
        _seal, flush, snap, _clean = tick.children
        writes = [s for s in _walk(flush) if s.name == "persist.write"]
        assert len(writes) >= 1
        assert all(w.tags["volume"] == "flush" and w.tags["bytes"] > 0
                   for w in writes)
        encodes = [s for s in snap.children if s.name == "encode.block"]
        assert len(encodes) == stats["snapshotted"] == snap.costs["buckets_n"]
        e = encodes[0]
        assert e.tags["series"] >= 1 and e.tags["window"] >= 1
        assert {"pad_ns", "prepare_ns", "device_wait_ns",
                "d2h_bytes"} <= set(e.costs)
        assert snap.costs["buffer_snapshot_n"] >= stats["snapshotted"]
        assert [s.tags["volume"] for s in snap.children
                if s.name == "persist.write"] == ["snapshot"] * len(encodes)
        inside = sum(c.duration_ns for c in tick.children)
        assert inside <= tick.duration_ns
        # the four children are the tick's work: what its thread computed
        # outside them, in CPU time ...
        cpu, cpu_inside = tick.tags["cpu_ns"], sum(
            c.tags["cpu_ns"] for c in tick.children)
        assert cpu - cpu_inside <= max(0.02 * cpu, 500_000)
        assert tick.tags["cpu_ns"] <= tick.duration_ns * 1.01
        # ... and what it waited outside them, on the wall clock. The
        # runner's load also opens a gap between two spans (the thread
        # descheduled: 4.5 ms of a 38 ms tick, 2 runs of 24 under six
        # workers), but in one tick; a wait in run_once outside its spans (a
        # lock, an fsync, a sleep) is in every tick. So the bound holds on
        # the tick with the smallest gap of at most three.
        def over(t):
            gap = t.duration_ns - sum(c.duration_ns for c in t.children)
            return gap - max(0.02 * t.duration_ns, 500_000)

        worst = over(tick)
        for n in (2, 3):
            if worst <= 0:
                break
            mediator.run_once()
            again = _roots(tracer, n)[-1]
            assert again.name == "mediator.tick" and again is not tick
            worst = min(worst, over(again))
        assert worst <= 0

    def test_a_seal_outside_any_trace_opens_nothing(self, served):
        coord, now, tracer, _ = served
        db = coord.engine.storage._db
        now["t"] += 3 * 3600 * S
        assert db.tick(now["t"])["sealed"] >= 1     # encode_block ran
        assert _roots(tracer, 0) == []
