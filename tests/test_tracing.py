"""Tracing + profiling (reference: x/instrument tracing options +
net/http/pprof endpoints every service exposes)."""

import json
import threading
import time
import urllib.request

import pytest

from m3_tpu.utils import tracing


class TestSpans:
    def test_span_tree_and_recent(self):
        tracer = tracing.Tracer()
        with tracer.span("root", op="test") as root:
            with tracer.span("child1"):
                pass
            with tracer.span("child2") as c2:
                c2.set_tag("rows", 7)
        traces = tracer.recent_traces()
        assert traces[-1]["name"] == "root"
        assert [c["name"] for c in traces[-1]["children"]] == ["child1", "child2"]
        assert traces[-1]["children"][1]["tags"]["rows"] == 7
        assert traces[-1]["duration_us"] >= 0

    def test_exception_tagged_and_stack_unwound(self):
        tracer = tracing.Tracer()
        with pytest.raises(RuntimeError):
            with tracer.span("boom"):
                raise RuntimeError("x")
        assert tracer.current() is None
        assert "error" in tracer.recent_traces()[-1]["tags"]

    def test_thread_local_isolation(self):
        tracer = tracing.Tracer()
        seen = {}

        def worker():
            with tracer.span("other-thread"):
                seen["cur"] = tracer.current().name

        with tracer.span("main-thread"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
            assert tracer.current().name == "main-thread"
        assert seen["cur"] == "other-thread"


class TestProfiling:
    def test_thread_stacks_lists_threads(self):
        out = tracing.thread_stacks()
        assert "--- thread" in out
        assert "test_thread_stacks_lists_threads" in out

    def test_sampling_profiler_catches_hot_thread(self):
        stop = threading.Event()

        def hot_loop_for_profiler():
            x = 0
            while not stop.is_set():
                x += 1

        t = threading.Thread(target=hot_loop_for_profiler)
        t.start()
        try:
            prof = tracing.profile(seconds=0.3, hz=200)
        finally:
            stop.set()
            t.join()
        assert prof, "no samples collected"
        flat = json.dumps(prof)
        assert "hot_loop_for_profiler" in flat


class TestDebugEndpoints:
    def test_traces_profile_stacks_over_http(self):
        from m3_tpu.cluster import kv as cluster_kv
        from m3_tpu.coordinator import run_embedded
        from m3_tpu.index.namespace_index import NamespaceIndex
        from m3_tpu.parallel.sharding import ShardSet
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.namespace import NamespaceOptions

        T0 = 1_700_000_000 * 1_000_000_000
        db = Database(ShardSet(4), clock=lambda: T0)
        db.create_namespace(b"default", NamespaceOptions(),
                            index=NamespaceIndex(clock=lambda: T0))
        c = run_embedded(db, kv_store=cluster_kv.MemStore(), clock=lambda: T0)
        try:
            c.writer.write({b"__name__": b"traced"}, T0 - 30 * 10**9, 1.0)
            c.engine.execute_range("traced", T0 - 60 * 10**9, T0, 10 * 10**9)
            traces = json.load(urllib.request.urlopen(
                c.endpoint + "/debug/traces"))["traces"]
            assert any(t["name"] == "query.execute_range" for t in traces)
            q = [t for t in traces if t["name"] == "query.execute_range"][-1]
            assert any(ch["name"] == "query.fetch"
                       for ch in q.get("children", []))
            prof = json.load(urllib.request.urlopen(
                c.endpoint + "/debug/pprof/profile?seconds=0.2"))
            assert "profile" in prof
            stacks = urllib.request.urlopen(
                c.endpoint + "/debug/pprof/goroutine").read().decode()
            assert "--- thread" in stacks
        finally:
            c.close()


# --------------------------------------------- detail, CPU time and phases


def _detailed_root(tracer, name="root", **kw):
    return tracer.span_from(tracing.SpanContext(7, 1), name, **kw)


class TestDetail:
    def test_span_clock_is_perf_counter_ns(self):
        """start_ns/end_ns are perf_counter_ns: the clock the benchmark's
        load generator stamps with and its profiler annotation maps."""
        tracer = tracing.Tracer()
        a = time.perf_counter_ns()
        with tracer.span("s") as s:
            pass
        b = time.perf_counter_ns()
        assert a <= s.start_ns <= s.end_ns <= b

    def test_a_root_may_start_at_an_earlier_stamp(self):
        tracer = tracing.Tracer()
        stamp = time.perf_counter_ns() - 5_000_000
        with _detailed_root(tracer, start_ns=stamp, cpu_start_ns=0) as root:
            with tracer.child_span("read", start_ns=stamp,
                                   cpu_start_ns=0) as read:
                pass
        assert root.start_ns == read.start_ns == stamp
        assert root.duration_ns >= 5_000_000
        # the CPU clock started at the stamp too: the thread's whole CPU
        assert root.tags["cpu_ns"] >= read.tags["cpu_ns"] > 0

    def test_to_dict_carries_start_ns_and_cpu_us(self):
        tracer = tracing.Tracer()
        with _detailed_root(tracer) as root:
            pass
        with tracer.span("plain") as plain:
            pass
        d = root.to_dict()
        assert d["start_ns"] == root.start_ns
        assert d["cpu_us"] == round(root.tags["cpu_ns"] / 1000, 1)
        assert plain.to_dict()["start_ns"] == plain.start_ns
        assert "cpu_us" not in plain.to_dict()

    @pytest.mark.parametrize("how,detailed", [
        ("span_from", True), ("background_span", True), ("span", False),
        ("child_of_span_from", True), ("child_of_span", False),
        ("child_of_nothing", False), ("span_from_no_context", False)])
    def test_which_spans_are_detailed(self, how, detailed):
        tracer = tracing.Tracer(sample_rate=1.0)
        if how == "span_from":
            sp = _detailed_root(tracer)
        elif how == "span_from_no_context":
            sp = tracer.span_from(None, "r")
        elif how == "background_span":
            sp = tracer.background_span("tick")
        elif how == "span":
            sp = tracer.span("q")
        elif how == "child_of_nothing":
            sp = tracer.child_span("c")
        else:
            parent = _detailed_root(tracer) if how == "child_of_span_from" \
                else tracer.span("q")
            with parent:
                sp = tracer.child_span("c")
        assert sp.detailed is detailed

    def test_background_span_is_sampling_gated(self):
        assert tracing.Tracer(sample_rate=0.0).background_span("t") \
            is tracing.NOOP_SPAN

    def test_only_detailed_spans_read_the_thread_cpu_clock(self, monkeypatch):
        calls = []
        real = time.thread_time_ns
        monkeypatch.setattr(tracing.time, "thread_time_ns",
                            lambda: calls.append(1) or real())
        tracer = tracing.Tracer(sample_rate=1.0)
        with tracer.span("q") as q:
            with tracer.child_span("c"):
                pass
        assert not calls and "cpu_ns" not in q.tags
        with _detailed_root(tracer) as r:
            with tracer.child_span("c") as c:
                pass
        assert len(calls) == 4 and "cpu_ns" in r.tags and "cpu_ns" in c.tags

    def test_cpu_time_tells_waiting_from_working(self):
        tracer = tracing.Tracer()
        with _detailed_root(tracer) as waiting:
            time.sleep(0.05)
        with _detailed_root(tracer) as working:
            t_end = time.perf_counter() + 0.05
            while time.perf_counter() < t_end:
                pass
        # against each other, not against wall time: under parallel test
        # workers a 50 ms spin may get well under half a core
        assert waiting.duration_ns - waiting.tags["cpu_ns"] > 30_000_000
        assert working.tags["cpu_ns"] > 5 * waiting.tags["cpu_ns"]

    def test_detail_is_the_current_detailed_span_or_none(self, monkeypatch):
        tracer = tracing.Tracer(sample_rate=1.0)
        monkeypatch.setattr(tracing, "TRACER", tracer)
        assert tracing.detail() is None
        with tracing.span("q"):
            assert tracing.detail() is None
        with _detailed_root(tracer):
            with tracing.child_span("c") as c:
                assert tracing.detail() is c


class TestPhase:
    @pytest.fixture
    def tracer(self, monkeypatch):
        tracer = tracing.Tracer(sample_rate=1.0)
        monkeypatch.setattr(tracing, "TRACER", tracer)
        return tracer

    def test_phase_accumulates_costs_and_opens_no_child(self, tracer):
        with _detailed_root(tracer) as root:
            for _ in range(3):
                with tracing.phase("work"):
                    time.sleep(0.001)
        assert root.children == []
        assert root.costs["work_n"] == 3
        assert 3_000_000 <= root.costs["work_ns"] <= root.duration_ns

    def test_a_phase_inside_itself_counts_once(self, tracer):
        with _detailed_root(tracer) as root:
            with tracing.phase("dispatch"):
                with tracing.phase("dispatch"):
                    time.sleep(0.001)
            with tracing.phase("dispatch"):
                pass
        assert root.costs["dispatch_n"] == 2
        assert root.costs["dispatch_ns"] <= root.duration_ns

    @pytest.mark.parametrize("under", ["nothing", "head_sampled_root"])
    def test_phase_is_the_shared_noop_outside_detail(self, tracer, under):
        if under == "nothing":
            assert tracing.phase("work", stage="work") is tracing._NOOP_PHASE
            return
        with tracing.span("q") as q:
            assert tracing.phase("work", stage="work") is tracing._NOOP_PHASE
        assert q.costs == {}

    def test_one_site_feeds_the_span_and_analyze(self, tracer):
        from m3_tpu.query import explain as qexplain

        with _detailed_root(tracer) as root:
            with qexplain.analyzing() as actx:
                with tracing.phase("device_wait", stage="result_materialize"):
                    time.sleep(0.001)
                with tracing.phase("dispatch"):     # no stage: span only
                    pass
        assert set(actx.stages) == {"result_materialize"}
        assert actx.stages["result_materialize"] * 1e9 == pytest.approx(
            root.costs["device_wait_ns"])
        assert root.costs["dispatch_n"] == 1

    def test_analyze_alone_is_fed_without_any_span(self, tracer):
        from m3_tpu.query import explain as qexplain

        with qexplain.analyzing() as actx:
            with tracing.phase("bind", stage="bind"):
                pass
        assert "bind" in actx.stages
        assert tracing._SINKS_ACTIVE == 0 and qexplain.current() is None
