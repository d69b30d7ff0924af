"""Aggregator tier tests (reference behaviors from src/aggregator:
windowed aggregation semantics, leader/follower flush hand-off, rollup
pipelines producing new IDs, shard ownership gating)."""

import numpy as np
import pytest

from m3_tpu.aggregator import (
    AggregatedMetric,
    Aggregator,
    AggregatorClient,
    CaptureHandler,
    ElectionManager,
    ElectionState,
    FlushManager,
    FlushTimesManager,
    MetricLists,
)
from m3_tpu.aggregator.elem import Elem, ElemKey
from m3_tpu.cluster import kv as cluster_kv
from m3_tpu.cluster.placement import Instance, initial_placement
from m3_tpu.cluster.services import LeaderService
from m3_tpu.metrics import aggregation as magg
from m3_tpu.metrics.metadata import Metadata, PipelineMetadata, StagedMetadata
from m3_tpu.metrics.metric import MetricType, MetricUnion
from m3_tpu.metrics.pipeline import Op, Pipeline
from m3_tpu.metrics.policy import StoragePolicy
from m3_tpu.metrics.transformation import TransformType
from m3_tpu.testing.cluster import SettableClock

S = 1_000_000_000
TEN_S = StoragePolicy.of("10s", "2d")
ONE_M = StoragePolicy.of("1m", "40d")


def meta(*pipelines):
    return (StagedMetadata(0, False, Metadata(tuple(pipelines))),)


def make_agg(clock, **kw):
    kw.setdefault("num_shards", 8)
    kw.setdefault("flush_handler", CaptureHandler())
    return Aggregator(clock=clock, **kw)


class TestElemWindows:
    def test_counter_sum_default(self):
        clock = SettableClock(100 * S)
        agg = make_agg(clock)
        mid = b"requests+service=api"
        for v in [1, 2, 3]:
            assert agg.add_untimed(
                MetricUnion.counter(mid, v),
                meta(PipelineMetadata(0, (TEN_S,))))
        clock.advance(10 * S)
        agg.flush()
        out = agg._flush_handler.by_id(mid)
        assert len(out) == 1
        # Counter default agg type is Sum, emitted under the bare ID at the
        # window end (generic_elem.go:283).
        assert out[0].value == 6.0
        assert out[0].time_nanos == 110 * S
        assert out[0].storage_policy == TEN_S

    def test_gauge_last(self):
        clock = SettableClock(100 * S)
        agg = make_agg(clock)
        mid = b"cpu+host=a"
        for v in [0.3, 0.9, 0.5]:
            agg.add_untimed(MetricUnion.gauge(mid, v),
                            meta(PipelineMetadata(0, (TEN_S,))))
        clock.advance(10 * S)
        agg.flush()
        out = agg._flush_handler.by_id(mid)
        assert [m.value for m in out] == [0.5]

    def test_timer_quantiles_and_suffixes(self):
        clock = SettableClock(100 * S)
        agg = make_agg(clock)
        mid = b"latency+service=api"
        values = list(np.arange(1.0, 101.0))  # 1..100
        agg.add_untimed(MetricUnion.batch_timer(mid, values),
                        meta(PipelineMetadata(0, (TEN_S,))))
        clock.advance(10 * S)
        agg.flush()
        cap = agg._flush_handler
        got = {m.id: m.value for m in cap.metrics}
        # Default timer agg types emit suffixed IDs (types_options.go).
        assert got[mid + b".sum"] == pytest.approx(5050.0)
        assert got[mid + b".count"] == 100
        assert got[mid + b".lower"] == 1.0
        assert got[mid + b".upper"] == 100.0
        assert got[mid + b".mean"] == pytest.approx(50.5)
        # Exact rank quantile: ceil(q*n) rank (cm/stream.go:160).
        assert got[mid + b".p95"] == 95.0
        assert got[mid + b".p99"] == 99.0
        assert got[mid + b".median"] == 50.0

    def test_explicit_aggregation_types(self):
        clock = SettableClock(100 * S)
        agg = make_agg(clock)
        mid = b"queue_depth"
        aggid = magg.AggID.compress([magg.AggType.MAX, magg.AggType.MEAN])
        for v in [5.0, 15.0, 10.0]:
            agg.add_untimed(MetricUnion.gauge(mid, v),
                            meta(PipelineMetadata(aggid, (TEN_S,))))
        clock.advance(10 * S)
        agg.flush()
        got = {m.id: m.value for m in agg._flush_handler.metrics}
        assert got[mid + b".upper"] == 15.0
        assert got[mid + b".mean"] == pytest.approx(10.0)

    def test_multi_policy_fanout(self):
        clock = SettableClock(600 * S)
        agg = make_agg(clock)
        mid = b"hits"
        # One sample lands in both a 10s and a 1m elem (entry.go: one elem
        # per storage policy).
        for i in range(6):
            agg.add_untimed(MetricUnion.counter(mid, 1),
                            meta(PipelineMetadata(0, (TEN_S, ONE_M))))
            clock.advance(10 * S)
        agg.flush()
        out = agg._flush_handler.by_id(mid)
        by_policy = {}
        for m in out:
            by_policy.setdefault(m.storage_policy, []).append(m.value)
        assert by_policy[TEN_S] == [1.0] * 6
        assert by_policy[ONE_M] == [6.0]

    def test_windows_partition_by_timestamp(self):
        clock = SettableClock(100 * S)
        agg = make_agg(clock)
        mid = b"w"
        agg.add_untimed(MetricUnion.counter(mid, 1), meta(PipelineMetadata(0, (TEN_S,))))
        clock.advance(10 * S)
        agg.add_untimed(MetricUnion.counter(mid, 2), meta(PipelineMetadata(0, (TEN_S,))))
        clock.advance(10 * S)
        agg.flush()
        out = agg._flush_handler.by_id(mid)
        assert [(m.time_nanos // S, m.value) for m in out] == [(110, 1.0), (120, 2.0)]


class TestPipelines:
    def test_persecond_transform(self):
        clock = SettableClock(1000 * S)
        agg = make_agg(clock)
        mid = b"bytes_total"
        pipe = Pipeline((Op.transform(TransformType.PERSECOND),))
        # Monotone counter: 0, 100, 250 at 10s spacing -> rates 10, 15.
        for v in [0, 100, 250]:
            agg.add_untimed(MetricUnion.counter(mid, v),
                            meta(PipelineMetadata(0, (TEN_S,), pipe)))
            clock.advance(10 * S)
        agg.flush()
        out = agg._flush_handler.by_id(mid)
        assert [m.value for m in out] == [pytest.approx(10.0), pytest.approx(15.0)]

    def test_rollup_forwarding_creates_new_id(self):
        clock = SettableClock(100 * S)
        agg = make_agg(clock)
        # Two services' latencies roll up into one cross-service metric via a
        # second aggregation stage (forwarded_writer.go loop-back).
        rollup_id = b"m3+all_latency"
        pipe = Pipeline((Op.roll(rollup_id, (b"region",),
                                 magg.AggID.compress([magg.AggType.SUM])),))
        for mid, v in [(b"lat+svc=a", 10.0), (b"lat+svc=b", 20.0)]:
            agg.add_untimed(MetricUnion.gauge(mid, v),
                            meta(PipelineMetadata(
                                magg.AggID.compress([magg.AggType.LAST]),
                                (TEN_S,), pipe)))
        clock.advance(10 * S)
        agg.flush()  # stage 1: consumes gauges, forwards into rollup elem
        clock.advance(10 * S)
        agg.flush()  # stage 2: consumes the forwarded partials
        # Explicit Sum on a non-counter gets the type suffix (types_options.go
        # overrides: only counter-Sum / gauge-Last emit bare IDs).
        out = agg._flush_handler.by_id(rollup_id + b".sum")
        assert len(out) == 1
        assert out[0].value == 30.0


class TestLeaderFollower:
    def _mk(self, store, clock, instance_id, handler):
        leader = LeaderService(store, "agg-election", instance_id,
                               lease_ttl_ns=30 * S, clock=clock)
        election = ElectionManager(leader)
        ftimes = FlushTimesManager(store, "shardset-0")
        return make_agg(clock, flush_handler=handler, election=election,
                        flush_times=ftimes), election

    def test_follower_shadows_then_takes_over_without_double_flush(self):
        store = cluster_kv.MemStore()
        clock = SettableClock(100 * S)
        cap_a, cap_b = CaptureHandler(), CaptureHandler()
        agg_a, el_a = self._mk(store, clock, "a", cap_a)
        agg_b, el_b = self._mk(store, clock, "b", cap_b)
        mid = b"ha_metric"
        md = meta(PipelineMetadata(0, (TEN_S,)))

        for i in range(3):
            agg_a.add_untimed(MetricUnion.counter(mid, 1), md)
            agg_b.add_untimed(MetricUnion.counter(mid, 1), md)
            clock.advance(10 * S)
            agg_a.flush()
            agg_b.flush()
        assert el_a.state == ElectionState.LEADER
        assert el_b.state == ElectionState.FOLLOWER
        # Leader emitted 3 windows; follower discarded them.
        assert len(cap_a.by_id(mid)) == 3
        assert len(cap_b.by_id(mid)) == 0

        # Leader dies: resign and advance past TTL.
        el_a.resign()
        clock.advance(31 * S)
        agg_b.add_untimed(MetricUnion.counter(mid, 1), md)
        clock.advance(10 * S)
        agg_b.flush()
        assert el_b.state == ElectionState.LEADER
        new = cap_b.by_id(mid)
        # New leader flushed only windows after the old leader's persisted
        # flush times — no re-emission of the first 3 windows.
        assert len(new) == 1
        old_times = {m.time_nanos for m in cap_a.by_id(mid)}
        assert all(m.time_nanos not in old_times for m in new)

    def test_a_round_walks_the_elems_once_a_target(self, monkeypatch):
        """A flush loop checks every second; the series are walked when
        the flush target (leader) or the leader's persisted flush time
        (follower) has moved, not at every check — and the windows come
        out as they did."""
        from m3_tpu.aggregator.list import MetricList

        walks = {"collect_into": 0, "collect": 0}
        for name in walks:
            real = getattr(MetricList, name)

            def spy(self, *a, _real=real, _name=name, **kw):
                walks[_name] += 1
                return _real(self, *a, **kw)
            monkeypatch.setattr(MetricList, name, spy)
        store = cluster_kv.MemStore()
        clock = SettableClock(100 * S)
        cap_a, cap_b = CaptureHandler(), CaptureHandler()
        agg_a, el_a = self._mk(store, clock, "a", cap_a)
        agg_b, el_b = self._mk(store, clock, "b", cap_b)
        mid = b"ha_metric"
        md = meta(PipelineMetadata(0, (TEN_S,)))
        for i in range(3):
            agg_a.add_untimed(MetricUnion.counter(mid, 1), md)
            agg_b.add_untimed(MetricUnion.counter(mid, 1), md)
            for _ in range(10):     # ten checks a window
                clock.advance(1 * S)
                agg_a.flush()
                agg_b.flush()
        assert el_a.state == ElectionState.LEADER
        assert len(cap_a.by_id(mid)) == 3 and not cap_b.by_id(mid)
        # one shard holds the series, one list: of thirty rounds an
        # instance, one walk a target (the first round's, which closes
        # nothing, and the three windows')
        assert walks == {"collect_into": 4, "collect": 4}
        # the follower takes over and emits what the leader had not
        el_a.resign()
        clock.advance(31 * S)
        agg_b.add_untimed(MetricUnion.counter(mid, 1), md)
        clock.advance(10 * S)
        agg_b.flush()
        agg_b.flush()
        assert el_b.state == ElectionState.LEADER
        assert len(cap_b.by_id(mid)) == 1
        assert walks["collect_into"] == 5


class TestFlushTimesIsolation:
    def test_multi_resolution_across_shards_no_double_flush(self):
        """Regression: per-shard flush-time commits must not clobber each
        other when shards host different resolutions."""
        store = cluster_kv.MemStore()
        clock = SettableClock(600 * S)
        cap_a, cap_b = CaptureHandler(), CaptureHandler()

        def mk(instance_id, cap):
            leader = LeaderService(store, "e", instance_id,
                                   lease_ttl_ns=3600 * S, clock=clock)
            return Aggregator(
                num_shards=64, clock=clock, flush_handler=cap,
                election=ElectionManager(leader),
                flush_times=FlushTimesManager(store, "ss"))

        agg_a, agg_b = mk("a", cap_a), mk("b", cap_b)
        # Find two IDs landing on different shards; give them different
        # resolutions so the shards' flush-time maps are disjoint.
        fast, slow = b"fast-metric", b"slow-metric-2"
        assert agg_a.shard_for(fast) != agg_a.shard_for(slow)
        md_fast = meta(PipelineMetadata(0, (TEN_S,)))
        md_slow = meta(PipelineMetadata(0, (ONE_M,)))
        for i in range(6):
            for agg in (agg_a, agg_b):
                agg.add_untimed(MetricUnion.counter(fast, 1), md_fast)
                agg.add_untimed(MetricUnion.counter(slow, 1), md_slow)
            clock.advance(10 * S)
            agg_a.flush()
            agg_b.flush()
        assert len(cap_a.by_id(fast)) == 6
        assert len(cap_a.by_id(slow)) == 1
        # Follower discarded everything the leader flushed (no buildup).
        for shard in agg_b._shards.values():
            for lst in shard.lists.lists():
                assert all(e.is_empty() for e in lst.elems())
        # Failover: new leader must not re-emit any flushed window.
        agg_a._election.resign()
        clock.advance(1 * S)
        agg_b.flush()
        flushed_times = {m.time_nanos for m in cap_a.by_id(fast)}
        assert all(m.time_nanos not in flushed_times for m in cap_b.by_id(fast))
        assert len(cap_b.by_id(fast)) == 0  # nothing new closed yet


class TestMetadataUpdate:
    def test_same_cutover_metadata_change_takes_effect(self):
        """Regression: a rules update that keeps cutover=0 but adds a policy
        must rebuild the elems (entry.go compares metadata contents)."""
        clock = SettableClock(600 * S)
        agg = make_agg(clock)
        mid = b"m"
        agg.add_untimed(MetricUnion.counter(mid, 1),
                        meta(PipelineMetadata(0, (TEN_S,))))
        # Same cutover (0), now with an extra 1m policy.
        md2 = meta(PipelineMetadata(0, (TEN_S, ONE_M)))
        for i in range(5):
            clock.advance(10 * S)
            agg.add_untimed(MetricUnion.counter(mid, 1), md2)
        clock.advance(10 * S)
        agg.flush()
        policies = {m.storage_policy for m in agg._flush_handler.by_id(mid)}
        assert ONE_M in policies


class TestShardOwnership:
    def test_unowned_shard_rejected(self):
        clock = SettableClock(0)
        agg = make_agg(clock)
        mid = b"some_metric"
        sid = agg.shard_for(mid)
        agg.assign_shards([s for s in range(agg.num_shards) if s != sid])
        assert not agg.add_untimed(MetricUnion.counter(mid, 1),
                                   meta(PipelineMetadata(0, (TEN_S,))))
        assert agg.writes_for_unowned_shard == 1

    def test_cutoff_stops_writes(self):
        clock = SettableClock(100 * S)
        agg = make_agg(clock)
        mid = b"m"
        md = meta(PipelineMetadata(0, (TEN_S,)))
        assert agg.add_untimed(MetricUnion.counter(mid, 1), md)
        agg.assign_shards([])  # placement removed all shards -> cutoff=now
        assert not agg.add_untimed(MetricUnion.counter(mid, 1), md)

    def test_client_routes_by_placement(self):
        clock = SettableClock(0)
        insts = [Instance(id="a", endpoint="l:1"), Instance(id="b", endpoint="l:2")]
        p = initial_placement(insts, num_shards=8, replica_factor=1)
        aggs = {i.id: make_agg(clock) for i in insts}
        for inst in p.instances.values():
            aggs[inst.id].assign_shards(inst.shard_ids())
        client = AggregatorClient(
            8, lambda: p,
            {iid: aggs[iid].add_untimed for iid in aggs})
        md = meta(PipelineMetadata(0, (TEN_S,)))
        for i in range(32):
            assert client.write_untimed_counter(b"metric-%d" % i, 1, md)
        total = sum(a.num_entries() for a in aggs.values())
        assert total == 32
        # Every aggregator only holds entries for shards it owns.
        assert all(a.writes_for_unowned_shard == 0 for a in aggs.values())


class TestEntryLifecycle:
    def test_rate_limit(self):
        clock = SettableClock(50 * S)
        agg = make_agg(clock, rate_limit_per_second=5)
        mid = b"noisy"
        md = meta(PipelineMetadata(0, (TEN_S,)))
        results = [agg.add_untimed(MetricUnion.counter(mid, 1), md) for _ in range(10)]
        assert results.count(True) == 5
        clock.advance(1 * S)
        assert agg.add_untimed(MetricUnion.counter(mid, 1), md)

    def test_tick_expires_idle_entries(self):
        clock = SettableClock(0)
        agg = make_agg(clock)
        md = meta(PipelineMetadata(0, (TEN_S,)))
        agg.add_untimed(MetricUnion.counter(b"old", 1), md)
        clock.advance(25 * 3600 * S)
        agg.add_untimed(MetricUnion.counter(b"new", 1), md)
        assert agg.tick() == 1
        assert agg.num_entries() == 1

    def test_tombstoned_metadata_drops(self):
        clock = SettableClock(0)
        agg = make_agg(clock)
        md = (StagedMetadata(0, True, Metadata()),)
        assert not agg.add_untimed(MetricUnion.counter(b"dead", 1), md)


class TestBatchedReduceParity:
    """The jitted batched reducer must agree with numpy for ragged windows."""

    def test_ragged_batches(self, rng):
        from m3_tpu.aggregator.list import batched_reduce
        buckets = [rng.normal(50, 10, size=n) for n in [1, 7, 128, 1000]]
        stats, quants = batched_reduce(buckets, (0.5, 0.99))
        for b, srow, qrow in zip(buckets, stats, quants):
            assert srow["sum"] == pytest.approx(b.sum(), rel=1e-9)
            assert srow["count"] == len(b)
            assert srow["min"] == pytest.approx(b.min())
            assert srow["max"] == pytest.approx(b.max())
            s = np.sort(b)
            assert qrow[0.5] == pytest.approx(s[max(1, int(np.ceil(0.5 * len(b)))) - 1])
            if len(b) > 1:
                assert srow["m2"] == pytest.approx(((b - b.mean()) ** 2).sum(), rel=1e-6)


class TestLeaderPromotionStaleWindows:
    def test_promoted_leader_discards_windows_old_leader_flushed(self):
        """Regression (ADVICE r1): a follower that had NOT yet discarded its
        closed windows must not re-emit them on promotion when the KV flush
        times show the old leader already flushed those window starts."""
        store = cluster_kv.MemStore()
        clock = SettableClock(100 * S)
        cap_a, cap_b = CaptureHandler(), CaptureHandler()

        def mk(instance_id, cap):
            leader = LeaderService(store, "agg-election", instance_id,
                                   lease_ttl_ns=30 * S, clock=clock)
            return (make_agg(clock, flush_handler=cap,
                             election=ElectionManager(leader),
                             flush_times=FlushTimesManager(store, "ss")),
                    leader)

        agg_a, lead_a = mk("a", cap_a)
        agg_b, _ = mk("b", cap_b)
        mid = b"failover_metric"
        md = meta(PipelineMetadata(0, (TEN_S,)))
        for i in range(3):
            agg_a.add_untimed(MetricUnion.counter(mid, 1), md)
            agg_b.add_untimed(MetricUnion.counter(mid, 1), md)
            clock.advance(10 * S)
            agg_a.flush()  # leader flushes; B never runs a follower pass
        assert len(cap_a.by_id(mid)) == 3

        # A dies; B is promoted while still holding all 3 closed windows.
        agg_a._election.resign()
        clock.advance(31 * S)
        agg_b.add_untimed(MetricUnion.counter(mid, 1), md)
        clock.advance(10 * S)
        agg_b.flush()
        assert agg_b._election.state == ElectionState.LEADER
        emitted = cap_b.by_id(mid)
        old_times = {m.time_nanos for m in cap_a.by_id(mid)}
        assert all(m.time_nanos not in old_times for m in emitted)
        assert len(emitted) == 1  # only the post-failover window


class TestTombstoneRevive:
    def test_readded_key_revives_tombstoned_elem(self):
        """Regression (ADVICE r1): metadata change removes a policy, a later
        change re-adds it before the list GCs the elem — samples must land in
        a live (revived) elem, not an orphan collect() silently drops."""
        clock = SettableClock(600 * S)
        agg = make_agg(clock)
        mid = b"revive_metric"
        md_both = meta(PipelineMetadata(0, (TEN_S, ONE_M,)))
        md_one = meta(PipelineMetadata(0, (ONE_M,)))
        agg.add_untimed(MetricUnion.counter(mid, 1), md_both)
        # Remove the 10s policy (tombstones its elem in the list), then
        # re-add it before any flush ran a GC pass.
        agg.add_untimed(MetricUnion.counter(mid, 1), md_one)
        agg.add_untimed(MetricUnion.counter(mid, 1), md_both)
        clock.advance(10 * S)
        agg.flush()
        ten_s = [m for m in agg._flush_handler.by_id(mid)
                 if m.storage_policy == TEN_S]
        assert len(ten_s) == 1
        # Another window keeps flowing through the revived elem.
        agg.add_untimed(MetricUnion.counter(mid, 5), md_both)
        clock.advance(10 * S)
        agg.flush()
        ten_s = [m for m in agg._flush_handler.by_id(mid)
                 if m.storage_policy == TEN_S]
        assert len(ten_s) == 2
        assert ten_s[-1].value == 5.0


class TestStatMappingParity:
    def test_scalar_twin_matches_columnar_mapping(self):
        """_stat_value (per-window scalar emit) and stat_column (vectorized
        flush emission) are hand-kept twins of the same agg-type -> value
        mapping; this pins their parity, including empty-window defaults
        (count==0 -> 0.0 for min/max/mean, count<=1 -> 0.0 for stdev)."""
        import numpy as np

        from m3_tpu.aggregator.elem import STAT_DEPS, _stat_value, stat_column

        rng = np.random.default_rng(42)
        for _ in range(100):
            cnt = int(rng.integers(0, 6))
            vals = rng.standard_normal(cnt) if cnt else np.zeros(0)
            m = {
                "count": float(cnt),
                "sum": float(vals.sum()),
                "sumsq": float((vals ** 2).sum()),
                "min": float(vals.min()) if cnt else float("inf"),
                "max": float(vals.max()) if cnt else float("-inf"),
                "last": float(vals[-1]) if cnt else float("nan"),
                "m2": float(((vals - vals.mean()) ** 2).sum()) if cnt else 0.0,
            }
            for at in STAT_DEPS:
                a = _stat_value(at, m)
                b = float(stat_column(at, m))
                assert (a == b or (np.isnan(a) and np.isnan(b))
                        or abs(a - b) < 1e-12), (at, a, b)
