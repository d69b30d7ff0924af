"""Aggregator-tier smoke: the <5s check_all tier for the mesh-sharded
columnar flush, batched rollup forwarding, and per-tenant fair-share.
Asserts, not just times:

  1. mesh-vs-ref bit-equality — a seeded mixed elem population
     (counters/gauges/timers with quantiles, transform+rollup
     pipelines, empty and NaN windows) flushed through the columnar
     production path (collect_into + emit_batch, quantile ordering
     forced through the shard x time mesh) emits BIT-identical rows to
     the retained host oracle (reduce_and_emit_ref), and the telemetry
     counter proves the mesh program actually dispatched;
  2. one-publish-per-destination forward batching — a flush round's
     emissions ride ONE ProducerHandler publish per topic shard
     (columnar payloads decode back exactly), and a round's rollup
     forwards ship as ONE fbatch frame per (destination, meta group)
     through ForwardedWriter.forward_batch;
  3. fairness shed order — past the high watermark a noisy tenant is
     shed at its weighted fair share, a quiet tenant arriving mid-burst
     is still admitted, and CRITICAL work is never tenant-shed (the
     DAGOR-style gate the rawtcp server charges per frame).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python scripts/agg_smoke.py
(The mesh leg degrades to a skip note on a true single-device platform.)
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
# Force the mesh route for any tile size: the smoke population is small
# by design, and the point is proving the mesh path, not its dispatch
# floor heuristic.
os.environ["M3_TPU_MESH_AGG_MIN_CELLS"] = "1"

# Persistent compile cache: the quantile-selector shapes compile once per
# checkout, keeping warm runs inside the budget.
from m3_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from m3_tpu.aggregator import elem as elem_mod  # noqa: E402
from m3_tpu.aggregator import list as list_mod  # noqa: E402
from m3_tpu.aggregator.flush import plan_jobs  # noqa: E402
from m3_tpu.metrics import aggregation as magg  # noqa: E402
from m3_tpu.metrics.metric import MetricType  # noqa: E402
from m3_tpu.metrics.pipeline import Op, Pipeline  # noqa: E402
from m3_tpu.metrics.policy import StoragePolicy  # noqa: E402
from m3_tpu.metrics.transformation import TransformType  # noqa: E402

S = 1_000_000_000
POL = StoragePolicy.parse("1m:40h")
BASE = 1_700_000_000 * S - (1_700_000_000 * S) % (60 * S)


def _population(seed: int, n: int = 400):
    """Seeded mixed elem population (the tests/test_agg_mesh.py shape):
    counters, gauges, timers (default suffixed set incl. p50/p95/p99),
    explicit agg sets, PerSecond+Rollup pipelines, empty/NaN windows."""
    rng = np.random.default_rng(seed)
    lists = list_mod.MetricLists()
    lst = lists.for_resolution(60 * S)
    for i in range(n):
        kind = int(rng.integers(0, 6))
        if kind == 0:
            key, mt = elem_mod.ElemKey(b"s.c.%d" % i, POL), MetricType.COUNTER
        elif kind == 1:
            key, mt = elem_mod.ElemKey(b"s.g.%d" % i, POL), MetricType.GAUGE
        elif kind == 2:
            key, mt = elem_mod.ElemKey(b"s.t.%d" % i, POL), MetricType.TIMER
        elif kind == 3:
            key = elem_mod.ElemKey(b"s.x.%d" % i, POL, magg.AggID.compress(
                [magg.AggType.MEAN, magg.AggType.STDEV, magg.AggType.MIN,
                 magg.AggType.MAX, magg.AggType.P99]))
            mt = MetricType.TIMER
        elif kind == 4:
            pipe = Pipeline((
                Op.transform(TransformType.PERSECOND),
                Op.roll(b"s.roll.%d" % (i % 5), (b"host",),
                        magg.AggID.compress([magg.AggType.SUM]))))
            key = elem_mod.ElemKey(b"s.p.%d" % i, POL,
                                   magg.AggID.compress([magg.AggType.LAST]),
                                   pipe)
            mt = MetricType.GAUGE
        else:
            key, mt = elem_mod.ElemKey(b"s.e.%d" % i, POL), MetricType.GAUGE
        e = lst.get_or_create(key, lambda k=key, m=mt: elem_mod.Elem(k, m))
        for w in range(int(rng.integers(1, 4))):
            nv = int(rng.integers(0, 8)) if kind != 5 else 0
            vals = rng.lognormal(0, 1, nv)
            if nv and rng.random() < 0.3:
                vals[int(rng.integers(0, nv))] = np.nan
            e.add_values(BASE + w * 60 * S, vals)
    return lists, lst


def _flush_rows(lists, lst, use_ref: bool):
    sink = []
    cap = lambda mid, t, v, p, _s=sink: _s.append((mid, t, v, str(p)))  # noqa: E731

    def fwd(new_id, t, v, meta, src, _s=sink):
        _s.append((b"FWD:" + new_id, t, v,
                   str(meta.storage_policy) + ":" + src.decode()))

    target = BASE + 10 * 60 * S
    if use_ref:
        jobs, _ = plan_jobs(lists, target, 0, cap, fwd)
        list_mod.reduce_and_emit_ref(jobs)
    else:
        lst.flush(target, cap, fwd)
    return sorted(sink, key=repr)


def check_mesh_vs_ref_bit_equality() -> str:
    from m3_tpu.parallel import telemetry
    from m3_tpu.parallel.ingest import flush_mesh

    mesh = flush_mesh()
    seed = int(os.environ.get("AGG_SMOKE_SEED", "7"))
    counter = telemetry._SCOPE.sub_scope(
        "mesh", kernel="agg_flush").counter("dispatches")
    before = counter.value()
    got = _flush_rows(*_population(seed), use_ref=False)
    dispatched = counter.value() - before
    want = _flush_rows(*_population(seed), use_ref=True)
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        ok = g == w or (g[0] == w[0] and g[1] == w[1] and g[3] == w[3]
                        and np.isnan(g[2]) and np.isnan(w[2]))
        assert ok, f"mesh row diverged from oracle: {g} vs {w}"
    if mesh is None:
        return (f"mesh-vs-ref: {len(got)} rows bit-identical "
                "(single-device platform: mesh leg skipped)")
    assert dispatched >= 1, \
        "columnar flush did not dispatch the mesh quantile program"
    return (f"mesh-vs-ref: {len(got)} emitted rows bit-identical across "
            f"{mesh.devices.size} devices ({dispatched} mesh dispatches)")


def check_forward_batching() -> str:
    from m3_tpu.aggregator.aggregator import Aggregator, ForwardedWriter
    from m3_tpu.aggregator.handler import (ProducerHandler,
                                           decode_aggregated_batch)
    from m3_tpu.cluster.placement import (Instance, Placement,
                                          ShardAssignment, ShardState)
    from m3_tpu.metrics.metadata import ForwardMetadata

    # --- flush handler plane: ONE publish per topic shard per round
    class FakeProducer:
        def __init__(self):
            self.published = []

        def publish(self, shard, payload):
            self.published.append((shard, payload))

    producer = FakeProducer()
    handler = ProducerHandler(producer, num_shards=4)
    lists, lst = _population(11, n=120)
    n = lst.flush(BASE + 10 * 60 * S, handler)
    assert n > 0
    shards_hit = {s for s, _ in producer.published}
    assert handler.publishes == len(producer.published) == len(shards_hit), (
        "expected ONE publish per topic shard per flush round, got "
        f"{len(producer.published)} publishes over {len(shards_hit)} shards")
    rows = [m for _, p in producer.published
            for m in decode_aggregated_batch(p)]
    # capture-sink mirror of the same population proves the columnar
    # payloads decode back to exactly the emitted rows
    sink = []
    lists2, lst2 = _population(11, n=120)
    lst2.flush(BASE + 10 * 60 * S,
               lambda mid, t, v, p, _s=sink: _s.append((mid, t, v, str(p))))
    got = sorted(((m.id, m.time_nanos, m.value, str(m.storage_policy))
                  for m in rows), key=repr)
    want = sorted(sink, key=repr)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g == w or (g[:2] == w[:2] and g[3] == w[3]
                          and np.isnan(g[2]) and np.isnan(w[2])), (g, w)

    # --- forwarded plane: ONE fbatch frame per destination per meta group
    class FakeTransport:
        def __init__(self):
            self.frames = []

        def send_forwarded(self, *a):
            raise AssertionError(
                "per-datapoint send_forwarded used; forward_batch must "
                "coalesce into send_forwarded_batch frames")

        def send_forwarded_batch(self, metric_type, rows):
            self.frames.append(list(rows))
            return True

    agg = Aggregator(num_shards=4)
    inst = Instance("other", "e:1", shards={
        s: ShardAssignment(s, ShardState.AVAILABLE) for s in range(4)})
    placement = Placement({"other": inst}, num_shards=4, replica_factor=1)
    tr = FakeTransport()
    fw = ForwardedWriter(agg)
    fw.set_routing(lambda: placement, {"other": tr}, "me")
    meta = ForwardMetadata(0, POL, Pipeline(), b"src", 1)
    items = [(b"roll.%d" % i, BASE + 60 * S, float(i), meta, b"src.%d" % i)
             for i in range(24)]
    fw.forward_batch(items)
    assert len(tr.frames) == 1, (
        f"one meta group to one destination must ride ONE fbatch frame, "
        f"got {len(tr.frames)}")
    assert sum(len(f) for f in tr.frames) == len(items)
    assert fw.dropped == 0
    return (f"forward batching: {len(rows)} emissions in "
            f"{handler.publishes} publishes ({len(shards_hit)} topic "
            f"shards), {len(items)} forwards in {len(tr.frames)} fbatch "
            "frame")


def check_tenant_fair_share() -> str:
    from m3_tpu.utils.health import AdmissionGate, HealthTracker, Priority
    from m3_tpu.utils.limits import Backpressure

    gate = AdmissionGate(8, high_watermark=0.5, name="",
                         tracker=HealthTracker())
    # noisy tenant fills the gate to the watermark, then sheds at its
    # fair share (8 * 1/(0 active + 1 + 1 reserve) = 4)...
    assert gate.try_admit(4, Priority.NORMAL, tenant=b"noisy")
    assert not gate.try_admit(1, Priority.NORMAL, tenant=b"noisy")
    shed_at = gate.tenant_depth(b"noisy")
    # ...a quiet tenant arriving mid-burst is still admitted...
    assert gate.try_admit(2, Priority.NORMAL, tenant=b"quiet"), \
        "quiet tenant shed by a noisy neighbor's burst"
    # ...and CRITICAL work (forwarded rollup partials) is never
    # tenant-shed, even from the saturated tenant.
    assert gate.try_admit(1, Priority.CRITICAL, tenant=b"noisy")
    assert gate.shed["critical"] == 0
    assert gate.shed_tenant >= 1
    try:
        gate.admit(1, Priority.NORMAL, tenant=b"noisy")
        raise AssertionError("noisy tenant admitted past its fair share")
    except Backpressure:
        pass
    return (f"tenant fair-share: noisy shed at depth {shed_at}/8, quiet "
            f"admitted mid-burst, CRITICAL never shed "
            f"({gate.shed_tenant} tenant sheds)")


def main() -> int:
    t_start = time.perf_counter()
    lines = [
        check_mesh_vs_ref_bit_equality(),
        check_forward_batching(),
        check_tenant_fair_share(),
    ]
    total_s = time.perf_counter() - t_start
    for ln in lines:
        print("  " + ln)
    print(f"AGG SMOKE PASS: total {total_s:.1f}s")
    # Nominal runtime is <5s warm (one quantile-selector compile cold,
    # persisted to .jax_cache); the overridable ceiling catches a real
    # regression without turning host contention into a flaky tier.
    budget_s = float(os.environ.get("AGG_SMOKE_BUDGET_S", "60"))
    assert total_s < budget_s, (
        f"smoke tier took {total_s:.1f}s (> {budget_s:.0f}s budget)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
