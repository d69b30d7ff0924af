"""Rules-engine smoke: the <5s check_all tier for the compiled streaming
rules engine (ISSUE 20). Asserts, not just times:

  1. batch-vs-ref bit-equality — a seeded (rule set x metric batch)
     corpus (mapping globs, DROP_MUST class, first-op rollup pipelines)
     driven through Downsampler.write_batch (compiled batch matcher +
     grouped columnar aggregator adds) emits counters and flushed rows
     IDENTICAL to the retained per-metric write_ref oracle;
  2. warm match-cache hit rate — re-matching the same batch after the
     cold pass is 100% (rule-set generation, id) memo hits, and a KV
     rule-set update invalidates every memoized result;
  3. standing compiled pipelines — one recording rule + one alert rule
     evaluated incrementally across two windows on a live embedded
     coordinator: the second round evaluates ONLY the new window, the
     alert emits its typed firing transition, and the recorded series
     queries back through the PromQL HTTP API.

Usage: JAX_PLATFORMS=cpu python scripts/rules_smoke.py
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from m3_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

from m3_tpu.cluster import kv as cluster_kv  # noqa: E402
from m3_tpu.coordinator.downsample import Downsampler  # noqa: E402
from m3_tpu.metrics import aggregation as magg  # noqa: E402
from m3_tpu.metrics.filters import TagsFilter  # noqa: E402
from m3_tpu.metrics.matcher import Matcher, RuleSetStore  # noqa: E402
from m3_tpu.metrics.metric import MetricType  # noqa: E402
from m3_tpu.metrics.pipeline import Op, Pipeline  # noqa: E402
from m3_tpu.metrics.policy import DropPolicy, StoragePolicy  # noqa: E402
from m3_tpu.metrics.rules import (  # noqa: E402
    MappingRuleSnapshot,
    RollupRuleSnapshot,
    RollupTarget,
    Rule,
    RuleSet,
)

S = 1_000_000_000
T0 = 1_704_067_200 * S
POL = (StoragePolicy.parse("1m:40h"),)


def _ruleset(version=1):
    mapping = [
        Rule([MappingRuleSnapshot(
            "svc", 0, TagsFilter({"__name__": f"svc{k}_*"}), 0, POL)])
        for k in range(8)
    ]
    mapping.append(Rule([MappingRuleSnapshot(
        "drop", 0, TagsFilter({"__name__": "drop_*"}), 0, POL,
        DropPolicy.DROP_MUST)]))
    rollup = [Rule([RollupRuleSnapshot(
        "roll", 0, TagsFilter({"__name__": "svc0_*"}),
        (RollupTarget(Pipeline((Op.roll(
            b"svc0:rolled", (b"dc",),
            magg.AggID.compress([magg.AggType.SUM])),)), POL),))])]
    return RuleSet(b"default", version, mapping, rollup)


def _batch(n=600, seed=5):
    rng = random.Random(seed)
    types = (MetricType.GAUGE, MetricType.COUNTER, MetricType.TIMER)
    out = []
    for i in range(n):
        name = (b"drop_%d" % i) if i % 25 == 24 else \
            b"svc%d_lat_%d" % (i % 10, i % 37)
        tags = {b"__name__": name, b"dc": rng.choice([b"east", b"west"]),
                b"host": b"h%d" % (i % 7)}
        out.append((tags, T0, float(i % 53) + 0.5, types[i % 3]))
    return out


def _downsampler(store, now):
    sink = []
    ds = Downsampler(Matcher(store, b"default", clock=lambda: now["t"]),
                     lambda *a: sink.append(a), clock=lambda: now["t"])
    return ds, sink


def check_batch_vs_ref_bit_equality() -> str:
    store = RuleSetStore(cluster_kv.MemStore())
    store.publish(_ruleset())
    now = {"t": T0}
    got_ds, got_sink = _downsampler(store, now)
    ref_ds, ref_sink = _downsampler(store, now)
    batch = _batch()
    matched, dropped = got_ds.write_batch(batch)
    for tags, t, v, mt in batch:
        ref_ds.write_ref(tags, t, v, mt)
    assert (matched, dropped) == (ref_ds.samples_matched,
                                  ref_ds.samples_dropped), (
        "batch counters diverged from per-metric oracle")
    assert dropped > 0, "corpus must exercise the DROP_MUST class"
    now["t"] = T0 + 120 * S
    got_ds.flush()
    ref_ds.flush()
    assert sorted(got_sink) == sorted(ref_sink), \
        "batched flush rows diverged from per-metric oracle"
    assert any(b"svc0:rolled" in row[0] for row in got_sink), \
        "corpus must exercise rollup-id generation"
    return (f"batch-vs-ref: {matched} matched + {dropped} dropped over "
            f"{len(batch)} samples, {len(got_sink)} flushed rows identical")


def check_warm_match_cache() -> str:
    store = RuleSetStore(cluster_kv.MemStore())
    store.publish(_ruleset())
    now = {"t": T0}
    m = Matcher(store, b"default", clock=lambda: now["t"])
    mids = []
    from m3_tpu.metrics import id as metric_id
    for tags, _t, _v, _mt in _batch():
        mids.append(metric_id.encode(
            tags[b"__name__"],
            {k: v for k, v in tags.items() if k != b"__name__"}))
    cold = m.match_batch(mids)
    h0, m0 = m.hits, m.misses
    warm = m.match_batch(mids)
    assert warm == cold
    hit_rate = (m.hits - h0) / len(mids)
    assert hit_rate == 1.0 and m.misses == m0, (
        f"warm pass must be 100% match-cache hits, got {hit_rate:.1%}")
    # a KV rules update invalidates the whole memo (dead generation)
    store.publish(_ruleset(version=2))
    m2 = m.match_batch(mids)
    assert all(r.version == 2 for r in m2)
    return (f"warm match cache: {len(mids)} ids re-matched at 100% hit "
            "rate; KV update invalidated every memoized result")


def _http(method, url, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read().decode())


def check_standing_pipelines() -> str:
    from m3_tpu.coordinator.rules_engine import AlertRule, RecordingRule
    from m3_tpu.coordinator.server import run_embedded
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.namespace import NamespaceOptions
    from m3_tpu.index.namespace_index import NamespaceIndex
    from m3_tpu.parallel.sharding import ShardSet

    step = 30 * S
    now = {"t": T0}
    db = Database(ShardSet(4), clock=lambda: now["t"])
    db.create_namespace(b"default", NamespaceOptions(),
                        index=NamespaceIndex(clock=lambda: now["t"]))
    c = run_embedded(db, clock=lambda: now["t"])
    try:
        re = c.rules_engine(step_ns=step)
        re.add_recording(RecordingRule(b"cpu:avg", "avg(cpu_pct)"))
        re.add_alert(AlertRule(b"cpu_hot", "avg(cpu_pct)", ">", 80.0))
        for i, v in enumerate([40.0, 50.0]):
            now["t"] = T0 + i * 15 * S
            c.writer.write({b"__name__": b"cpu_pct", b"host": b"a"},
                           now["t"], v)
        now["t"] = T0 + step
        r1 = re.evaluate()
        assert r1.recorded_rows > 0 and r1.transitions == []
        # window two: spike past the threshold; ONLY the new step runs
        now["t"] = T0 + step + 5 * S
        c.writer.write({b"__name__": b"cpu_pct", b"host": b"a"},
                       now["t"], 95.0)
        now["t"] = T0 + 2 * step
        r2 = re.evaluate()
        assert r2.steps == 1, "second round must evaluate only the new window"
        assert [t.kind for t in r2.transitions] == ["firing"], (
            "alert must emit exactly one typed firing transition")
        # recorded series round-trips through the PromQL HTTP API
        out = _http("GET", f"{c.endpoint}/api/v1/query_range?"
                    f"query=cpu:avg&start={(T0 + step) / S}"
                    f"&end={(T0 + 2 * step) / S}&step=30s")
        series = out["data"]["result"]
        assert len(series) == 1, "recorded series not queryable over HTTP"
        vals = [float(v) for _t, v in series[0]["values"]]
        assert vals[-1] == 95.0
        return (f"standing pipelines: 2 incremental windows, "
                f"{r1.recorded_rows + r2.recorded_rows} recorded rows "
                f"queryable over HTTP, firing transition at "
                f"t={r2.transitions[0].time_nanos // S}")
    finally:
        c.close()


def main() -> int:
    t_start = time.perf_counter()
    lines = [
        check_batch_vs_ref_bit_equality(),
        check_warm_match_cache(),
        check_standing_pipelines(),
    ]
    total_s = time.perf_counter() - t_start
    for ln in lines:
        print("  " + ln)
    print(f"RULES SMOKE PASS: total {total_s:.1f}s")
    # Nominal runtime is <5s; the overridable ceiling catches a real
    # regression without turning host contention into a flaky tier.
    budget_s = float(os.environ.get("RULES_SMOKE_BUDGET_S", "60"))
    assert total_s < budget_s, (
        f"smoke tier took {total_s:.1f}s (> {budget_s:.0f}s budget)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
