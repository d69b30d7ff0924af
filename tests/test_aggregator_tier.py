"""The aggregation tier as M3 deploys it, booted through `services.run_*`
from configuration alone: a KV service, a leader/follower m3aggregator
pair (placement RF 2 over 64 shards, flush handler: the m3msg producer to
topic `aggregated_metrics`), a dbnode whose embedded coordinator sends
every remote-write sample to `default` directly and, matched by the
`downsample.all` rule, as a TIMED gauge to both replicas, and whose m3msg
consumer writes what the leader flushes into `metrics_1m_72h`.

Every case reads the aggregated namespace over HTTP and compares it
EXACTLY with the plain reference (`benchmark/reference/aggtier_ref.py`,
which imports nothing of the program): for every series and every closed
minute, the acknowledged sample of greatest timestamp in it, stamped at
the minute's end, once. Injected clock, tiny fleet, CPU."""

import importlib.util
import json
import os
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest
import yaml

from m3_tpu.coordinator import promremote as pr
from m3_tpu.services import (ConfigError, load_dict, load_file,
                             run_aggregator, run_dbnode, run_kv)
from m3_tpu.utils import tracing
from m3_tpu.utils.instrument import ROOT

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = 1_000_000_000
MS = 1_000_000
T0_S = 1_700_000_400        # on a minute
FIELDS = ["usage_user", "usage_system"]
HOSTS = 6
CADENCE_S = 10
RES_S = 60
BUFFER_PAST_S = 10


def _reference():
    spec = importlib.util.spec_from_file_location(
        "aggtier_ref_under_test", os.path.join(
            ROOT_DIR, "benchmark", "reference", "aggtier_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

NODE = {
    "num_shards": 8,
    "commitlog_enabled": True,
    "namespaces": [
        {"name": "default", "block_size": "20m", "retention": "2h",
         "buffer_past": "10m", "buffer_future": "2m"},
        {"name": "metrics_1m_72h", "block_size": "2h", "retention": "72h",
         "buffer_past": "10m", "buffer_future": "2m"}],
    "coordinator": {
        "namespaces": [
            {"namespace": "default", "type": "unaggregated",
             "retention": "2h"},
            {"namespace": "metrics_1m_72h", "type": "aggregated",
             "retention": "72h", "resolution": "1m",
             "downsample": {"all": True}}],
        "downsample": {"remote_aggregator": {
            "placement_key": "_agg_placement", "replicas": 2}},
        "ingest": {"m3msg": {"topic": "aggregated_metrics",
                             "consumer_service": "m3coordinator",
                             "num_shards": 64}}},
}
AGG = {
    "num_shards": 64, "shard_set_id": "shardset-0",
    "election_id": "agg-election", "election_ttl": "10s",
    "flush_interval": "40ms", "buffer_past": "10s",
    "placement_key": "_agg_placement", "register_in_placement": True,
    "flush_handler": "producer", "topic": "aggregated_metrics",
    "admin_address": "127.0.0.1:0",
}
# what the reference reads of a configuration (the benchmark's own shape)
REF_CFG = {"scale": HOSTS, "cadence_s": CADENCE_S,
           "schema": {"measurement": "cpu", "fields": FIELDS},
           "dbnode": NODE}


def _counters():
    return {k: v for k, v in ROOT.snapshot().items()
            if isinstance(v, (int, float))}


class Tier:
    """The topology, booted from configuration FILES through the
    services' own entry points, under one injected clock."""

    def __init__(self, tmp_path, aggregators=("agg0", "agg1")):
        self.now = [T0_S * S]
        clock = lambda: self.now[0]     # noqa: E731
        self.clock = clock
        self.c0 = _counters()
        files = {}
        self.kv = run_kv(load_dict({"listen_address": "127.0.0.1:0"}, "kv"))
        node = dict(NODE, data_dir=str(tmp_path / "data"),
                    kv_endpoint=self.kv.endpoint)
        files["dbnode"] = tmp_path / "dbnode.yml"
        files["dbnode"].write_text(yaml.safe_dump({"dbnode": node}))
        self.node = run_dbnode(load_file(str(files["dbnode"]), "dbnode"),
                               clock=clock)
        self.base = self.node.coordinator.endpoint
        self.aggs = {}
        self.tmp_path = tmp_path
        for iid in aggregators:
            self.join(iid)
        rng = np.random.default_rng(51)
        self.off_ms = rng.integers(0, CADENCE_S * 1000, HOSTS)
        self.vals = rng.integers(0, 100, (HOSTS * len(FIELDS), 64)
                                 ).astype(np.float64)
        self.acked = np.zeros((HOSTS, 64), bool)
        self.requests, self.traced = 0, False

    def join(self, iid: str):
        path = self.tmp_path / f"{iid}.yml"
        path.write_text(yaml.safe_dump(dict(
            AGG, instance_id=iid, kv_endpoint=self.kv.endpoint)))
        self.aggs[iid] = run_aggregator(load_file(str(path), "aggregator"),
                                        clock=self.clock)

    # -- the clock ---------------------------------------------------------

    def advance_to(self, t_ns: int):
        """The injected clock moved on in steps a lease outlives, each
        given a few flush rounds of real time (the leader renews its
        lease on the injected clock at every round)."""
        while self.now[0] < t_ns:
            self.now[0] = min(t_ns, self.now[0] + 4 * S)
            time.sleep(0.1)

    # -- writes ------------------------------------------------------------

    def sample_ts_ms(self, host: int, k: int) -> int:
        return T0_S * 1000 + k * CADENCE_S * 1000 + int(self.off_ms[host])

    def write(self, rows, expect=200, trace_id=None):
        """`rows`: (host, scrape) pairs, one remote-write request."""
        series = []
        for h, k in rows:
            for f, field in enumerate(FIELDS):
                series.append((
                    {b"__name__": b"cpu", b"field": field.encode(),
                     b"hostname": b"host_%d" % h},
                    [(self.sample_ts_ms(h, k),
                      float(self.vals[h * len(FIELDS) + f, k]))]))
        self.requests += 1
        req = urllib.request.Request(
            self.base + "/api/v1/prom/remote/write",
            data=pr.snappy_compress(pr.encode_write_request(series)),
            method="POST", headers={"Content-Encoding": "snappy",
                                    "Content-Type": "application/x-protobuf"})
        if self.traced:     # a traced request: its root is detailed
            req.add_header("X-M3-Trace", "%d:1" % self.requests)
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                status, wrote = r.status, json.loads(r.read())["wrote"]
        except urllib.error.HTTPError as e:
            status, wrote = e.code, 0
        assert status == expect, (status, expect)
        if status == 200:
            assert wrote == len(series)
            for h, k in rows:
                self.acked[h, k] = True
        return status

    def write_scrape(self, k: int):
        """Scrape k of every host, two requests, the clock following."""
        self.advance_to((T0_S + k * CADENCE_S) * S + 9 * S)
        hosts = list(range(HOSTS))
        self.write([(h, k) for h in hosts[:3]])
        self.write([(h, k) for h in hosts[3:]])

    # -- the tier's progress -------------------------------------------------

    def moved(self, key: str) -> float:
        return _counters().get(key, 0) - self.c0.get(key, 0)

    def moved_sum(self, prefix: str) -> float:
        now = _counters()
        return sum(v - self.c0.get(k, 0) for k, v in now.items()
                   if k.startswith(prefix))

    def wait_rows(self, n: int, timeout=15.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.moved("coordinator.m3msg.rows") >= n and not any(
                    a.flush_handler.unacked() for a in self.aggs.values()):
                return
            time.sleep(0.05)
        raise AssertionError(
            f"{self.moved('coordinator.m3msg.rows')} of {n} rows ingested; "
            f"flush errors {self.moved_sum('aggregator.flush.errors')}")

    def close_minute(self, j: int, rows_so_far: int):
        """The clock past minute j's end and its buffer_past; then the
        leader's flush, the produce, the consume and the sink of it."""
        self.advance_to((T0_S + (j + 1) * RES_S + BUFFER_PAST_S + 1) * S)
        self.wait_rows(rows_so_far)

    def leader(self):
        leaders = [iid for iid, a in self.aggs.items()
                   if a.aggregator._election.is_leader()]
        assert len(leaders) <= 1, leaders
        return leaders[0] if leaders else None

    # -- reads ---------------------------------------------------------------

    def read_minute(self, host: int, stamp_s: int) -> dict:
        """field -> the aggregated namespace's point stamped `stamp_s`,
        over HTTP: a range that starts before the unaggregated retention
        resolves to the 1-minute namespace alone, and a 1-minute window
        at a minute's end holds that minute's point or nothing."""
        q = 'max_over_time(cpu{hostname="host_%d"}[1m])' % host
        url = self.base + "/api/v1/query_range?" + urllib.parse.urlencode({
            "query": q, "start": stamp_s - 8100, "end": stamp_s,
            "step": 60})
        with urllib.request.urlopen(url, timeout=30) as r:
            res = json.loads(r.read())["data"]["result"]
        out = {}
        for s in res:
            last = [float(v) for t, v in s["values"] if int(t) == stamp_s]
            if last:
                out[s["metric"]["field"]] = last[0]
        return out

    def served(self, minutes: int) -> np.ndarray:
        """[series, minutes]: what the aggregated namespace serves."""
        got = np.full((HOSTS * len(FIELDS), minutes), np.nan)
        for j in range(minutes):
            for h in range(HOSTS):
                point = self.read_minute(h, T0_S + (j + 1) * RES_S)
                for f, field in enumerate(FIELDS):
                    got[h * len(FIELDS) + f, j] = point.get(field, np.nan)
        return got

    def reference(self, minutes: int) -> np.ndarray:
        stamps, pv = REF.minute_points(REF_CFG, self.vals, self.acked,
                                       self.off_ms, T0_S)
        assert stamps[:minutes].tolist() == [
            T0_S + (j + 1) * RES_S for j in range(minutes)]
        return pv[:, :minutes]

    def windows_flushed(self) -> float:
        return self.moved_sum("aggregator.flush.windows")

    def close(self):
        for a in self.aggs.values():
            a.close()
        self.node.close()
        self.kv.close()


def _same(got: np.ndarray, want: np.ndarray):
    assert got.shape == want.shape
    assert (np.isnan(got) == np.isnan(want)).all(), (got, want)
    assert (got[~np.isnan(got)] == want[~np.isnan(want)]).all(), (got, want)


@pytest.fixture
def tier(tmp_path):
    t = Tier(tmp_path)
    yield t
    t.close()


N = HOSTS * len(FIELDS)


# ------------------------------------------------------------- the assembly


def test_the_topology_is_what_the_files_say(tier):
    from m3_tpu.cluster import kv as kvmod
    from m3_tpu.cluster.placement import Placement
    from m3_tpu.msg.topic import TopicService

    obj, version = kvmod.get_json(tier.kv.store, "_agg_placement")
    p = Placement.from_json(obj, version)
    assert sorted(p.instances) == ["agg0", "agg1"]
    assert (p.num_shards, p.replica_factor, p.is_mirrored) == (64, 2, True)
    p.validate_mirrored()
    assert {i.endpoint for i in p.instances.values()} == {
        a.endpoint for a in tier.aggs.values()}
    topic = TopicService(tier.kv.store).get("aggregated_metrics")
    assert [c.service_id for c in topic.consumer_services] == [
        "m3coordinator"]
    coord = tier.node.coordinator
    assert coord.m3msg_consumer is not None and coord.aggregator_client
    # the writer's downsample leg is the remote one; nothing is embedded
    assert type(coord.downsampler).__name__ == "RemoteDownsampler"
    assert coord._flush_thread is None


def test_exactly_one_of_the_pair_leads(tier):
    tier.advance_to((T0_S + 5) * S)
    assert tier.leader() in ("agg0", "agg1")
    states = sorted(a.aggregator._election.state.name
                    for a in tier.aggs.values())
    assert states == ["FOLLOWER", "LEADER"]


def test_writes_are_refused_until_the_pair_is_whole(tmp_path):
    t = Tier(tmp_path, aggregators=("agg0",))
    try:
        assert t.write([(0, 0)], expect=429) == 429
        assert not t.acked.any()
        t.join("agg1")
        deadline = time.time() + 10
        while time.time() < deadline and len(
                t.node.coordinator.aggregator_client.transports) < 2:
            time.sleep(0.05)
        t.write([(0, 0)])
    finally:
        t.close()


@pytest.mark.parametrize("block,key", [
    ({"downsample": {"remote_aggregator": {"placement": "x"}}}, "placement"),
    ({"ingest": {"m3msg": {"subject": "x"}}}, "subject"),
    ({"ingest": {"kafka": {}}}, "kafka"),
])
def test_an_unknown_key_of_the_new_blocks_is_refused(block, key):
    with pytest.raises(ConfigError, match=key):
        load_dict(block, "coordinator")


@pytest.mark.parametrize("block,why", [
    ({"flush_handler": "kafka"}, "producer, file"),
    ({"flush_handler": "blackhole"}, "producer, file"),
    ({"flush_handler": "file"}, "flush_log"),
    ({"register_in_placement": True}, "placement_key"),
])
def test_an_aggregator_block_that_cannot_work_is_refused(block, why):
    with pytest.raises(ConfigError, match=why):
        load_dict(block, "aggregator")


def test_the_example_files_are_this_topology():
    ex = os.path.join(ROOT_DIR, "examples")
    node = load_file(os.path.join(ex, "dbnode-aggregator-tier.yml"), "dbnode")
    assert node.coordinator.remote_aggregator.replicas == 2
    assert node.coordinator.m3msg.topic == "aggregated_metrics"
    for name in ("aggregator-tier-agg0.yml", "aggregator-tier-agg1.yml"):
        agg = load_file(os.path.join(ex, name), "aggregator")
        assert agg.flush_handler == "producer" and agg.register_in_placement
        assert agg.placement_key == \
            node.coordinator.remote_aggregator.placement_key
        assert (agg.num_shards, agg.buffer_past, agg.election_ttl,
                agg.flush_interval) == (64, "10s", "10s", "1s")


# ------------------------------------------------- (i) the steady state


def test_steady_state_is_the_reference_exactly(tier):
    for k in range(13):            # two whole minutes and the third begun
        tier.write_scrape(k)
        if k % 6 == 5:
            tier.close_minute(k // 6, (k // 6 + 1) * N)
    _same(tier.served(2), tier.reference(2))
    # the third minute is open: it has no point, and no window was
    # flushed twice or by both
    assert np.isnan(tier.served(3)[:, 2]).all()
    assert tier.windows_flushed() == 2 * N
    assert tier.moved("coordinator.m3msg.rows") == 2 * N
    assert tier.moved_sum("aggregator.add.timed") == 2 * 13 * N
    assert tier.moved_sum("aggregator.add.late_dropped") == 0
    # the direct write lost nothing to the remote branch beside it
    url = tier.base + "/api/v1/query?" + urllib.parse.urlencode({
        "query": 'count(count_over_time(cpu[3m]))',
        "time": T0_S + 125})
    with urllib.request.urlopen(url, timeout=30) as r:
        res = json.loads(r.read())["data"]["result"]
    assert float(res[0]["value"][1]) == N


def test_the_counters_are_at_debug_vars(tier):
    for k in range(6):
        tier.write_scrape(k)
    tier.close_minute(0, N)
    with urllib.request.urlopen(tier.base + "/debug/vars", timeout=30) as r:
        names = set(json.loads(r.read())["metrics"])
    lead = tier.leader()
    for want in ("aggregator.add.timed{instance=agg0}",
                 "aggregator.add.timed{instance=agg1}",
                 "aggregator.add.late_dropped{instance=agg0}",
                 "aggregator.flush.windows{instance=%s,role=leader}" % lead,
                 "aggregator.flush.rows{instance=%s,role=leader}" % lead,
                 "aggregator.election.transitions{instance=%s,to=leader}"
                 % lead,
                 "aggregator.flush.errors{instance=agg0}",
                 "msg.producer.redeliveries", "msg.consumer.acks",
                 "coordinator.m3msg.rows"):
        assert want in names, want


def test_the_spans_carry_the_tiers_costs(tier, monkeypatch):
    # a ring that holds the whole minute's roots (the benchmark's traced
    # runs install one the same way), and requests that ask for a trace
    monkeypatch.setattr(tracing, "TRACER", tracing.Tracer(
        max_traces=4096, sample_rate=1.0))
    reported = []       # a reporter is handed every finished root
    tracing.TRACER.reporters.append(reported.append)
    tier.traced = True
    began = tracing.clock_ns()
    for k in range(6):
        tier.write_scrape(k)
    tier.close_minute(0, N)
    roots = [r for r in tracing.TRACER.recent_traces()
             if r["start_ns"] >= began]

    def named(name):
        out = []

        def walk(d):
            if d.get("name") == name:
                out.append(d)
            for c in d.get("children", ()):
                walk(c)
        for r in roots:
            walk(r)
        return out

    frames = named("aggregator.rawtcp.frame")
    assert frames and {f["tags"]["role"] for f in frames} == {
        "leader", "follower"}
    assert all({"decode_ns", "add_ns", "samples_n", "late_dropped_n"}
               <= set(f["costs"]) for f in frames)
    flushes = named("aggregator.flush")
    assert flushes and all(
        {"collect_ns", "reduce_ns", "emit_ns", "flush_times_ns", "rows_n"}
        <= set(f["costs"]) for f in flushes)
    assert sum(f["costs"]["rows_n"] for f in flushes) == N
    # who emitted which minute: the round's windows by their END
    stamp = (T0_S + RES_S) * S
    assert [f["tags"]["window_ends"] for f in flushes] == [[(stamp, N)]]
    assert {f["tags"]["instance"] for f in flushes} == {tier.leader()}
    produced = named("msg.produce")
    assert produced and all({"ack_wait_ns", "redelivered_n", "bytes"}
                            <= set(p["costs"]) for p in produced)
    ingests = named("coordinator.m3msg.ingest")
    assert sum(i["costs"]["rows_n"] for i in ingests) == N
    assert {tuple(i["tags"]["window_ends"]) for i in ingests} == {
        (stamp, stamp)}
    assert [r.name for r in reported if r.start_ns >= began] == [
        r["name"] for r in roots]
    stale = max(i["costs"]["staleness_ns"] for i in ingests)
    # flushed from buffer_past after the minute's end, on the injected clock
    assert BUFFER_PAST_S * S <= stale <= (BUFFER_PAST_S + 8) * S
    # the client's span is a child of the write request's
    clients = named("aggregator.client.write_batch")
    assert clients and all(
        {"match_ns", "encode_ns", "send_ns", "samples_n", "frames_n"}
        <= set(c["costs"]) and c["tags"]["replicas"] == 2 for c in clients)
    assert not [r for r in roots
                if r.get("name") == "aggregator.client.write_batch"]


# ------------------------------------------------ (ii) a graceful handoff


def test_a_resign_between_two_minutes_loses_and_doubles_nothing(tier):
    for k in range(6):
        tier.write_scrape(k)
    tier.close_minute(0, N)
    first = tier.leader()
    admin = tier.aggs[first].admin_endpoint
    req = urllib.request.Request(admin + "/resign", data=b"", method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        assert json.loads(r.read())["state"] == "OK"
    for k in range(6, 12):
        tier.write_scrape(k)
    tier.close_minute(1, 2 * N)
    second = tier.leader()
    assert second is not None and second != first
    _same(tier.served(2), tier.reference(2))
    # `windows_flushed` summed over the instances equals the windows due:
    # no closed window lost, none emitted by both
    assert tier.windows_flushed() == 2 * N
    by = {iid: tier.moved("aggregator.flush.windows{instance=%s,role=leader}"
                          % iid) for iid in tier.aggs}
    assert by == {first: N, second: N}
    assert tier.moved("coordinator.m3msg.rows") == 2 * N


# --------------------------------- (iii) an acknowledgement withheld once


def test_a_withheld_acknowledgement_redelivers_and_stores_one_point(tier):
    consumer = tier.node.coordinator.m3msg_consumer
    ingest = consumer._handler
    withheld = []

    def once(shard, payload):
        ingest(shard, payload)      # the rows ARE written ...
        if not withheld:            # ... and one acknowledgement is not sent
            withheld.append(shard)
            raise RuntimeError("acknowledgement withheld by the test")

    consumer._handler = once
    for k in range(6):
        tier.write_scrape(k)
    tier.advance_to((T0_S + RES_S + BUFFER_PAST_S + 1) * S)
    deadline = time.time() + 15
    while time.time() < deadline and not (
            withheld and tier.moved("msg.producer.redeliveries") >= 1
            and not any(a.flush_handler.unacked()
                        for a in tier.aggs.values())):
        time.sleep(0.05)
    assert withheld and tier.moved("msg.producer.redeliveries") >= 1
    # delivered twice, stored once: the rows of that message were
    # written again over themselves
    assert tier.moved("coordinator.m3msg.rows") > N
    _same(tier.served(1), tier.reference(1))
    assert tier.windows_flushed() == N


# ------------------------------------------------------------ (iv) lateness


def test_a_sample_inside_buffer_past_joins_and_a_later_one_is_dropped(tier):
    for k in range(5):
        tier.write_scrape(k)
    # scrape 5 of hosts 1.. arrives on time; host 0's arrives 5 s after
    # the minute's end, inside buffer_past: it is the minute's aggregate
    tier.advance_to((T0_S + 5 * CADENCE_S) * S + 9 * S)
    tier.write([(h, 5) for h in range(1, HOSTS)])
    tier.advance_to((T0_S + RES_S + 5) * S)
    tier.write([(0, 5)])
    tier.close_minute(0, N)
    _same(tier.served(1), tier.reference(1))
    assert tier.moved_sum("aggregator.add.late_dropped") == 0
    # host 0's scrape 4 again, with another value, after the minute was
    # flushed: acknowledged by the raw namespace, dropped and counted by
    # both replicas, never a second point for the minute
    want = tier.reference(1)
    tier.vals[0:len(FIELDS), 4] += 1000.0
    tier.write([(0, 4)])
    tier.advance_to((T0_S + 2 * RES_S + BUFFER_PAST_S + 1) * S)
    time.sleep(0.5)
    assert tier.moved_sum("aggregator.add.late_dropped") == \
        2 * len(FIELDS)
    assert tier.windows_flushed() == N
    _same(tier.served(1), want)


# -------------------------------- (v) the timed batch against its oracle


@pytest.mark.parametrize("drop_late", [False, True])
def test_the_timed_batch_client_matches_add_timed_row_for_row(drop_late):
    from m3_tpu.aggregator import Aggregator
    from m3_tpu.aggregator.client import AggregatorClient
    from m3_tpu.aggregator.handler import CaptureHandler
    from m3_tpu.aggregator.server import RawTCPServer, TCPTransport
    from m3_tpu.cluster.placement import (Instance, Placement,
                                          ShardAssignment, ShardState)
    from m3_tpu.metrics.metric import MetricType
    from m3_tpu.metrics.policy import StoragePolicy

    policy = StoragePolicy.parse("1m:72h")
    now = [(T0_S + 200) * S]
    rng = np.random.default_rng(7)
    ids = [b"cpu+field=f%d,hostname=host_%d" % (i % 3, i) for i in range(40)]
    rows_ids = [ids[i] for i in rng.integers(0, 40, 500)]
    # three minutes' worth, one of them already closed at `now`
    times = ((T0_S + rng.integers(0, 200, 500)) * S).tolist()
    values = rng.normal(size=500)

    def make():
        sink = CaptureHandler()
        return sink, Aggregator(
            num_shards=64, clock=lambda: now[0], flush_handler=sink,
            buffer_past_ns=BUFFER_PAST_S * S, drop_late_timed=drop_late)

    oracle_sink, oracle = make()
    for mid, t, v in zip(rows_ids, times, values.tolist()):
        oracle.add_timed(MetricType.GAUGE, mid, t, v, policy)
    sinks, servers = [], []
    for _ in range(2):
        sink, agg = make()
        sinks.append(sink)
        servers.append(RawTCPServer(agg).start())
    insts = {"a%d" % i: Instance("a%d" % i, srv.endpoint, shards={
        s: ShardAssignment(s, ShardState.AVAILABLE) for s in range(64)})
        for i, srv in enumerate(servers)}
    placement = Placement(insts, 64, 2, is_mirrored=True)
    transports = {iid: TCPTransport(inst.endpoint)
                  for iid, inst in insts.items()}
    try:
        client = AggregatorClient(64, lambda: placement, transports)
        assert client.write_timed_batch(
            MetricType.GAUGE, rows_ids, times, values, policy) == 0
        deadline = time.time() + 10
        while time.time() < deadline and any(
                srv.frames < 500 for srv in servers):
            time.sleep(0.02)
        now[0] = (T0_S + 400) * S
        oracle.flush()
        want = sorted((m.id, m.time_nanos, m.value) for m in
                      oracle_sink.metrics)
        assert want
        for srv, sink in zip(servers, sinks):
            srv.aggregator.flush()
            assert sorted((m.id, m.time_nanos, m.value)
                          for m in sink.metrics) == want
        late = sum(1 for t in times if t < (T0_S + 180) * S)
        assert late > 0
        assert len(want) == len({(i, t // (60 * S)) for i, t in zip(
            rows_ids, times) if not drop_late or t >= (T0_S + 180) * S})
    finally:
        for tr in transports.values():
            tr.close()
        for srv in servers:
            srv.close()


def test_the_timed_batch_memo_keeps_a_live_series_entry_alive():
    """A memo hit is an access: `tick()` never tombstones a series that
    is still written (its minute would be cut in two, one point from
    each elem), and lets go of one that went idle, memo and all."""
    from m3_tpu.aggregator import Aggregator
    from m3_tpu.aggregator.handler import CaptureHandler
    from m3_tpu.metrics.metric import MetricType
    from m3_tpu.metrics.policy import StoragePolicy

    policy = StoragePolicy.parse("1m:72h")
    hour = 3600 * S
    now = [T0_S * S]
    sink = CaptureHandler()
    agg = Aggregator(num_shards=64, clock=lambda: now[0], flush_handler=sink,
                     buffer_past_ns=BUFFER_PAST_S * S, drop_late_timed=True)
    ids = [b"cpu+hostname=host_%d" % i for i in range(8)]

    def add(value):
        agg.add_timed_batch(MetricType.GAUGE, ids, [now[0]] * len(ids),
                            np.full(len(ids), value), policy)

    add(1.0)                    # looked up: the memo fills
    now[0] += 23 * hour
    add(2.0)                    # memo hits, 23 h after the entries' birth
    now[0] += 2 * hour + 5 * S  # 25 h after it, 2 h after the last write
    add(3.0)
    assert agg.tick() == 0
    now[0] += 5 * S
    add(4.0)                    # the same minute as 3.0
    now[0] += 2 * 60 * S
    agg.flush()
    newest = max(m.time_nanos for m in sink.metrics)
    last = [(m.id, m.value) for m in sink.metrics if m.time_nanos == newest]
    assert sorted(last) == [(mid, 4.0) for mid in sorted(ids)]
    assert len(sink.metrics) == 3 * len(ids)
    now[0] += 25 * hour         # idle past the entries' lives
    assert agg.tick() == len(ids)
    assert not any(agg._timed_elems.values())


def test_a_drain_beside_a_stager_neither_raises_nor_loses_a_window():
    """The flush thread's `collect_into` peeks a single-window elem while
    a rawtcp thread stages that elem's NEXT window, a fresh key: the peek
    is one C call (`min`), where an `iter` and its `next` with the insert
    between them raised "dictionary changed size during iteration" and
    failed the round (one chip run in ten, PR 51). Every window staged
    is collected once."""
    import sys
    import threading

    from m3_tpu.aggregator.elem import Elem, ElemKey
    from m3_tpu.aggregator.list import FlushBatch, MetricList
    from m3_tpu.metrics.metric import MetricType
    from m3_tpu.metrics.policy import StoragePolicy

    policy, res, n, rounds = StoragePolicy.parse("1m:72h"), RES_S * S, 2000, 40
    lst = MetricList(res)
    elems = []
    for i in range(n):
        key = ElemKey(b"m%d" % i, policy, 0)
        elems.append(lst.get_or_create(
            key, lambda key=key: Elem(key, MetricType.GAUGE)))
    one = np.ones(1)
    for e in elems:
        e._stage(0, one)
    collected = 0
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)     # a thread switch every few bytecodes
    try:
        for r in range(rounds):
            def stage(r=r):
                for e in elems:
                    e._stage((r + 1) * res, one)

            stager = threading.Thread(target=stage)
            stager.start()
            while stager.is_alive():
                batch = FlushBatch()
                lst.collect_into((r + 1) * res, batch)
                collected += len(batch)
            stager.join()
            batch = FlushBatch()
            lst.collect_into((r + 1) * res, batch)
            collected += len(batch)
    finally:
        sys.setswitchinterval(interval)
    assert collected == rounds * n


# ------------------------------------------------------------- satellites


def test_a_flush_that_fails_is_counted_not_swallowed(tmp_path, caplog):
    kv = run_kv(load_dict({}, "kv"))
    now = [T0_S * S]

    class Broken:
        def handle_columnar(self, groups):
            raise OSError("the sink is gone")

    cfg = load_dict(dict(AGG, instance_id="aggx", kv_endpoint=kv.endpoint,
                         flush_handler="", register_in_placement=False,
                         placement_key=""), "aggregator")
    handle = run_aggregator(cfg, flush_handler=Broken(),
                            clock=lambda: now[0])
    c0 = _counters().get("aggregator.flush.errors{instance=aggx}", 0)
    try:
        from m3_tpu.metrics.metric import MetricType
        from m3_tpu.metrics.policy import StoragePolicy

        handle.aggregator.add_timed(MetricType.GAUGE, b"m", T0_S * S, 1.0,
                                    StoragePolicy.parse("1m:72h"))
        now[0] = (T0_S + 80) * S
        deadline = time.time() + 10
        while time.time() < deadline and _counters().get(
                "aggregator.flush.errors{instance=aggx}", 0) == c0:
            time.sleep(0.02)
        assert _counters()["aggregator.flush.errors{instance=aggx}"] > c0
        assert any("flush failed" in r.getMessage() for r in caplog.records)
    finally:
        handle.close()
        kv.close()


def test_a_resigned_leader_leaves_the_lease_to_the_others_for_a_ttl():
    from m3_tpu.cluster import kv as kvmod
    from m3_tpu.cluster.services import CampaignState, LeaderService

    store = kvmod.MemStore()
    now = [100 * S]
    a, b = (LeaderService(store, "e", iid, lease_ttl_ns=10 * S,
                          clock=lambda: now[0]) for iid in ("a", "b"))
    assert a.campaign() == CampaignState.LEADER
    assert b.campaign() == CampaignState.FOLLOWER
    a.resign()
    assert a.campaign() == CampaignState.FOLLOWER     # does not take it back
    assert b.campaign() == CampaignState.LEADER
    now[0] += 11 * S                                  # b dies, unrenewed
    assert a.campaign() == CampaignState.LEADER
    # alone, a resigner takes the lease back once a TTL has passed
    a.resign()
    assert a.campaign() == CampaignState.FOLLOWER
    now[0] += 10 * S
    assert a.campaign() == CampaignState.LEADER


def test_the_reference_takes_the_greatest_acknowledged_timestamp():
    cfg = dict(REF_CFG, scale=2)
    vals = np.arange(2 * 2 * 12, dtype=np.float64).reshape(4, 12)
    acked = np.ones((2, 12), bool)
    acked[0, 5] = False             # host 0's last scrape of minute 0: lost
    acked[1, 6:12] = False          # host 1: nothing of minute 1
    stamps, pv = REF.minute_points(cfg, vals, acked,
                                   np.array([0, 9999]), T0_S)
    assert stamps.tolist() == [T0_S + 60, T0_S + 120]
    assert pv[0].tolist() == [vals[0, 4], vals[0, 11]]
    assert pv[2, 0] == vals[2, 5] and np.isnan(pv[2, 1])
    src = open(os.path.join(ROOT_DIR, "benchmark", "reference",
                            "aggtier_ref.py")).read()
    assert "import m3_tpu" not in src and "from m3_tpu" not in src
