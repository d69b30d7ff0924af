"""The coordinator's namespace resolver (query/storage.py `resolve`,
`ResolvingStorage`): which of a coordinator's cluster namespaces answers
a fetch, by the fetch's start against each namespace's retention on the
coordinator's clock (the reference's cluster_resolver.go) — the three
cases and their edges as a table, then the same cases through a
`ResolvingStorage` whose members are `LocalStorage` and `SessionStorage`
alike; and the edge of a retention as the node honours it: the block
that straddles `now - retention` is served until it lies wholly outside."""

import numpy as np
import pytest

from m3_tpu.client import Session, SessionOptions
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.query.model import Matcher, MatchType
from m3_tpu.query.storage import (FanoutStorage, LocalStorage, NamespaceAttrs,
                                  ResolvingStorage, SessionStorage,
                                  merge_points, resolve)
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.testing import ClusterHarness
from m3_tpu.utils import instrument, tracing, xtime

S, M, H, D = xtime.SECOND, xtime.MINUTE, xtime.HOUR, xtime.DAY
NOW = 1_700_000_000 * S

RAW = NamespaceAttrs(b"default", False, 2 * H)
A1M = NamespaceAttrs(b"agg_1m", True, 72 * H, M)
A10M = NamespaceAttrs(b"agg_10m", True, 30 * D, 10 * M)
A1M_LONG = NamespaceAttrs(b"agg_1m_long", True, 96 * H, M)
P30S = NamespaceAttrs(b"part_30s", True, 6 * H, 30 * S, complete=False)
P5M = NamespaceAttrs(b"part_5m", True, 6 * H, 5 * M, complete=False)
SID = b"cpu;host=a"
TAGS = {b"__name__": b"cpu", b"host": b"a"}
MATCH = (Matcher(MatchType.EQUAL, b"__name__", b"cpu"),)


@pytest.mark.parametrize("attrs,back,want,how", [
    # case 1: the unaggregated namespace reaches back to the start
    ([RAW, A1M, A10M], 1 * H, [b"default"], "unaggregated"),
    ([RAW, A1M, A10M], 2 * H, [b"default"], "unaggregated"),        # the edge
    # case 2: the finest complete aggregated namespace that reaches back
    ([RAW, A1M, A10M], 2 * H + 1, [b"agg_1m"], "aggregated"),
    ([RAW, A1M, A10M], 72 * H, [b"agg_1m"], "aggregated"),          # the edge
    ([RAW, A1M, A10M], 72 * H + 1, [b"agg_10m"], "aggregated"),
    ([A10M, A1M, RAW], 3 * H, [b"agg_1m"], "aggregated"),           # any order
    # two complete ones of one resolution: the longer retention
    ([RAW, A1M, A1M_LONG], 3 * H, [b"agg_1m_long"], "aggregated"),
    ([RAW, A1M_LONG, A1M], 3 * H, [b"agg_1m_long"], "aggregated"),
    # a partial one of finer resolution that reaches back joins, finest first
    ([RAW, A1M, P30S], 3 * H, [b"part_30s", b"agg_1m"], "aggregated"),
    ([RAW, A1M, P30S], 7 * H, [b"agg_1m"], "aggregated"),   # it no longer does
    ([RAW, A1M, P5M], 3 * H, [b"agg_1m"], "aggregated"),    # a coarser one never
    # case 3: nothing reaches back: unaggregated + the longest aggregated
    ([RAW, A1M, A10M], 31 * D, [b"default", b"agg_10m"], "partial"),
    # (only partial ones, of one retention: the finer of them)
    ([RAW, P30S, P5M], 3 * H, [b"default", b"part_30s"], "partial"),
])
def test_the_rule(attrs, back, want, how):
    picked, got = resolve(attrs, NOW, NOW - back)
    assert ([attrs[i].name for i in picked], got) == (want, how)


def test_merge_points_one_run_first_part_wins():
    t, v = merge_points(
        [np.array([10, 20, 30]), np.array([5, 20, 40]), np.array([30])],
        [np.array([1., 2., 3.]), np.array([9., 9., 9.]), np.array([7.])])
    assert t.tolist() == [5, 10, 20, 30, 40]
    assert v.tolist() == [9., 1., 2., 3., 9.]
    one = (np.array([3, 1]), np.array([1., 2.]))
    assert merge_points([one[0]], [one[1]])[0] is one[0]   # one store: as is


def test_a_storage_needs_one_unaggregated_and_an_aggregated():
    with pytest.raises(ValueError):
        ResolvingStorage([(RAW, object())])
    with pytest.raises(ValueError):
        ResolvingStorage([(A1M, object()), (A10M, object())])


# ------------------------------------------------- the configuration

LIST = [{"namespace": "default", "type": "unaggregated", "retention": "2h"},
        {"namespace": "agg_1m", "type": "aggregated", "retention": "72h",
         "resolution": "1m"},
        {"namespace": "agg_5m", "type": "aggregated", "retention": "30d",
         "resolution": "5m", "downsample": {"all": False}}]


def test_the_namespace_list_is_configuration():
    from m3_tpu.services import load_dict
    from m3_tpu.services.run import _cluster_namespaces

    cfg = load_dict({"namespaces": LIST}, "coordinator")
    assert [(n.namespace, n.aggregated, n.downsample_all)
            for n in cfg.namespaces] == [("default", False, False),
                                         ("agg_1m", True, True),
                                         ("agg_5m", True, False)]
    assert _cluster_namespaces(cfg) == [
        NamespaceAttrs(b"default", False, 2 * H),
        NamespaceAttrs(b"agg_1m", True, 72 * H, M),
        NamespaceAttrs(b"agg_5m", True, 30 * D, 5 * M, complete=False)]
    # the old single key: one unaggregated namespace, no list, no resolver
    old = load_dict({"namespace": "metrics"}, "coordinator")
    assert old.namespaces == [] and old.namespace == "metrics"
    assert _cluster_namespaces(old) is None
    node = load_dict({"namespaces": [{"name": "a", "index_block_size": "24h"},
                                     {"name": "b"}]}, "dbnode")
    assert [n.index_block_size_ns for n in node.namespaces] == [24 * H, None]


@pytest.mark.parametrize("bad", [
    {"namespace": "default", "namespaces": LIST},           # both keys
    {"namespaces": LIST[1:]},                               # no unaggregated
    {"namespaces": LIST[:1] + LIST[:1]},                    # a name twice
    {"namespaces": [LIST[0], {"namespace": "x", "type": "aggregated",
                              "retention": "1d"}]},         # no resolution
    {"namespaces": [dict(LIST[0], resolution="1m"), LIST[1]]},
    {"namespaces": [LIST[0], dict(LIST[1], type="rolled")]},
    {"namespaces": [LIST[0], dict(LIST[1], downsample={"some": True})]},
], ids=["both-keys", "no-unaggregated", "repeated", "no-resolution",
        "unaggregated-resolution", "unknown-type", "unknown-key"])
def test_a_namespace_list_that_says_nothing_sound_is_refused(bad):
    from m3_tpu.services import load_dict
    from m3_tpu.services.config import ConfigError

    with pytest.raises(ConfigError):
        load_dict(bad, "coordinator")


def test_a_node_refuses_a_list_that_promises_what_it_does_not_hold(tmp_path):
    from m3_tpu.services import load_dict, run_dbnode
    from m3_tpu.services.config import ConfigError

    for namespaces in ([{"name": "default", "retention": "2h"}],
                       [{"name": "default", "retention": "2h"},
                        {"name": "agg_1m", "retention": "48h"}]):
        cfg = load_dict({"data_dir": str(tmp_path), "namespaces": namespaces,
                         "coordinator": {"namespaces": LIST[:2]}}, "dbnode")
        with pytest.raises(ConfigError):
            run_dbnode(cfg)


def test_downsample_all_installs_the_default_rule_and_no_rule_set(tmp_path):
    """`downsample.all` alone, no KV rule set: a gauge written through the
    coordinator's writer comes out of the aggregated namespace as the
    `last` of its window, stamped at the window's end, under the id and
    the tags it was written with; a `downsample.all: false` namespace
    gets nothing."""
    from m3_tpu.services import load_dict, run_dbnode

    base = NOW - NOW % M + M                  # a minute's first instant
    now = {"t": base}
    node = [{"name": n["namespace"], "retention": n["retention"],
             "block_size": "2h"} for n in LIST]
    cfg = load_dict({"data_dir": str(tmp_path), "num_shards": 4,
                     "namespaces": node,
                     "coordinator": {"namespaces": LIST}}, "dbnode")
    handle = run_dbnode(cfg, clock=lambda: now["t"])
    try:
        coord = handle.coordinator
        assert coord._flush_thread is not None      # the services' cadence
        tags = {b"__name__": b"cpu", b"host": b"a"}
        for k in range(12):                          # two minutes of scrapes
            now["t"] = base + k * 10 * S
            coord.writer.write_batch([(tags, now["t"], float(k))])
        now["t"] = base + 120 * S
        coord.flush_downsampler()
        got = LocalStorage(handle.db, b"agg_1m").fetch_raw(MATCH, 0, now["t"] + 1)
        assert list(got) == [SID] and got[SID]["tags"] == TAGS
        assert np.asarray(got[SID]["t"]).tolist() == [base + 60 * S,
                                                      base + 120 * S]
        assert np.asarray(got[SID]["v"]).tolist() == [5.0, 11.0]
        assert LocalStorage(handle.db, b"agg_5m").fetch_raw(
            MATCH, 0, now["t"] + S) == {}
        raw = LocalStorage(handle.db, b"default").fetch_raw(
            MATCH, 0, now["t"] + 1)
        assert list(raw) == [SID] and len(raw[SID]["t"]) == 12
    finally:
        handle.close()


# ------------------------------------------------- through real members

NAMES = [b"default", b"agg_1m", b"agg_10m"]
# what each namespace holds of the one series: (minutes before NOW, value);
# every namespace has a point 90 minutes back, each with its own value
HELD = {b"default": [(90, 1.0), (30, 1.5)],
        b"agg_1m": [(600, 2.0), (90, 2.5)],
        b"agg_10m": [(3000, 3.0), (600, 3.5), (90, 3.7)]}


@pytest.fixture(scope="module", params=["local", "session"])
def members(request):
    """(kind, [(attrs, storage)], clock) over one node that holds three
    namespaces, each with other points of the same series."""
    opts = NamespaceOptions(retention_ns=40 * D, block_size_ns=2 * H,
                            buffer_past_ns=10 * M)
    h = ClusterHarness(n_nodes=1, replica_factor=1, num_shards=4,
                       ns_opts=opts, namespaces=NAMES,
                       start_ns=NOW - 3001 * M)
    sess = Session(h.topology, SessionOptions(timeout_s=10))
    writes = sorted((back, ns, v) for ns, pts in HELD.items()
                    for back, v in pts)
    for back, ns, v in reversed(writes):        # oldest first
        h.clock.now_ns = NOW - back * M
        sess.write_tagged(ns, SID, TAGS, NOW - back * M, v)
    h.clock.now_ns = NOW
    node = next(iter(h.nodes.values()))
    if request.param == "local":
        stores = [LocalStorage(node.db, ns) for ns in NAMES]
    else:
        stores = [SessionStorage(sess, ns) for ns in NAMES]
    yield request.param, list(zip([RAW, A1M, A10M], stores)), h.clock
    sess.close()
    h.close()


@pytest.mark.parametrize("back,how,want", [
    (60, "unaggregated", [(30, 1.5)]),
    (120, "unaggregated", [(90, 1.0), (30, 1.5)]),      # start at the edge
    (121, "aggregated", [(90, 2.5)]),
    (72 * 60, "aggregated", [(600, 2.0), (90, 2.5)]),   # start at the edge
    (72 * 60 + 1, "aggregated", [(3000, 3.0), (600, 3.5), (90, 3.7)]),
    # nothing reaches back 31 days: the unaggregated and the longest
    # aggregated, merged; at 90 minutes back the finer one's value wins
    (31 * 24 * 60, "partial",
     [(3000, 3.0), (600, 3.5), (90, 1.0), (30, 1.5)]),
])
def test_a_fetch_is_answered_by_the_namespaces_the_rule_names(
        members, back, how, want):
    kind, pairs, clock = members
    storage = ResolvingStorage(pairs, clock)
    scope = instrument.ROOT.sub_scope("query.resolve")
    before = {k: scope.counter(k).value()
              for k in ("unaggregated", "aggregated", "partial")}
    got = storage.fetch_raw(MATCH, NOW - back * M, NOW + 1)
    assert list(got) == [SID]
    assert got[SID]["tags"] == TAGS
    assert list(zip(np.asarray(got[SID]["t"]).tolist(),
                    np.asarray(got[SID]["v"]).tolist())) == \
        [(NOW - b * M, v) for b, v in want]
    moved = {k: scope.counter(k).value() - before[k] for k in before}
    assert moved == {k: int(k == how) for k in before}


def test_writes_go_to_the_unaggregated_namespace(members):
    kind, pairs, clock = members
    storage = ResolvingStorage(pairs, clock)
    other = {b"__name__": b"mem", b"host": b"a"}
    storage.write(b"mem;host=a", other, NOW - M, 4.0)
    storage.write_batch([b"mem;host=a"], [other], [NOW], [5.0])
    q = (Matcher(MatchType.EQUAL, b"__name__", b"mem"),)
    raw, agg = pairs[0][1], pairs[1][1]
    assert np.asarray(raw.fetch_raw(q, NOW - H, NOW + 1)[b"mem;host=a"]["v"]
                      ).tolist() == [4.0, 5.0]
    assert agg.fetch_raw(q, 0, NOW + 1) == {}
    # and the tags of a range resolve as the fetch of that range does
    names = storage.complete_tags((), NOW - H, NOW + 1)[b"__name__"]
    assert names == {b"cpu", b"mem"}
    assert storage.complete_tags((), NOW - 3 * H, NOW + 1)[b"__name__"] == \
        {b"cpu"}


def test_the_fetchs_span_says_what_was_resolved_and_which_namespace_read(
        members, monkeypatch):
    kind, pairs, clock = members
    tracer = tracing.Tracer(sample_rate=1.0)
    monkeypatch.setattr(tracing, "TRACER", tracer)
    storage = ResolvingStorage(pairs, clock)
    with tracer.background_span("query.fetch") as sp:
        storage.fetch_raw(MATCH, NOW - 31 * D, NOW + 1)
    costs = sp.to_dict()["costs"]
    assert costs["namespaces_n"] == 2 and costs["resolve_ns"] > 0
    if kind == "local":     # read_many's costs are the embedded read path's
        assert costs["series_n"] == 2   # one series, read in two namespaces
    with tracer.background_span("query.fetch") as sp:
        storage.fetch_raw(MATCH, NOW - 3 * H, NOW + 1)
    assert sp.to_dict()["costs"]["namespaces_n"] == 1


def test_fanout_merges_every_store_in_one_pass(members):
    kind, pairs, _clock = members
    fan = FanoutStorage([s for _a, s in pairs])
    got = fan.fetch_raw(MATCH, 0, NOW + 1)[SID]
    # the first store's value where several hold a timestamp
    assert list(zip(((NOW - np.asarray(got["t"])) // M).tolist(),
                    np.asarray(got["v"]).tolist())) == \
        [(3000, 3.0), (600, 2.0), (90, 1.0), (30, 1.5)]


# ------------------------------------------------- the retention's edge

BSZ = 20 * M
T0 = 1_700_000_400 * S      # a block boundary


def _node(tmp_path, clock):
    db = Database(ShardSet(4), clock=clock)
    opts = NamespaceOptions(retention_ns=2 * H, block_size_ns=BSZ,
                            buffer_past_ns=10 * M)
    db.create_namespace(b"default", opts,
                        index=NamespaceIndex(clock=clock))
    db.mark_bootstrapped()
    return db


@pytest.mark.parametrize("restarted", [False, True],
                         ids=["ticked", "restarted"])
def test_the_block_that_straddles_the_retention_edge_is_served(
        tmp_path, restarted):
    """Seven blocks and a bit written, sealed and flushed; `now` stands
    2 h 10 min after the first point, so `now - retention` falls in the
    middle of the first block but one... a fetch that starts exactly
    there finds every point from there on, after the tick's expiry and
    after a restart's bootstrap alike; the block before it is gone."""
    from m3_tpu.persist.fs import PersistManager
    from m3_tpu.storage.bootstrap import BootstrapContext, BootstrapProcess
    from m3_tpu.storage.mediator import Mediator

    now = {"t": T0}
    clock = lambda: now["t"]    # noqa: E731
    db = _node(tmp_path, clock)
    persist = PersistManager(str(tmp_path / "data"))
    mediator = Mediator(db, persist)
    steps = (2 * H + 50 * M) // (10 * S)
    for k in range(steps):
        now["t"] = T0 + k * 10 * S
        db.write(b"default", SID, now["t"], float(k), tags=TAGS)
        if k % 120 == 119:
            mediator.run_once()
    end = now["t"] = T0 + steps * 10 * S        # T0 + 2h50m
    mediator.run_once()
    if restarted:
        db.close()
        db = _node(tmp_path, clock)
        BootstrapProcess(chain=("filesystem",), ctx=BootstrapContext(
            persist=persist, shard_lookup=db.shard_set.lookup)).run(db)
    edge = end - 2 * H                          # T0 + 50m: inside block 2
    ns = db.namespace(b"default")
    starts = sorted({bs for sh in ns.shards.values() for bs in sh.blocks})
    assert starts[0] == T0 + 2 * BSZ            # the straddling block, kept
    got = LocalStorage(db, b"default").fetch_raw(MATCH, edge, end + 1)[SID]
    # (a restart over filesets alone brings back what was sealed: this
    # node keeps no commit log for the open buffer's ten minutes)
    want = np.arange((edge - T0) // (10 * S), 8 * 120 if restarted else steps)
    assert np.asarray(got["t"]).tolist() == (T0 + want * 10 * S).tolist()
    assert np.asarray(got["v"]).tolist() == want.astype(float).tolist()
    # one cadence later the edge has not left the block: still whole
    now["t"] = end + 10 * S
    mediator = Mediator(db, persist)
    mediator.run_once()
    got = LocalStorage(db, b"default").fetch_raw(
        MATCH, edge + 10 * S, end + 1)[SID]
    assert len(got["t"]) == len(want) - 1
    db.close()
