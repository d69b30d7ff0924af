"""A remote-write request travels from the decoder to the WAL as ONE
batch (one admission, one append per shard touched, one commit-log
append), and stores exactly what the same samples store one `write` at
a time: points, registry tags, WAL bytes. Shard routing on that path is
the shard memo (ShardSet.lookup_memo): no hash_batch, no codec dispatch."""

import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from m3_tpu.coordinator import promremote as pr
from m3_tpu.coordinator import run_embedded
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.parallel import sharding
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.persist import commitlog
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.utils import hashing
from m3_tpu.utils.instrument import ROOT

S = 1_000_000_000
T0 = 1_700_000_000 * S
NS = b"default"


class Node:
    """A dbnode with its commit log on, behind an embedded coordinator,
    under a clock the test moves."""

    def __init__(self, tmp_path, name="node", shards=8,
                 strategy=commitlog.Strategy.WRITE_BEHIND, **kw):
        self.now = {"t": T0}
        self.wal_dir = str(tmp_path / name / "commitlog")
        self.log = commitlog.CommitLog(self.wal_dir, strategy=strategy,
                                       clock=lambda: self.now["t"])
        self.db = Database(ShardSet(shards), commitlog=self.log,
                           clock=lambda: self.now["t"])
        self.db.create_namespace(
            NS, NamespaceOptions(),
            index=NamespaceIndex(clock=lambda: self.now["t"]))
        self.coord = run_embedded(self.db, clock=lambda: self.now["t"], **kw)

    def post(self, series):
        req = urllib.request.Request(
            self.coord.endpoint + "/api/v1/prom/remote/write",
            data=pr.snappy_compress(pr.encode_write_request(series)),
            method="POST")
        req.add_header("Content-Type", "application/x-protobuf")
        with urllib.request.urlopen(req) as resp:
            return resp.status, resp.read()

    def read(self, sid):
        return self.db.read(NS, sid, T0 - 3600 * S, T0 + 3600 * S)

    def registry_tags(self, sid):
        shard = self.db.namespace(NS).shards[self.db.shard_set.lookup(sid)]
        idx = shard.registry.get(sid)
        return None if idx is None else shard.registry.tags_of(idx)

    def wal(self):
        """(entries, tags by series, each file's entry bytes: its chunk
        bodies joined, since where a chunk ends is when a flush fell
        due, not the format) of the closed log."""
        self.log.close()
        entries, tags = [], {}
        for b in commitlog.replay_batches(self.wal_dir):
            for ns, sid, t, v, tg in zip(b.namespaces, b.ids, b.t_ns,
                                         b.values, b.tags):
                entries.append((ns, sid, int(t), float(v)))
                tags[sid] = tg
        raw = [b"".join(body for body, _ in commitlog._iter_chunks(f))
               for f in self.log.files()]
        return entries, tags, raw

    def close(self):
        self.coord.close()


@pytest.fixture
def node(tmp_path):
    n = Node(tmp_path)
    yield n
    n.close()


def _scrape(hosts, steps=1, t0_ms=T0 // 1_000_000, name=b"cpu", first=0):
    return [({b"__name__": name, b"host": b"h%03d" % i, b"dc": b"d%d" % (i % 3)},
             [(t0_ms + 10_000 * k, float(i * 100 + k)) for k in range(steps)])
            for i in range(first, first + hosts)]


def _sid(tags):
    from m3_tpu.coordinator.ingest import _series_id

    return _series_id(tags)


def _counters(prefix):
    return {k: v for k, v in ROOT.snapshot().items()
            if k.startswith(prefix) and isinstance(v, (int, float))}


def _moved(before, prefix):
    after = _counters(prefix)
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


# ------------------------------------------------- (a) batch == per sample


@pytest.mark.parametrize("hosts,steps", [(1, 1), (40, 3), (250, 2)])
def test_one_request_stores_what_per_sample_writes_store(tmp_path, hosts,
                                                         steps):
    series = _scrape(hosts, steps)
    batch, ref = Node(tmp_path, "batch"), Node(tmp_path, "ref")
    try:
        status, body = batch.post(series)
        assert status == 200
        assert body == b'{"status": "success", "wrote": %d}' % (hosts * steps)
        for tags, samples in series:
            for t_ms, v in samples:
                ref.coord.writer.write(tags, t_ms * 1_000_000, v)
        for tags, samples in series:
            sid = _sid(tags)
            (t, v), (rt, rv) = batch.read(sid), ref.read(sid)
            assert t.tolist() == rt.tolist() == [
                t_ms * 1_000_000 for t_ms, _ in samples]
            assert v.tolist() == rv.tolist() == [x for _, x in samples]
            assert batch.registry_tags(sid) == ref.registry_tags(sid) == tags
        (entries, tags_b, raw_b), (rentries, tags_r, raw_r) = \
            batch.wal(), ref.wal()
        assert entries == rentries and len(entries) == hosts * steps
        assert tags_b == tags_r
        assert all(tags_b[_sid(tags)] == tags for tags, _ in series)
        assert raw_b == raw_r       # the on-disk format, byte for byte
    finally:
        batch.close()
        ref.close()


@pytest.mark.parametrize("first", ["tagged", "untagged"])
def test_a_batch_of_known_series_packs_the_same_wal_bytes(tmp_path, first):
    """The commit log packs a batch of series its file already knows as
    one column-built run; a series first logged untagged gets its
    tagged meta entry before its next data entry, as one write at a
    time would log it."""
    scrapes = [_scrape(40, 1, T0 // 1_000_000 + 10_000 * k) for k in range(3)]
    ids = [_sid(tags) for tags, _ in scrapes[0]]
    batch, ref = Node(tmp_path, "batch"), Node(tmp_path, "ref")
    try:
        for n in (batch, ref):
            n.now["t"] = T0 + 30 * S
            if first == "untagged":
                n.db.write_batch(NS, ids, np.full(40, T0 - 10 * S),
                                 np.zeros(40))
        for series in scrapes:
            assert batch.post(series)[0] == 200
            for tags, samples in series:
                for t_ms, v in samples:
                    ref.coord.writer.write(tags, t_ms * 1_000_000, v)
        (entries, tags_b, raw_b), (rentries, tags_r, raw_r) = \
            batch.wal(), ref.wal()
        assert len(entries) == 120 + 40 * (first == "untagged")
        assert entries == rentries and tags_b == tags_r
        assert all(tags_b[_sid(tags)] == tags for tags, _ in scrapes[0])
        assert raw_b == raw_r
    finally:
        batch.close()
        ref.close()


# -------------------------------------------------- (b) concurrent senders


def test_eight_concurrent_senders_every_acknowledged_sample_reads_back(node):
    senders, requests, hosts = 8, 6, 25
    acked, errors = [], []

    def send(s):
        try:
            for r in range(requests):
                series = _scrape(hosts, 1, T0 // 1_000_000 + 10_000 * r,
                                 first=s * hosts)
                status, _ = node.post(series)
                if status == 200:
                    acked.extend((_sid(tags), t_ms * 1_000_000, v)
                                 for tags, smp in series for t_ms, v in smp)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    node.now["t"] = T0 + 60 * S
    threads = [threading.Thread(target=send, args=(s,))
               for s in range(senders)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert len(acked) == senders * requests * hosts
    want = {}
    for sid, t, v in acked:
        want.setdefault(sid, {})[t] = v
    for sid, points in want.items():
        t, v = node.read(sid)
        assert dict(zip(t.tolist(), v.tolist())) == points
    entries, _tags, _raw = node.wal()
    assert len(entries) == len(acked)
    assert sorted(entries) == sorted((NS, sid, t, v) for sid, t, v in acked)


# ----------------------------------------- (c) shed, and the window's edge


def test_a_shed_request_answers_429_and_writes_nothing(node):
    gate = node.coord.writer.gate
    gate.admit(gate.capacity)            # the gate is at capacity
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            node.post(_scrape(20))
        assert e.value.code == 429
        assert e.value.headers["Retry-After"] == "1"
    finally:
        gate.release(gate.capacity)
    for tags, _ in _scrape(20):
        assert node.registry_tags(_sid(tags)) is None
    assert node.wal()[0] == []


def test_a_sample_outside_the_window_answers_400_and_applies_nothing(node):
    """Until PR 31 this pinned a partial application (the shards below
    the offending one appended, and the rescue logged them). The window
    is now checked once for the batch before any shard is touched."""
    series = _scrape(30)
    past = node.db.namespace(NS).opts.buffer_past_ns
    tags, _ = series[17]
    series[17] = (tags, [((T0 - past - 60 * S) // 1_000_000, 1.0)])
    with pytest.raises(urllib.error.HTTPError) as e:
        node.post(series)
    assert e.value.code == 400
    assert b"1 datapoints outside acceptance window" in e.value.read()
    for tg, _samples in series:
        assert len(node.read(_sid(tg))[0]) == 0
        assert node.registry_tags(_sid(tg)) is None
    assert node.wal()[0] == []


def test_a_failed_commit_log_append_fails_the_request(tmp_path):
    from m3_tpu.testing import faultfs

    faultfs.install(faultfs.DiskFaultPlan(seed=9, write_eio=1.0))
    n = Node(tmp_path, strategy=commitlog.Strategy.WRITE_WAIT)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            n.post(_scrape(20))
        assert e.value.code == 400       # as the per-sample path answered
        assert n.db.disk_health.failures >= 1
    finally:
        faultfs.uninstall()
        n.close()


# ------------------------------------------- the one routing pass (PR 31)
#
# Database.write_batch sorts a batch by shard once, checks the window and
# works out the block start once, and hands each shard contiguous slices.
# Held to the same rows written one `Database.write` (one `Shard.write`,
# one `commitlog.write`) at a time: bucket columns in row order, registry
# ids and tags, commit-log bytes.

APPENDS = "storage.write_batch."
# T0 is 800 s into its 2-hour block: the boundary below it, and a clock
# from which both sides of it are inside the acceptance window
BOUNDARY = T0 - 800 * S


def _rows(db, hosts, t_ns, per_shard=None, name=b"cpu", value=1.0):
    """(id, tags, t, v) rows in host order; `per_shard`: only hosts whose
    series is among the first `per_shard` of its shard."""
    out, seen = [], {}
    for tags, _ in _scrape(hosts, name=name):
        sid = _sid(tags)
        shard = db.shard_set.lookup(sid)
        seen[shard] = seen.get(shard, 0) + 1
        if per_shard is None or seen[shard] <= per_shard:
            out.append((sid, tags, t_ns, value + len(out)))
    return out


def _write_batch(node, rows, tagged=True):
    ids = [r[0] for r in rows]
    node.db.write_batch(
        NS, ids, np.array([r[2] for r in rows], np.int64),
        np.array([r[3] for r in rows]),
        tags=[r[1] for r in rows] if tagged else None,
        shard_ids=node.db.shard_set.lookup_memo(ids))


def _write_each(node, rows, tagged=True):
    for sid, tags, t, v in rows:
        node.db.write(NS, sid, t, v, tags if tagged else None)


def _stored(node):
    """Every shard's registry and open buckets, rows in stored order."""
    out = {}
    for shard_id, shard in node.db.namespace(NS).shards.items():
        reg = shard.registry
        out[shard_id] = (
            reg.all_ids(), [reg.tags_of(i) for i in range(len(reg))],
            {bs: tuple(col.tolist() for col in b.cols.view())
             for bs, b in sorted(shard.buffer.buckets.items())})
    return out


def _appends(db, writes):
    """(shard appends, fast ones) the writes should count: an append is
    fast where its rows share a block, every id is known and no series
    gets its tags from it."""
    block = db.namespace(NS).opts.block_size_ns
    known, untagged, appends, fast = set(), set(), 0, 0
    for rows, tagged in writes:
        by_shard = {}
        for r in rows:
            by_shard.setdefault(db.shard_set.lookup(r[0]), []).append(r)
        for part in by_shard.values():
            ids = {r[0] for r in part}
            appends += 1
            fast += (ids <= known and len({r[2] // block for r in part}) == 1
                     and not (tagged and ids & untagged))
        ids = {r[0] for r in rows}
        untagged = untagged - ids if tagged else untagged | (ids - known)
        known |= ids
    return appends, fast


def _one_block(node):
    rows = _rows(node.db, 2000, T0, per_shard=8)
    assert len(rows) == 64 * 8
    later = [(sid, tags, T0 + 10 * S, v + 0.5) for sid, tags, _t, v in rows]
    return [(rows, True), (later, True)]


def _straddling(node):
    node.now["t"] = BOUNDARY + 60 * S
    rows = _rows(node.db, 400, BOUNDARY - 30 * S)
    # alternate sides of the boundary inside every shard, twice over
    rows = [(sid, tags, BOUNDARY + (30 if i % 2 else -30) * S, v)
            for i, (sid, tags, _t, v) in enumerate(rows + rows)]
    return [(rows[:400], True), (rows[400:], True)]


def _known_and_first_seen(node):
    known = _rows(node.db, 300, T0)
    # a shard appends its known rows, then drains its first sightings:
    # known rows first in the batch is the arrival order that matches
    mixed = [(sid, tags, T0 + 10 * S, v) for sid, tags, _t, v in known] + \
        _rows(node.db, 200, T0 + 10 * S, name=b"mem")
    return [(known, True), (mixed, True)]


def _backfilled(node):
    rows = _rows(node.db, 300, T0)
    again = [(sid, tags, T0 + 10 * S, v) for sid, tags, _t, v in rows]
    third = [(sid, tags, T0 + 20 * S, v) for sid, tags, _t, v in rows]
    return [(rows[:120], False), (again, True), (third, True)]


@pytest.mark.parametrize("case", [_one_block, _straddling,
                                  _known_and_first_seen, _backfilled])
def test_a_routed_batch_stores_what_single_writes_store(tmp_path, case):
    batch = Node(tmp_path, "batch", shards=64)
    ref = Node(tmp_path, "ref", shards=64)
    try:
        c0 = _counters(APPENDS)
        for node, write in ((batch, _write_batch), (ref, _write_each)):
            writes = case(node)
            for rows, tagged in writes:
                write(node, rows, tagged)
        appends, fast = _appends(batch.db, writes)
        assert _moved(c0, APPENDS) == {APPENDS + "shard_appends": appends,
                                       APPENDS + "fast_appends": fast}
        assert 0 < fast < appends
        assert _stored(batch) == _stored(ref)
        assert any(buckets for _i, _t, buckets in _stored(batch).values())
        (entries, tags_b, raw_b), (rentries, tags_r, raw_r) = \
            batch.wal(), ref.wal()
        assert entries == rentries and len(entries) == sum(
            len(rows) for rows, _ in writes)
        assert tags_b == tags_r
        assert raw_b == raw_r       # the on-disk format, byte for byte
    finally:
        batch.close()
        ref.close()


def test_a_straddling_batch_fills_two_buckets_in_arrival_order(node):
    writes = _straddling(node)
    for rows, tagged in writes:
        _write_batch(node, rows, tagged)
    for _ids, _tags, buckets in _stored(node).values():
        assert len(buckets) <= 2
        for bs, (_sidx, ts, _vals) in buckets.items():
            assert set(ts) == {BOUNDARY + (30 if bs == BOUNDARY else -30) * S}


def test_a_batch_of_tagged_series_calls_ensure_tags_for_none(
        node, monkeypatch):
    from m3_tpu.storage.series import SeriesRegistry

    calls = []
    real = SeriesRegistry.ensure_tags
    monkeypatch.setattr(
        SeriesRegistry, "ensure_tags",
        lambda self, idx, tags: calls.append(idx) or real(self, idx, tags))
    rows = _rows(node.db, 200, T0)
    _write_batch(node, rows[:50], tagged=False)   # 50 known, untagged
    assert sum(sh.registry.untagged
               for sh in node.db.namespace(NS).shards.values()) == 50
    _write_batch(node, rows)                 # 150 first seen, 50 backfilled
    assert len(calls) == 50
    assert all(node.registry_tags(sid) == tags for sid, tags, _t, _v in rows)
    assert not any(sh.registry.untagged
                   for sh in node.db.namespace(NS).shards.values())
    del calls[:]
    _write_batch(node, [(sid, tags, T0 + 10 * S, v)
                        for sid, tags, _t, v in rows])
    assert calls == []


def test_sixteen_threads_backfilling_leave_the_untagged_count_exact(tmp_path):
    """The count behind "no series here is untagged" is read without a
    lock and moved by racing writers (tagged batches backfilling the
    same series, tagless batches creating new ones): a lost update
    would leave it off its column for good, and a count stuck at 0
    would stop every later backfill."""
    import sys
    import time

    n = Node(tmp_path, shards=2)
    rows = _rows(n.db, 600, T0)
    _write_batch(n, rows[:400], tagged=False)
    errors = []
    deadline = time.monotonic() + 20

    def work(k):
        try:
            for r in range(12):
                part = rows[(k * 25) % 300:][:150] if k % 4 else \
                    rows[400 + 12 * k + r:][:1]         # a new one, tagless
                _write_batch(n, [(sid, tags, T0 + (k * 12 + r) * 1_000_000, v)
                                 for sid, tags, _t, v in part],
                             tagged=bool(k % 4))
                for sh in n.db.namespace(NS).shards.values():
                    assert sh.registry.untagged >= 0
                if time.monotonic() > deadline:
                    raise TimeoutError
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    try:
        assert not errors and not any(th.is_alive() for th in threads)
        left = 0
        for sh in n.db.namespace(NS).shards.values():
            reg = sh.registry
            held = [reg.tags_of(i) for i in range(len(reg))]
            assert reg.untagged == held.count(None)
            left += reg.untagged
        # every series a tagged batch touched holds its tags
        assert all(n.registry_tags(sid) == tags
                   for sid, tags, _t, _v in rows[25:400])
        assert 0 < left
    finally:
        n.close()


@pytest.mark.parametrize("where", ["past", "future"])
def test_one_row_outside_the_window_refuses_the_batch_whole(node, where):
    opts = node.db.namespace(NS).opts
    rows = _rows(node.db, 200, T0)
    known = rows[:100]
    _write_batch(node, known)
    before, c0 = _stored(node), _counters(APPENDS)
    bad_t = (T0 - opts.buffer_past_ns - S if where == "past"
             else T0 + opts.buffer_future_ns + S)
    rows = [(sid, tags, T0 + 10 * S, v) for sid, tags, _t, v in rows]
    rows[150] = rows[150][:2] + (bad_t, 1.0)     # in the batch's last shards
    rows[3] = rows[3][:2] + (bad_t, 1.0)
    with pytest.raises(ValueError, match="^2 datapoints outside acceptance"):
        _write_batch(node, rows)
    assert _stored(node) == before               # no shard appended a row
    assert _moved(c0, APPENDS) == {}
    assert len(node.wal()[0]) == len(known)      # and none was logged


@pytest.mark.parametrize("k", [1, 2, 5, 8])
def test_a_shed_in_the_kth_shard_logs_the_rows_of_the_shards_before_it(
        node, monkeypatch, k):
    from m3_tpu.utils.limits import Backpressure

    rows = _rows(node.db, 120, T0)
    by_shard = {}
    for r in rows:
        by_shard.setdefault(node.db.shard_set.lookup(r[0]), []).append(r)
    assert len(by_shard) == 8
    kth = sorted(by_shard)[k - 1]

    def shed(*a, **kw):
        raise Backpressure("insert queue full")

    monkeypatch.setattr(node.db.namespace(NS).shards[kth], "write_batch", shed)
    c0 = _counters(APPENDS)
    with pytest.raises(Backpressure):
        _write_batch(node, rows)
    applied = [r for r in rows if node.db.shard_set.lookup(r[0]) < kth]
    for sid, _tags, t, v in rows:
        got_t, got_v = node.read(sid)
        want = node.db.shard_set.lookup(sid) < kth
        assert (got_t.tolist(), got_v.tolist()) == (([t], [v]) if want
                                                    else ([], []))
    assert _moved(c0, APPENDS) == (
        {APPENDS + "shard_appends": k - 1} if k > 1 else {})
    entries, wal_tags, _raw = node.wal()
    # in the request's own order, as an acknowledged batch is logged
    assert entries == [(NS, sid, t, v) for sid, _tags, t, v in applied]
    assert wal_tags == {sid: tags for sid, tags, _t, _v in applied}


def test_the_append_counters_move_once_a_batch_by_its_totals(
        node, monkeypatch):
    from m3_tpu.utils.instrument import Counter

    rows = _rows(node.db, 200, T0)
    incs = []
    real = Counter.inc
    monkeypatch.setattr(Counter, "inc",
                        lambda self, n=1: incs.append(n) or real(self, n))
    c0 = _counters(APPENDS)
    _write_batch(node, rows)                 # first sightings: none fast
    assert _moved(c0, APPENDS) == {APPENDS + "shard_appends": 8}
    c0 = _counters(APPENDS)
    del incs[:]
    _write_batch(node, [(sid, tags, T0 + 10 * S, v)
                        for sid, tags, _t, v in rows])
    assert _moved(c0, APPENDS) == {APPENDS + "shard_appends": 8,
                                   APPENDS + "fast_appends": 8}
    assert incs.count(8) == 2 and len(incs) <= 4   # not one a shard


# ------------------------------------------------ (d) routing by the memo


def test_the_request_path_never_hashes_a_batch_on_the_device_route(
        node, monkeypatch):
    calls = []
    real = hashing.hash_batch
    for mod in (hashing, sharding):
        monkeypatch.setattr(mod, "hash_batch",
                            lambda ids, seed=0: calls.append(len(ids))
                            or real(ids, seed))
    codec0, memo0, ing0 = (_counters("telemetry.codec."),
                           _counters("sharding.memo."),
                           _counters("coordinator.ingest.batch"))
    series = _scrape(60)
    node.post(series)                    # first sightings
    assert _moved(memo0, "sharding.memo.") == {"sharding.memo.misses": 60}
    memo1 = _counters("sharding.memo.")
    node.post(_scrape(60, t0_ms=T0 // 1_000_000 + 10_000))   # known series
    assert _moved(memo1, "sharding.memo.") == {"sharding.memo.hits": 60}
    assert calls == []
    assert not {k: v for k, v in _moved(codec0, "telemetry.codec.").items()
                if k.endswith("_hash")}
    assert _moved(ing0, "coordinator.ingest.batch") == {
        "coordinator.ingest.batches": 2,
        "coordinator.ingest.batch_samples": 120}
    # the bulk route is as it was: one hash_batch for the whole load
    ids = [_sid(tags) for tags, _ in series]
    node.db.write_batch(NS, ids, np.full(60, T0 + 30 * S), np.ones(60))
    assert calls == [60]


# ------------------------------------------------------- the memo itself


def _ids(n, seed, width=(1, 40)):
    rng = np.random.default_rng(seed)
    return [rng.bytes(int(rng.integers(*width))) for _ in range(n)]


@pytest.mark.parametrize("shards", [1, 64, 4096])
def test_lookup_memo_routes_like_lookup(shards):
    ss = ShardSet(shards)
    ids = _ids(300, shards) + [b"", b"x" * 256, b"y" * 257, b"z" * 1000]
    want = [ss.lookup(i) for i in ids]
    assert ss.lookup_memo(ids).tolist() == want          # all misses
    assert ss.lookup_memo(ids[::-1]).tolist() == want[::-1]   # hits
    assert ss.lookup_memo(ids[:5] + _ids(5, 99)).tolist()[:5] == want[:5]
    assert ss.lookup_memo([]).tolist() == []
    assert ss.lookup_memo(ids).dtype == ss.lookup_batch(ids).dtype


def test_lookup_memo_is_bounded_in_entries_and_key_size(monkeypatch):
    monkeypatch.setattr(sharding, "MEMO_MAX_ENTRIES", 100)
    ss = ShardSet(64)
    big = [b"k" * 300 + b"%d" % i for i in range(10)]
    ss.lookup_memo(big)
    assert len(ss._memo) == 0            # oversize ids are never kept
    a, b, bulk = _ids(80, 1), _ids(80, 2), _ids(101, 3, width=(8, 40))
    ss.lookup_memo(a)
    assert len(ss._memo) == 80
    ss.lookup_memo(b)                    # would pass the bound: flushed whole
    assert set(ss._memo) == set(b)
    ss.lookup_memo(bulk)                 # larger than the memo: passes by
    assert set(ss._memo) == set(b)
    assert ss.lookup_memo(a + big).tolist() == [ss.lookup(i) for i in a + big]


def test_lookup_memo_counts_hits_and_misses():
    ss = ShardSet(16)
    ids = _ids(50, 7, width=(4, 30))
    c0 = _counters("sharding.memo.")
    ss.lookup_memo(ids)
    ss.lookup_memo(ids + _ids(10, 8, width=(31, 40)))
    assert _moved(c0, "sharding.memo.") == {"sharding.memo.misses": 60,
                                           "sharding.memo.hits": 50}


@pytest.mark.parametrize("seed", [0, 7, 0xDEADBEEF])
def test_hash_batch_host_is_murmur3(seed):
    ids = _ids(200, seed or 1, width=(0, 70))
    assert hashing.hash_batch_host(ids, seed).tolist() == [
        hashing.murmur3_32(i, seed) for i in ids]
    assert hashing.hash_batch_host([], seed).tolist() == []


# --------------------------------------------------------- the label memo
#
# The remote-write decode's memo (promremote.LabelMemo, the writer's
# `label_memo`): a series' label bytes -> (tags, series id), so a request
# of known series decodes no label and encodes no id.


MEMO = "coordinator.remote_write.label_memo."


def _request(hosts, first=0, t_ms=T0 // 1_000_000):
    return pr.encode_write_request(_scrape(hosts, 1, t_ms, first=first))


def _full(data):
    return [pr._decode_timeseries(v)
            for f, wt, v in pr._fields(memoryview(data)) if f == 1 and wt == 2]


def test_the_label_memo_is_bounded_and_stays_right_past_its_bound(monkeypatch):
    memo = pr.LabelMemo(_sid, max_entries=64)
    c0 = _counters(MEMO)
    sizes = []
    for first in range(0, 200, 40):          # 200 series through 64 places
        data = _request(40, first)
        series, ids = pr.decode_write_request(data, memo)
        assert series == _full(data) and ids == [_sid(t) for t, _ in series]
        sizes.append(len(memo))
    # full at 64, a first sighting drops the oldest eighth and goes in
    assert sizes == [40, 64, 64, 64, 64] and max(sizes) <= 64
    assert _moved(c0, MEMO) == {MEMO + "misses": 200}
    c0 = _counters(MEMO)
    newest, oldest = _request(40, 160), _request(40, 0)
    assert pr.decode_write_request(newest, memo)[0] == _full(newest)
    assert _moved(c0, MEMO) == {MEMO + "hits": 40}
    c0 = _counters(MEMO)
    assert pr.decode_write_request(oldest, memo)[0] == _full(oldest)
    assert _moved(c0, MEMO) == {MEMO + "misses": 40}     # they had gone
    assert len(memo) <= 64
    # a label block past the key bound is decoded every time, never kept
    monkeypatch.setattr(pr, "LABEL_MEMO_MAX_KEY", 30)
    memo, c0 = pr.LabelMemo(_sid), _counters(MEMO)
    for _ in range(2):
        assert pr.decode_write_request(newest, memo)[0] == _full(newest)
    assert len(memo) == 0
    assert _moved(c0, MEMO) == {MEMO + "misses": 80}


def test_label_blocks_one_byte_apart_are_two_series():
    a = {b"__name__": b"cpu", b"host": b"h001"}
    b = {b"__name__": b"cpu", b"host": b"h002"}
    c = {b"__name__": b"cpv", b"host": b"h001"}
    memo = pr.LabelMemo(_sid)
    data = pr.encode_write_request([(t, [(1, 1.0)]) for t in (a, b, c, a)])
    for _ in range(2):                        # first sightings, then hits
        series, ids = pr.decode_write_request(data, memo)
        assert [t for t, _ in series] == [a, b, c, a]
        assert ids == [_sid(a), _sid(b), _sid(c), _sid(a)]
        assert len(set(ids)) == 3 and len(memo) == 3


def test_a_request_with_ids_stores_what_one_without_stores(tmp_path):
    """The handler hands the memo's ids to the writer; a writer left to
    make them itself stores the same rows, log entries and answer."""
    given, made = Node(tmp_path, "given"), Node(tmp_path, "made")
    seen = []
    inner = made.coord.writer.write_batch

    def without_ids(rows, series_ids=None, **kw):
        seen.append(series_ids)
        return inner(rows, **kw)

    made.coord.writer.write_batch = without_ids
    try:
        for r in range(3):                   # first sightings, then hits
            series = _scrape(60, 2, T0 // 1_000_000 + 20_000 * r)
            assert given.post(series) == made.post(series) == (
                200, b'{"status": "success", "wrote": 120}')
        assert len(seen) == 3 and all(
            ids == [_sid(t) for t, smp in _scrape(60, 2) for _ in smp]
            for ids in seen)
        for tags, _ in series:
            sid = _sid(tags)
            (t, v), (mt, mv) = given.read(sid), made.read(sid)
            assert t.tolist() == mt.tolist() and len(t) == 6
            assert v.tolist() == mv.tolist()
            assert given.registry_tags(sid) == made.registry_tags(sid) == tags
        assert given.wal() == made.wal()      # entries, tags, bytes
    finally:
        given.close()
        made.close()


def test_sixteen_threads_on_a_memo_too_small_for_them_all_decode_right():
    """Probes are lock-free while another thread makes room: with more
    threads than cores, a short switch interval and a memo a quarter of
    the series' number, every decode is still the full decoder's and
    the bound holds at every look."""
    import sys
    import time

    memo = pr.LabelMemo(_sid, max_entries=32)
    requests = [_request(24, first) for first in range(0, 120, 8)]
    want = [(_full(d), [_sid(t) for t, _ in _full(d)]) for d in requests]
    errors, sizes = [], []
    deadline = time.monotonic() + 20

    def work(k):
        try:
            for i in range(60):
                j = (k + i) % len(requests)
                assert pr.decode_write_request(requests[j], memo) == want[j]
                sizes.append(len(memo))
                if time.monotonic() > deadline:
                    raise TimeoutError
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(th.is_alive() for th in threads)
    assert len(sizes) == 16 * 60 and 0 < max(sizes) <= 32


def test_eight_senders_of_overlapping_groups_leave_each_series_its_tags(node):
    """Eight handler threads read and fill one memo; groups overlap, so
    the same label block is a first sighting on several threads at
    once. Every series reads back whole, under its own tags, and every
    entry of the memo is still what the full decoder makes of its key:
    nothing downstream wrote to a dict it shares."""
    senders, rounds, hosts, stride = 8, 5, 40, 15
    errors = []
    c0 = _counters(MEMO)

    def send(s):
        try:
            for r in range(rounds):
                status, _ = node.post(_scrape(
                    hosts, 1, T0 // 1_000_000 + 10_000 * r, first=s * stride))
                assert status == 200
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    node.now["t"] = T0 + 60 * S
    threads = [threading.Thread(target=send, args=(s,))
               for s in range(senders)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    everyone = _scrape(stride * (senders - 1) + hosts, rounds)
    for tags, samples in everyone:
        sid = _sid(tags)
        t, v = node.read(sid)
        assert dict(zip(t.tolist(), v.tolist())) == {
            t_ms * 1_000_000: samples[0][1] for t_ms, _ in samples}
        assert node.registry_tags(sid) == tags
    memo = node.coord.writer.label_memo
    assert len(memo) == len(everyone)
    for block, (tags, sid) in memo._entries.items():
        assert pr._decode_timeseries(memoryview(block)) == (tags, [])
        assert sid == _sid(tags)
    moved = _moved(c0, MEMO)
    assert moved[MEMO + "hits"] + moved[MEMO + "misses"] == \
        senders * rounds * hosts
    assert moved[MEMO + "misses"] >= len(everyone)
    assert len(node.wal()[0]) == senders * rounds * hosts


# ----------------------------------------- the downsample leg asks first


def test_the_downsampler_reads_no_row_while_no_rule_set_is_installed():
    from m3_tpu.cluster import kv as cluster_kv
    from m3_tpu.coordinator.downsample import Downsampler
    from m3_tpu.metrics.matcher import Matcher, RuleSetStore

    matcher = Matcher(RuleSetStore(cluster_kv.MemStore()), b"default")
    assert not matcher.has_rules()
    ds = Downsampler(matcher, lambda *a: None)

    def rows():
        raise AssertionError("a row was read")
        yield

    assert ds.write_batch(rows()) == (0, 0)


def test_a_write_signature_carries_no_accumulator():
    import inspect

    from m3_tpu.coordinator.ingest import DownsamplerAndWriter
    from m3_tpu.query import remote, storage
    from m3_tpu.storage.namespace import Namespace
    from m3_tpu.storage.shard import Shard

    for fn in (DownsamplerAndWriter.write, storage.LocalStorage.write,
               storage.SessionStorage.write, storage.FanoutStorage.write,
               remote.RemoteStorage.write, Database.write, Namespace.write,
               Shard.write):
        assert "acc" not in inspect.signature(fn).parameters, fn
