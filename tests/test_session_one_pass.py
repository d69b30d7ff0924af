"""The session builds a fetch's points in one pass over its responders
(ISSUE 35): one decode dispatch a geometry for the whole fetch and one
merge a series. What it gives equals the per-responder fold it replaced
(kept here as the reference: a decode a tile, a merge a series a frame,
one more a series for every later frame) bit for bit under LAST_PUSHED,
HIGHEST_VALUE and LOWEST_VALUE; HIGHEST_FREQUENCY_VALUE, a vote, keeps
the fold; three responders of one geometry are ONE dispatch, on the
session's device scope; and a geometry's first decode leaves every
bucket a later stack can need compiled."""

import jax
import numpy as np
import pytest

from m3_tpu.client import ConflictStrategy, Session, SessionOptions
from m3_tpu.client.decode import merge_replica_points
from m3_tpu.client.session import _ReadCosts
from m3_tpu.cluster.topology import ReadConsistencyLevel
from m3_tpu.index import query as iq
from m3_tpu.ops import decode_rows as rows_mod
from m3_tpu.ops.decode_rows import decode_rows
from m3_tpu.parallel import scope as dscope, telemetry
from m3_tpu.rpc import wire
from m3_tpu.storage.block import encode_block
from m3_tpu.testing import ClusterHarness
from m3_tpu.utils import instrument, xtime

NS = b"default"
S = xtime.SECOND
STEP = 10 * S
BLOCK = 16 * STEP
T0 = 1_600_000_000 * S - (1_600_000_000 * S) % BLOCK
ONE_PASS = [ConflictStrategy.LAST_PUSHED, ConflictStrategy.HIGHEST_VALUE,
            ConflictStrategy.LOWEST_VALUE]


def session_with(strategy):
    session = Session.__new__(Session)
    session.opts = SessionOptions(conflict_strategy=strategy)
    return session


def tile_of(bs, rows, points, window):
    """A frame's tile of `rows` (positions in its series list) over the
    sealed block at `bs`; `points` a (t, v) a row, at most `window`."""
    ts = np.zeros((len(rows), window), np.int64)
    vals = np.zeros((len(rows), window), np.float64)
    npoints = np.array([len(t) for t, _ in points], np.int32)
    for i, (t, v) in enumerate(points):
        ts[i, :len(t)], vals[i, :len(t)] = t, v
        ts[i, len(t):], vals[i, len(t):] = t[-1], v[-1]
    blk = encode_block(bs, np.arange(len(rows), dtype=np.int32), ts, vals,
                       npoints)
    return {"bs": bs, "rows": np.asarray(rows, np.int32), "words": blk.words,
            "nbits": blk.nbits, "npoints": blk.npoints,
            "window": blk.window, "time_unit": int(blk.time_unit)}


def tile_planes(tile):
    """A tile's rows decoded by themselves: (ts [rows, window], vals)."""
    return decode_rows(tile["words"], tile["npoints"], tile["window"],
                       xtime.Unit(tile["time_unit"]).nanos)[:2]


def replica_frame(rng, ids, tags, windows, no_tiles=False):
    """One replica's frame over `ids` (this replica's own order): two
    sealed blocks, each series holding a seeded part of the grid with
    values the replicas disagree on in places, the first block cut into
    two tiles; a buffer sidecar over the open block that also reaches
    back into the second sealed block (timestamps twice in one frame)."""
    tiles = []
    for b, window in enumerate(windows):
        bs = T0 + b * BLOCK
        rows, points = [], []
        for pos in range(len(ids)):
            keep = np.flatnonzero(rng.random(window) < 0.7)
            if not len(keep) or rng.random() < 0.15:
                continue        # the series holds nothing of this block
            rows.append(pos)
            vals = rng.integers(0, 4, len(keep)).astype(np.float64)
            if rng.random() < 0.3:
                vals += 0.25 * rng.integers(0, 4, len(keep))
            points.append((bs + keep * STEP, vals))
        cut = len(rows) // 2 if b == 0 else 0
        for part in (slice(0, cut), slice(cut, None)):
            if rows[part]:
                tiles.append(tile_of(bs, rows[part], points[part], window))
    offs, bt, bv = [0], [], []
    for pos in range(len(ids)):
        grid = T0 + BLOCK + np.arange(8, 24) * STEP
        t = grid[rng.random(len(grid)) < 0.5]
        bt.append(t)
        bv.append(rng.integers(0, 4, len(t)).astype(np.float64))
        offs.append(offs[-1] + len(t))
    return {"series": [{"id": sid, "tags": tags.get(sid, {})} for sid in ids],
            "tiles": [] if no_tiles else tiles,
            "bufs": {"offs": np.asarray(offs, np.int64),
                     "t": np.concatenate(bt), "v": np.concatenate(bv)}}


def frames_of(seed, n_frames, case):
    rng = np.random.default_rng(seed)
    ids = [b"s-%02d" % i for i in range(12)]
    tags = {sid: {b"__name__": b"m", b"id": sid} for sid in ids}
    windows = (16, 8) if case == "two-geometries" else (16, 16)
    frames = []
    for f in range(n_frames):
        mine = list(ids)
        if case == "a-series-missing" and f != 1:
            mine = [sid for sid in ids if sid != ids[3 + f]]
        if case == "another-order":
            mine = [mine[i] for i in rng.permutation(len(mine))]
        held = tags
        if case == "tags-on-a-later-frame" and f == 0:
            held = {sid: tg for sid, tg in tags.items() if sid > b"s-05"}
        frames.append(replica_frame(
            rng, mine, held, windows,
            no_tiles=(case == "a-frame-without-tiles" and f == 0)))
    if case == "disjoint":      # every replica holds a time of its own
        frames = [shifted(r, 4 * f * BLOCK) for f, r in enumerate(frames)]
    return [wire.decode(wire.encode(r)) for r in frames]


def sealed_points(frame):
    """A frame's sealed points a position: [(t, v), ...] in tile order."""
    out = [[] for _ in frame["series"]]
    for tile in frame["tiles"]:
        ts, vs = tile_planes(tile)
        for j, (pos, k) in enumerate(zip(tile["rows"].tolist(),
                                         tile["npoints"].tolist())):
            out[pos].append((ts[j, :k], vs[j, :k]))
    return out


def shifted(frame, by):
    """The frame with every point of it `by` later (tiles re-encoded)."""
    tiles = []
    for tile in frame["tiles"]:
        ts, vs = tile_planes(tile)
        tiles.append(tile_of(
            tile["bs"] + by, tile["rows"],
            [(ts[j, :k] + by, vs[j, :k])
             for j, k in enumerate(tile["npoints"].tolist())],
            tile["window"]))
    return dict(frame, tiles=tiles,
                bufs=dict(frame["bufs"], t=frame["bufs"]["t"] + by))


def fold(frames, strategy):
    """What the session did before: a responder at a time, a decode a
    tile, a merge a series, then a merge into the earlier responders'."""
    merged = {}
    for r in frames:
        n = len(r["series"])
        parts_t = [[] for _ in range(n)]
        parts_v = [[] for _ in range(n)]
        for tile in sorted(r["tiles"], key=lambda d: d["bs"]):
            ts, vs = tile_planes(tile)
            for j, (pos, k) in enumerate(zip(tile["rows"].tolist(),
                                             tile["npoints"].tolist())):
                parts_t[pos].append(ts[j, :k])
                parts_v[pos].append(vs[j, :k])
        offs = r["bufs"]["offs"].tolist()
        for j in range(n):
            parts_t[j].append(r["bufs"]["t"][offs[j]:offs[j + 1]])
            parts_v[j].append(r["bufs"]["v"][offs[j]:offs[j + 1]])
        for j, entry in enumerate(r["series"]):
            t, v = merge_replica_points(parts_t[j], parts_v[j], strategy)
            cur = merged.get(entry["id"])
            if cur is None:
                merged[entry["id"]] = {"tags": entry["tags"], "t": t, "v": v}
                continue
            if not cur["tags"] and entry["tags"]:
                cur["tags"] = entry["tags"]
            cur["t"], cur["v"] = merge_replica_points(
                [cur["t"], t], [cur["v"], v], strategy)
    return merged


def assert_same_points(got, want):
    assert list(got) == list(want) and len(want)
    for sid in want:
        assert got[sid]["tags"] == want[sid]["tags"], sid
        np.testing.assert_array_equal(got[sid]["t"], want[sid]["t"])
        assert got[sid]["v"].tobytes() == want[sid]["v"].tobytes(), sid


CASES = ["overlapping", "disjoint", "a-series-missing", "another-order",
         "a-frame-without-tiles", "tags-on-a-later-frame", "two-geometries"]


@pytest.mark.parametrize("strategy", ONE_PASS, ids=lambda s: s.value)
@pytest.mark.parametrize("n_frames", [2, 3])
@pytest.mark.parametrize("case", CASES)
def test_one_pass_equals_the_per_responder_fold(case, n_frames, strategy):
    for seed in (1, 2, 3):
        frames = frames_of(seed, n_frames, case)
        acc = _ReadCosts()
        got = session_with(strategy)._merged_points(frames, acc)
        assert_same_points(got, fold(frames, strategy))
        geometries = {(t["window"], t["time_unit"], t["words"].shape[-1])
                      for r in frames for t in r["tiles"]}
        assert acc.decode_n == len(geometries) == (
            2 if case == "two-geometries" else 1)
        if case == "tags-on-a-later-frame":
            assert not frames[0]["series"][0]["tags"] and got[b"s-00"]["tags"]


def test_the_cases_hold_what_they_are_named_for():
    frames = frames_of(1, 3, "overlapping")
    last, high, low = (fold(frames, st) for st in ONE_PASS)
    # a timestamp twice in one frame: in a sealed block and in the buffer
    one, offs = frames[0], frames[0]["bufs"]["offs"].tolist()
    assert any(
        set(t.tolist()) & set(one["bufs"]["t"][offs[pos]:offs[pos + 1]].tolist())
        for pos, parts in enumerate(sealed_points(one)) for t, _v in parts)
    # replicas that disagree, and an answer that depends on their order
    assert any(high[sid]["v"].tobytes() != low[sid]["v"].tobytes()
               for sid in last)
    again = fold(frames[::-1], ConflictStrategy.LAST_PUSHED)
    assert any(again[sid]["v"].tobytes() != last[sid]["v"].tobytes()
               for sid in last)
    missing = frames_of(1, 3, "a-series-missing")
    assert len({tuple(e["id"] for e in r["series"]) for r in missing}) == 3
    assert not frames_of(1, 2, "a-frame-without-tiles")[0]["tiles"]
    spans = [(r["bufs"]["t"].min() - 2 * BLOCK, r["bufs"]["t"].max())
             for r in frames_of(1, 3, "disjoint")]
    assert all(hi < lo for (_, hi), (lo, _) in zip(spans, spans[1:]))


@pytest.mark.parametrize("n_frames", [1, 2, 3])
def test_a_vote_keeps_the_per_responder_fold(n_frames):
    strategy = ConflictStrategy.HIGHEST_FREQUENCY_VALUE
    for case in ("overlapping", "a-series-missing"):
        frames = frames_of(5, n_frames, case)
        got = session_with(strategy)._merged_points(frames, _ReadCosts())
        assert_same_points(got, fold(frames, strategy))


def test_a_fold_of_votes_is_not_the_vote_of_all():
    """Replicas read 1, 1, 2 at one timestamp: the fold merges the first
    two to 1, then ties 1 against 2 and the last pushed wins; one vote
    over all three parts would say 1. The session answers as it did."""
    t = np.array([T0], np.int64)
    frames = [wire.decode(wire.encode({
        "series": [{"id": b"a", "tags": {b"k": b"v"}}], "tiles": [],
        "bufs": {"offs": np.array([0, 1]), "t": t, "v": np.array([v])}}))
        for v in (1.0, 1.0, 2.0)]
    strategy = ConflictStrategy.HIGHEST_FREQUENCY_VALUE
    got = session_with(strategy)._merged_points(frames, _ReadCosts())
    assert got[b"a"]["v"].tolist() == [2.0]
    assert merge_replica_points([t] * 3, [r["bufs"]["v"] for r in frames],
                                strategy)[1].tolist() == [1.0]


def decode_counters():
    return {k: v for k, v in instrument.ROOT.snapshot().items()
            if k.startswith(("client.decode_tile.dispatches{",
                             "client.fetch_tagged."))}


def test_three_responders_of_one_geometry_are_one_dispatch_on_the_scope():
    cluster = ClusterHarness(n_nodes=3, replica_factor=3, num_shards=8)
    session = Session(cluster.topology, SessionOptions(
        read_consistency=ReadConsistencyLevel.ALL, timeout_s=10))
    try:
        now = cluster.clock.now_ns
        ids = [b"one-pass-%d" % i for i in range(6)]
        for k in range(20):
            session.write_batch(
                NS, ids, [now - k * S] * len(ids),
                np.arange(len(ids), dtype=np.float64) + k,
                [{b"app": b"one-pass", b"i": sid} for sid in ids])
        session.drain()
        cluster.clock.advance(2 * xtime.HOUR + 11 * xtime.MINUTE)
        cluster.tick_all()
        before = decode_counters()
        mine = dscope.DeviceScope([5], "coordinator")
        with mine:
            got = session.fetch_tagged(NS, iq.new_term(b"app", b"one-pass"),
                                       now - xtime.HOUR, now + xtime.MINUTE)
    finally:
        session.close()
        cluster.close()
    assert sorted(got) == ids
    for i, sid in enumerate(ids):
        assert got[sid]["v"].tolist() == [float(i + k)
                                          for k in reversed(range(20))]
    moved = {k: v - before.get(k, 0) for k, v in decode_counters().items()
             if v != before.get(k, 0)}
    assert moved.pop("client.fetch_tagged.bytes_in") > 0
    assert moved == {
        "client.fetch_tagged.replicas_merged": 3,
        "client.fetch_tagged.decode_dispatches": 1,
        "client.decode_tile.dispatches{device=%d}" % jax.devices()[5].id: 1}


def test_a_traced_three_replica_fetch_carries_each_frames_gathers():
    """What a session recovers from three replicas' frames is what was
    written, bit for bit, and every replica's detailed span says how many
    array operations its tiles took: four a tile."""
    from m3_tpu.utils import tracing

    cluster = ClusterHarness(n_nodes=3, replica_factor=3, num_shards=8)
    session = Session(cluster.topology, SessionOptions(
        read_consistency=ReadConsistencyLevel.ALL, timeout_s=10))
    try:
        now = cluster.clock.now_ns
        ids = [b"gathers-%02d" % i for i in range(12)]
        ts = [now - k * S for k in reversed(range(20))]
        for k, t in enumerate(ts):
            session.write_batch(
                NS, ids, [t] * len(ids),
                np.arange(len(ids), dtype=np.float64) * 0.1 + k,
                [{b"app": b"gathers", b"i": sid} for sid in ids])
        session.drain()
        cluster.clock.advance(2 * xtime.HOUR + 11 * xtime.MINUTE)
        cluster.tick_all()
        with tracing.TRACER.span("test.query") as root:
            got = session.fetch_tagged(NS, iq.new_term(b"app", b"gathers"),
                                       now - xtime.HOUR, now + xtime.MINUTE)
    finally:
        session.close()
        cluster.close()
    assert sorted(got) == ids
    for i, sid in enumerate(ids):
        assert got[sid]["t"].tobytes() == np.array(ts, np.int64).tobytes()
        assert got[sid]["v"].tobytes() == (
            np.float64(i) * 0.1 + np.arange(20, dtype=np.float64)).tobytes()
    client = root.to_dict()["children"][0]
    grafts = [c for c in client.get("children", [])
              if c.get("name") == "rpc.fetch_tagged"]
    assert len(grafts) == 3
    for g in grafts:
        # 12 series over 8 shards: more pieces than tiles
        assert g["costs"]["tiles_n"] >= 1
        assert g["costs"]["tile_gathers_n"] == 4 * g["costs"]["tiles_n"]


def test_a_geometrys_first_decode_compiles_every_bucket_a_stack_can_reach(
        monkeypatch):
    """On an accelerator (steered here: the CPU compiles a shape where it
    meets it) the first stacked decode of a geometry on a scope brings
    the power-of-two buckets up to the row bound through their compile;
    after it no stack of one, two or three responders' rows, nor one
    past the bound, compiles a program."""
    monkeypatch.setattr(rows_mod, "_compiles_are_dear", lambda: True)
    seen = []
    real = telemetry.record_bucket
    monkeypatch.setattr(
        telemetry, "record_bucket",
        lambda path, key: (seen.append((path, key)), real(path, key))[1])
    rng = np.random.default_rng(35)
    tile = tile_of(T0, [0, 1, 2], [
        (T0 + np.arange(4) * STEP, rng.integers(0, 9, 4).astype(np.float64))
        for _ in range(3)], 4)
    args = (tile["window"], xtime.Unit(tile["time_unit"]).nanos)
    width = tile["words"].shape[-1]
    compiles = []

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    with dscope.DeviceScope([6], "warm-test"):
        ts, vs, calls = decode_rows(
            tile["words"], tile["npoints"], *args)
        assert calls == 1 and len(ts) == 3
        buckets = [key[0] for path, key in seen
                   if path == "block.decode_plane"
                   and key[1:] == (width, tile["window"])]
        assert buckets == [8, 16, 32, 64, 128, 256, 512, 1024, 8]
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for rows in (1, 8, 9, 24, 72, 240, 480, 720, 1024, 1025, 2100):
                at = rng.integers(0, 3, rows)
                got_t, got_v, calls = decode_rows(
                    tile["words"][at], tile["npoints"][at], *args)
                assert calls == -(-rows // rows_mod.ROW_BUCKETS[-1])
                np.testing.assert_array_equal(got_t, ts[at])
                assert got_v.tobytes() == vs[at].tobytes()
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)
        assert not compiles
        # warmed once a geometry a scope: the next decode warms nothing
        del seen[:]
        decode_rows(tile["words"], tile["npoints"], *args)
        assert [key[0] for _path, key in seen] == [8]
    # another scope's device has programs of its own to compile
    monkeypatch.setattr(rows_mod, "ROW_BUCKETS", (8, 16))
    with dscope.DeviceScope([7], "another-scope"):
        del seen[:]
        decode_rows(tile["words"], tile["npoints"], *args)
        assert [key[0] for _path, key in seen] == [8, 16, 8]
