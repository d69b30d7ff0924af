"""The frame `NodeService.rpc_fetch_tagged` builds in one pass over the
query's ids: what the client recovers from it equals a per-series
`db.read`; it carries one tile a (block start, window, unit, width),
cut at the row bound; a seal between two buffer chunks hides no point;
the bytes-read limit and the deadline stop it inside the frame;
`rpc_query` returns the identity sweep alone; and the tiles
`gather_tiles` makes of a frame's pieces equal a plain gather (a fancy
take a piece a column, concatenated) in value, dtype, shape and
contiguity, charged tile by tile before they exist, with a number of
array operations a tile that does not grow with its pieces."""

import numpy as np
import pytest

from m3_tpu.client.session import Session, SessionOptions, _ReadCosts
from m3_tpu.index import query as iq
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.rpc import node_server, wire
from m3_tpu.rpc.node_server import NodeService
from m3_tpu.storage.block import SealedBlock
from m3_tpu.storage.database import Database
from m3_tpu.storage import tiles
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.utils import limits as xlimits
from m3_tpu.utils import tracing, xtime
from m3_tpu.utils.limits import LimitOptions, QueryLimits, ResourceExhausted
from m3_tpu.utils.retry import Deadline, DeadlineExceeded

NS = b"frame"
BLOCK = 10 * xtime.MINUTE
STEP = 30 * xtime.SECOND
T0 = 1_600_000_000 * 10**9
T0 -= T0 % BLOCK
GHOST = b"ghost"            # in the index, never written here
SHORT_SHARD = 3             # its series hold 5 points of block 0: window 8


class Node:
    """One dbnode: `num_shards` shards, blocks 0 and 1 sealed, block 2
    open (unless `seal_open_later`: then block 1 is the open one, already
    past its seal time, and no tick has run since). Series `late-*` are
    first written in block 1, so block 0 does not hold them; `s-00`, the
    first index of its shard, skips block 1, whose rows are therefore
    not its shard's indices in a row."""

    def __init__(self, num_shards=4, n_series=24, seal_open_later=False):
        self.now = T0
        self.db = Database(ShardSet(num_shards), clock=lambda: self.now)
        self.db.mark_bootstrapped()
        opts = NamespaceOptions(block_size_ns=BLOCK, buffer_past_ns=xtime.MINUTE,
                                writes_to_commitlog=False)
        self.db.create_namespace(NS, opts, index=NamespaceIndex(
            opts.index_block_size_ns, clock=lambda: self.now))
        self.ids = [b"s-%02d" % i for i in range(n_series)] \
            + [b"late-%d" % i for i in range(n_series // 4)]
        self.tags = {sid: {b"__name__": b"m", b"id": sid,
                           b"kind": sid.split(b"-")[0]} for sid in self.ids}
        shard_of = self.db.shard_set.lookup
        rng = np.random.default_rng(33)
        for k in range(2 * BLOCK // STEP + (0 if seal_open_later else 4)):
            t = T0 + k * STEP
            self.now = t
            rows = [sid for sid in self.ids
                    if not (t < T0 + BLOCK and (
                        sid.startswith(b"late")
                        or (num_shards > SHORT_SHARD
                            and shard_of(sid) == SHORT_SHARD and k >= 5)))
                    and not (sid == b"s-00" and T0 + BLOCK <= t < T0 + 2 * BLOCK)]
            self.db.write_batch(
                NS, rows, np.full(len(rows), t, np.int64),
                rng.integers(0, 1000, len(rows)).astype(np.float64),
                [self.tags[sid] for sid in rows])
            if t == T0 + BLOCK + xtime.MINUTE or (
                    t == T0 + 2 * BLOCK + xtime.MINUTE):
                self.db.tick(t)
        self.end = self.now + STEP
        if seal_open_later:
            self.now = T0 + 2 * BLOCK + xtime.MINUTE
        self.nsobj = self.db.namespace(NS)
        self.nsobj.index.insert(GHOST, {b"__name__": b"m", b"kind": b"ghost"}, T0)
        self.svc = NodeService(self.db)

    def args(self, query=None, start=T0, end=None):
        return {"ns": NS, "query": wire.query_to_wire(query or iq.AllQuery()),
                "start_ns": start, "end_ns": end or self.end}

    def frame(self, **kw):
        return self.svc.dispatch("fetch_tagged", self.args(**kw))

    def shard_idx(self, sid):
        shard = self.nsobj.shards[self.db.shard_set.lookup(sid)]
        return shard, shard.registry.get(sid)


@pytest.fixture(scope="module")
def node():
    return Node()


def client_points(frame):
    """What a session recovers from one host's frame, over the wire."""
    session = Session.__new__(Session)
    session.opts = SessionOptions()
    frame = wire.decode(wire.encode(frame))
    merged = session._merged_points([frame], _ReadCosts())
    return {sid: (e["t"], e["v"]) for sid, e in merged.items()}


QUERIES = {
    "all": (iq.AllQuery(), 0, 3 * BLOCK),
    "one-kind": (iq.new_term(b"kind", b"late"), 0, 3 * BLOCK),
    "one-series": (iq.new_term(b"id", b"s-07"), 0, 3 * BLOCK),
    "block-0-only": (iq.AllQuery(), 0, BLOCK),
    "inside-block-1": (iq.AllQuery(), BLOCK + 3 * STEP, 2 * BLOCK - 2 * STEP),
    "open-buffer-only": (iq.AllQuery(), 2 * BLOCK, 3 * BLOCK),
}


@pytest.mark.parametrize("name", QUERIES)
def test_frame_equals_per_series_reads(node, name):
    q, lo, hi = QUERIES[name]
    got = client_points(node.frame(query=q, start=T0 + lo, end=T0 + hi))
    want = node.db.query_ids(NS, q, T0 + lo, T0 + hi)
    assert list(got) == list(want) and len(want)
    for sid in want:
        t, v = got[sid]
        if sid == GHOST:
            assert not len(t) and not len(v)
            continue
        rt, rv = node.db.read(NS, sid, T0 + lo, T0 + hi)
        keep = (t >= T0 + lo) & (t < T0 + hi)  # a tile row is a whole block
        np.testing.assert_array_equal(t[keep], rt)
        assert v[keep].tobytes() == np.asarray(rv, np.float64).tobytes()


def test_the_fixture_holds_what_the_frame_must_cope_with(node):
    assert len(node.nsobj.shards) >= 4
    starts = {bs for sh in node.nsobj.shards.values() for bs in sh.blocks}
    assert starts == {T0, T0 + BLOCK}
    windows = {sh.blocks[T0].window for sh in node.nsobj.shards.values()}
    assert len(windows) == 2
    shard, idx = node.shard_idx(b"late-0")
    assert shard.blocks[T0].row_of(idx) is None
    assert shard.blocks[T0 + BLOCK].row_of(idx) is not None
    shard, idx = node.shard_idx(b"s-00")
    held = shard.blocks[T0 + BLOCK].series_indices
    assert idx == 0 and held[0] == 1 and held[-1] == len(held)
    assert all(len(sh.buffer.buckets) for sh in node.nsobj.shards.values())


def held_positions(node, frame, bs):
    return sorted(
        pos for pos, e in enumerate(frame["series"]) if e["id"] != GHOST
        for shard, idx in [node.shard_idx(e["id"])]
        if shard.blocks[bs].row_of(idx) is not None)


@pytest.mark.parametrize("bound", [None, 5, 1])
def test_one_tile_a_block_start_and_geometry_cut_at_the_row_bound(
        node, monkeypatch, bound):
    if bound is not None:
        monkeypatch.setattr(node_server, "TILE_MAX_ROWS", bound)
    bound = bound or node_server.TILE_MAX_ROWS
    frame = node.frame()
    by_key = {}
    for tile in frame["tiles"]:
        n = len(tile["rows"])
        assert 0 < n <= bound
        assert tile["words"].shape[0] == n == len(tile["nbits"]) \
            == len(tile["npoints"])
        assert tile["rows"].dtype == tile["nbits"].dtype \
            == tile["npoints"].dtype == np.int32
        by_key.setdefault((tile["bs"], tile["window"], tile["time_unit"],
                           tile["words"].shape[1]), []).append(n)
    # block 0 in two windows (the short shard's and the others'), block 1 in one
    assert sorted(k[:2] for k in by_key) == [(T0, 8), (T0, 32), (T0 + BLOCK, 32)]
    for sizes in by_key.values():  # every tile of a key full, but its last
        assert all(n == bound for n in sizes[:-1])
    for bs in (T0, T0 + BLOCK):
        rows = np.concatenate(
            [t["rows"] for t in frame["tiles"] if t["bs"] == bs]).tolist()
        assert sorted(rows) == held_positions(node, frame, bs)  # each once


def test_the_span_and_the_counters_carry_the_frame(node):
    from m3_tpu.utils import tracing
    from m3_tpu.utils.instrument import ROOT

    before = ROOT.counter("rpc.fetch_tagged.tiles").value()
    frame, sp = node.svc.dispatch_traced(
        "fetch_tagged", node.args(), trace_ctx=tracing.SpanContext(33, 1))
    costs = sp["costs"]
    assert costs["tiles_n"] == len(frame["tiles"]) == 3
    assert costs["series_n"] == len(frame["series"])
    assert {"index_ns", "read_ns", "tile_ns"} <= set(costs)
    # the chunks' ShardBuffer.read loops, a stretch inside read_ns
    assert 0 < costs["buffer_ns"] <= costs["read_ns"]
    assert sp["tags"]["host"] == node.svc.host_id
    assert ROOT.counter("rpc.fetch_tagged.tiles").value() - before == 3
    # PR 33's before-and-after is settled: tiles_n is the reading
    assert "shard_blocks_n" not in costs
    assert "rpc.fetch_tagged.shard_blocks" not in ROOT.snapshot()


def test_a_seal_between_two_buffer_chunks_loses_no_point(monkeypatch):
    node = Node(num_shards=1, n_series=8, seal_open_later=True)
    shard = node.nsobj.shards[0]
    want = {sid: node.db.read(NS, sid, T0, node.end) for sid in node.ids}
    assert T0 + BLOCK not in shard.blocks and len(shard.buffer.buckets) == 1
    monkeypatch.setattr(node_server, "BUFFER_CHUNK", 3)
    checks = []

    def check_then_seal(what):
        checks.append(what)
        if len(checks) == 3:  # the shard's identities, chunk 0, now chunk 1
            assert node.db.tick(node.now)["sealed"] == 1

    monkeypatch.setattr(node.svc, "_check_deadline", check_then_seal)
    frame = node.frame()
    assert T0 + BLOCK in shard.blocks and not shard.buffer.buckets
    from_buffer = np.diff(frame["bufs"]["offs"]) > 0
    # chunk 0 alone read the open buffer (of its three, `s-00` has no point there)
    assert from_buffer[:3].sum() == 2 and not from_buffer[3:].any()
    assert {t["bs"] for t in frame["tiles"]} == {T0, T0 + BLOCK}
    got = client_points(frame)
    for sid in node.ids:
        np.testing.assert_array_equal(got[sid][0], want[sid][0])
        np.testing.assert_array_equal(got[sid][1], want[sid][1])


def counting_columns(monkeypatch):
    """Counts the tiles the pass has materialised: four columns each."""
    made = []
    real = tiles._column

    def column(parts, *a):
        made.append(len(parts))
        return real(parts, *a)

    monkeypatch.setattr(tiles, "_column", column)
    return lambda: len(made) // 4


def test_bytes_read_limit_refuses_before_the_last_tile_is_gathered(
        node, monkeypatch):
    monkeypatch.setattr(node_server, "TILE_MAX_ROWS", 5)
    tiles = len(node.frame()["tiles"])
    whole = xlimits.last_scope_totals()["bytes_read"]
    tiles_made = counting_columns(monkeypatch)
    svc = NodeService(node.db, limits=QueryLimits(
        bytes_read=LimitOptions(per_query=whole - 1)))
    with pytest.raises(ResourceExhausted):
        svc.dispatch("fetch_tagged", node.args())
    assert tiles > 3 and tiles_made() == tiles - 1
    # a tags-only fetch is metered too, before any data is read
    svc = NodeService(node.db, limits=QueryLimits(
        bytes_read=LimitOptions(per_query=40)))
    reads = []
    for shard in node.nsobj.shards.values():
        monkeypatch.setattr(shard.buffer, "read", lambda *a: reads.append(a))
    with pytest.raises(ResourceExhausted):
        svc.dispatch("fetch_tagged", node.args())
    with pytest.raises(ResourceExhausted):
        svc.dispatch("query", {k: v for k, v in node.args().items()})
    assert not reads and tiles_made() == tiles - 1


class CountedClock:
    """A deadline's clock that passes its end at the `expire_at`-th look."""

    def __init__(self, expire_at=None):
        self.looks, self.expire_at = 0, expire_at

    def __call__(self):
        self.looks += 1
        return 2.0 if self.looks == self.expire_at else 0.0


@pytest.mark.parametrize("where", ["identities", "buffers", "last-tile"])
def test_an_expired_deadline_stops_the_pass_inside_the_frame(
        node, monkeypatch, where):
    clock = CountedClock()
    frame = node.svc.dispatch("fetch_tagged", node.args(),
                              deadline=Deadline(1.0, clock))
    shards, tiles = len(node.nsobj.shards), len(frame["tiles"])
    # the dispatch's own look, one a shard's identities, one a buffer
    # chunk, one a tile: not one a series
    assert clock.looks == 1 + shards + shards + tiles < len(frame["series"])
    tiles_made = counting_columns(monkeypatch)
    reads = []
    for shard in node.nsobj.shards.values():
        real = shard.buffer.read
        monkeypatch.setattr(
            shard.buffer, "read",
            lambda *a, real=real: (reads.append(a), real(*a))[1])
    at = {"identities": 3, "buffers": 1 + shards + 2,
          "last-tile": clock.looks}[where]
    with pytest.raises(DeadlineExceeded):
        node.svc.dispatch("fetch_tagged", node.args(),
                          deadline=Deadline(1.0, CountedClock(at)))
    assert tiles_made() == {"last-tile": tiles - 1}.get(where, 0)
    members = len(frame["series"]) - 1
    assert (len(reads) == 0 if where == "identities"
            else 0 < len(reads) < members if where == "buffers"
            else len(reads) == members)


@pytest.mark.parametrize("name", ["all", "one-kind", "one-series"])
def test_query_returns_the_identity_sweep_alone(node, name):
    q, lo, hi = QUERIES[name]
    got = node.svc.dispatch("query", node.args(query=q))
    want = [{"id": sid, "tags": node.tags.get(sid, {})}
            for sid in node.db.query_ids(NS, q, T0, node.end)]
    assert got == {"series": want}
    frame = node.svc.dispatch("fetch_tagged",
                              dict(node.args(query=q), fetch_data=False))
    assert frame["series"] == want and frame["tiles"] == []
    assert not frame["bufs"]["offs"].any() and not len(frame["bufs"]["t"])


def test_ids_of_shards_this_node_does_not_hold_leave_no_row():
    node = Node(num_shards=4, n_series=12)
    gone = node.db.shard_set.lookup(b"s-03")
    node.nsobj.remove_shard(gone)
    frame = node.frame()
    ids = [e["id"] for e in frame["series"]]
    assert b"s-03" not in ids and ids == [
        sid for sid in node.db.query_ids(NS, iq.AllQuery(), T0, node.end)
        if node.db.shard_set.lookup(sid) != gone]
    got = client_points(frame)
    for sid in ids:
        if sid != GHOST:
            np.testing.assert_array_equal(
                got[sid][0], node.db.read(NS, sid, T0, node.end)[0])


# ------------------------------------------------- the gather, piece by piece


def sealed(rng, bs, held, window=32, width=40):
    """A block of `held` registry indices (sorted), random words."""
    held = np.asarray(held, np.int32)
    return SealedBlock(
        block_start=bs, window=window, series_indices=held,
        words=rng.integers(0, 2**32, (len(held), width), dtype=np.uint32),
        nbits=rng.integers(1, 32 * width, len(held)).astype(np.int32),
        npoints=rng.integers(1, window, len(held)).astype(np.int32),
        time_unit=xtime.Unit.SECOND)


def pieces_of(groups, as_lists):
    """The node's piece loop over (blocks, wanted registry indices)
    groups, positions numbered through the groups: a piece's rows and
    positions as plain ints where the block holds every index and the
    caller has lists (the node RPC), as arrays otherwise (the cold
    read)."""
    pieces, pos = {}, 0
    for blocks, idxs in groups:
        poss = list(range(pos, pos + len(idxs)))
        pos += len(idxs)
        if not as_lists:
            idxs, poss = np.asarray(idxs), np.asarray(poss)
        for blk in blocks:
            at, present = blk.rows_of(idxs, int(max(idxs)))
            if present is not None:
                poss_b = np.asarray(poss)[present]
            else:
                poss_b = poss
            if len(at):
                pieces.setdefault(tiles.piece_key(blk), []).append(
                    (blk, at, poss_b))
    return pieces


def plain_tiles(pieces, max_rows):
    """The reference: a fancy take a piece a column, concatenated a key,
    cut at the bound."""
    out = []
    for key in sorted(pieces):
        bs, window, unit, width = key
        cols = {
            "rows": np.concatenate([np.asarray(poss) for _, _, poss in
                                    pieces[key]]).astype(np.int32),
            "words": np.concatenate([np.asarray(blk.words)[np.asarray(at)]
                                     for blk, at, _ in pieces[key]]),
            "nbits": np.concatenate([np.asarray(blk.nbits)[np.asarray(at)]
                                     for blk, at, _ in pieces[key]]
                                    ).astype(np.int32),
            "npoints": np.concatenate([np.asarray(blk.npoints)[np.asarray(at)]
                                       for blk, at, _ in pieces[key]]
                                      ).astype(np.int32),
        }
        for lo in range(0, len(cols["rows"]), max_rows):
            out.append({"bs": bs, "window": window, "time_unit": unit,
                        **{k: np.ascontiguousarray(v[lo:lo + max_rows])
                           for k, v in cols.items()}})
    return out


def grid_case(rows_a_piece, shards, starts=1, held=625):
    def build(rng):
        blocks = [[sealed(rng, T0 + b * BLOCK, range(held))
                   for b in range(starts)] for _ in range(shards)]
        return [(blks, sorted(rng.choice(held, rows_a_piece,
                                         replace=False).tolist()))
                for blks in blocks]
    return build


def lacking_case(rng):
    """Blocks that lack a series: one without index 3 (its rows are not
    its indices in a row), one that holds none of the wanted."""
    whole = sealed(rng, T0, range(12))
    gap = sealed(rng, T0, [i for i in range(12) if i != 3])
    none = sealed(rng, T0, [0, 1])
    return [([whole], [2, 3, 7]), ([gap], [2, 3, 7]), ([none], [5, 9]),
            ([gap], [3])]


def two_geometries_case(rng):
    """One block start, two windows and two words widths."""
    return [([sealed(rng, T0, range(9), window=8, width=12)], [0, 4, 8]),
            ([sealed(rng, T0, range(9), window=32, width=40)], [1, 2]),
            ([sealed(rng, T0, range(9), window=32, width=12)], [5])]


GATHERS = {
    **{"%d-row pieces x %d shards" % (k, sh): (grid_case(k, sh), 4096)
       for k in (1, 2, 7, 625) for sh in (1, 3, 20)},
    "3 tiles x 23 one-row pieces": (grid_case(1, 23, starts=3, held=60), 4096),
    "a block lacks a series": (lacking_case, 4096),
    "a cut straddles the bound": (grid_case(7, 3, held=40), 5),
    "a piece of many rows straddles the bound": (
        grid_case(600, 2), 512),
    "two geometries at one start": (two_geometries_case, 4096),
}


@pytest.mark.parametrize("as_lists", [True, False], ids=["ints", "arrays"])
@pytest.mark.parametrize("name", GATHERS)
def test_gathered_tiles_equal_the_plain_gather(name, as_lists, monkeypatch):
    from m3_tpu.utils.instrument import ROOT

    build, bound = GATHERS[name]
    pieces = pieces_of(build(np.random.default_rng(47)), as_lists)
    want = plain_tiles(pieces, bound)
    made = counting_columns(monkeypatch)
    charged = []
    before = {k: ROOT.counter("storage.tiles." + k).value()
              for k in ("gathers", "rows")}
    got = tiles.gather_tiles(
        pieces, bound, lambda n_bytes: charged.append((n_bytes, made())))
    moved = {k: ROOT.counter("storage.tiles." + k).value() - v
             for k, v in before.items()}
    assert len(got) == len(want) and len(want)
    for g, w in zip(got, want):
        assert set(g) == set(w)
        for k in ("bs", "window", "time_unit"):
            assert g[k] == w[k]
        for k in ("rows", "words", "nbits", "npoints"):
            assert g[k].dtype == w[k].dtype and g[k].shape == w[k].shape
            assert g[k].flags["C_CONTIGUOUS"] and g[k].flags["OWNDATA"]
            np.testing.assert_array_equal(g[k], w[k])
    # charged tile by tile, each before its columns exist, the bytes of
    # its words
    assert charged == [(w["words"].nbytes, i) for i, w in enumerate(want)]
    # the array operations: four a tile and three a many-row piece,
    # whatever the number of pieces
    many = sum(len(at) > tiles.PIECE_TAKE_ROWS for key in pieces
               for cut in tiles.cut_rows(pieces[key], bound)
               for _, at, _ in cut)
    assert moved == {"gathers": 4 * len(want) + 3 * many,
                     "rows": sum(len(w["rows"]) for w in want)}
    if name == "3 tiles x 23 one-row pieces":
        assert len(want) == 3 and moved["gathers"] == 12
        assert sum(len(v) for v in pieces.values()) == 69


@pytest.mark.parametrize("bound", [None, 5, 1])
def test_a_frames_tiles_are_the_plain_gather_of_its_pieces(
        node, monkeypatch, bound):
    """The node's own piece loop: blocks that lack a series, two
    geometries at one start, cuts at the bound."""
    if bound is not None:
        monkeypatch.setattr(node_server, "TILE_MAX_ROWS", bound)
    seen = []
    real = node_server.gather_tiles

    def gather(pieces, *rest):
        seen.append(pieces)
        return real(pieces, *rest)

    monkeypatch.setattr(node_server, "gather_tiles", gather)
    frame, sp = node.svc.dispatch_traced(
        "fetch_tagged", node.args(), trace_ctx=tracing.SpanContext(47, 1))
    want = plain_tiles(seen[0], bound or node_server.TILE_MAX_ROWS)
    assert len(frame["tiles"]) == len(want) == sp["costs"]["tiles_n"]
    for g, w in zip(frame["tiles"], want):
        for k in ("rows", "words", "nbits", "npoints"):
            assert g[k].dtype == w[k].dtype and g[k].shape == w[k].shape
            assert g[k].flags["C_CONTIGUOUS"]
            np.testing.assert_array_equal(g[k], w[k])
    # a piece whose block holds every index carries plain ints
    kinds = {type(at) for v in seen[0].values() for _, at, _ in v}
    assert kinds == {list, np.ndarray}
    assert sp["costs"]["tile_gathers_n"] == 4 * len(want)
