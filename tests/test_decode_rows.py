"""One way to decode rows (ops/decode_rows.py, ISSUE 45): whoever asks
— a node's batched cold read, a block's row read or whole decode, a
session's decode of the frames it holds — the same rows run the same
programs (the rungs of ROW_BUCKETS), a device fault answers from the
host oracle, a geometry's rungs are compiled once a device scope, and a
device-held input at a rung is decoded where it lies. The layer map the
function restores is pinned by an import check."""

import ast
import pathlib

import jax
import numpy as np
import pytest

from m3_tpu.client import Session, SessionOptions
from m3_tpu.client.session import _ReadCosts
from m3_tpu.index import query as iq
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.ops import decode_rows as rows_mod
from m3_tpu.ops import ref_codec, tsz
from m3_tpu.ops.decode_rows import ROW_BUCKETS, decode_rows
from m3_tpu.parallel import scope as dscope
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.rpc import wire
from m3_tpu.rpc.node_server import NodeService
from m3_tpu.storage import block_cache
from m3_tpu.storage.block import encode_block
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.storage.read_batch import read_many
from m3_tpu.testing import faultcomp
from m3_tpu.utils import instrument, xtime
from m3_tpu.utils.hbm import HBMBudget

NS = b"rows"
BLOCK = 10 * xtime.MINUTE
STEP = xtime.MINUTE
T0 = 1_600_000_000 * 10**9
T0 -= T0 % BLOCK
ROWS = (1, 5, 8, 9, 20, 100, 300, 625, 1024, 1025, 2500)
POINTS = 6


def rungs_for(n):
    """The programs `n` rows of one geometry take: calls of the largest
    rung, the last padded to the smallest that holds it."""
    top = ROW_BUCKETS[-1]
    full, rest = divmod(n, top)
    return [top] * full + ([next(b for b in ROW_BUCKETS if b >= rest)]
                           if rest else [])


class Node:
    """One shard holding one sealed block of max(ROWS) series, whole and
    two-decimal values in turn; series i carries a tag `n<k>` for every
    k of ROWS above i, so a term query finds the first k."""

    def __init__(self):
        self.now = T0
        self.db = Database(ShardSet(1), clock=lambda: self.now)
        self.db.mark_bootstrapped()
        opts = NamespaceOptions(block_size_ns=BLOCK,
                                buffer_past_ns=xtime.MINUTE,
                                writes_to_commitlog=False)
        self.db.create_namespace(NS, opts, index=NamespaceIndex(
            opts.index_block_size_ns, clock=lambda: self.now))
        self.ids = [b"s-%04d" % i for i in range(max(ROWS))]
        tags = [{b"__name__": b"m", b"id": sid,
                 **{b"n%d" % k: b"y" for k in ROWS if i < k}}
                for i, sid in enumerate(self.ids)]
        rng = np.random.default_rng(45)
        for k in range(POINTS):
            self.now = T0 + k * STEP
            v = rng.integers(0, 1000, len(self.ids)).astype(np.float64)
            v[1::2] = np.round(v[1::2] + rng.random(len(v[1::2])), 2)
            self.db.write_batch(NS, self.ids,
                                np.full(len(self.ids), self.now, np.int64),
                                v, tags)
        self.now = T0 + BLOCK + 2 * xtime.MINUTE
        self.db.tick(self.now)
        self.ns = self.db.namespace(NS)
        (shard,) = self.ns.shards.values()
        (self.block,) = shard.blocks.values()
        self.registry = shard.registry
        self.svc = NodeService(self.db)

    def oracle(self, sid):
        """ref_codec's reading of the series' sealed row."""
        blk = self.block
        row = blk.row_of(self.registry.get(sid))
        t, v = ref_codec.decode(ref_codec.EncodedBlock(
            words=np.asarray(blk.words)[row], nbits=0,
            npoints=int(blk.npoints[row])))
        return (np.asarray(t, np.int64) * blk.time_unit.nanos,
                np.asarray(v, np.float64))

    def cold_read(self, n):
        """The node's own batched read of the first n series."""
        got = read_many(self.ns, self.db.shard_set, self.ids[:n], T0,
                        T0 + BLOCK)
        return {sid: (t, v) for sid, (_tags, t, v) in zip(self.ids, got)}

    def frame(self, n):
        """The fetch_tagged frame a session is sent for the first n."""
        return wire.decode(wire.encode(self.svc.dispatch("fetch_tagged", {
            "ns": NS, "query": wire.query_to_wire(
                iq.new_term(b"n%d" % n, b"y")),
            "start_ns": T0, "end_ns": T0 + BLOCK})))


def session_read(frame):
    session = Session.__new__(Session)
    session.opts = SessionOptions()
    costs = _ReadCosts()
    merged = session._merged_points([frame], costs)
    return {sid: (e["t"], e["v"]) for sid, e in merged.items()}, costs


@pytest.fixture(scope="module")
def node():
    return Node()


@pytest.fixture()
def no_cache(monkeypatch):
    """A block cache that admits nothing: every sealed row is cold."""
    monkeypatch.setitem(
        dscope.DEFAULT._owned, "block_cache", block_cache.DeviceBlockCache(
            budget=HBMBudget(1 << 30), admit_after=10**9,
            scope=instrument.ROOT.sub_scope("test.decode_rows.cache")))


@pytest.fixture()
def programs(monkeypatch):
    """The row count of every decode program run, in order."""
    ran = []
    real = tsz._decode_fused_jit

    def spy(*key):
        run = real(*key)
        return lambda w, n: (ran.append(int(w.shape[0])), run(w, n))[1]

    monkeypatch.setattr(tsz, "_decode_fused_jit", spy)
    return ran


def assert_oracle(node, got, n):
    assert sorted(got) == node.ids[:n]
    for sid in node.ids[:n]:
        t, v = node.oracle(sid)
        np.testing.assert_array_equal(got[sid][0], t)
        assert np.asarray(got[sid][1], np.float64).tobytes() == v.tobytes()


@pytest.mark.parametrize("n", ROWS)
def test_same_rows_same_programs_whoever_asks(node, no_cache, programs, n):
    frame = node.frame(n)
    del programs[:]
    cold = node.cold_read(n)
    by_node = programs[:]
    del programs[:]
    held, costs = session_read(frame)
    by_session = programs[:]
    assert by_node == by_session == rungs_for(n)
    assert set(by_node) <= set(ROW_BUCKETS)
    assert costs.decode_n == len(by_session)
    assert_oracle(node, cold, n)
    assert_oracle(node, held, n)


@pytest.mark.parametrize("kinds", [dict(dispatch_raise=1.0),
                                   dict(corrupt=1.0)])
def test_a_fault_in_a_sessions_decode_answers_from_the_host_oracle(
        node, kinds):
    frame = node.frame(20)
    plan = faultcomp.ComputeFaultPlan(seed=45, route_filter="block.decode",
                                      **kinds)
    try:
        with faultcomp.injected(plan) as seam:
            got, costs = session_read(frame)
    finally:
        from m3_tpu.parallel import guard

        guard.reset()
    assert seam.decisions.get("block.decode")
    assert costs.decode_n == 1
    assert_oracle(node, got, 20)


@pytest.mark.parametrize("asker", ["node", "block-row", "session"])
def test_a_geometrys_first_decode_warms_every_rung_once_a_scope(
        node, no_cache, programs, monkeypatch, asker):
    """Where compiles are dear (steered here: the CPU compiles a shape
    where it meets it) the first decode of a geometry under a device
    scope runs every rung once before its own; the second runs its own
    alone. What is warm is the scope's: another scope's device has
    programs of its own."""
    monkeypatch.setattr(rows_mod, "_compiles_are_dear", lambda: True)
    frame = node.frame(20)
    ask = {"node": lambda: node.cold_read(20),
           "block-row": lambda: node.block.read(
               node.registry.get(node.ids[3])),
           "session": lambda: session_read(frame)}[asker]
    own = [8] if asker == "block-row" else [32]
    admits_nothing = block_cache.get_cache()
    for at, name in ((5, "one"), (6, "another")):
        with dscope.DeviceScope([at], f"decode-rows-{asker}-{name}") as sc:
            sc.put("block_cache", admits_nothing)
            del programs[:]
            ask()
            assert programs == list(ROW_BUCKETS) + own
            del programs[:]
            ask()
            assert programs == own


@pytest.mark.parametrize("rows,uploads,ran", [
    (8, 0, [8]), (32, 0, [32]), (1024, 0, [1024]),
    (4, 1, [8]), (2048, 2, [1024, 1024])])
def test_a_device_held_input_at_a_rung_is_decoded_where_it_lies(
        programs, rows, uploads, ran):
    rng = np.random.default_rng(rows)
    w = 8
    ts = T0 + np.arange(w, dtype=np.int64)[None, :] * STEP \
        + np.zeros((rows, 1), np.int64)
    vals = rng.integers(0, 100, (rows, w)).astype(np.float64)
    blk = encode_block(T0, np.arange(rows, dtype=np.int32), ts, vals,
                       np.full(rows, w, np.int32))
    held = jax.device_put((np.asarray(blk.words), np.asarray(blk.npoints)))
    moved = instrument.ROOT.counter("codec.decode.uploads")
    before = moved.value()
    got_t, got_v, calls = decode_rows(*held, blk.window, blk.time_unit.nanos)
    assert moved.value() - before == uploads
    assert programs == ran and calls == len(ran)
    np.testing.assert_array_equal(got_t[:, :w], ts)
    np.testing.assert_array_equal(got_v[:, :w], vals)
    # a block's own whole decode hands the retained encode over the
    # same way, and falls back to its host words off a rung
    del programs[:]
    before = moved.value()
    plane_t, plane_v = blk._decode_plane(held)
    assert moved.value() - before == uploads
    assert programs == ran
    np.testing.assert_array_equal(plane_t, got_t)
    assert plane_v.tobytes() == got_v.tobytes()
    assert not plane_t.flags.writeable and not plane_v.flags.writeable


def test_no_rows_are_no_call(programs):
    ts, vals, calls = decode_rows(np.zeros((0, 4), np.uint32), [], 8, 1)
    assert ts.shape == vals.shape == (0, 8) and calls == 0
    assert ts.dtype == np.int64 and vals.dtype == np.float64
    assert programs == []


PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "m3_tpu"


def imports_of(path: pathlib.Path):
    """Absolute names of the modules `path` (a file of m3_tpu) imports,
    relative ones resolved against its own place in the package."""
    here = ("m3_tpu",) + path.relative_to(PACKAGE).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - node.level + 1] if node.level else ()
            mod = ".".join(base + ((node.module,) if node.module else ()))
            yield mod
            yield from (f"{mod}.{a.name}" for a in node.names)


@pytest.mark.parametrize("below,above", [
    ("storage", "m3_tpu.client.decode"), ("client", "m3_tpu.storage"),
    ("ops", "m3_tpu.storage"), ("ops", "m3_tpu.client")])
def test_the_decode_keeps_to_the_layer_map(below, above):
    """L4 storage does not reach up to the client's decode, L6 client
    does not reach down to storage for one, and the codec layer both
    now call knows neither."""
    found = [(str(p.relative_to(PACKAGE)), name)
             for p in sorted((PACKAGE / below).rglob("*.py"))
             for name in imports_of(p)
             if name == above or name.startswith(above + ".")]
    assert found == []
