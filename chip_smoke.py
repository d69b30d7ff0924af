#!/usr/bin/env python3
"""chip_smoke.py — the served path, end to end, on the chip.

One process (a chip belongs to one process) boots the reference's
single-binary deployment — `run_dbnode` with an embedded coordinator —
under an injected clock and drives it over localhost: samples in through
the node RPC batch, the coordinator's `writer.write_batch` and
Prometheus remote-write over HTTP; seal + M3TSZ encode + flush to
filesets; PromQL out of the HTTP API through the compiled plan route;
a sealed block read back from its fileset; a timer p99 rollup through
the embedded downsampler. Every answer is compared with a host
reference, and the run FAILS (nonzero exit, no result line) when the
platform is not a TPU, when any phase raises, or when anything on the
path quietly ran somewhere other than where the repo says it runs: a
compute-fault / runtime-fallback counter moved, a breaker is not
CLOSED, an accepted query missed the compiled route, a codec route
counter disagrees with the dispatch gate, or the second pass over the
same queries compiled.

It prints counts and facts (sizes, compile seconds, route counters, peak
device bytes) — never a rate. The last stdout line is
{"ok": true, "device": {...}} as JAX reports the device.

    python chip_smoke.py [--seed N] [--out FILE] [--compare FILE]

Sizes default to the repo's documented deployment (BASELINE.json north
star): 100,000 series, 10 s cadence, 120-point blocks.
The phases are importable functions; tests/test_chip_smoke.py runs them
tiny on the CPU test platform.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import hashlib
import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Dict, List, Optional

import numpy as np

S = 1_000_000_000
CADENCE_NS = 10 * S
BLOCK_POINTS = 120            # never cut: the codec's served window
BLOCK_NS = BLOCK_POINTS * CADENCE_NS
T0 = 1_700_000_400 * S        # block-aligned
assert T0 % BLOCK_NS == 0
KINDS = (b"counter", b"gauge2dp", b"float", b"nanhole", b"const")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class Sizes:
    series: int = 100_000       # metric `m`, the node-RPC + coordinator legs
    hosts: int = 1_000          # `host` label values; 8 `dc` values
    sealed_blocks: int = 2      # full 120-point blocks sealed + flushed
    open_steps: int = 12        # steps left in the open buffer
    http_series: int = 5_000    # metric `m_http`, the remote-write leg
    http_steps: int = 6
    timer_groups: int = 1_000   # rollup ids (group-by svc)
    timer_per_group: int = 50   # source timer ids per group
    timer_samples: int = 3      # samples per source id in the window
    codec_sample: int = 512     # sealed rows checked against ref_codec
    num_shards: int = 64
    load_budget_s: float = 500.0  # ~half the 1200 s limit minus compile

    @property
    def timer_ids(self) -> int:
        return self.timer_groups * self.timer_per_group


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str):
    print(f"[smoke t+{time.perf_counter() - _T_START:7.1f}s] {msg}",
          flush=True)


_T_START = time.perf_counter()


# --------------------------------------------------------------- compile log


class CompileLog:
    """Counts XLA backend compiles (each is a trace-cache miss, whether
    or not the persistent cache then served it) and persistent-cache
    hits/misses, through jax.monitoring."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == CACHE_MISS_EVENT:
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 2),
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses}


# ------------------------------------------------------------------- context


@dataclasses.dataclass
class Ctx:
    sizes: Sizes
    seed: int
    workdir: str
    now: Dict[str, int]
    handle: object = None
    compile_log: Optional[CompileLog] = None
    tags: List[dict] = dataclasses.field(default_factory=list)
    ids: List[bytes] = dataclasses.field(default_factory=list)
    # generated truth, per written block index: (ts [N, P] ns, vals [N, P])
    blocks: Dict[int, tuple] = dataclasses.field(default_factory=dict)
    http_truth: Optional[tuple] = None
    results: Dict[str, dict] = dataclasses.field(default_factory=dict)
    facts: Dict[str, object] = dataclasses.field(default_factory=dict)
    cuts: List[str] = dataclasses.field(default_factory=list)
    end_ns: int = 0
    counters0: Dict[str, float] = dataclasses.field(default_factory=dict)

    def moved(self) -> Dict[str, float]:
        """Instrument counters as deltas since boot (the registry is
        process-global: a test process has history)."""
        return {k: v - self.counters0.get(k, 0)
                for k, v in counters().items()
                if isinstance(v, (int, float))}

    @property
    def engine(self):
        return self.handle.coordinator.engine

    @property
    def base(self) -> str:
        return self.handle.coordinator.endpoint


def counters() -> dict:
    from m3_tpu.utils.instrument import ROOT

    return ROOT.snapshot()


def _http_json(url: str, data: Optional[bytes] = None, timeout: float = 600):
    req = urllib.request.Request(url, data=data,
                                 method="POST" if data is not None else "GET")
    if data is not None:  # what a Prometheus remote_write sends
        req.add_header("Content-Type", "application/x-protobuf")
        req.add_header("Content-Encoding", "snappy")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"HTTP {e.code} from {url.split('?')[0]}: "
                           f"{e.read().decode(errors='replace')[:2000]}")


# ---------------------------------------------------------------- generators


def series_tags(i: int, hosts: int) -> dict:
    return {b"__name__": b"m", b"host": b"h%03d" % (i % hosts),
            b"dc": b"dc%d" % ((i // hosts) % 8),
            b"kind": KINDS[i % len(KINDS)], b"i": b"%06d" % i}


def gen_block(seed: int, block: int, n: int, points: int, carry):
    """Truth for one block of metric `m`: (ts [n, points] ns, vals f64),
    mixing int-mode counters, 2-dp scaled-int gauges, raw floats,
    NaN-hole gauges and constants (scripts/codec_smoke.py:_corpus), at a
    10 s cadence with a per-series phase and, on 5% of series, jitter so
    irregular timestamp codes are exercised. `carry` keeps counters
    monotonic across blocks."""
    rng = np.random.default_rng([seed, block])
    idx = np.arange(n)
    kind = idx % len(KINDS)
    start = T0 + block * BLOCK_NS
    ts = (start + np.arange(points, dtype=np.int64)[None, :] * CADENCE_NS
          + ((idx % 8)[:, None] * S))
    jittered = (idx % 20 == 7)[:, None]
    ts = ts + np.where(jittered, rng.integers(0, 2, (n, points)), 0) * S
    vals = np.empty((n, points), np.float64)
    inc = rng.poisson(5.0, (n, points)).astype(np.float64)
    base = carry if carry is not None else (idx % 1000).astype(np.float64)
    counters_ = base[:, None] + np.cumsum(inc, axis=1)
    vals[:] = counters_
    g = kind == 1
    vals[g] = np.round(rng.normal(100, 5, (int(g.sum()), points)), 2)
    f = kind == 2
    vals[f] = rng.normal(0, 1, (int(f.sum()), points))
    h = kind == 3
    holes = np.round(rng.normal(10, 1, (int(h.sum()), points)), 3)
    holes[rng.random(holes.shape) < 0.1] = np.nan
    vals[h] = holes
    c = kind == 4
    vals[c] = (idx[c] % 97).astype(np.float64)[:, None]
    return ts, vals, counters_[:, -1]


# --------------------------------------------------------------------- phases


def phase_boot(sizes: Sizes, seed: int, workdir: str) -> Ctx:
    """Publish the rollup rule to KV, then boot dbnode + embedded
    coordinator through the normal service entry point."""
    from m3_tpu.cluster import kv as cluster_kv
    from m3_tpu.metrics import aggregation as magg
    from m3_tpu.metrics.filters import TagsFilter
    from m3_tpu.metrics.matcher import RuleSetStore
    from m3_tpu.metrics.pipeline import Op, Pipeline
    from m3_tpu.metrics.policy import StoragePolicy
    from m3_tpu.metrics.rules import (RollupRuleSnapshot, RollupTarget, Rule,
                                      RuleSet)
    from m3_tpu.services import load_dict, run_dbnode

    kv_path = os.path.join(workdir, "kv.json")
    policy = (StoragePolicy.parse("10s:2d"),)
    rollup = [Rule([RollupRuleSnapshot(
        "lat_p99", 0, TagsFilter({"__name__": "lat_ms"}),
        (RollupTarget(Pipeline((Op.roll(
            b"lat:by_svc", (b"svc",),
            magg.AggID.compress([magg.AggType.P99])),)), policy),))])]
    RuleSetStore(cluster_kv.FileStore(kv_path)).publish(
        RuleSet(b"default", 1, [], rollup))

    now = {"t": T0}
    ctx = Ctx(sizes=sizes, seed=seed, workdir=workdir, now=now)
    ctx.compile_log = CompileLog()
    ctx.counters0 = {k: v for k, v in counters().items()
                     if isinstance(v, (int, float))}
    cfg = load_dict({
        "data_dir": os.path.join(workdir, "data"),
        "num_shards": sizes.num_shards,
        "kv_path": kv_path,
        "namespaces": [{"name": "default", "block_size": "20m",
                        "retention": "12h"}],
        "coordinator": {},
    }, "dbnode")
    ctx.handle = run_dbnode(cfg, clock=lambda: now["t"])
    say(f"dbnode listening on {ctx.handle.endpoint}, embedded coordinator "
        f"on {ctx.base}")
    return ctx


def _rpc_step(cli, ctx: Ctx, block: int, k: int, lo: int, hi: int,
              with_tags: bool):
    ts, vals = ctx.blocks[block][:2]
    cli.call("write_batch", ns=b"default", ids=ctx.ids[lo:hi],
             ts=np.ascontiguousarray(ts[lo:hi, k]),
             vals=np.ascontiguousarray(vals[lo:hi, k]),
             tags=ctx.tags[lo:hi] if with_tags else None)


def phase_load(ctx: Ctx):
    """Load state through the batched write entry points, advancing the
    clock so blocks seal and flush to filesets as on a live node."""
    from m3_tpu.client.session import HostClient
    from m3_tpu.coordinator import promremote
    from m3_tpu.metrics import id as metric_id
    from m3_tpu.storage.mediator import Mediator

    sz, now = ctx.sizes, ctx.now
    t_load = time.perf_counter()
    n = sz.series
    ctx.tags = [series_tags(i, sz.hosts) for i in range(n)]
    ctx.ids = [metric_id.encode(b"m", {k: v for k, v in t.items()
                                       if k != b"__name__"})
               for t in ctx.tags]
    ctx.blocks[0] = gen_block(ctx.seed, 0, n, BLOCK_POINTS, None)
    cli = HostClient(ctx.handle.endpoint, timeout=300.0)
    mediator = Mediator(ctx.handle.db, ctx.handle.persist)

    # -- calibrate on the first tenth, then decide the cuts BEFORE loading
    cal = max(1, n // 10)
    now["t"] = T0 + CADENCE_NS
    _rpc_step(cli, ctx, 0, 0, 0, cal, with_tags=True)   # new-series path
    t0 = time.perf_counter()
    for k in (1, 2):
        now["t"] = T0 + (k + 1) * CADENCE_NS
        _rpc_step(cli, ctx, 0, k, 0, cal, with_tags=False)
    per_sample = (time.perf_counter() - t0) / (2 * cal)
    total_steps = sz.sealed_blocks * BLOCK_POINTS + sz.open_steps
    # the coordinator leg and the seals cost more per sample than the
    # steady RPC append: 1.5x is the observed ratio on the CPU container
    projected = per_sample * n * total_steps * 1.5
    say(f"load calibration: {per_sample * 1e6:.2f} us/sample steady over "
        f"{cal} series -> projected load {projected:.0f}s "
        f"(budget {sz.load_budget_s:.0f}s)")
    if projected > sz.load_budget_s and sz.sealed_blocks > 1:
        ctx.cuts.append(f"sealed_blocks {sz.sealed_blocks} -> 1 (projected "
                        f"load {projected:.0f}s > {sz.load_budget_s:.0f}s)")
        sz.sealed_blocks = 1
        total_steps = BLOCK_POINTS + sz.open_steps
        projected = per_sample * n * total_steps * 1.5
    if projected > sz.load_budget_s:
        keep = max(cal, int(n * sz.load_budget_s / projected))
        ctx.cuts.append(f"series {n} -> {keep} (projected load "
                        f"{projected:.0f}s > {sz.load_budget_s:.0f}s)")
        n = sz.series = keep
        ctx.tags, ctx.ids = ctx.tags[:n], ctx.ids[:n]
        ctx.blocks[0] = tuple(a[:n] for a in ctx.blocks[0])
    for cut in ctx.cuts:
        say(f"CUT: {cut}")
    # the rest of the series catch up on the calibration steps
    if n > cal:
        for k in (0, 1, 2):
            _rpc_step(cli, ctx, 0, k, cal, n, with_tags=(k == 0))

    # -- node RPC batch leg (framed binary wire over TCP): sealed blocks
    for b in range(sz.sealed_blocks):
        if b > 0:
            ctx.blocks[b] = gen_block(ctx.seed, b, n, BLOCK_POINTS,
                                      ctx.blocks[b - 1][2])
        for k in range(3 if b == 0 else 0, BLOCK_POINTS):
            now["t"] = T0 + b * BLOCK_NS + (k + 1) * CADENCE_NS
            _rpc_step(cli, ctx, b, k, 0, n, with_tags=False)
            if b > 0 and k == BLOCK_POINTS // 2:
                # a block becomes sealable buffer_past (10m) after its
                # end: mid-way through the next one, as on a live node
                say(f"mediator at block {b} step {k}: "
                    f"{mediator.run_once()}")
        say(f"block {b} written over node RPC ({n} series x "
            f"{BLOCK_POINTS} points)")
    cli.close()

    # -- coordinator writer.write_batch leg: the open buffer
    b = sz.sealed_blocks
    ctx.blocks[b] = gen_block(ctx.seed, b, n, sz.open_steps,
                              ctx.blocks[b - 1][2])
    ts_o, vals_o = ctx.blocks[b][:2]
    writer = ctx.handle.coordinator.writer
    for k in range(sz.open_steps):
        now["t"] = T0 + b * BLOCK_NS + (k + 1) * CADENCE_NS
        writer.write_batch([(ctx.tags[i], int(ts_o[i, k]),
                             float(vals_o[i, k])) for i in range(n)])
    say(f"open buffer written through coordinator writer.write_batch "
        f"({n} series x {sz.open_steps} points)")

    # -- HTTP leg: Prometheus remote-write (snappy + protobuf) of `m_http`
    hn = sz.http_series
    rng = np.random.default_rng([ctx.seed, 1000])
    h_vals = np.round(rng.normal(50, 10, (hn, sz.http_steps)), 1)
    h_ts = (T0 + b * BLOCK_NS
            + (sz.open_steps - sz.http_steps
               + np.arange(sz.http_steps, dtype=np.int64))[None, :]
            * CADENCE_NS + np.zeros((hn, 1), np.int64))
    h_tags = [{b"__name__": b"m_http", b"dc": b"dc%d" % (i % 8),
               b"i": b"%05d" % i} for i in range(hn)]
    chunk = 1000
    wrote = 0
    for lo in range(0, hn, chunk):
        body = promremote.snappy_compress(promremote.encode_write_request([
            (h_tags[i], [(int(h_ts[i, k] // 1_000_000), float(h_vals[i, k]))
                         for k in range(sz.http_steps)])
            for i in range(lo, min(lo + chunk, hn))]))
        wrote += _http_json(f"{ctx.base}/api/v1/prom/remote/write",
                            data=body)["wrote"]
    check(wrote == hn * sz.http_steps,
          f"remote-write acked {wrote} of {hn * sz.http_steps} samples")
    ctx.http_truth = (h_tags, h_ts, h_vals)
    say(f"remote-write over HTTP: {wrote} samples of m_http acked")

    # -- seal + flush everything that is due
    ctx.end_ns = T0 + b * BLOCK_NS + sz.open_steps * CADENCE_NS
    now["t"] = ctx.end_ns
    stats = mediator.run_once()
    say(f"mediator at end of load: {stats}")
    # (the newest full block is still inside buffer_past here; it seals
    # when the clock moves on — phase_seal_all)
    sealed, filesets, _ = _sealed_and_filesets(ctx)
    ctx.facts["load"] = {
        "series": n, "sealed_block_starts": len(sealed),
        "filesets": filesets, "open_steps": sz.open_steps,
        "samples_written": n * total_steps + wrote,
        "load_s": round(time.perf_counter() - t_load, 1),
        "cuts": list(ctx.cuts)}
    say(f"load done: {ctx.facts['load']}")


def _sealed_and_filesets(ctx: Ctx):
    ns = ctx.handle.db.namespace(b"default")
    sealed = sorted({bs for sh in ns.shards.values() for bs in sh.blocks})
    filesets = sum(len(ctx.handle.persist.list_filesets(b"default", sid))
                   for sid in ns.shards)
    return sealed, filesets, len(ns.shards)


def phase_seal_all(ctx: Ctx):
    """Move the clock past buffer_past so every full block is sealed and
    flushed (what wall time does on a live node); the open buffer's
    samples stay queryable within the 5m lookback of ctx.end_ns."""
    from m3_tpu.storage.mediator import Mediator

    sz = ctx.sizes
    ctx.now["t"] = T0 + sz.sealed_blocks * BLOCK_NS + 10 * 60 * S
    stats = Mediator(ctx.handle.db, ctx.handle.persist).run_once()
    sealed, filesets, n_shards = _sealed_and_filesets(ctx)
    check(len([bs for bs in sealed if bs < T0 + sz.sealed_blocks * BLOCK_NS])
          == sz.sealed_blocks,
          f"sealed block starts {sealed}: expected {sz.sealed_blocks} full "
          "blocks")
    check(filesets >= sz.sealed_blocks * n_shards,
          f"{filesets} filesets on disk, expected >= "
          f"{sz.sealed_blocks * n_shards}")
    ctx.facts["seal"] = {"mediator": stats, "sealed_block_starts": len(sealed),
                         "filesets": filesets}
    say(f"all full blocks sealed + flushed: {ctx.facts['seal']}")


def _sealed_row(ctx: Ctx, series: int, block: int):
    db = ctx.handle.db
    sid = ctx.ids[series]
    shard = db.namespace(b"default").shards[db.shard_set.lookup(sid)]
    blk = shard.blocks[T0 + block * BLOCK_NS]
    row = blk.row_of(shard.registry.get(sid))
    check(row is not None, f"series {series} missing from sealed block")
    return blk, row


def phase_codec_ref(ctx: Ctx):
    """ops/ref_codec bit-identity on a sample of sealed series: the
    scalar reference decodes what the device encoded back to exactly
    the samples that were written."""
    from m3_tpu.ops import ref_codec

    sz = ctx.sizes
    rng = np.random.default_rng([ctx.seed, 2000])
    sample = rng.choice(sz.series, min(sz.codec_sample, sz.series),
                        replace=False)
    checked = 0
    for b in range(sz.sealed_blocks):
        ts, vals = ctx.blocks[b][:2]
        for i in sample:
            blk, row = _sealed_row(ctx, int(i), b)
            npts = int(blk.npoints[row])
            check(npts == BLOCK_POINTS,
                  f"series {i} block {b}: {npts} points sealed")
            t_ref, v_ref = ref_codec.decode(ref_codec.EncodedBlock(
                words=np.asarray(blk.words[row]), nbits=0, npoints=npts))
            check(np.array_equal(np.asarray(t_ref, np.int64)
                                 * blk.time_unit.nanos, ts[i]),
                  f"series {i} block {b}: ref_codec timestamps differ")
            check(np.array_equal(np.asarray(v_ref, np.float64).view(np.uint64),
                                 vals[i].view(np.uint64)),
                  f"series {i} block {b}: ref_codec value bits differ")
            checked += 1
    ctx.facts["codec_ref_rows"] = checked
    say(f"ref_codec bit-identity: {checked} sealed rows decode to the "
        "written samples")


def _block_to_map(block) -> dict:
    vals = np.asarray(block.values, np.float64)
    return {frozenset(t.pairs): vals[r]
            for r, t in enumerate(block.series_tags)}


def _matrix_to_map(resp: dict, start_ns: int, step_ns: int, steps: int):
    check(resp["status"] == "success", f"query failed: {str(resp)[:300]}")
    out = {}
    for s in resp["data"]["result"]:
        row = np.full(steps, np.nan)
        pts = s["values"] if "values" in s else [s["value"]]
        for t, v in pts:
            row[int(round((float(t) * S - start_ns) / step_ns))] = float(v)
        key = frozenset((k.encode(), v.encode())
                        for k, v in s["metric"].items())
        out[key] = row
    return out


def compare_maps(name: str, got: dict, want: dict, rtol: float = 1e-5):
    """Order-insensitive, keyed on the label set. Tolerance: the compiled
    route accumulates in f32, the interpreter in f64 (rtol 1e-5); values
    that cancel to near zero get an absolute allowance scaled to the
    result's magnitude."""
    want = {k: v for k, v in want.items() if np.isfinite(v).any()}
    got = {k: v for k, v in got.items() if np.isfinite(v).any()}
    check(set(got) == set(want),
          f"{name}: {len(got)} series served vs {len(want)} in reference "
          f"({len(set(got) ^ set(want))} label sets differ)")
    if not want:
        raise SmokeFailure(f"{name}: reference is empty — nothing compared")
    g = np.stack([got[k] for k in want])
    w = np.stack([want[k] for k in want])
    scale = float(np.nanmax(np.abs(w)))
    ok = np.isclose(g, w, rtol=rtol, atol=1e-6 * scale, equal_nan=True)
    check(bool(ok.all()),
          f"{name}: {int((~ok).sum())} of {ok.size} values differ from the "
          f"reference (worst |d|={np.nanmax(np.abs(g - w)):.3g})")
    check(bool(np.isfinite(g).any()), f"{name}: no finite value served")
    return g.shape


def smoke_queries(ctx: Ctx) -> List[dict]:
    end = ctx.end_ns
    rng_q = dict(kind="range", start=end - 10 * 60 * S, end=end, step=30 * S)
    return [
        dict(name="sum_by_host_rate",
             q='sum by (host) (rate(m{dc="dc0"}[5m]))', **rng_q),
        dict(name="bare_rate", q='rate(m{dc="dc0"}[5m])', **rng_q),
        dict(name="instant_sum_by_host", kind="instant",
             q='sum by (host) (m{dc=~"dc[0-3]"})', start=end, end=end,
             step=S),
        dict(name="instant_http_leg", kind="instant", q="m_http",
             start=end, end=end, step=S),
    ]


def _run_query(ctx: Ctx, spec: dict, explain: bool) -> dict:
    params = {"query": spec["q"]}
    if spec["kind"] == "range":
        path = "/api/v1/query_range"
        params.update(start=spec["start"] / S, end=spec["end"] / S,
                      step=f"{spec['step'] // S}s")
    else:
        path = "/api/v1/query"
        params.update(time=spec["start"] / S)
    if explain:
        params["explain"] = "true"
    resp = _http_json(f"{ctx.base}{path}?{urllib.parse.urlencode(params)}")
    steps = (spec["end"] - spec["start"]) // spec["step"] + 1
    out = {"map": _matrix_to_map(resp, spec["start"], spec["step"], steps)}
    if explain:
        out["executed"] = resp["data"]["explain"]["executed"]
    return out


def phase_queries(ctx: Ctx, specs: Optional[List[dict]] = None,
                  label: str = "queries"):
    """A few queries over HTTP from client threads, twice. Pass 1 asks
    for ?explain=true and requires the compiled route; pass 2 is the
    plain columnar render and must not compile. Both passes are compared
    with the retained interpreter (execute_range_ref)."""
    from m3_tpu.storage import block_cache

    specs = specs if specs is not None else smoke_queries(ctx)
    log = ctx.compile_log
    before = counters()
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        c0 = log.compiles
        pass1 = list(pool.map(lambda s: _run_query(ctx, s, True), specs))
        # (the blocks pass 1 touched are decoded whole by the block
        # cache's own thread: its work is pass 1's)
        block_cache.get_cache().wait_filled()
        c1 = log.compiles
        pass2 = list(pool.map(lambda s: _run_query(ctx, s, False), specs))
        c2 = log.compiles
    for spec, p1 in zip(specs, pass1):
        ex = p1["executed"]
        check(ex and ex.get("route") == "compiled",
              f"{spec['name']}: executed route {ex} — the plan compiler "
              "accepted this query, it must run compiled")
    check(c2 == c1, f"{label}: {c2 - c1} XLA compile(s) in the second pass "
          "over the same queries")
    after = counters()
    executed = (after.get("query.plan.executed", 0)
                - before.get("query.plan.executed", 0))
    check(executed == 2 * len(specs),
          f"{label}: query.plan.executed moved by {executed}, expected "
          f"{2 * len(specs)}")
    shapes = {}
    for spec, p1, p2 in zip(specs, pass1, pass2):
        ref = _block_to_map(ctx.engine.execute_range_ref(
            spec["q"], spec["start"], spec["end"], spec["step"]))
        shapes[spec["name"]] = compare_maps(
            f"{spec['name']} (explain pass)", p1["map"], ref)
        compare_maps(f"{spec['name']} (columnar pass)", p2["map"], ref)
        ctx.results[spec["name"]] = p2["map"]
    ctx.facts[label] = {"first_pass_compiles": c1 - c0,
                        "second_pass_compiles": c2 - c1,
                        "result_shapes": shapes}
    say(f"{label}: {len(specs)} queries x 2 passes over HTTP, all on the "
        f"compiled route, equal to the interpreter; {ctx.facts[label]}")


def phase_query_truth(ctx: Ctx):
    """Independent of every engine route: the served instant vector of
    the remote-write leg against the samples that were sent."""
    h_tags, _h_ts, h_vals = ctx.http_truth
    got = ctx.results["instant_http_leg"]
    want = {frozenset(t.items()): h_vals[i, -1:]
            for i, t in enumerate(h_tags)}
    compare_maps("instant_http_leg vs written samples", got, want, rtol=1e-6)
    say(f"m_http instant vector equals the {len(want)} samples written "
        "over remote-write")


def phase_fileset_read(ctx: Ctx):
    """Read a sealed block back from its fileset: attach the disk
    retriever, evict the flushed in-memory blocks, drop the device block
    cache, and query the oldest block's range. The rows the retriever
    seeks go into the fetch's one batched cold decode
    (storage/read_batch.py: one dispatch a geometry, rows padded to a
    bucket, never a one-row program: ROADMAP C16). The cache admits
    nothing meanwhile: the phase is about the cold read, and a second
    touch's whole-block decode would be the second pass's compile where
    a shape compiles when it is first met (the CPU)."""
    from m3_tpu.persist.fs import FilesetReader
    from m3_tpu.storage import block_cache
    from m3_tpu.storage.retriever import BlockRetriever

    db = ctx.handle.db
    # first, every flushed tile as its fileset gives it back: data.bin
    # holds a row's used words, and zero-filling them has to restore what
    # this platform's pack route left in memory, to the bit
    tiles = 0
    for sid, shard in db.namespace(b"default").shards.items():
        for bs, path in ctx.handle.persist.list_filesets(b"default", sid):
            held = shard.blocks.get(bs)
            if held is not None:
                back, _ = FilesetReader(path).to_block()
                tiles += 1
                check(np.array_equal(back.words, np.asarray(held.words)),
                      f"shard {sid} block {bs}: the fileset's tile differs "
                      "from the sealed block it was written from")
    check(tiles > 0, "no flushed block was still held to compare with "
          "its fileset")
    retr = BlockRetriever(ctx.handle.persist)
    db.set_retriever(retr)
    evicted = db.evict_flushed()
    cache = block_cache.get_cache()
    cache.clear()
    admit_after, cache.admit_after = cache.admit_after, 1 << 62
    check(evicted >= len(db.namespace(b"default").shards),
          f"evict_flushed dropped {evicted} blocks")
    c0 = counters()
    start = T0 + 60 * S
    spec = dict(name="fileset_read", kind="range",
                q='max_over_time(m{host=~"h00."}[1m])',
                start=start, end=start + 10 * 60 * S, step=10 * S)
    phase_queries(ctx, [spec], label="fileset_query")
    cache.admit_after = admit_after
    check(retr.stats["seeks"] > 0,
          f"retriever stats {retr.stats}: the query never read a fileset")
    cold = counters()
    rows, calls = (cold.get(k, 0) - c0.get(k, 0) for k in (
        "storage.read.cold_rows", "storage.read.cold_dispatches"))
    check(rows >= retr.stats["seeks"] and 0 < calls < rows,
          f"{rows} cold rows in {calls} decode dispatches for "
          f"{retr.stats['seeks']} seeks: the fileset's rows were not "
          "decoded in batches")
    # and against the written samples themselves (one series, exact)
    i = 7
    ts, vals = ctx.blocks[0][:2]
    key = frozenset((k, v) for k, v in
                    series_tags(i, ctx.sizes.hosts).items()
                    if k != b"__name__")   # *_over_time drops the name
    got = ctx.results["fileset_read"][key]
    for k, t in enumerate(range(spec["start"], spec["end"] + 1,
                                spec["step"])):
        win = vals[i][(ts[i] > t - 60 * S) & (ts[i] <= t)]
        win = win[~np.isnan(win)]
        want = win.max() if win.size else np.nan
        check(np.isclose(got[k], want, rtol=1e-5, atol=1e-5, equal_nan=True),
              f"fileset_read: step {k} served {got[k]} vs written {want}")
    ctx.facts["fileset"] = {"tiles_equal": tiles,
                            "evicted_blocks": evicted, "cold_rows": rows,
                            "cold_dispatches": calls,
                            "retriever": dict(retr.stats)}
    say(f"sealed block served from its fileset: {ctx.facts['fileset']}")


def phase_aggregator(ctx: Ctx):
    """Timer ids through the embedded downsampler's rollup rule (p99 by
    svc), flushed, and `lat:by_svc.p99` read back over HTTP against
    np.quantile on the host."""
    from m3_tpu.metrics.metric import MetricType

    sz = ctx.sizes
    rng = np.random.default_rng([ctx.seed, 3000])
    coord = ctx.handle.coordinator
    # window-aligned, within buffer_past of the loaded data
    w0 = ctx.now["t"] - ctx.now["t"] % CADENCE_NS + CADENCE_NS
    ctx.now["t"] = w0 + S
    # multiples of 1/16 below 2^12: exact in f32, so the device's f32
    # ordering is the f64 ordering, ties are equal values, and neighbours
    # in the order differ by >= 1.5e-5 relative (a wrong rank shows)
    vals = rng.integers(1, 1 << 16, (sz.timer_ids, sz.timer_samples)) / 16.0
    before = counters()
    samples = []
    for i in range(sz.timer_ids):
        tags = {b"__name__": b"lat_ms", b"svc": b"s%04d" % (i % sz.timer_groups),
                b"inst": b"%05d" % i}
        for v in vals[i]:
            samples.append((tags, ctx.now["t"], float(v)))
    coord.writer.write_batch(samples, metric_type=MetricType.TIMER)
    ds = coord.downsampler
    check(ds.samples_matched == len(samples),
          f"downsampler matched {ds.samples_matched} of {len(samples)}")
    ctx.now["t"] = w0 + 2 * CADENCE_NS + S
    rows = coord.flush_downsampler()
    check(rows >= sz.timer_groups, f"flush emitted {rows} rows")
    q = '{__name__="lat:by_svc.p99"}'
    start = w0 + CADENCE_NS
    spec = dict(name="timer_p99", kind="range", q=q, start=start,
                end=start + 4 * CADENCE_NS, step=CADENCE_NS)
    ctx.now["t"] = spec["end"]
    phase_queries(ctx, [spec], label="aggregator_query")
    got = ctx.results["timer_p99"]
    check(len(got) == sz.timer_groups,
          f"{len(got)} p99 series served, expected {sz.timer_groups}")
    by_group = vals.reshape(sz.timer_per_group, sz.timer_groups, -1)
    for key, row in got.items():
        svc = dict(key)[b"svc"]
        want = np.quantile(by_group[:, int(svc[1:])].ravel(), 0.99,
                           method="inverted_cdf")
        served = row[np.isfinite(row)]
        check(served.size > 0
              and bool(np.isclose(served, want, rtol=1e-6).all()),
              f"p99 of {svc!r}: served {served[:3]} vs np.quantile {want}")
    after = counters()
    ctx.facts["aggregator"] = {
        "timer_ids": sz.timer_ids, "samples": len(samples),
        "rollup_ids": sz.timer_groups, "flushed_rows": rows,
        "agg_flush_mesh_dispatches":
            after.get("telemetry.mesh.dispatches{kernel=agg_flush}", 0)
            - before.get("telemetry.mesh.dispatches{kernel=agg_flush}", 0)}
    say(f"aggregator leg: {ctx.facts['aggregator']}; every p99 equals "
        "np.quantile(inverted_cdf)")


FAULT_COUNTERS = ("faults", "trips", "trip_open", "quarantined",
                  "oom_reclaims")


def check_no_compute_faults(ctx: Ctx, also: tuple = ()) -> dict:
    """No telemetry.compute.* fault counter moved since boot and every
    guard breaker is CLOSED; returns the moved-counter map."""
    from m3_tpu.parallel import guard
    from m3_tpu.utils import retry as uretry

    c = ctx.moved()
    bad = {k: v for k, v in c.items()
           if v and k.startswith("telemetry.compute.")
           and any(s in k for s in FAULT_COUNTERS + also)}
    check(not bad, f"compute-fault counters moved: {bad}")
    not_closed = {r: s for r, s in guard.debug_snapshot().items()
                  if s["state"] != uretry.Breaker.CLOSED}
    check(not not_closed, f"guard breakers not CLOSED: {not_closed}")
    return c


def phase_served_verdict(ctx: Ctx):
    """After the served phases, before the codec twins touch the other
    routes on purpose: nothing on the path may have degraded or run
    somewhere else than the repo says."""
    import jax

    from m3_tpu.ops import pallas_codec
    from m3_tpu.parallel import guard

    # "fallback": a dispatch that took its route's twin (disabled route,
    # open breaker, quarantined bucket) — none is expected on this path
    c = check_no_compute_faults(ctx, also=("fallback",))
    runtime = {k: v for k, v in c.items() if v and k.startswith(
        "telemetry.plan_fallback.count") and "scope=runtime" in k}
    check(not runtime, f"runtime plan fallbacks: {runtime}")
    snap = guard.debug_snapshot()
    pallas = pallas_codec.enabled()
    want, other = ("pallas_", "xla_") if pallas else ("xla_", "pallas_")
    routes = {k.split(".")[-1]: v for k, v in c.items()
              if k.startswith("telemetry.codec.")
              and k.split(".")[-1].split("_")[-1] in ("encode", "decode",
                                                      "hash")}
    for kernel in ("encode", "decode", "hash"):
        check(routes.get(want + kernel, 0) > 0,
              f"codec route: gate says {want}{kernel} is the default here "
              f"but it never ran ({routes})")
        check(routes.get(other + kernel, 0) == 0,
              f"codec route: {other}{kernel} ran {routes.get(other + kernel)} "
              f"time(s) against the gate ({routes})")
    ndev = len(jax.devices())
    mesh_encode = c.get("storage.flush.mesh_encode", 0)
    plan_mesh = c.get("telemetry.mesh.dispatches{kernel=plan}", 0)
    if ndev > 1:
        check(mesh_encode > 0, "no seal went through the flush mesh")
        check(plan_mesh > 0, "no compiled plan dispatched on the query mesh")
        check(ctx.engine.mesh is not None
              and ctx.engine.mesh.devices.size == ndev,
              f"query mesh {ctx.engine.mesh} does not span {ndev} devices")
        from m3_tpu.parallel import ingest as pingest

        check(pingest.flush_mesh().devices.size == ndev,
              "flush mesh does not span every device")
    ctx.facts["routes"] = {
        "codec_gate": "pallas" if pallas else "xla", "codec": routes,
        "guard": {r: s["state"] for r, s in snap.items()},
        "plan_executed": c.get("query.plan.executed", 0),
        "plan_cache": {k.split(".")[-1]: v for k, v in c.items()
                       if k.startswith("telemetry.plan_cache.")
                       and not k.endswith("compile_s")},
        "mesh_encode": mesh_encode, "plan_mesh_dispatches": plan_mesh,
        "query_mesh_devices": (ctx.engine.mesh.devices.size
                               if ctx.engine.mesh is not None else 1)}
    say(f"served-path verdict clean: {ctx.facts['routes']}")


FEW_ROWS = (1, 2, 3, 5, 8, 16)


def few_row_twin_faults(seed: int, rows=FEW_ROWS, starts: int = 4) -> list:
    """Planes of 1-16 rows (a sealed block's first-touch read decodes
    one row; a thin dashboard read a handful), encoded and decoded on
    the default route and on the XLA twin, over block starts either
    side of many low-word wraps of the nanosecond pair: every mismatch
    with the twin or with the written samples, as a line of text. On a
    v5e the one-row plane of the block two after T0 read 12 timestamps
    ~2^31 ns low (PR 32): XLA:TPU lowers the degenerate reshapes of a
    one-row program as u32 reduce-adds inside the fused unit multiply;
    tsz.decode_plane builds no one-row program on that route. The
    program weaves each pair into rows of 64-bit cells on the device (a
    reshape of u32 pairs, the same family of lowering): the planes must
    come back C-contiguous each, which only a chip's layout can deny,
    and the smoke runs every rung a caller's rows are padded to
    (ROW_BUCKETS). What `decode_rows` hands a caller for the same rows
    (padded to their rung, cut back) must be the default route's bits."""
    from m3_tpu.ops import tsz
    from m3_tpu.ops.decode_rows import decode_rows
    from m3_tpu.parallel import guard
    from m3_tpu.storage.block import encode_block

    rng = np.random.default_rng([seed & 0xFFFFFFFF, 16])
    n, w = max(rows), BLOCK_POINTS
    at = [T0 + 2 * BLOCK_NS] + [
        T0 + int(k) * BLOCK_NS for k in rng.integers(0, 1 << 16, starts - 1)]
    faults = []
    for bs in at:
        t = bs + np.arange(w, dtype=np.int64)[None, :] * CADENCE_NS \
            + np.zeros((n, 1), np.int64)
        v = np.clip(np.cumsum(rng.integers(-1, 2, (n, w)), 1) + 50,
                    0, 100).astype(np.float64)
        v[1::2] = np.round(v[1::2] + rng.random((len(v[1::2]), w)), 2)
        whole = encode_block(bs, np.arange(n), t, v, np.full(n, w, np.int32))
        for r in rows:
            blk = encode_block(bs, np.arange(r), t[:r], v[:r],
                               np.full(r, w, np.int32))
            where = f"block {(bs - T0) // BLOCK_NS}, {r} row(s)"
            if not np.array_equal(np.asarray(blk.words)[:r],
                                  np.asarray(whole.words)[:r]):
                faults.append(f"{where}: encoded alone != encoded among {n}")
            got = {}
            for route in ("default", "xla"):
                guard.set_disabled("codec.decode", route == "xla")
                try:
                    planes = tsz.decode_plane(
                        np.asarray(whole.words)[:r],
                        np.asarray(whole.npoints)[:r], window=whole.window,
                        unit_nanos=whole.time_unit.nanos)
                finally:
                    guard.set_disabled("codec.decode", False)
                if not all(isinstance(a, np.ndarray) and a.flags.c_contiguous
                           and a.shape == (r, whole.window) for a in planes):
                    faults.append(f"{where}: {route} route's planes are not "
                                  f"C-contiguous [rows, window] arrays")
                got[route] = [a[:, :w] for a in planes]
            ts_d, vs_d = got["default"]
            if not (np.array_equal(ts_d, got["xla"][0]) and np.array_equal(
                    vs_d.view(np.uint64), got["xla"][1].view(np.uint64))):
                faults.append(f"{where}: default route != XLA twin")
            ts_r, vs_r, calls = decode_rows(
                np.asarray(whole.words)[:r], np.asarray(whole.npoints)[:r],
                whole.window, whole.time_unit.nanos)
            if not (calls == 1 and np.array_equal(ts_r[:, :w], ts_d)
                    and np.array_equal(vs_r[:, :w].view(np.uint64),
                                       vs_d.view(np.uint64))):
                faults.append(f"{where}: decode_rows != the plane decode")
            bad = np.argwhere(ts_d != t[:r])
            if len(bad):
                faults.append(
                    f"{where}: {len(bad)} timestamps off, first at "
                    f"{bad[0].tolist()} by {int((ts_d - t[:r])[tuple(bad[0])])}")
            if not np.array_equal(vs_d, v[:r]):
                faults.append(f"{where}: values differ from the written")
    return faults


def phase_codec_twins(ctx: Ctx):
    """Each Pallas codec kernel against its XLA / numpy twin at the
    smoke's served shapes, bit for bit, ON THIS DEVICE (interpret-mode
    parity on a CPU proves the algebra, not the Mosaic build)."""
    from m3_tpu.ops import pallas_codec, tsz
    from m3_tpu.parallel import guard
    from m3_tpu.storage.block import _next_pow2
    from m3_tpu.utils import hashing

    sz = ctx.sizes
    rows = min(_next_pow2(max(sz.series // sz.num_shards, 1), floor=1),
               sz.series)
    ts, vals = (a[:rows] for a in ctx.blocks[0][:2])
    window = _next_pow2(BLOCK_POINTS)
    pad = window - BLOCK_POINTS
    ts = np.concatenate([ts, np.repeat(ts[:, -1:], pad, 1)], 1) // S
    vals = np.concatenate([vals, np.repeat(vals[:, -1:], pad, 1)], 1)
    npts = np.full(rows, BLOCK_POINTS, np.int32)
    mw = tsz.max_words_for(window)
    inp = tsz.prepare_encode_inputs(ts, vals, npts)
    args = (inp["dt"], inp["t0"], inp["vhi"], inp["vlo"], inp["int_mode"],
            inp["k"], inp["npoints"], inp["ts_regular"], inp["delta0"])
    packs = {p: tuple(np.asarray(a) for a in
                      tsz.encode_batch(*args, max_words=mw, pack=p))
             for p in (None, "tree", "scatter")}
    for p in ("tree", "scatter"):
        check(np.array_equal(packs[None][0], packs[p][0])
              and np.array_equal(packs[None][1], packs[p][1]),
              f"encode: default pack differs from pack={p!r}")
    words = packs[None][0]
    dec_default = tsz.decode_plane(words, npts, window=window,
                                   unit_nanos=S, with_f32=True)
    guard.set_disabled("codec.decode", True)
    try:
        dec_xla = tsz.decode_plane(words, npts, window=window,
                                   unit_nanos=S, with_f32=True)
    finally:
        guard.set_disabled("codec.decode", False)
    for a, b_, what in zip(dec_default, dec_xla, ("ts", "vals", "f32")):
        check(np.array_equal(np.asarray(a)[:, :BLOCK_POINTS].view(np.uint8),
                             np.asarray(b_)[:, :BLOCK_POINTS].view(np.uint8)),
              f"decode: default route differs from the XLA scan ({what})")
    check(np.array_equal(dec_default[0][:, :BLOCK_POINTS],
                         ts[:, :BLOCK_POINTS] * S),
          "decode: timestamps differ from the written samples")
    check(np.array_equal(dec_default[1][:, :BLOCK_POINTS].view(np.uint64),
                         vals[:, :BLOCK_POINTS].view(np.uint64)),
          "decode: value bits differ from the written samples")
    from m3_tpu.ops.decode_rows import ROW_BUCKETS

    faults = few_row_twin_faults(
        ctx.seed, rows=tuple(sorted(set(FEW_ROWS + ROW_BUCKETS))))
    check(not faults, "few-row planes: " + "; ".join(faults[:5]))
    ids = ctx.ids[:min(sz.series, 20_000)]
    h_default = hashing.hash_batch(ids)
    guard.set_disabled("codec.hash", True)
    try:
        h_numpy = hashing.hash_batch(ids)
    finally:
        guard.set_disabled("codec.hash", False)
    check(np.array_equal(h_default, h_numpy),
          "hash: default route differs from the numpy twin")
    scalar = np.array([hashing.murmur3_32(i) for i in ids[:256]], np.uint32)
    check(np.array_equal(h_default[:256], scalar),
          "hash: batch differs from scalar murmur3_32")
    ctx.facts["codec_twins"] = {
        "gate": "pallas" if pallas_codec.enabled() else "xla",
        "tile": [int(rows), int(window)], "max_words": int(mw),
        "hash_ids": len(ids)}
    say(f"codec twins bit-identical on this device: "
        f"{ctx.facts['codec_twins']}")


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "peak_bytes_in_use": peaks}


def run_phases(sizes: Sizes, seed: int, workdir: str) -> Ctx:
    """Every phase, in order; any failure raises."""
    ctx = phase_boot(sizes, seed, workdir)
    try:
        phase_load(ctx)
        phase_seal_all(ctx)
        phase_codec_ref(ctx)
        phase_queries(ctx)
        phase_query_truth(ctx)
        phase_aggregator(ctx)
        phase_fileset_read(ctx)
        phase_served_verdict(ctx)
        phase_codec_twins(ctx)
        check_no_compute_faults(ctx)  # the twins included
    finally:
        ctx.handle.close()
    return ctx


# ------------------------------------------------------ results across runs


def results_digest(ctx: Ctx) -> dict:
    """What two runs on different device counts must agree on: sealed
    bitstreams exactly (checksums), query answers to rtol 1e-5."""
    out = {}
    for name, m in ctx.results.items():
        keys = sorted(m, key=lambda k: sorted(k))
        out[name] = {
            # lists, not tuples: what a JSON round trip gives back
            "labels": [sorted([a.decode(), b.decode()] for a, b in k)
                       for k in keys],
            "values": [[None if np.isnan(x) else float(x) for x in m[k]]
                       for k in keys]}
    return out


def sealed_checksum(ctx: Ctx) -> str:
    """sha256 over every flushed fileset's packed codewords, as data.bin
    lays them out: each row's used words, then the rows' counts
    (persist/fs.py). The padded-tile layout of an older tree hashed other
    bytes, so a reference run made by one compares unequal."""
    persist = ctx.handle.persist
    h = hashlib.sha256()
    for sid in sorted(ctx.handle.db.namespace(b"default").shards):
        for _bs, path in sorted(persist.list_filesets(b"default", sid)):
            with open(os.path.join(path, "data.bin"), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def compare_runs(results: dict, ref_path: str):
    with open(ref_path) as f:
        ref = json.load(f)
    check(ref["sealed_sha256"] == results["sealed_sha256"],
          "sealed filesets differ from the reference run "
          f"({ref['device']} vs {results['device']}); a reference made "
          "before data.bin held used words hashed the padded tiles and "
          "cannot compare equal: make it again on this tree")
    for name, want in ref["queries"].items():
        got = results["queries"][name]
        check(got["labels"] == want["labels"],
              f"{name}: label sets differ from the reference run")
        g = np.array(got["values"], np.float64)
        w = np.array(want["values"], np.float64)
        scale = float(np.nanmax(np.abs(w)))
        ok = np.isclose(g, w, rtol=1e-5, atol=1e-6 * scale, equal_nan=True)
        check(bool(ok.all()), f"{name}: {int((~ok).sum())} of {ok.size} "
              "values differ from the reference run")
    say(f"results equal the reference run {ref_path} ({ref['device']})")


# ----------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=None,
                    help="write results JSON here (default: "
                         "chiprun_out/chip_smoke_<count>chip.json)")
    ap.add_argument("--compare", default=None,
                    help="results JSON of another run (e.g. the one-chip "
                         "run) that this run's results must equal")
    args = ap.parse_args(argv)

    import jax

    from m3_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()
    t_init = time.perf_counter()
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: jax.devices()[0].platform is {platform!r} "
              f"({devs[0].device_kind}, {len(devs)} device(s)), not 'tpu' — "
              "this smoke only passes on the chip", file=sys.stderr)
        return 2
    say(f"platform={platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"backend_init_s={time.perf_counter() - t_init:.1f} "
        f"compile_cache={cache_dir}")

    sizes = Sizes()
    t_run = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        ctx = run_phases(sizes, args.seed, workdir)
        sealed_sha = sealed_checksum(ctx)
    wall = time.perf_counter() - t_run
    dev = device_facts()
    if dev["count"] > 1:
        check(all(p > 0 for p in dev["peak_bytes_in_use"]),
              f"a device held nothing: peak bytes {dev['peak_bytes_in_use']}")
    comp = ctx.compile_log.snapshot()
    report = {
        "device": dev, "seed": args.seed,
        "sizes": dataclasses.asdict(sizes), "cuts": ctx.cuts,
        "compile": comp, "wall_s": round(wall, 1),
        "run_s_excluding_compile": round(wall - comp["compile_s"], 1),
        "facts": ctx.facts, "sealed_sha256": sealed_sha,
    }
    print(json.dumps(report, indent=1, default=str), flush=True)
    results = {"device": dev, "sealed_sha256": sealed_sha,
               "queries": results_digest(ctx)}
    out = args.out
    if out is None:
        os.makedirs("chiprun_out", exist_ok=True)
        out = os.path.join("chiprun_out",
                           f"chip_smoke_{dev['count']}chip.json")
    with open(out, "w") as f:
        json.dump(results, f)
    say(f"results written to {out}")
    if args.compare:
        compare_runs(results, args.compare)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
