"""What a node with TWO namespaces that ran for days leaves on its disk,
written with the program's own writers, the node then RESTARTED over it,
and the newest scrapes sent through the coordinator's own ingest path
with its embedded downsampler flushing: `filesets-restart.py`'s recipe
for a deployment whose coordinator has a namespace list (an
unaggregated namespace at the scrape's cadence and a `downsample.all`
aggregated one).

ONE seeded walk (`server.vals`, made by the harness) is the truth of
both namespaces. The aggregated namespace's points are derived from it
by the rule the configuration states: the window [t, t + R) is one point
stamped t + R holding the last of its scrapes.

Before the restart, from the truth, a block at a time and a namespace
at a time: one `storage.block.encode_block` over every series in shard
order (a partial block, the aggregated namespace's first, is the same
120-column tile with a count a row), `SealedBlock.take` a shard,
`FilesetWriter.write_rows`; then one index segment for every full index
block of each namespace (`NamespaceIndex.index_in_block`,
`index/persist.flush_index`). The unaggregated namespace gets the
`unagg_sealed_blocks` blocks that end where the live stretch begins (its
retention, all of it held); the aggregated one every block that has
closed by then. A block still OPEN where the live stretch begins (the
aggregated namespace's last: `datagen.T0` lies 1,200 s past a 2-hour
boundary) is no fileset on a node's disk: its windows are written to the
node before the restart, `Database.write_batch` with the clock following
them, so they are in its commit log, and the restart's own WAL replay
brings them back into the open buffer.

Then `handle.restart()`: both namespaces come back through the node's
own bootstrap chain. The last `live_steps` scrapes go through
`DownsamplerAndWriter.write_batch` in requests of `batch_samples` rows,
the clock standing at each scrape's own timestamp while it is written
(an untimed sample joins the window the coordinator's clock is in), and
moved to each window's end, where the downsampler is flushed: the newest
aggregated points are the program's own downsampler's.

Nothing here installs a block or fills a registry. Facts beside the
usual two: `walk_s` (from the deployment's boot to this set-up's first
line: the harness making labels and the walk), `fileset_build_s`
(`encode_s` / `persist_s` / `index_build_s` inside it),
`bootstrap_fs_s` (the `bootstrap.filesystem` roots' time, one a
namespace; `verify_s`, `install_s`, `index_s`), `restart_s`,
`downsample_live_s` (the live stretch: writes and flushes) with
`live_rows_flushed`, and what the harness does not count itself:
`unagg_sealed_blocks`, `unagg_filesets`, `agg_points`."""

import time

import numpy as np

from harness import datagen

S = datagen.S


def _bootstrap_spans(since_ns: int) -> dict:
    """The `bootstrap.filesystem` roots the program's tracer kept since
    the restart began (one a namespace), summed, as seconds."""
    from m3_tpu.utils import tracing

    roots = [r for r in tracing.TRACER.recent_traces()
             if r.get("name") == "bootstrap.filesystem"
             and r["start_ns"] >= since_ns]
    if not roots:
        return {}
    out = {"bootstrap_fs_s": sum(r["duration_us"] for r in roots) / 1e6}
    for cost, fact in (("verify_ns", "verify_s"), ("install_ns", "install_s"),
                       ("index_ns", "index_s")):
        out[fact] = sum(r.get("costs", {}).get(cost, 0) for r in roots) / 1e9
    return out


class _Shards:
    """Every series in shard order, so a shard's rows are one slice."""

    def __init__(self, db, ids):
        n = len(ids)
        shard_ids = np.asarray(db.shard_set.lookup_batch(ids), np.int64)
        self.order = np.argsort(shard_ids, kind="stable")
        by_shard = shard_ids[self.order]
        cuts = np.flatnonzero(by_shard[1:] != by_shard[:-1]) + 1
        bounds = list(zip([0] + cuts.tolist(), cuts.tolist() + [n]))
        self.cut = [(int(by_shard[a]), [ids[i] for i in
                                        self.order[a:b].tolist()],
                     np.arange(a, b), np.arange(b - a, dtype=np.int32))
                    for a, b in bounds]
        self.everyone = np.arange(n, dtype=np.int32)


def _write_namespace(handle, ns_name: bytes, shards: _Shards, ts: np.ndarray,
                     vals: np.ndarray, per: int, end_ns: int, spent: dict,
                     say):
    """Points `ts[j]` holding `vals[:, j]` (series in shard order) as
    filesets of namespace `ns_name`, a block at a time, for every block
    that has closed by `end_ns`. Returns (block starts written, points
    written): the points past them lie in the block open at `end_ns`."""
    from m3_tpu.storage.block import encode_block

    bsz = handle.db.namespace(ns_name).opts.block_size_ns
    starts = ts - ts % bsz
    cuts = np.flatnonzero(starts[1:] != starts[:-1]) + 1
    bounds = [(lo, hi) for lo, hi in zip([0] + cuts.tolist(),
                                         cuts.tolist() + [len(ts)])
              if starts[lo] + bsz <= end_ns]
    n = vals.shape[0]
    writer = handle.persist.writer
    for b, (lo, hi) in enumerate(bounds):
        t0 = time.perf_counter()
        count = hi - lo
        if count > per:
            raise RuntimeError(f"{count} points in one block of "
                               f"{ns_name!r}: more than block_steps {per}")
        # the same `per`-column tile whatever the block holds: a partial
        # block repeats its last point and says how many are real
        cols = np.minimum(np.arange(lo, lo + per), hi - 1)
        tdense = np.ascontiguousarray(np.broadcast_to(ts[cols], (n, per)))
        vdense = vals[:, cols].astype(np.float64)
        blk = encode_block(int(starts[lo]), shards.everyone, tdense, vdense,
                           np.full(n, count, np.int32))
        t1 = time.perf_counter()
        for shard, sids, rows, idx in shards.cut:
            writer.write_rows(ns_name, shard, blk.take(rows, idx), sids)
        t2 = time.perf_counter()
        spent["encode"] += t1 - t0
        spent["persist"] += t2 - t1
        if b % 8 == 7 or b == len(bounds) - 1:
            say(f"filesets of {ns_name.decode()}: block {b + 1}/"
                f"{len(bounds)} written (encode {spent['encode']:.1f}s, "
                f"persist {spent['persist']:.1f}s)")
    return len(bounds), bounds[-1][1] if bounds else 0


def _write_index(handle, ns_name: bytes, items, first_ns: int, end_ns: int
                 ) -> int:
    """One segment for every full index block of [first, end) holding
    every series; the block open at `end` is the live writes'."""
    from m3_tpu.index import persist as idx_persist
    from m3_tpu.index.namespace_index import NamespaceIndex

    ns = handle.db.namespace(ns_name)
    index = NamespaceIndex(ns.index.block_size_ns)
    isz = index.block_size_ns
    for ib in range(first_ns - first_ns % isz, end_ns, isz):
        if ib + isz <= end_ns:
            index.index_in_block(items, ib)
    return len(idx_persist.flush_index(handle.persist.root, ns_name, index,
                                       end_ns, ns.opts.retention_ns))


def load(server, say) -> dict:
    t_entry = time.perf_counter()
    from m3_tpu.index.namespace_index import NamespaceIndex
    from m3_tpu.metrics import id as metric_id

    cfg = server.cfg
    setup = server.cell.traffic["setup"]
    handle = server.handle
    if not (hasattr(handle, "restart")
            and hasattr(handle, "unaggregated_namespace")
            and hasattr(handle.persist.writer, "write_rows")
            and hasattr(NamespaceIndex, "index_in_block")):
        raise SystemExit(
            "benchmark: this set-up needs the deployment dbnode-aggns (a "
            "node that restarts, with a coordinator over a namespace list) "
            "and a program whose fileset writer takes rows with their ids")
    steps = int(setup["load_steps"])
    live = int(setup["live_steps"])
    per = int(setup["block_steps"])
    unagg_blocks = int(setup["unagg_sealed_blocks"])
    batch = int(setup["batch_samples"])
    cadence = int(cfg["cadence_s"]) * S
    raw_ns, agg_ns = handle.unaggregated_namespace, handle.namespace
    res = handle.resolution_ns
    win = res // cadence                # scrapes a window
    before = steps - live               # the steps the filesets hold
    if before % win or live % win or before < unagg_blocks * per:
        raise RuntimeError(f"{before} steps before the live stretch and "
                           f"{live} in it are not whole windows of {win}")
    tags = datagen.wire_tags(server.labels)
    name = cfg["schema"]["measurement"].encode()
    # ONE rule for a series' id, in both namespaces and on both ways in:
    # the name and the sorted tags (what the coordinator's writer and its
    # downsampler's sink both make of a label set)
    ids = [metric_id.encode(name, {k: v for k, v in t.items()
                                   if k != b"__name__"}) for t in tags]
    n = len(ids)
    items = list(zip(ids, tags))
    shards = _Shards(handle.db, ids)
    spent = {"encode": 0.0, "persist": 0.0}
    t_build = time.perf_counter()
    vals = server.vals
    # the unaggregated namespace: the blocks that end at the live stretch
    lo = before - unagg_blocks * per
    raw_ts = datagen.step_ts(cfg, np.arange(lo, before))
    if raw_ts[0] % handle.db.namespace(raw_ns).opts.block_size_ns:
        raise RuntimeError("the unaggregated blocks do not start on a block "
                           "boundary")
    sealed_end = int(datagen.step_ts(cfg, before))
    raw_starts, raw_done = _write_namespace(
        handle, raw_ns, shards, raw_ts, vals[shards.order, lo:before], per,
        sealed_end, spent, say)
    if raw_done != len(raw_ts):
        raise RuntimeError("the unaggregated blocks do not end where the "
                           "live stretch begins")
    # the aggregated namespace: window k (k >= 1) holds step k * win - 1
    # and is stamped at its end, step k * win's own timestamp
    k = np.arange(1, before // win + 1)
    agg_ts = datagen.step_ts(cfg, k * win)
    agg_vals = np.ascontiguousarray(vals[:, win - 1:before:win])
    agg_starts, agg_done = _write_namespace(
        handle, agg_ns, shards, agg_ts, agg_vals[shards.order], per,
        sealed_end, spent, say)
    t3 = time.perf_counter()
    segments = (_write_index(handle, raw_ns, items, int(raw_ts[0]), sealed_end)
                + _write_index(handle, agg_ns, items, int(agg_ts[0]),
                               sealed_end))
    index_build_s = time.perf_counter() - t3
    fileset_build_s = time.perf_counter() - t_build
    say(f"index segments: {segments} written in {index_build_s:.1f}s")
    # the block open where the live stretch begins: into the node's
    # buffer and its commit log, the clock following the windows
    t_open = time.perf_counter()
    for j in range(agg_done, len(agg_ts)):
        server.clock[0] = int(agg_ts[j])
        handle.db.write_batch(agg_ns, ids, np.full(n, agg_ts[j], np.int64),
                              agg_vals[:, j].astype(np.float64),
                              tags if j == agg_done else None)
    open_write_s = time.perf_counter() - t_open
    say(f"open block of {agg_ns.decode()}: {len(agg_ts) - agg_done} windows "
        f"a series written to the node in {open_write_s:.1f}s")
    del agg_vals
    # the restart: where the live stretch begins
    server.clock[0] = sealed_end
    t4 = time.perf_counter()
    t4_ns = time.perf_counter_ns()
    results = handle.restart()
    restart_s = time.perf_counter() - t4
    from m3_tpu.storage.mediator import Mediator

    server.mediator = Mediator(handle.db, handle.persist)
    for res_ in results.values():
        for note in res_.notes:
            say(f"bootstrap note: {note}")
    boot = _bootstrap_spans(t4_ns)
    say(f"restarted in {restart_s:.1f}s: {boot}")
    # the live stretch: the coordinator's writer, its downsampler flushed
    # at every window's end
    t5 = time.perf_counter()
    write_batch = handle.writer.write_batch
    flushed = 0
    for step in range(before, steps):
        ts = int(datagen.step_ts(cfg, step))
        server.clock[0] = ts
        values = vals[:, step].astype(np.float64).tolist()
        rows = [(t, ts, v) for t, v in zip(tags, values)]
        for a in range(0, n, batch):
            write_batch(rows[a:a + batch], series_ids=ids[a:a + batch])
        server.clock[0] = ts + cadence
        if (step + 1) % win == 0:
            flushed += handle.node.coordinator.flush_downsampler()
    downsample_live_s = time.perf_counter() - t5
    say(f"live stretch: {live} scrapes written, {flushed} rows flushed by "
        f"this set-up's calls, in {downsample_live_s:.1f}s")
    raw = handle.db.namespace(raw_ns)
    facts = {"series": n, "samples": n * steps,
             "walk_s": t_entry - handle.booted_at,
             "fileset_build_s": fileset_build_s,
             "encode_s": spent["encode"], "persist_s": spent["persist"],
             "index_build_s": index_build_s, "restart_s": restart_s,
             "downsample_live_s": downsample_live_s,
             "live_rows_flushed": flushed,
             "agg_points": n * (steps // win),
             "agg_sealed_blocks": agg_starts,
             "agg_sealed_points": n * agg_done,
             "open_write_s": open_write_s,
             "unagg_sealed_blocks": len({bs for sh in raw.shards.values()
                                         for bs in sh.blocks}),
             "unagg_filesets": sum(
                 len(handle.persist.list_filesets(raw_ns, sid))
                 for sid in raw.shards)}
    if facts["unagg_sealed_blocks"] != raw_starts:
        raise RuntimeError(
            f"the restart brought back {facts['unagg_sealed_blocks']} block "
            f"starts of {raw_ns!r}; {raw_starts} were written")
    facts.update(boot)
    facts.setdefault("bootstrap_fs_s", restart_s)
    return facts
