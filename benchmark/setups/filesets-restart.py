"""What a node that ran for hours leaves on its disk, written with the
program's own writers, and the node then RESTARTED over it: the store is
what the node's own filesystem bootstrap reads back, not what a write
path built in memory.

From the seed's truth (`server.vals`), a 20-minute block at a time:

- the block's `[series, block_steps]` planes go through the program's
  batched device encode (`storage.block.encode_block`, the seal's own
  path: span `encode.block`), ONE call a block over every series in
  shard order, and the sealed block is cut a shard
  (`SealedBlock.take`);
- each shard's block goes through `persist/fs.py`'s fileset writer
  (`FilesetWriter.write_rows`: the rows' ids in hand, no registry);
- every full index block of the reverse index gets one segment holding
  every series written in it, through a `NamespaceIndex` of the
  set-up's own (`index_in_block`, as the write path indexes a series in
  every index block it is written in) and `index/persist.flush_index`.

Then `handle.restart()` (deployments/dbnode-restarted.py): the node is
closed and `services.run_dbnode` runs again over the same directory
with `bootstrap_enabled`, so it comes to serving through its own
bootstrap chain, index segments before data. The last `open_steps`
scrapes are live writes through `Database.write_batch` with the clock
following them: the open buffer, and the index block that was open at
the restart.

Nothing here installs a block or fills a registry. Facts beside the
usual two: `fileset_build_s` (encode + cut + write, and the index
segments), `encode_s` / `persist_s` / `index_build_s` inside it,
`bootstrap_fs_s` (the `bootstrap.filesystem` root span's time where the
program has one, else the restart's wall time) with its `verify_s`,
`install_s` and `index_s`, `restart_s` and `live_write_s`."""

import time

import numpy as np

from harness import datagen

S = datagen.S


def _bootstrap_span() -> dict:
    """The newest `bootstrap.filesystem` root the program's tracer kept,
    as seconds; {} where the program opens no such span."""
    from m3_tpu.utils import tracing

    roots = [r for r in tracing.TRACER.recent_traces()
             if r.get("name") == "bootstrap.filesystem"]
    if not roots:
        return {}
    root = max(roots, key=lambda r: r["start_ns"])
    costs = root.get("costs", {})
    out = {"bootstrap_fs_s": root["duration_us"] / 1e6}
    for cost, fact in (("verify_ns", "verify_s"), ("install_ns", "install_s"),
                       ("index_ns", "index_s")):
        if cost in costs:
            out[fact] = costs[cost] / 1e9
    return out


def load(server, say) -> dict:
    from m3_tpu.index import persist as idx_persist
    from m3_tpu.index.namespace_index import NamespaceIndex
    from m3_tpu.metrics import id as metric_id
    from m3_tpu.storage.block import encode_block

    cfg = server.cfg
    setup = server.cell.traffic["setup"]
    handle = server.handle
    if not (hasattr(handle, "restart")
            and hasattr(handle.persist.writer, "write_rows")
            and hasattr(NamespaceIndex, "index_in_block")):
        raise SystemExit(
            "benchmark: this set-up needs a deployment that restarts "
            "(dbnode-restarted) and a program whose fileset writer takes "
            "rows with their ids and whose index takes a series in every "
            "index block it is written in; this program has not")
    steps = int(setup["load_steps"])
    per = int(setup["block_steps"])
    sealed = int(setup["sealed_blocks"])
    open_steps = int(setup["open_steps"])
    if sealed * per + open_steps != steps:
        raise RuntimeError(f"{sealed} blocks of {per} steps and {open_steps} "
                           f"open steps are not the {steps} load steps")
    cadence = int(cfg["cadence_s"]) * S
    tags = datagen.wire_tags(server.labels)
    name = cfg["schema"]["measurement"].encode()
    ids = [metric_id.encode(name, {k: v for k, v in t.items()
                                   if k != b"__name__"}) for t in tags]
    n = len(ids)
    ns_name = handle.namespace
    ns = handle.db.namespace(ns_name)
    bsz = ns.opts.block_size_ns
    if per * cadence != bsz or datagen.T0 % bsz:
        raise RuntimeError("block_steps scrapes are not one block of the "
                           "namespace")
    # every series in shard order, so a shard's rows are one slice
    shard_ids = np.asarray(handle.db.shard_set.lookup_batch(ids), np.int64)
    order = np.argsort(shard_ids, kind="stable")
    by_shard = shard_ids[order]
    cuts = np.flatnonzero(by_shard[1:] != by_shard[:-1]) + 1
    bounds = list(zip([0] + cuts.tolist(), cuts.tolist() + [n]))
    shard_of = [int(by_shard[a]) for a, _b in bounds]
    ids_of = [[ids[i] for i in order[a:b].tolist()] for a, b in bounds]
    rows_of = [np.arange(a, b) for a, b in bounds]
    local = [np.arange(b - a, dtype=np.int32) for a, b in bounds]
    writer = handle.persist.writer
    vals = server.vals
    spent = {"encode": 0.0, "persist": 0.0}
    t_build = time.perf_counter()
    npoints = np.full(n, per, np.int32)
    everyone = np.arange(n, dtype=np.int32)
    for b in range(sealed):
        t0 = time.perf_counter()
        bs = int(datagen.step_ts(cfg, b * per))
        ts_row = datagen.step_ts(cfg, np.arange(b * per, (b + 1) * per))
        tdense = np.broadcast_to(ts_row, (n, per))
        vdense = vals[order, b * per:(b + 1) * per].astype(np.float64)
        blk = encode_block(bs, everyone, np.ascontiguousarray(tdense),
                           vdense, npoints)
        t1 = time.perf_counter()
        for shard, sids, rows, idx in zip(shard_of, ids_of, rows_of, local):
            writer.write_rows(ns_name, shard, blk.take(rows, idx), sids)
        t2 = time.perf_counter()
        spent["encode"] += t1 - t0
        spent["persist"] += t2 - t1
        if b % 8 == 7 or b == sealed - 1:
            say(f"filesets: block {b + 1}/{sealed} written "
                f"(encode {spent['encode']:.1f}s, persist "
                f"{spent['persist']:.1f}s)")
    # the reverse index as the node's flushes left it: every full index
    # block holds every series written in it
    t3 = time.perf_counter()
    sealed_end = int(datagen.step_ts(cfg, sealed * per))
    index = NamespaceIndex(ns.index.block_size_ns)
    items = list(zip(ids, tags))
    isz = index.block_size_ns
    for ib in range(datagen.T0 - datagen.T0 % isz, sealed_end, isz):
        if ib + isz <= sealed_end:      # the open one: the live writes'
            index.index_in_block(items, ib)
    segments = idx_persist.flush_index(handle.persist.root, ns_name, index,
                                       sealed_end, ns.opts.retention_ns)
    index_build_s = time.perf_counter() - t3
    fileset_build_s = time.perf_counter() - t_build
    say(f"index segments: {len(segments)} written in {index_build_s:.1f}s")
    # the restart: at the end of the last sealed block, the live writes
    # then carry the clock on
    server.clock[0] = sealed_end
    t4 = time.perf_counter()
    results = handle.restart()
    restart_s = time.perf_counter() - t4
    from m3_tpu.storage.mediator import Mediator

    server.mediator = Mediator(handle.db, handle.persist)
    for res in results.values():
        for note in res.notes:
            say(f"bootstrap note: {note}")
    boot = _bootstrap_span()
    say(f"restarted in {restart_s:.1f}s: {boot}")
    # live writes: the open buffer, and the index block open at the restart
    t5 = time.perf_counter()
    db = handle.db
    for k in range(sealed * per, steps):
        ts = int(datagen.step_ts(cfg, k))
        server.clock[0] = ts + cadence
        db.write_batch(ns_name, ids, np.full(n, ts, np.int64),
                       vals[:, k].astype(np.float64),
                       tags if k == sealed * per else None)
    live_write_s = time.perf_counter() - t5
    facts = {"series": n, "samples": n * steps,
             "fileset_build_s": fileset_build_s,
             "encode_s": spent["encode"], "persist_s": spent["persist"],
             "index_build_s": index_build_s, "restart_s": restart_s,
             "live_write_s": live_write_s}
    facts.update(boot)
    facts.setdefault("bootstrap_fs_s", restart_s)
    return facts
