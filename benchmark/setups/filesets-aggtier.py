"""What a cluster with an aggregation tier that ran for half a day
leaves behind, and the tier then run LIVE over the last minutes before
the window: `filesets-aggns.py`'s recipe for the aggregated namespace,
called (nothing of it is copied), then the newest scrapes written
through the whole tier in the window's request shape.

ONE seeded walk (`server.vals`) and the seed's per-host offsets
(`harness/promoffsets.py`) are the truth. With a host's offset under
10 s and `datagen.T0` on a minute, a minute's last scrape is the same
scrape on the shared grid and on the offsets, so the 1-minute points a
node would hold of the history are `filesets-aggns`'s: the window
[t, t + 60 s) is one point stamped t + 60 s holding its last scrape.

1. **History as filesets** (`filesets-aggns`'s `_Shards`,
   `_write_namespace`, `_write_index`): every whole minute before the
   live stretch, the 2-hour blocks that have closed by then as filesets
   (`setup.agg_sealed_blocks` block starts x 64 shards), the block still
   open written to the node so that it is in its commit log; one index
   segment a full index block. The unaggregated namespace gets nothing
   (`unaggregated_history_held`: no fetch of the window resolves to it).
2. **The restart** (`handle.restart()`): the node comes back through its
   own bootstrap chain, its coordinator registers its m3msg consumer
   again and finds the aggregator pair's placement again, in KV.
3. **The live stretch**: the last `live_steps` scrapes before the
   warm-up's, each host at its own offset, in requests of
   `batch_samples` rows, hosts in offset order (the window's groups:
   `promoffsets.send_groups`), through the coordinator's writer: every
   sample to `default` directly and, matched by the downsample-all rule,
   as a timed gauge to BOTH aggregators. The injected clock follows the
   newest scrape sent, waits at the end of each scrape until both
   replicas have taken it in (`aggregator.add.timed`), and never runs
   more than half a lease past the leader's last renewal (the lease is on the injected clock; the set-up
   writes faster than real time). A minute is waited for once the clock
   has passed its end and `buffer_past`: flushed by the leader,
   produced, consumed and written by the coordinator's ingester
   (`coordinator.m3msg.rows`, acknowledged in full). 17 scrapes before a
   warm-up at :40 begin with the :50 scrape of a minute, so THREE
   minutes close in the stretch (the first holds that one scrape, its
   last): after the second, the leader is asked to step down (`POST
   /resign` at its admin address, as an operator would) and the third is
   flushed by the other instance, from the flush times the first left in
   KV. Every run's store was produced across a handoff.
4. **Two mediator ticks**, as a live node's would have come meanwhile
   (the window's tick every 10 s): the snapshot of the open buffers
   compiles its encode shapes in set-up.

Facts beside the usual two: `walk_s`, `fileset_build_s` (`encode_s`,
`persist_s`, `index_build_s`), `restart_s`, `bootstrap_fs_s`,
`tier_live_s` (the live stretch: writes, flushes, the handoff), in it
`tier_write_s` (inside the writer) and `tier_wait_s` (waiting for
minutes), `tier_us_per_sample` (tier_write_s over the stretch's samples:
direct write + match + client + both replicas' adds on this GIL),
`live_rows_ingested`, `windows_by_leader`, `handoff_s`, `agg_points`,
`agg_sealed_blocks`, `agg_filesets`."""

import json
import time
import urllib.request

import numpy as np

from harness import datagen, promoffsets, spec

S = datagen.S
_aggns = spec.load_part("setups", "filesets-aggns")


def _moved(c0: dict, prefix: str) -> float:
    from harness import server as server_mod

    return sum(v - c0.get(k, 0) for k, v in server_mod.counters().items()
               if k.startswith(prefix))


def _status(agg) -> dict:
    with urllib.request.urlopen(agg.admin_endpoint + "/status",
                                timeout=30) as r:
        return json.loads(r.read())["status"]["flushStatus"]


def leader_of(handle):
    for iid, agg in handle.aggregators.items():
        if _status(agg)["electionState"] == "leader":
            return iid
    return None


def wait_for(what: str, done, timeout_s: float = 120.0):
    deadline = time.perf_counter() + timeout_s
    while not done():
        if time.perf_counter() > deadline:
            raise RuntimeError(f"set-up: {what} did not come in {timeout_s}s")
        time.sleep(0.05)


def pace(server, lease_key: str, ttl_ns: int, target_ns: int):
    """The clock to `target_ns`, no further than half a lease past the
    leader's last renewal (a leader renews at each of its flush checks,
    once a second of REAL time)."""
    store = server.handle.kv.store
    while True:
        val = store.get(lease_key)
        at = json.loads(val.data.decode())["at"] if val is not None else 0
        if at == 0 or target_ns - at <= ttl_ns // 2:
            break
        # as far as the lease allows, then wait for the next renewal
        if at + ttl_ns // 2 > server.clock[0]:
            server.clock[0] = at + ttl_ns // 2
        time.sleep(0.02)
    if target_ns > server.clock[0]:
        server.clock[0] = target_ns


def load(server, say) -> dict:
    t_entry = time.perf_counter()
    from m3_tpu.metrics import id as metric_id
    from m3_tpu.query.promql import parse_duration_ns
    from m3_tpu.storage.mediator import Mediator
    from harness import server as server_mod

    cfg = server.cfg
    setup = server.cell.traffic["setup"]
    handle = server.handle
    if not (hasattr(handle, "restart") and hasattr(handle, "aggregators")
            and hasattr(handle, "aggregated_namespace")):
        raise SystemExit("benchmark: this set-up needs the deployment "
                         "aggregator-tier")
    steps, live = int(setup["load_steps"]), int(setup["live_steps"])
    per, batch = int(setup["block_steps"]), int(setup["batch_samples"])
    cadence = int(cfg["cadence_s"]) * S
    raw_ns, agg_ns = handle.namespace, handle.aggregated_namespace
    res = handle.resolution_ns
    win = res // cadence
    before = steps - live                 # the live stretch's first scrape
    whole = before - before % win         # the steps the history's minutes hold
    tags = datagen.wire_tags(server.labels)
    name = cfg["schema"]["measurement"].encode()
    ids = [metric_id.encode(name, {k: v for k, v in t.items()
                                   if k != b"__name__"}) for t in tags]
    n = len(ids)
    nf = len(cfg["schema"]["fields"])
    vals = server.vals
    # 1. the history: filesets-aggns's aggregated-namespace recipe
    shards = _aggns._Shards(handle.db, ids)
    spent = {"encode": 0.0, "persist": 0.0}
    t_build = time.perf_counter()
    k = np.arange(1, whole // win + 1)
    agg_ts = datagen.step_ts(cfg, k * win)
    agg_vals = np.ascontiguousarray(vals[:, win - 1:whole:win])
    restart_at = int(datagen.step_ts(cfg, before))
    agg_starts, agg_done = _aggns._write_namespace(
        handle, agg_ns, shards, agg_ts, agg_vals[shards.order], per,
        restart_at, spent, say)
    if agg_starts != int(setup["agg_sealed_blocks"]):
        raise RuntimeError(f"{agg_starts} block starts of {agg_ns!r} had "
                           f"closed; the traffic file says "
                           f"{setup['agg_sealed_blocks']}")
    t3 = time.perf_counter()
    segments = _aggns._write_index(handle, agg_ns, list(zip(ids, tags)),
                                   int(agg_ts[0]), restart_at)
    index_build_s = time.perf_counter() - t3
    fileset_build_s = time.perf_counter() - t_build
    say(f"index segments: {segments} written in {index_build_s:.1f}s")
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
    # 2. the restart, where the live stretch begins
    server.clock[0] = restart_at
    t4, t4_ns = time.perf_counter(), time.perf_counter_ns()
    results = handle.restart()
    restart_s = time.perf_counter() - t4
    server.mediator = Mediator(handle.db, handle.persist)
    for res_ in results.values():
        for note in res_.notes:
            say(f"bootstrap note: {note}")
    boot = _aggns._bootstrap_spans(t4_ns)
    agg = handle.db.namespace(agg_ns)
    filesets = sum(len(handle.persist.list_filesets(agg_ns, sid))
                   for sid in agg.shards)
    sealed = len({bs for sh in agg.shards.values() for bs in sh.blocks})
    if sealed != agg_starts or filesets < agg_starts * len(agg.shards):
        raise RuntimeError(
            f"the restart brought back {sealed} block starts of {agg_ns!r} "
            f"from {filesets} filesets; {agg_starts} x {len(agg.shards)} "
            "were written")
    say(f"restarted in {restart_s:.1f}s: {boot}")
    # 3. the live stretch, through the whole tier
    t5 = time.perf_counter()
    c0 = server_mod.counters()
    agg_cfg = cfg["aggregators"][0]
    lease_key = "_leader/" + agg_cfg["election_id"]
    ttl = parse_duration_ns(agg_cfg["election_ttl"])
    buffer_past = parse_duration_ns(agg_cfg["buffer_past"])
    wait_for("a leader", lambda: leader_of(handle) is not None)
    first = leader_of(handle)
    off_ns = promoffsets.series_offsets_ns(cfg, server.seed)
    groups = []
    for hosts, due_ms in promoffsets.send_groups(cfg, server.seed,
                                                 max(1, batch // nf)):
        rows = (hosts[:, None] * nf + np.arange(nf)[None, :]).ravel()
        groups.append((due_ms, rows.tolist(), off_ns[rows],
                       [ids[r] for r in rows], [tags[r] for r in rows]))
    write_batch = handle.writer.write_batch
    minutes_due = []            # (stamp, rows ingested once it is in)
    tier_write_s = tier_wait_s = handoff_s = 0.0
    windows_by = {}
    resigned = False

    def ingested() -> float:
        return _moved(c0, "coordinator.m3msg.rows")

    def unacked() -> int:
        return sum(a.flush_handler.unacked()
                   for a in handle.aggregators.values())

    for step in range(before, steps):
        ts = int(datagen.step_ts(cfg, step))
        col = vals[:, step].astype(np.float64)
        for due_ms, rows, offs, gids, gtags in groups:
            pace(server, lease_key, ttl, ts + due_ms * promoffsets.MS)
            t_w = time.perf_counter()
            write_batch([(t, ts + int(o), v) for t, o, v in zip(
                gtags, offs.tolist(), col[rows].tolist())], series_ids=gids)
            tier_write_s += time.perf_counter() - t_w
        # both replicas have taken this scrape in before the clock moves
        # on: the set-up's clock runs ahead of real time, and a frame
        # still queued when the clock passed its window's end +
        # buffer_past would be dropped as late
        t_q = time.perf_counter()
        sent = len(handle.aggregators) * n * (step - before + 1)
        wait_for(f"the aggregators' adds of scrape {step}",
                 lambda: _moved(c0, "aggregator.add.") >= sent)
        tier_wait_s += time.perf_counter() - t_q
        # the minute that has just closed for flushing, if one has
        closed = (ts + cadence - buffer_past) // res * res
        if closed > restart_at - cadence and closed not in [
                m for m, _ in minutes_due] and closed <= ts:
            minutes_due.append((closed, n * (len(minutes_due) + 1)))
            t_q = time.perf_counter()
            pace(server, lease_key, ttl, closed + buffer_past + S)
            want = minutes_due[-1][1]
            wait_for(f"the minute stamped {closed // S}",
                     lambda: ingested() >= want and unacked() == 0)
            tier_wait_s += time.perf_counter() - t_q
            lead = leader_of(handle)
            windows_by[lead] = windows_by.get(lead, 0) + 1
            say(f"minute stamped {closed // S} flushed by {lead}, "
                f"{int(ingested())} rows ingested so far")
            if len(minutes_due) == 2 and not resigned:
                # a graceful handoff between two minutes, as an operator's
                t_h = time.perf_counter()
                req = urllib.request.Request(
                    handle.aggregators[first].admin_endpoint + "/resign",
                    data=b"", method="POST")
                with urllib.request.urlopen(req, timeout=30) as r:
                    r.read()
                wait_for("the follower's takeover", lambda: leader_of(
                    handle) not in (None, first))
                handoff_s = time.perf_counter() - t_h
                resigned = True
                say(f"{first} resigned; {leader_of(handle)} leads after "
                    f"{handoff_s:.2f}s")
    tier_live_s = time.perf_counter() - t5
    flushed = _moved(c0, "aggregator.flush.windows")
    if len(minutes_due) != 3 or flushed != 3 * n or not resigned:
        raise RuntimeError(
            f"the live stretch closed {len(minutes_due)} minutes and the "
            f"pair flushed {flushed} windows; 3 minutes of {n} were due")
    samples = n * live
    say(f"live stretch: {live} scrapes through the tier in "
        f"{tier_live_s:.1f}s ({tier_write_s:.1f}s in the writer, "
        f"{tier_write_s / samples * 1e6:.1f} us a sample; "
        f"{tier_wait_s:.1f}s waiting for minutes), windows by leader "
        f"{windows_by}")
    # the mediator as a live node's would have ticked meanwhile: its
    # snapshot of the open buffers brings every encode shape a tick of
    # the window needs through its compile here
    for _ in range(2):
        say(f"mediator after the live stretch: {server.tick()}")
    # where each closed minute of the stretch came from, for the checks
    server.tier_minutes = {"stamps_s": [m // S for m, _ in minutes_due],
                           "first_leader": first,
                           "second_leader": leader_of(handle),
                           "history_minutes": int(whole // win),
                           "counters0": c0}
    facts = {"series": n, "samples": n * (whole // win) + samples,
             "walk_s": t_entry - handle.booted_at,
             "fileset_build_s": fileset_build_s,
             "encode_s": spent["encode"], "persist_s": spent["persist"],
             "index_build_s": index_build_s, "restart_s": restart_s,
             "open_write_s": open_write_s,
             "tier_live_s": tier_live_s, "tier_write_s": tier_write_s,
             "tier_wait_s": tier_wait_s, "handoff_s": handoff_s,
             "tier_us_per_sample": tier_write_s / samples * 1e6,
             "live_rows_ingested": ingested(),
             "agg_points": n * (whole // win + 3),
             "agg_sealed_blocks": agg_starts, "agg_filesets": filesets}
    facts.update(boot)
    facts.setdefault("bootstrap_fs_s", restart_s)
    return facts
