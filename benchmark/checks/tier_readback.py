"""The aggregation tier's guarantees, as far as a run can show them
(`aggregation`, `delivery`, `handoff`, `lateness` in the configuration):
every closed minute of the set-up's live stretch and of the window is in
the 1-minute namespace ONCE and EXACT, across the set-up's leader
handoff, and nothing the tier was given is still unacknowledged.

**Read back over HTTP**, each pair (host, minute) as a range query that
begins before the unaggregated retention (so the resolver answers from
the aggregated namespace alone) and ends at the minute's stamp, a
1-minute window a step: the last step holds that minute's point or
nothing; a host's ten series are ten pairs. `tier_readback.history_pairs`
pairs from the filesets and the commit-log replay (at least two from
every block start), `setup_pairs` from the minutes the tier flushed in
set-up (every one of them, so both leaders' windows), `window_pairs`
from the minutes that closed in the window. The truth is
`reference/aggtier_ref.py`'s `minute_points` over the seed's walk, the
seed's offsets and the ACKNOWLEDGED write requests alone.

Rows, limit 0 each: `tier_readback_mismatched` (a point that is not the
reference's, a point where it has none, none where it has one),
`tier_reads_failed`, `windows_missing` and `windows_emitted_by_both`,
counted A MINUTE AND AN INSTANCE from the program's `aggregator.flush`
spans (the deployment's `tier_log`: who emitted how many windows of
which minute): every minute due since the live stretch began has ONE
emitter, and that one emitted as many windows as the reference has
series with an acknowledged sample in the minute — what the busiest
emitter lacks of them is missing, what it has over them and everything
any other instance emitted of that minute was emitted by both; one
window lost and another doubled do not cancel, in one minute or across
two. These are the rows that cover all 40,000 windows of a minute; the
read-back samples.
`rows_unacknowledged_at_end` (still in a producer once
`settle_s` have passed), `late_dropped_in_window`
(`aggregator.add.late_dropped`: the fleet's own samples are never late),
`fetches_not_resolved_to_aggregated` (the window's fetches the resolver
sent anywhere else), `tier_flush_errors`. Without a limit: how many
pairs of each kind were compared (`*_pairs_compared_at_least` holds
them to what was asked for), `minutes_closed_in_window`.

Controls, put in the program's place: `lost_window` (one topic shard's
windows of the window's minute neither emitted nor stored),
`both_flush` (the follower emitted the window's minute too), `stale`
(nothing the tier flushed is there: the store ends where the filesets
end). `benchmark/tests/test_aggtier.py` plants the first two IN the
program as well (a follower that emits, a shard whose windows are
dropped before the emit) and reads the same rows."""

import json
import time
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen, promoffsets, spec
from harness.cellrun import say

NO_LIMIT = 1e18
S = datagen.S


def acked_matrix(run, m) -> np.ndarray:
    """[hosts, steps] bool: scrape k of host h was acknowledged. The
    history's steps count as acknowledged (the filesets are derived from
    them), the live stretch's and the warm-up's were (the set-up and the
    warm-up fail otherwise), the window's by the generator's records."""
    t, cfg = m.cell.traffic, m.cell.config
    setup = t["setup"]
    steps, live = int(setup["load_steps"]), int(setup["live_steps"])
    win = int(run.server.handle.resolution_ns // S) // int(cfg["cadence_s"])
    before = steps - live
    acked = np.zeros((int(cfg["scale"]), run.server.vals.shape[1]), bool)
    acked[:, :before - before % win] = True
    acked[:, before:steps + 1] = True
    kind = spec.load_part("traffic_kinds", t["kind"])
    for hosts, k, _sent, _done, full in kind.window_writes(
            m.cell.to_wire(), run.seed, m.rec):
        if full:
            acked[hosts, k] = True
    return acked


def closed_minutes(run) -> int:
    """How many minute stamps are closed for flushing on the injected
    clock as it stands: end + buffer_past <= now."""
    cfg = run.server.cfg
    from m3_tpu.query.promql import parse_duration_ns

    past = parse_duration_ns(cfg["aggregators"][0]["buffer_past"])
    res = run.server.handle.resolution_ns
    return int((int(run.server.clock[0]) - past - datagen.T0) // res)


def emitted_by(handle) -> dict:
    """{window end in seconds: {instance: windows emitted}}, from the
    `aggregator.flush` roots the deployment's reporter kept."""
    out = {}
    for instance, _role, _began, ends in list(handle.tier_log.flushes):
        for end_ns, n in ends:
            by = out.setdefault(int(end_ns) // S, {})
            by[instance] = by.get(instance, 0) + int(n)
    return out


def window_accounts(emitted: dict, due: dict) -> dict:
    """`due` {window end s: windows the reference has}; see the rows."""
    missing = both = 0
    for end_s in sorted(set(due) | set(emitted)):
        counts = sorted(emitted.get(end_s, {}).values(), reverse=True)
        top = counts[0] if counts else 0
        want = due.get(end_s, 0)
        missing += max(0, want - top)
        both += max(0, top - want) + sum(counts[1:])
    return {"windows_missing": missing, "windows_emitted_by_both": both}


def _read(server, host: int, stamp_s: int, name: str, res_s: int):
    q = 'max_over_time(%s{hostname="host_%d"}[%ds])' % (name, host, res_s)
    url = server.base + "/api/v1/query_range?" + urllib.parse.urlencode({
        "query": q, "start": stamp_s - 8100, "end": stamp_s, "step": res_s})
    with urllib.request.urlopen(url, timeout=120) as r:
        res = json.loads(r.read())["data"]["result"]
    out = {}
    for s in res:
        t, v = s["values"][-1]
        if int(float(t)) == stamp_s:
            out[s["metric"].get("field")] = float(v)
    return out


def read_back(run, m, control=None) -> dict:
    from m3_tpu.metrics import id as metric_id
    from m3_tpu.utils.hashing import murmur3_32_cached
    from harness import server as server_mod

    cell, cfg, server = m.cell, m.cell.config, run.server
    handle, tier = server.handle, server.tier_minutes
    ref = spec.load_part("reference", "aggtier_ref")
    want_n = cell.traffic["tier_readback"]
    fields = cfg["schema"]["fields"]
    nf = len(fields)
    name = cfg["schema"]["measurement"]
    res_s = handle.resolution_ns // S
    t0_s = datagen.T0 // S
    # settled: nothing in a producer, no flush round in progress
    deadline = time.perf_counter() + float(want_n.get("settle_s", 20))
    n_closed = closed_minutes(run)
    c0 = tier["counters0"]
    history = tier["history_minutes"]
    acked = acked_matrix(run, m)
    off = promoffsets.offsets_ms(cfg, run.seed)
    stamps, truth = ref.minute_points(cfg, server.vals, acked, off, t0_s)
    stamps = stamps.tolist()
    # the windows due a minute: the series the reference has a point for
    due = {stamps[j]: int((~np.isnan(truth[:, j])).sum())
           for j in range(history, n_closed)}

    def flushed():
        return sum(sum(by.values()) for by in emitted_by(handle).values())

    unacked = lambda: sum(a.flush_handler.unacked()     # noqa: E731
                          for a in handle.aggregators.values())
    while time.perf_counter() < deadline and (
            unacked() or flushed() < sum(due.values())):
        time.sleep(0.1)
    # the minutes by where they came from: 0-based minute j is stamped
    # t0 + (j + 1) * res
    setup_js = [s // res_s - t0_s // res_s - 1 for s in tier["stamps_s"]]
    window_js = list(range(setup_js[-1] + 1, n_closed))
    rng = np.random.default_rng([run.seed & 0xFFFFFFFF, run.seed >> 32, 53])
    block_s = ref.aggregated_namespace(cfg)["block_s"]
    block_of = (t0_s + (np.arange(history) + 1) * res_s) // block_s
    picks = []
    per_block = max(2, -(-int(want_n["history_pairs"])
                         // (nf * len(np.unique(block_of)))))
    for b in np.unique(block_of):
        js = np.flatnonzero(block_of == b)
        picks += [("history", int(rng.choice(js)),
                   int(rng.integers(0, cfg["scale"])))
                  for _ in range(per_block)]
    for kind_, js, pairs in (("setup", setup_js, want_n["setup_pairs"]),
                             ("window", window_js, want_n["window_pairs"])):
        if js:
            per = -(-int(pairs) // (nf * len(js)))
            picks += [(kind_, int(j), int(rng.integers(0, cfg["scale"])))
                      for j in js for _ in range(per)]
    # the control `lost_window`: one topic shard's rows of the newest
    # closed minute were never stored
    labels = datagen.wire_tags(server.labels)
    lost = set()
    if control == "lost_window":
        shards_n = int(cfg["dbnode"]["coordinator"]["ingest"]["m3msg"]
                       ["num_shards"])
        for i, tags in enumerate(labels):
            sid = metric_id.encode(tags[b"__name__"], {
                k: v for k, v in tags.items() if k != b"__name__"})
            if murmur3_32_cached(sid) % shards_n == 0:
                lost.add(i)
    out = {"history_pairs": 0, "setup_pairs": 0, "window_pairs": 0,
           "tier_readback_mismatched": 0, "tier_reads_failed": 0}
    shown = 0
    newest = n_closed - 1
    for kind_, j, host in picks:
        try:
            got = _read(server, host, stamps[j], name, res_s)
        except (OSError, ValueError, KeyError, IndexError):
            out["tier_reads_failed"] += 1
            continue
        for f, fname in enumerate(fields):
            i = host * nf + f
            have = got.get(fname)
            if control == "stale" and kind_ != "history":
                have = None
            if control == "lost_window" and j == newest and i in lost:
                have = None
            want = truth[i, j]
            out[kind_ + "_pairs"] += 1
            same = (have is None and np.isnan(want)) or (
                have is not None and have == float(want))
            if not same:
                out["tier_readback_mismatched"] += 1
                if shown < 5 and control is None:
                    shown += 1
                    say(f"aggregate ({kind_}, host {host}, minute stamped "
                        f"{stamps[j]}, {fname}): want {want}, read {have}")
    emitted = emitted_by(handle)
    if control in ("both_flush", "lost_window"):
        by = emitted.setdefault(stamps[newest], {})
        leader = max(by, key=by.get, default="agg0")
        if control == "both_flush":     # the follower emitted it as well
            other = next(i for i in handle.aggregators if i != leader)
            by[other] = by.get(other, 0) + due[stamps[newest]]
        else:
            by[leader] = by.get(leader, 0) - len(lost)
    out.update(window_accounts(emitted, due))
    out["rows_unacknowledged_at_end"] = unacked()
    out["minutes_closed_in_window"] = len(window_js)
    out["late_dropped_in_window"] = sum(
        m.moved(k) for k in m.counters1
        if k.startswith("aggregator.add.late_dropped"))
    out["fetches_not_resolved_to_aggregated"] = (
        m.moved("query.resolve.unaggregated")
        + m.moved("query.resolve.partial"))
    out["tier_flush_errors"] = sum(
        v - c0.get(k, 0) for k, v in server_mod.counters().items()
        if k.startswith("aggregator.flush.errors"))
    return out


def check(run, m, control=None):
    rb = read_back(run, m, control)
    say(f"tier read-back: {rb}")
    want = m.cell.traffic["tier_readback"]
    rows = [(k, rb[k], 0) for k in (
        "tier_readback_mismatched", "tier_reads_failed", "windows_missing",
        "windows_emitted_by_both", "rows_unacknowledged_at_end",
        "late_dropped_in_window", "fetches_not_resolved_to_aggregated",
        "tier_flush_errors")]
    rows += [("tier_history_pairs_compared_at_least", -rb["history_pairs"],
              -int(want["history_pairs"])),
             ("tier_setup_pairs_compared_at_least", -rb["setup_pairs"],
              -int(want["setup_pairs"])),
             ("tier_window_pairs_compared_at_least", -rb["window_pairs"],
              -int(want["window_pairs"]) * bool(
                  rb["minutes_closed_in_window"])),
             ("minutes_closed_in_window", rb["minutes_closed_in_window"],
              NO_LIMIT)]
    failed = int(rb["tier_readback_mismatched"] + rb["tier_reads_failed"]
                 + rb["windows_missing"] + rb["windows_emitted_by_both"])
    return rows, failed
