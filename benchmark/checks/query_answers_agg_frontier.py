"""`query_answers` for panels that read the aggregated namespace while
the tier appends to it: every read of the window answered, and a seeded
sample of the answers equal to what the plain reference
(`reference/aggtier_ref.py`) computes from the seed's walk, the seed's
offsets and the acknowledged writes, with the AGGREGATED frontier held
exactly, the way `query_answers_frontier` holds the raw one.

What a read may have seen of a minute is decided a series, from stamps
taken on the one clock both processes share (`perf_counter_ns`): the
program's own spans, as the deployment's reporter kept them
(`handle.tier_log`) — when each flush round that emitted a minute BEGAN
(the `aggregator.flush` root's start and its `window_ends`) and when
the ingest of each consumed batch ENDED (the `coordinator.m3msg.ingest`
span's end, after its write returned: the m3msg shard, the batch's
oldest and newest stamp) — against the generator's `sent` and `done`.
An answer DONE before the round that
flushed a minute began must lack that minute's point; one SENT after the
write of the series' row returned must hold it; one between may do
either. The reference's rows are evaluated over the points an answer
must hold and again over those joined with the points it may hold
(`series_rows(..., points=...)`; a minute that is not there yet leaves
its subquery step to the lookback, the minute before), and
`compare_frontier` accepts, for these MAX classes, any group value some
choice a series between the two gives, at `worst_rel_gap`. No step is
left out because it is "too close to now". The history, and the minutes the set-up's live
stretch closed, every answer must hold.

Rows beyond `query_answers`' own: `frontier_pairs` ((row, step) pairs
that a may-point reaches), `answers_took_in_flight` (pairs whose served
value was a may-point's) and `answers_not_resolved_to_aggregated`
(limit 0). Controls, put in the program's place: `stale` (an answer
without anything the tier flushed) and `bf16`."""

import numpy as np

from harness import datagen, promoffsets, spec
from harness.cellrun import say

NO_LIMIT = 1e18
S = datagen.S

_readback = spec.load_part("checks", "tier_readback")


def _frontier_times(run, stamps, n_series):
    """(began [K], written [series, K]) in perf_counter_ns: when the
    flush round that emitted minute j began (inf: never), and when the
    ingester's write of series i's point of minute j returned."""
    from m3_tpu.metrics import id as metric_id
    from m3_tpu.utils.hashing import murmur3_32_cached

    server = run.server
    handle = server.handle
    shards_n = int(server.cfg["dbnode"]["coordinator"]["ingest"]["m3msg"]
                   ["num_shards"])
    shard_of = np.array([murmur3_32_cached(metric_id.encode(
        t[b"__name__"], {k: v for k, v in t.items() if k != b"__name__"}))
        % shards_n for t in datagen.wire_tags(server.labels)])
    at = {int(s): j for j, s in enumerate(stamps)}
    began = np.full(len(stamps), np.inf)
    for _instance, _role, t_began, ends in list(handle.tier_log.flushes):
        for end_ns, _n in ends:
            j = at.get(int(end_ns) // S)
            if j is not None:
                began[j] = min(began[j], t_began)
    written = np.full((n_series, len(stamps)), np.inf)
    for shard, oldest, newest, t_done in list(handle.tier_log.writes):
        rows = np.flatnonzero(shard_of == shard)
        for s in range(oldest // S, newest // S + 1, 60):
            j = at.get(s)
            if j is not None:
                # a redelivery writes the row again: the first return
                written[rows, j] = np.minimum(written[rows, j], t_done)
    return began, written


def compare_answers(run, m, control=None) -> dict:
    ref = spec.load_part("reference", m.cell.reference)
    if control not in ref.CONTROLS:     # another check's control
        control = None
    kind = spec.load_part("traffic_kinds", m.cell.traffic["kind"])
    cell, server, keep = m.cell, run.server, m.keep
    cfg = cell.config
    t0_s = datagen.T0 // S
    cadence_s = int(cfg["cadence_s"])
    base_step = int(m.rec["base_step"][0])
    reqs = kind.requests_for(cell.to_wire(), run.seed, m.seconds, base_step)
    due_s = (m.rec["due"] - m.window[0]) / 1e9
    vals = server.vals
    acked = _readback.acked_matrix(run, m)
    off = promoffsets.offsets_ms(cfg, run.seed)
    stamps, truth = ref.minute_points(cfg, vals, acked, off, t0_s)
    n_closed = _readback.closed_minutes(run)
    tier = server.tier_minutes
    settled = (tier["stamps_s"][-1] - t0_s) // 60   # minutes before the window
    began, written = _frontier_times(run, stamps, truth.shape[0])
    at = {int(i): j for j, i in enumerate(m.rec["i"])}
    gap_limit = float(cell.traffic["limits"]["worst_rel_gap"])
    stale_after_s = t0_s + tier["history_minutes"] * 60
    agg = {"answers": 0, "values": 0, "label_sets_differ": 0,
           "points_missing_or_extra": 0, "worst_rel_gap": 0.0,
           "unanswered": 0, "frontier_pairs": 0, "took_in_flight": 0,
           "not_aggregated": 0}
    for lo in range(0, len(keep), 50):
        bodies = run.child.call(op="bodies",
                                indices=keep[lo:lo + 50])["bodies"]
        for i in keep[lo:lo + 50]:
            got = bodies.get(str(i))
            if got is None or got[0] != 200 or i not in at:
                agg["unanswered"] += 1
                continue
            sent, done = (int(m.rec[k][at[i]]) for k in ("sent", "done"))
            req = reqs[i]
            cls = cell.classes[req["cls"]]
            # the request's own series alone: a copy of every series'
            # points an answer would be 270 MB at the cell's size
            idx = ref.select(cfg, req["hosts"], req["fields"])
            mine = truth[idx]
            must = np.full_like(mine, np.nan)
            must[:, :settled] = mine[:, :settled]
            may = must.copy()
            for j in range(settled, min(n_closed, mine.shape[1])):
                has = written[idx, j] <= sent
                maybe = ~has & (began[j] < done)
                must[has, j] = mine[has, j]
                may[has | maybe, j] = mine[has | maybe, j]
            now_s = t0_s + base_step * cadence_s + int(due_s[at[i]])
            try:
                keys, rows_must, _ = ref.series_rows(
                    cls, cfg, server.labels, vals, req, t0_s,
                    points=(stamps, must), now_s=now_s, selected=True)
            except ref.NotAggregated as e:
                agg["not_aggregated"] += 1
                say(f"answer {i}: {e}")
                continue
            # (all but the few answers in flight beside a flush have
            # nothing they may hold beyond what they must)
            rows_may = rows_must if np.array_equal(
                may, must, equal_nan=True) else ref.series_rows(
                cls, cfg, server.labels, vals, req, t0_s,
                points=(stamps, may), now_s=now_s, selected=True)[1]
            if control is None:
                have = ref.parse_response(got[1], req)
            else:   # the control, put in the program's place
                have = ref.evaluate(cls, cfg, server.labels, vals, req, t0_s,
                                    control=control, points=(stamps, must),
                                    now_s=now_s, stale_after_s=stale_after_s,
                                    selected=True)
            c = ref.compare_frontier(have, cls["reference"], keys, rows_must,
                                     rows_may)
            if (c["worst_rel_gap"] > gap_limit or c["label_sets_differ"]
                    or c["points_missing_or_extra"]):
                say(f"answer {i} differs from the reference: {c}; "
                    f"{req['path'][:300]}")
            agg["answers"] += 1
            for key in ("values", "label_sets_differ",
                        "points_missing_or_extra", "frontier_pairs",
                        "took_in_flight"):
                agg[key] += c[key]
            agg["worst_rel_gap"] = max(agg["worst_rel_gap"],
                                       c["worst_rel_gap"])
    return agg


def check(run, m, control=None):
    bad = int((m.rec["status"] != 200).sum())
    agg = compare_answers(run, m, control)
    limits = m.cell.traffic["limits"]
    gap_limit = float(limits["worst_rel_gap"])
    rows = [
        ("requests_failed", bad, 0),
        ("answers_unanswered", agg["unanswered"], 0),
        ("answers_not_resolved_to_aggregated", agg["not_aggregated"], 0),
        ("label_sets_differ", agg["label_sets_differ"], 0),
        ("points_missing_or_extra", agg["points_missing_or_extra"], 0),
        ("worst_rel_gap", agg["worst_rel_gap"], gap_limit),
        ("answers_compared_at_least", -agg["answers"],
         -min(len(m.keep), len(m.rec["status"]),
              int(limits["answers_compared_at_least"]))),
        ("frontier_pairs", agg["frontier_pairs"], NO_LIMIT),
        ("answers_took_in_flight", agg["took_in_flight"], NO_LIMIT),
    ]
    failed = (bad + agg["unanswered"] + agg["not_aggregated"]
              + agg["label_sets_differ"] + agg["points_missing_or_extra"]
              + int(agg["worst_rel_gap"] > gap_limit))
    return rows, failed
