"""The fleet's writes kept their pace beside the reads: every write
request of the window answered 200 with `wrote` equal to what it sent,
none failed, as many sent as came due, and fewer than
`limits.writes_late_share` of them sent more than
`limits.write_late_after_s` after they were due.

Its rows also carry what reads under writes cost, each without a limit
(these are readings, printed here because `BENCHMARK.json`'s per-layer
list is full): the acknowledgement's p50 and p99, the rows a read's tail
scan of an open bucket looked through, how often and for how long a
bucket was grouped again under the shard lock, and how often the block
cache's fill waited out its stand-back without a quiet moment. A traced
run adds the read side's wait for the shard lock a query
(`lock_wait_ns` of `query.fetch`), the packed layout's cost a query that
paid it (`window_pack_ns`), and every per-layer reading
`cpu4k-query-thin` reports, read by its accepted reader on this window,
as `reading.<name>`: the split of a query's time beside its control's.
A counter or cost the program does not have is left out, not zero. It
has no control: `drop` and the others belong to the checks that read
answers back."""

import numpy as np

from harness import phases, promoffsets, spans, spec

NO_LIMIT = 1e18
CONTROL_CELL = "cpu4k-query-thin"
# PR 42's readings of the range selector's layout, which thin's subquery
# phrasing never reaches and these plain selectors do
RANGE_SELECTOR_READINGS = ("range_window_ms_per_query",
                           "window_samples_seen_share")


def _counter_rows(m):
    rows = []
    c1 = m.counters1

    def have(*keys):
        return all(k in c1 for k in keys)

    if have("storage.buffer.read.tail_rows"):
        reads = (m.moved("storage.buffer.read.indexed")
                 + m.moved("storage.buffer.read.tail_scans"))
        rows.append(("buffer_tail_rows_per_read",
                     m.moved("storage.buffer.read.tail_rows")
                     / max(reads, 1), NO_LIMIT))
    if have("storage.buffer.index.builds"):
        rows.append(("buffer_regroups_in_window",
                     m.moved("storage.buffer.index.builds"), NO_LIMIT))
    if have("storage.buffer.index.regroup_ns"):
        rows.append(("buffer_regroup_ms_in_window",
                     m.moved("storage.buffer.index.regroup_ns") / 1e6,
                     NO_LIMIT))
    if have("storage.block_cache.fill.quiet_timeouts"):
        rows.append(("fill_quiet_timeouts_in_window",
                     m.moved("storage.block_cache.fill.quiet_timeouts"),
                     NO_LIMIT))
    return rows


def _span_rows(m):
    """A traced run's: the read side's costs, and the control cell's
    per-layer readings on this window."""
    if not m.span_trees:
        return []
    rows = []
    fetches = [n for n in spans.named(m.span_trees, "query.fetch")
               if "lock_wait_ns" in n["costs"]]
    if fetches:
        rows.append(("read_lock_wait_us_per_query",
                     phases.cost(fetches, "lock_wait_ns") / len(fetches)
                     / 1e3, NO_LIMIT))
    packed = [n["costs"]["window_pack_ns"]
              for n in spans.named(m.span_trees, "query.execute_range")
              if "window_pack_ns" in n["costs"]]
    if packed:
        rows.append(("window_pack_ms_per_packed_query",
                     sum(packed) / len(packed) / 1e6, NO_LIMIT))
    for decl in spec.load_benchmark()["per_layer"]:
        if CONTROL_CELL in decl.get("workloads", []) \
                or decl["name"] in RANGE_SELECTOR_READINGS:
            value = spec.load_reader("layer_metrics", decl["name"])(m)
            if value is not None:
                rows.append(("reading." + decl["name"], float(value),
                             NO_LIMIT))
    return rows


def check(run, m, control=None):
    rec, t = m.rec, m.cell.traffic
    cfg = m.cell.config
    limits = t["limits"]
    n = len(rec["w_status"])
    failed = int((rec["w_status"] != 200).sum())
    short = int(((rec["w_status"] == 200)
                 & (rec["w_samples"] != rec["w_want"])).sum())
    late_s = (rec["w_sent"] - rec["w_due"]) / 1e9
    late = float((late_s > float(limits["write_late_after_s"])).mean()) \
        if n else 0.0
    # what came due inside the window, by the fleet's own schedule
    nf = len(cfg["schema"]["fields"])
    due_ms = np.array([d for _h, d in promoffsets.send_groups(
        cfg, run.seed, max(1, int(t["samples_per_send"]) // nf))])
    cycles = np.arange(int(m.seconds // int(cfg["cadence_s"])) + 1)
    came_due = int(((cycles[:, None] * int(cfg["cadence_s"]) * 1000
                     + due_ms[None, :]) < m.seconds * 1000).sum())
    ack_ms = (rec["w_done"] - rec["w_sent"]) / 1e6
    rows = [
        ("writes_failed", failed, 0),
        ("writes_not_acknowledged_in_full", short, 0),
        ("writes_late_share", late, float(limits["writes_late_share"])),
        ("writes_sent_at_least", -n, -came_due),
    ]
    if n:
        rows += [("write_ack_p50_ms", float(np.percentile(ack_ms, 50)),
                  NO_LIMIT),
                 ("write_ack_p99_ms", float(np.percentile(ack_ms, 99)),
                  NO_LIMIT),
                 ("write_late_max_ms", float(late_s.max() * 1e3), NO_LIMIT)]
    rows += _counter_rows(m) + _span_rows(m)
    return rows, failed + short
