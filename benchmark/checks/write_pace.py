"""The fleet's writes beside the reads: every write request of the
window answered 200 with `wrote` equal to what it sent, none failed, and
as many were sent as came due.

Its rows also carry the write side's own readings, each without a limit:
the share of requests sent more than `limits.write_late_after_s` after
they were due, the acknowledgement's p50 and p99, the latest send, how
often and for how long a bucket was grouped again under the shard lock,
and how often the block cache's fill waited out its stand-back without a
quiet moment. `writes_late_share` had the limit 0.01 until the driver's
check of PR 50 read 0.031 on a host that stood still for 2.3 s: a write
that is sent late is late, not wrong, and what it says is judged by the
rows above and by `mixed_readback`.
A counter the program does not have is left out, not zero. What the
reads pay is the cell's per-layer readings since PR 50
(`layer_metrics/buffer_tail_rows_per_read.py`,
`read_lock_wait_us_per_query.py`, `window_pack_ms_per_packed_query.py`
and the readings the cell shares with `cpu4k-query-thin` and
`net4k-query-rate`, under their own names): a number stands in one
place. It has no control: `drop` and the others belong to the checks
that read answers back."""

import numpy as np

from harness import promoffsets

NO_LIMIT = 1e18


def _counter_rows(m):
    rows = []
    c1 = m.counters1
    if "storage.buffer.index.builds" in c1:
        rows.append(("buffer_regroups_in_window",
                     m.moved("storage.buffer.index.builds"), NO_LIMIT))
    if "storage.buffer.index.regroup_ns" in c1:
        rows.append(("buffer_regroup_ms_in_window",
                     m.moved("storage.buffer.index.regroup_ns") / 1e6,
                     NO_LIMIT))
    if "storage.block_cache.fill.quiet_timeouts" in c1:
        rows.append(("fill_quiet_timeouts_in_window",
                     m.moved("storage.block_cache.fill.quiet_timeouts"),
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
        ("writes_sent_at_least", -n, -came_due),
        ("writes_late_share", late, NO_LIMIT),
    ]
    if n:
        rows += [("write_ack_p50_ms", float(np.percentile(ack_ms, 50)),
                  NO_LIMIT),
                 ("write_ack_p99_ms", float(np.percentile(ack_ms, 99)),
                  NO_LIMIT),
                 ("write_late_max_ms", float(late_s.max() * 1e3), NO_LIMIT)]
    rows += _counter_rows(m)
    return rows, failed + short
