"""`readback` for reads under writes (`readback.py` reads the
remote-write generator's records under its own names; this kind keeps
the write requests' under `w_*`). The guarantee the configuration states
(`read_your_writes`, `no_loss`), held four ways once the window has
closed:

1. a seeded sample of acknowledged (host, scrape) pairs read back over
   HTTP, half from the set-up's load (sealed blocks and buffer), half
   from the window, each field's value exactly (`readback.py`'s rows);
2. EVERY acknowledged sample of the window read back through the node's
   read path (`storage/read_batch.py::read_many` over all series),
   millisecond timestamp and value bit for bit, and nothing read that
   the fleet's schedule did not send;
3. the window's acknowledged samples in the commit log: flushed as a
   graceful stop flushes it, replayed, counted, and a seeded sample of
   them found entry for entry;
4. once, the sealed blocks of `decode_check_series` series decoded
   through `ops/decode_rows.py` on the device the run is on and
   compared with the samples written, timestamps and values bit for
   bit; every block the node sealed took the MILLISECOND unit.

The truth is the seed's data and offsets, so no reference file is
loaded. Control `drop`: one acknowledged sample of each read not
stored."""

import json
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen, promoffsets, spec
from harness.cellrun import say


def _bits(a) -> np.ndarray:
    return np.ascontiguousarray(a, np.float64).view(np.int64)


def _bit(x) -> int:
    return int(np.float64(x).view(np.int64))


def _acked(m, seed):
    """[hosts, steps] bool pairs: acknowledged in full; sent at all."""
    t = m.cell.traffic
    kind = spec.load_part("traffic_kinds", t["kind"])
    steps = int(t["setup"]["load_steps"]) + int(t["max_window_steps"])
    acked = np.zeros((int(m.cell.config["scale"]), steps), bool)
    sent = np.zeros_like(acked)
    for hosts, k, _sent, _done, full in kind.window_writes(
            m.cell.to_wire(), seed, m.rec):
        sent[hosts, k] = True
        acked[hosts, k] |= full
    return acked, sent


def http_sample(run, m, acked, drop: bool) -> dict:
    """`readback.py`'s read: max_over_time over one scrape interval
    ending at the whole second at or after the sample, which holds that
    one sample of the host whatever its offset."""
    cfg, server, seed = m.cell.config, run.server, run.seed
    t = m.cell.traffic
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 29])
    off = promoffsets.offsets_ms(cfg, seed)
    pairs = int(t["readback_reads"])
    load_steps = int(t["setup"]["load_steps"])
    picks = [(int(rng.integers(0, load_steps)),
              int(rng.integers(0, cfg["scale"])))
             for _ in range(pairs // 2)]
    hs, ks = np.nonzero(acked)
    if len(hs):
        for j in rng.choice(len(hs), min(pairs - len(picks), len(hs)),
                            replace=False):
            picks.append((int(ks[j]), int(hs[j])))
    out = {"pairs": 0, "readback_mismatched": 0, "reads_failed": 0}
    cadence = int(cfg["cadence_s"])
    name = cfg["schema"]["measurement"]
    fields = cfg["schema"]["fields"]
    for step, host in picks:
        ts_ns = int(promoffsets.sample_ts_ns(cfg, off[host], step))
        at_s = -(-ts_ns // datagen.S)
        q = 'max_over_time(%s{hostname="host_%d"}[%ds])' % (name, host,
                                                           cadence)
        url = (server.base + "/api/v1/query?"
               + urllib.parse.urlencode({"query": q, "time": at_s}))
        try:
            with urllib.request.urlopen(url, timeout=60) as r:
                res = json.loads(r.read())["data"]["result"]
        except (OSError, ValueError, KeyError):
            out["reads_failed"] += 1
            continue
        got = {s["metric"].get("field"): float(s["value"][1]) for s in res}
        for f, fname in enumerate(fields):
            if drop and f == 0:          # the control: one sample not stored
                got.pop(fname, None)
            out["pairs"] += 1
            if got.get(fname) != float(server.vals[host * len(fields) + f,
                                                   step]):
                out["readback_mismatched"] += 1
    return out


def _series_ids(server):
    from m3_tpu.metrics import id as metric_id

    name = server.cfg["schema"]["measurement"].encode()
    return [metric_id.encode(name, {k: v for k, v in t.items()
                                    if k != b"__name__"})
            for t in datagen.wire_tags(server.labels)]


def node_read(run, m, acked, sent, ids, drop: bool) -> dict:
    """Every acknowledged sample of the window through the node's read
    path, bit for bit."""
    from m3_tpu.storage import read_batch

    cfg, server = m.cell.config, run.server
    nf = len(cfg["schema"]["fields"])
    off = promoffsets.offsets_ms(cfg, run.seed)
    ks = np.flatnonzero(sent.any(axis=0))
    out = {"acked": int(acked.sum()) * nf, "missing": 0, "off_schedule": 0,
           "read": 0}
    if not len(ks):
        return out
    lo = int(datagen.step_ts(cfg, int(ks[0])))
    hi = int(datagen.step_ts(cfg, int(ks[-1]) + 1))
    db = server.handle.db
    got = read_batch.read_many(db.namespace(server.handle.namespace),
                               db.shard_set, ids, lo, hi)
    dropped = False
    for s, entry in enumerate(got):
        h = s // nf
        t, v = (entry[1], entry[2]) if entry is not None else ((), ())
        t, v = np.asarray(t, np.int64), np.asarray(v, np.float64)
        if drop and not dropped and len(t):   # the control
            t, v, dropped = t[1:], v[1:], True
        out["read"] += len(t)
        want_k = np.flatnonzero(acked[h])
        want_t = promoffsets.sample_ts_ns(cfg, off[h], want_k)
        pos = np.minimum(np.searchsorted(t, want_t), max(len(t) - 1, 0))
        if len(t):
            ok = (t[pos] == want_t) & (_bits(v[pos]) == _bits(
                server.vals[s, want_k]))
            out["missing"] += int((~ok).sum())
        else:
            out["missing"] += len(want_k)
        sent_t = promoffsets.sample_ts_ns(cfg, off[h],
                                          np.flatnonzero(sent[h]))
        out["off_schedule"] += int((~np.isin(t, sent_t)).sum())
    return out


def commit_log(run, m, acked, ids) -> dict:
    """The window's acknowledged samples in the commit log's stream."""
    from m3_tpu.persist import commitlog

    cfg, server, seed = m.cell.config, run.server, run.seed
    log = server.handle.db.commitlog
    out = {"acked": 0, "logged": 0, "sampled": 0, "sampled_missing": 0}
    hs, ks = np.nonzero(acked)
    if log is None or not len(hs):
        return out
    nf = len(cfg["schema"]["fields"])
    off = promoffsets.offsets_ms(cfg, seed)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 31])
    want = {}
    for j in rng.choice(len(hs), min(1000, len(hs)), replace=False):
        h, k, f = int(hs[j]), int(ks[j]), int(rng.integers(0, nf))
        s = h * nf + f
        want[ids[s], int(promoffsets.sample_ts_ns(cfg, off[h], k))] = \
            _bit(server.vals[s, k])
    lo = int(datagen.step_ts(cfg, int(ks.min())))
    hi = int(datagen.step_ts(cfg, int(ks.max()) + 1))
    log.flush()
    for b in commitlog.replay_batches(log.directory):
        inside = np.flatnonzero((b.t_ns >= lo) & (b.t_ns < hi))
        out["logged"] += len(inside)
        for j in inside:
            key = (b.ids[j], int(b.t_ns[j]))
            if want.get(key) == _bit(b.values[j]):
                del want[key]
    out["acked"] = len(hs) * nf
    out["sampled"] = min(1000, len(hs))
    out["sampled_missing"] = len(want)
    return out


def sealed_decode(run, m, ids) -> dict:
    """The sealed blocks of a seeded sample of series, decoded on the
    device through `ops/decode_rows.py` and held to the samples written."""
    from m3_tpu.ops import decode_rows

    cfg, server, seed = m.cell.config, run.server, run.seed
    nf = len(cfg["schema"]["fields"])
    off = promoffsets.offsets_ms(cfg, seed)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 43])
    n = min(int(m.cell.traffic["decode_check_series"]), len(ids))
    picks = sorted(int(s) for s in rng.choice(len(ids), n, replace=False))
    db = server.handle.db
    ns = db.namespace(server.handle.namespace)
    shard_of = db.shard_set.lookup_memo([ids[s] for s in picks])
    out = {"series_blocks": 0, "mismatched": 0, "units": set(), "calls": 0}
    cadence = int(cfg["cadence_s"]) * datagen.S
    for shard_id in sorted(set(int(x) for x in shard_of)):
        shard = ns.shards[shard_id]
        mine = [s for s, sh in zip(picks, shard_of) if int(sh) == shard_id]
        reg = shard.registry.lookup_known([ids[s] for s in mine])
        for bs, blk in sorted(shard.blocks.items()):
            rows = np.searchsorted(blk.series_indices, reg)
            ts, vs, calls = decode_rows.decode_rows(
                blk.words[rows], blk.npoints[rows], blk.window,
                blk.time_unit.nanos)
            out["calls"] += calls
            out["units"].add(blk.time_unit.name)
            for r, s in enumerate(mine):
                k = int(blk.npoints[rows[r]])
                k0 = -(-(bs - datagen.T0 - int(off[s // nf])
                         * promoffsets.MS) // cadence)
                want_k = np.arange(k0, k0 + k)
                same = (k0 + k <= server.vals.shape[1]
                        and blk.series_indices[rows[r]] == reg[r]
                        and (ts[r, :k] == promoffsets.sample_ts_ns(
                            cfg, off[s // nf], want_k)).all()
                        and (_bits(vs[r, :k]) == _bits(
                            server.vals[s, want_k])).all())
                out["series_blocks"] += 1
                out["mismatched"] += not same
    return out


def check(run, m, control=None):
    cfg, t = m.cell.config, m.cell.traffic
    drop = control == "drop"
    acked, sent = _acked(m, run.seed)
    ids = _series_ids(run.server)
    rb = http_sample(run, m, acked, drop)
    nr = node_read(run, m, acked, sent, ids, drop)
    cl = commit_log(run, m, acked, ids)
    sd = sealed_decode(run, m, ids)
    from harness import server as server_mod

    # (since this server was booted: a test process has run other cells)
    sealed = {k: v - run.server.counters0.get(k, 0)
              for k, v in server_mod.counters().items()
              if k.startswith("storage.block.sealed{")}
    other_unit = sum(v for k, v in sealed.items()
                     if "unit=millisecond" not in k)
    say(f"readback: {rb}; node read {nr}; commit log {cl}; sealed decode "
        f"{sd}; sealed by unit {sealed}")
    nf = len(cfg["schema"]["fields"])
    rows = [
        ("readback_mismatched", rb["readback_mismatched"], 0),
        ("readback_reads_failed", rb["reads_failed"], 0),
        ("readback_pairs_compared_at_least", -rb["pairs"],
         -int(t["readback_reads"]) * nf // 2),
        ("window_samples_missing", nr["missing"], 0),
        ("window_samples_off_schedule", nr["off_schedule"], 0),
        ("window_samples_read_at_least", -nr["read"], -nr["acked"]),
        ("commitlog_samples_short", max(0, cl["acked"] - cl["logged"]), 0),
        ("commitlog_sampled_missing", cl["sampled_missing"], 0),
        ("sealed_decode_mismatched", sd["mismatched"], 0),
        ("sealed_decode_series_blocks_at_least", -sd["series_blocks"],
         -int(t["setup"]["sealed_blocks"]) * min(
             int(t["decode_check_series"]), len(ids))),
        ("sealed_blocks_off_millisecond",
         other_unit + len(sd["units"] - {"MILLISECOND"}), 0),
    ]
    failed = (rb["readback_mismatched"] + rb["reads_failed"]
              + nr["missing"] + nr["off_schedule"] + cl["sampled_missing"]
              + sd["mismatched"])
    return rows, failed
