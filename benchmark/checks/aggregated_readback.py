"""The downsampler's guarantee, as far as a run can show it: an aggregate
of a closed window, once flushed, is read back exactly and equals the
`last` of that window's samples, stamped at the window's end.

Two stretches of the aggregated namespace, both compared exactly with
the truth the reference derives from the seed's 10 s data
(`aggns_ref.aggregated_truth`):

- the FILESET stretch, over HTTP: a seeded sample of (host, window)
  picks older than the unaggregated retention, at least two from every
  block start the set-up wrote and `readback_pairs` pairs in all, each
  read as an instant query over the window (its ten series are ten
  pairs), which the resolver has to answer from the aggregated
  namespace;
- the OPEN block, what the namespace's buffer holds: the windows the
  restart's WAL replay brought back and, newest, the ones the program's
  own downsampler flushed in set-up: every window of
  `readback_live_series` series, read from the aggregated namespace
  through the node's own `Database.read` (over HTTP a range that recent
  resolves to the unaggregated namespace), the points' timestamps
  compared too.

Rows, each with a limit of 0: `agg_readback_mismatched`,
`agg_reads_failed`, `agg_block_starts_not_covered`,
`agg_live_mismatched`. Controls, put in the program's place:
`wrong_namespace` (the fileset stretch answered from the 10 s namespace:
the window's max, not its last) and `stale` (the live stretch never
flushed)."""

import json
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen, spec
from harness.cellrun import say


def _geometry(cell, handle):
    cfg, setup = cell.config, cell.traffic["setup"]
    cadence = int(cfg["cadence_s"])
    res_s = handle.resolution_ns // datagen.S
    win = res_s // cadence
    steps, live = int(setup["load_steps"]), int(setup["live_steps"])
    k_fs = (steps - live) // win
    # the windows stamped inside the aggregated block that is open where
    # the live stretch begins are the buffer's, not a fileset's
    bsz_s = handle.db.namespace(handle.namespace).opts.block_size_ns \
        // datagen.S
    end_s = datagen.T0 // datagen.S + (steps - live) * cadence
    open_s = end_s - end_s % bsz_s
    k_sealed = min(k_fs, -(-(open_s - datagen.T0 // datagen.S) // res_s) - 1)
    return cadence, res_s, win, steps, k_sealed, steps // win


def read_back(run, m, control=None) -> dict:
    cell, cfg, server = m.cell, m.cell.config, run.server
    handle = server.handle
    ref = spec.load_part("reference", "aggns_ref")
    fields = cfg["schema"]["fields"]
    nf = len(fields)
    name = cfg["schema"]["measurement"]
    cadence, res_s, win, steps, k_fs, k_all = _geometry(cell, handle)
    t0_s = datagen.T0 // datagen.S
    now_s = t0_s + steps * cadence
    truth = ref.aggregated_truth(server.vals[:, :steps], cadence, res_s)
    nss = {ns["name"]: ns for ns in ref.namespaces(cfg)}
    raw = nss[handle.unaggregated_namespace.decode()]
    agg = nss[handle.namespace.decode()]
    # windows k = 1 .. k_old are read over HTTP: a fetch of (t - res, t]
    # has to start before the unaggregated retention
    k_old = min(k_fs, (now_s - raw["retention_s"] - t0_s - 1) // res_s)
    block_of = (t0_s + np.arange(1, k_old + 1) * res_s) // agg["block_s"]
    starts = np.unique(block_of)
    want = int(cell.traffic.get("readback_pairs", 1000))
    per_block = max(2, -(-want // (nf * len(starts))))
    rng = np.random.default_rng([run.seed & 0xFFFFFFFF, run.seed >> 32, 41])
    out = {"pairs": 0, "agg_readback_mismatched": 0, "agg_reads_failed": 0,
           "agg_live_mismatched": 0, "live_pairs": 0}
    compared = {int(b): 0 for b in starts}
    shown = 0
    for b in starts:
        ks = np.flatnonzero(block_of == b) + 1
        for _ in range(per_block):
            k = int(rng.choice(ks))
            host = int(rng.integers(0, cfg["scale"]))
            ts = t0_s + k * res_s
            q = 'max_over_time(%s{hostname="host_%d"}[%ds])' % (name, host,
                                                                res_s)
            url = (server.base + "/api/v1/query?"
                   + urllib.parse.urlencode({"query": q, "time": ts}))
            try:
                with urllib.request.urlopen(url, timeout=120) as r:
                    res = json.loads(r.read())["data"]["result"]
            except (OSError, ValueError, KeyError):
                out["agg_reads_failed"] += 1
                continue
            got = {s["metric"].get("field"): float(s["value"][1])
                   for s in res}
            for f, fname in enumerate(fields):
                i = host * nf + f
                have = got.get(fname)
                if control == "wrong_namespace":
                    have = float(server.vals[i, (k - 1) * win:k * win].max())
                out["pairs"] += 1
                if have != float(truth[i, k - 1]):
                    out["agg_readback_mismatched"] += 1
                    if shown < 5 and control is None:
                        shown += 1
                        say(f"aggregate (host {host}, window {k}, {fname}): "
                            f"want {float(truth[i, k - 1])}, read {have}")
                else:
                    compared[int(b)] += 1
    out["agg_block_starts_not_covered"] = sum(
        1 for c in compared.values() if c < 2 * nf)
    # the live stretch: every window the program's own downsampler closed
    from m3_tpu.metrics import id as metric_id
    from m3_tpu.parallel import scope as dscope

    labels = datagen.wire_tags(server.labels)
    n_live = min(int(cell.traffic.get("readback_live_series", 100)),
                 len(labels))
    lo_ns = (t0_s + (k_fs + 1) * res_s) * datagen.S
    hi_ns = (t0_s + k_all * res_s) * datagen.S + 1
    want_t = ((t0_s + np.arange(k_fs + 1, k_all + 1) * res_s)
              * datagen.S).tolist()
    with dscope.entered(handle.db.scope):
        for i in rng.choice(len(labels), n_live, replace=False).tolist():
            tags = labels[i]
            sid = metric_id.encode(tags[b"__name__"], {
                k: v for k, v in tags.items() if k != b"__name__"})
            t, v = handle.db.read(handle.namespace, sid, lo_ns, hi_ns)
            if control == "stale":
                t, v = t[:0], v[:0]
            out["live_pairs"] += len(want_t)
            if (np.asarray(t).tolist() != want_t or np.asarray(v).tolist()
                    != truth[i, k_fs:k_all].astype(float).tolist()):
                out["agg_live_mismatched"] += 1
                if shown < 8 and control is None:
                    shown += 1
                    say(f"live aggregate (series {i}): want "
                        f"{truth[i, k_fs:k_all].tolist()} at {want_t}, read "
                        f"{np.asarray(v).tolist()} at "
                        f"{np.asarray(t).tolist()}")
    return out


def check(run, m, control=None):
    rb = read_back(run, m, control)
    say(f"aggregated read-back: {rb}")
    rows = [("agg_readback_mismatched", rb["agg_readback_mismatched"], 0),
            ("agg_reads_failed", rb["agg_reads_failed"], 0),
            ("agg_block_starts_not_covered",
             rb["agg_block_starts_not_covered"], 0),
            ("agg_live_mismatched", rb["agg_live_mismatched"], 0),
            ("agg_readback_pairs_compared_at_least", -rb["pairs"],
             -int(m.cell.traffic.get("readback_pairs", 1000))),
            ("agg_live_pairs_compared_at_least", -rb["live_pairs"], -1)]
    return rows, (rb["agg_readback_mismatched"] + rb["agg_reads_failed"]
                  + rb["agg_live_mismatched"])
