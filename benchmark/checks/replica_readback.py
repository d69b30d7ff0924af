"""The cluster's guarantee, as far as a run can show it: a seeded sample
of acknowledged (series, timestamp) pairs, half from sealed blocks, half
from the scrapes the set-up wrote through the cluster, each read from
EVERY node on its own over the node RPC (`fetch`: the node decodes its
own block) and once through the coordinator (an instant query over HTTP,
merged from replicas by the session). The truth is the seed's data; who
must hold what, and what a merge of replicas gives, are
`reference/replica_ref.py`'s.

Rows, each with a limit of 0: a pair held exactly by fewer than a
majority of its replicas; a pair its replicas do not all hold alike
(nothing was lost, no node is down, the session was drained); a pair the
coordinator answers otherwise than the truth and the merge of the nodes'
own answers; reads that failed; and arrays off their service's device
(every node's resident encoded blocks on that node's device, every
client-side tile decode of the run on the coordinator's).

Control `drop_replica_write`: one sample of each pick withheld from two
replicas, as a write acknowledged by one host alone would leave it."""

import json
import urllib.parse
import urllib.request

import numpy as np

from harness import datagen, spec
from harness.cellrun import say


def _picks(cell, seed: int):
    t = cell.traffic
    cfg = cell.config
    nf = len(cfg["schema"]["fields"])
    hosts = max(2, int(t.get("readback_pairs", 1000)) // nf)
    steps = int(t["setup"]["load_steps"])
    open_from = steps - int(t["setup"]["open_steps"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 31])
    out = []
    for j in range(hosts):
        lo, hi = (0, open_from) if j % 2 == 0 else (open_from, steps)
        out.append((int(rng.integers(lo, hi)),
                    int(rng.integers(0, cfg["scale"]))))
    return out


def _devices_off(handle) -> int:
    import jax

    from m3_tpu.storage import block_cache
    from m3_tpu.utils import instrument

    devs = jax.devices()
    off = 0
    for node, want in zip(handle.nodes, handle.node_devices):
        want = {devs[i] for i in want}
        with node.db.scope:
            cache = block_cache.get_cache()
        with cache._lock:
            entries = list(cache._entries.values())
        for e in entries:
            if e.encoded is not None and not set(
                    e.encoded[0].devices()) <= want:
                off += 1
    # where the client-side decodes ran: a counter a device
    mark = "client.decode_tile.dispatches{device="
    ran_on = {k[len(mark):-1] for k, v in instrument.ROOT.snapshot().items()
              if k.startswith(mark) and v}
    if not ran_on or not ran_on <= {str(devs[i].id)
                                   for i in handle.coordinator_devices}:
        off += 1
    return off


def read_back(run, m, drop: bool = False) -> dict:
    from m3_tpu.client.session import HostClient
    from m3_tpu.metrics import id as metric_id

    ref = spec.load_part("reference", "replica_ref")
    cell, cfg, server = m.cell, m.cell.config, run.server
    handle = server.handle
    fields = cfg["schema"]["fields"]
    nf = len(fields)
    name = cfg["schema"]["measurement"]
    cadence = int(cfg["cadence_s"])
    placement = handle.session.topology.get().placement
    owners = {s: [i.id for i in placement.replicas_for(s)]
              for s in range(placement.num_shards)}
    need = ref.majority(placement.replica_factor)
    clients = {n.server.service.host_id: HostClient(n.endpoint)
               for n in handle.nodes}
    out = {"pairs": 0, "short_of_majority": 0, "not_identical": 0,
           "coordinator_mismatched": 0, "reads_failed": 0}
    tags = datagen.wire_tags(server.labels)
    shown = 0
    try:
        for step, host in _picks(cell, run.seed):
            ts_ns = int(datagen.step_ts(cfg, step))
            q = 'max_over_time(%s{hostname="host_%d"}[%ds])' % (
                name, host, cadence)
            url = (server.base + "/api/v1/query?" + urllib.parse.urlencode(
                {"query": q, "time": ts_ns // datagen.S}))
            try:
                with urllib.request.urlopen(url, timeout=60) as r:
                    res = json.loads(r.read())["data"]["result"]
            except (OSError, ValueError, KeyError):
                out["reads_failed"] += 1
                continue
            served = {s["metric"].get("field"): float(s["value"][1])
                      for s in res}
            rows = [host * nf + f for f in range(nf)]
            ids = [metric_id.encode(name.encode(), {
                k: v for k, v in tags[i].items() if k != b"__name__"})
                for i in rows]
            for f, (i, sid, holding) in enumerate(zip(
                    rows, ids, ref.holders(ids, owners,
                                           placement.num_shards))):
                want = float(server.vals[i, step])
                parts_t, parts_v, exact = [], [], 0
                for k, iid in enumerate(holding):
                    try:
                        got = clients[iid].call(
                            "fetch", ns=handle.namespace, id=sid,
                            start_ns=ts_ns, end_ns=ts_ns + 1)
                    except Exception as e:  # noqa: BLE001 - a failed read is a number
                        say(f"replica read of {sid!r} from {iid}: {e!r}")
                        out["reads_failed"] += 1
                        continue
                    t, v = np.asarray(got["t"]), np.asarray(got["v"])
                    if drop and f == 0 and k < 2:   # the control
                        t, v = t[:0], v[:0]
                    parts_t.append(t)
                    parts_v.append(v)
                    exact += int(len(t) == 1 and int(t[0]) == ts_ns
                                 and float(v[0]) == want)
                out["pairs"] += 1
                if exact < len(holding) and shown < 5:
                    shown += 1
                    say(f"pair (host {host}, step {step}, {fields[f]}): want "
                        f"{want} at {ts_ns}; replicas "
                        f"{[(t.tolist(), v.tolist()) for t, v in zip(parts_t, parts_v)]}"
                        f"; coordinator {served.get(fields[f])}")
                out["short_of_majority"] += int(exact < min(need,
                                                            len(holding)))
                out["not_identical"] += int(exact < len(holding))
                mt, mv = ref.merge_replicas(parts_t, parts_v)
                merged = float(mv[0]) if len(mt) == 1 else None
                have = served.get(fields[f])
                out["coordinator_mismatched"] += int(
                    have != want or (not drop and merged != have))
    finally:
        for c in clients.values():
            c.close()
    return out


def check(run, m, control=None):
    rb = read_back(run, m, drop=(control == "drop_replica_write"))
    say(f"replica read-back: {rb}")
    rows = [("acked_on_fewer_than_2_replicas", rb["short_of_majority"], 0),
            ("replicas_not_identical", rb["not_identical"], 0),
            ("coordinator_readback_mismatched",
             rb["coordinator_mismatched"], 0),
            ("replica_reads_failed", rb["reads_failed"], 0),
            ("arrays_off_their_service_device",
             _devices_off(run.server.handle), 0),
            ("replica_pairs_compared_at_least", -rb["pairs"],
             -int(m.cell.traffic.get("readback_pairs", 1000)) // 2)]
    failed = (rb["short_of_majority"] + rb["coordinator_mismatched"]
              + rb["reads_failed"])
    return rows, failed
