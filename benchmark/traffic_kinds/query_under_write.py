"""Reads under writes: a Prometheus fleet remote-writing while Grafana
reads, two generators in one window on one clock. It runs what
`query.py` and `remote_write.py` run and copies neither: the reads are
`query.py`'s open loop (`_run_open`, `_send`: its records, its trace
ids, its pool of `max_in_flight` threads), the writes go to
`remote_write.py`'s path with its headers.

**Writes**, open loop at the fleet's own rate: every target is scraped
at `offset + k * interval` (`harness/promoffsets.py`), a request holds
`samples_per_send` samples = the scrapes of the hosts that came due
together (hosts in offset order) and is due when the last of them is,
`senders` connections send what is due. The injected clock follows the
newest scrape sent: while the senders keep pace that is one second a
second from the window's first instant.

**Reads**, open loop at `rate_per_s`, the mix's classes from the
schedule's decks, hosts and fields from `--seed`; a request ends at the
clock's now when it is DUE on that schedule (not when it was sent: the
requests are the seed's and the schedule's alone), less a draw of
0..`end_within_last_s` whole seconds.

The set-up holds scrapes 0 .. `load_steps` - 1. `warm` sends scrape
`load_steps` whole, live, in the window's request shape and at the
window's pace, while the reads run `warm_first` and then every class
`warm_per_class` times at the clock's now; the window starts at scrape
`load_steps` + 1.

Records: the reads' under `query.py`'s own names (`i`, `due`, `sent`,
`done`, `status`, `cls`, `bytes`), the write requests' beside them under
`w_*` (`w_i`, `w_step`, `w_group`, `w_due`, `w_sent`, `w_done`,
`w_status`, `w_samples`, `w_want`; `base_step`: the window's first
scrape). Read `i` carries trace id `i + 1`,
write `i` trace id `WRITE_TRACE_BASE + i + 1`.

`init`, `warm` and `run` are the load-generator child's; they import
neither JAX nor the program. `keep_indices`, `requests_for` and
`window_writes` are the server side's."""

import json
import threading
import time

import numpy as np

from harness import datagen, promoffsets, promwire, schedule, spec

query = spec.load_part("traffic_kinds", "query")
remote_write = spec.load_part("traffic_kinds", "remote_write")

CHECKS = ["query_answers_frontier", "mixed_readback", "served_path_verdict",
          "write_pace"]
WREC = ("w_i", "w_step", "w_group", "w_due", "w_sent", "w_done", "w_status",
        "w_samples", "w_want")
WRITE_TRACE_BASE = 1_000_000
S = datagen.S
now_ns = time.perf_counter_ns
keep_indices = query.keep_indices


def first_window_step(traffic: dict) -> int:
    """The set-up holds 0 .. load_steps - 1, the warm-up writes
    load_steps, the window starts one scrape later."""
    return int(traffic["setup"]["load_steps"]) + 1


def requests_for(cell: dict, seed: int, seconds: float, base_step=None,
                 salt: int = 0):
    """The window's read requests: `schedule.requests_for`'s classes and
    draws, each request ending at the clock's now when it is due (the
    window opens with the clock at scrape `base_step`'s interval) less
    its draw."""
    traffic, cfg, classes = cell["traffic"], cell["config"], cell["classes"]
    if base_step is None:
        base_step = first_window_step(traffic)
    due = schedule.arrivals(traffic, seconds)
    seq = schedule.class_sequence(traffic, len(due))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17, salt])
    now0 = int(datagen.step_ts(cfg, base_step))
    out = []
    for i in range(len(due)):
        cls = classes[int(seq[i])]
        now_s = (now0 + int(due[i] * 1e9)) // S
        r = schedule.build_request(
            cls, cfg, schedule._draw(rng, cls, cfg, now_s, traffic))
        r["cls"] = int(seq[i])
        out.append(r)
    return out


# The data's seed is the child's first: a later `init` of the same child
# (a further window of benchmark/tools/sweep.py, which passes a seed of
# its own) draws other requests over the same fleet, offsets and values.
_data_seed = []


def window_writes(cell: dict, seed: int, rec: dict):
    """The window's write requests from the generator's records, for the
    checks: (hosts, scrape, sent, done, acknowledged in full) each."""
    nf = len(cell["config"]["schema"]["fields"])
    groups = promoffsets.send_groups(
        cell["config"], seed,
        max(1, int(cell["traffic"]["samples_per_send"]) // nf))
    full = (rec["w_status"] == 200) & (rec["w_samples"] == rec["w_want"])
    return [(groups[int(g)][0], int(k), int(s), int(d), bool(f))
            for g, k, s, d, f in zip(rec["w_group"], rec["w_step"],
                                     rec["w_sent"], rec["w_done"], full)]


def init(gen, msg: dict):
    t, cfg = gen.traffic, gen.cfg
    _data_seed.append(gen.seed)
    data_seed = _data_seed[0]
    gen.due = schedule.arrivals(t, gen.seconds)
    gen.base_step = first_window_step(t)
    gen.requests = requests_for(gen.cell, gen.seed, gen.seconds)
    gen.max_step = int(t["setup"]["load_steps"]) + int(t["max_window_steps"])
    gen.vals = datagen.walk(cfg, data_seed, gen.max_step)
    labels = datagen.series_labels(cfg, data_seed)
    nf = len(cfg["schema"]["fields"])
    gen.off_ms = promoffsets.offsets_ms(cfg, data_seed)
    # (hosts, due_ms, series rows, their offsets in ms, the wire template)
    gen.groups = []
    for hosts, due_ms in promoffsets.send_groups(
            cfg, data_seed, max(1, int(t["samples_per_send"]) // nf)):
        rows = (hosts[:, None] * nf + np.arange(nf)[None, :]).ravel()
        gen.groups.append((hosts, due_ms, rows,
                           np.repeat(gen.off_ms[hosts], nf),
                           promwire.Template([labels[r] for r in rows])))
    gen.clock = np.memmap(msg["clock_file"], np.int64, "r+", shape=(1,))


def _fill(template, ts_ms: np.ndarray, values: np.ndarray) -> bytes:
    """`promwire.Template.fill` with a timestamp a series: a request
    holds scrapes of hosts at different offsets."""
    body = template.block.copy()
    body[template.val_at] = np.ascontiguousarray(
        values, "<f8").view(np.uint8).reshape(-1, 8)
    n7 = template.ts_at.shape[1]
    shifts = 7 * np.arange(n7, dtype=np.int64)
    tsb = ((ts_ms[:, None] >> shifts[None, :]) & 0x7F).astype(np.uint8)
    tsb[:, :-1] |= 0x80
    body[template.ts_at] = tsb
    return body.tobytes()


def _send_writes(gen, first: int, end: int, t0: int, t1, trace: bool,
                 i0: int = 0) -> dict:
    """Scrape cycles `first` .. `end` - 1, each request sent when it is
    due: request i (numbered from `i0`) is group i % G of cycle
    first + i // G, due at t0 + (i // G) intervals + its group's
    offset. Nothing is sent that is due at or after `t1`."""
    rec = {k: [] for k in WREC}
    lock = threading.Lock()
    nxt = [0]
    G = len(gen.groups)
    cfg = gen.cfg
    cadence_ns = int(cfg["cadence_s"]) * S

    def worker():
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            k, g = first + i // G, i % G
            hosts, due_ms, rows, off_ms, template = gen.groups[g]
            due = t0 + (i // G) * cadence_ns + due_ms * promoffsets.MS
            if k >= end or (t1 is not None and due >= t1):
                return
            wait = (due - now_ns()) / 1e9
            if wait > 0:
                time.sleep(wait)
            step_ns = int(datagen.step_ts(cfg, k))
            # the injected clock follows the newest scrape sent
            newest = step_ns + due_ms * promoffsets.MS
            with lock:
                if newest > gen.clock[0]:
                    gen.clock[0] = newest
            body = _fill(template, step_ns // promoffsets.MS + off_ms,
                         gen.vals[rows, k])
            hdr = remote_write.HEADERS
            if trace:
                hdr = dict(hdr, **{"X-M3-Trace": "%d:1" % (
                    WRITE_TRACE_BASE + i0 + i + 1)})
            sent = now_ns()
            status, out = gen.client.fetch(remote_write.PATH, body=body,
                                           headers=hdr)
            done = now_ns()
            wrote = 0
            if status == 200:
                wrote = int(json.loads(out).get("wrote", 0))
            else:
                gen.bodies[-(i0 + i + 1)] = (status, out)
            with lock:
                for key, v in zip(WREC, (i0 + i, k, g, due, sent, done,
                                         status, wrote, len(rows))):
                    rec[key].append(v)

    ts = [threading.Thread(target=worker, daemon=True)
          for _ in range(int(gen.traffic["senders"]))]
    for th in ts:
        th.start()
    rec["_threads"] = ts
    return rec


def _join(rec: dict) -> dict:
    for th in rec.pop("_threads"):
        th.join()
    return rec


def _warm_reads(gen, now_s: int) -> list:
    """`query.warm`'s requests (the traffic file's `warm_first`, then
    every class `warm_per_class` times, on draws of their own), ending
    at the clock's now less their draw."""
    t = gen.traffic
    plan = [(w["class"], int(w["count"]), 2) for w in t.get("warm_first", [])]
    plan += [(m["class"], int(t["warm_per_class"]), 1) for m in t["mix"]]
    errors = []
    for name, count, salt in plan:
        cls = spec.load_class(name)
        rng = np.random.default_rng(
            [gen.seed & 0xFFFFFFFF, gen.seed >> 32, 19, salt])
        for _ in range(count):
            r = schedule.build_request(cls, gen.cfg, schedule._draw(
                rng, cls, gen.cfg, now_s, t))
            status, body = gen.client.fetch(r["path"])
            if status != 200:
                errors.append([r["path"][:200],
                               body[:300].decode(errors="replace")])
    return errors


def warm(gen) -> dict:
    step = gen.base_step - 1
    wrec = _send_writes(gen, step, step + 1, now_ns(), None, False,
                        i0=-len(gen.groups))
    errors = _warm_reads(gen, int(datagen.step_ts(gen.cfg, step)) // S)
    _join(wrec)
    errors += [[remote_write.PATH, "status %d, wrote %d of %d: %s" % (
                    s, n, w, gen.bodies.pop(-(i + 1), (0, b""))[1][:300]
                    .decode(errors="replace"))]
               for i, s, n, w in zip(wrec["w_i"], wrec["w_status"],
                                     wrec["w_samples"], wrec["w_want"])
               if s != 200 or n != w]
    if len(wrec["w_i"]) != len(gen.groups):
        errors.append([remote_write.PATH, "the warm-up sent %d of %d "
                       "requests" % (len(wrec["w_i"]), len(gen.groups))])
    return {"ok": not errors, "errors": errors[:5]}


def run(gen, msg: dict) -> dict:
    t0 = int(msg["t0"])
    t1 = t0 + int(gen.seconds * 1e9)
    cadence_ns = int(gen.cfg["cadence_s"]) * S
    # the clock stands in the interval before the window's first scrape;
    # a later window of the same set-up (benchmark/tools/sweep.py) goes
    # on from where the clock is
    base = max(gen.base_step,
               -(-(int(gen.clock[0]) - datagen.T0) // cadence_ns))
    if base != gen.base_step:
        gen.base_step = base
        gen.requests = requests_for(gen.cell, gen.seed, gen.seconds, base)
    wrec = _send_writes(gen, base, gen.max_step, t0, t1,
                        bool(msg.get("trace")))
    rec = query._run_open(gen, msg)
    _join(wrec)
    return {**rec, **wrec, "base_step": [base]}
