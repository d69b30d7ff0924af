"""Write traffic: Prometheus remote-write. Closed loop: each of
`senders` posts the next `samples_per_send`-sample WriteRequest when the
last is acknowledged; requests are successive scrapes of every series,
in series order; the injected clock follows the newest scrape sent.

The set-up holds scrapes 0 .. `load_steps` - 1. `warm` sends scrape
`load_steps` whole, through remote-write, in the window's own request
shape and by the window's own senders: a node that has been up for a
scrape has seen every series once (the shard memo, a tagged entry per
series in the commit log's current file), and the window, which starts
at scrape `load_steps` + 1, measures the path a deployment runs
continuously and not a restart's first scrape.

`init`, `warm` and `run` are the load-generator child's; they import
neither JAX nor the program. `keep_indices` is the server side's."""

import json
import threading
import time

import numpy as np

from harness import datagen, promwire

CHECKS = ["readback", "served_path_verdict"]
REC = ("i", "step", "group", "sent", "done", "status", "samples", "want")
PATH = "/api/v1/prom/remote/write"
HEADERS = {"Content-Type": "application/x-protobuf",
           "Content-Encoding": "snappy"}
now_ns = time.perf_counter_ns


def init(gen, msg: dict):
    t, cfg = gen.traffic, gen.cfg
    gen.first_step = int(t["setup"]["load_steps"])
    gen.max_steps = int(t["max_window_steps"])
    gen.vals = datagen.walk(cfg, gen.seed, gen.first_step + gen.max_steps)
    labels = datagen.series_labels(cfg, gen.seed)
    per = int(t["samples_per_send"])
    gen.groups = [(lo, min(lo + per, len(labels)))
                  for lo in range(0, len(labels), per)]
    gen.templates = [promwire.Template(labels[lo:hi])
                     for lo, hi in gen.groups]
    gen.clock = np.memmap(msg["clock_file"], np.int64, "r+", shape=(1,))


def _send_scrapes(gen, first: int, end: int, t0: int, t1, trace: bool):
    """Scrapes `first` .. `end` - 1, request after request, by the mix's
    senders from `t0` on; no request starts at or after `t1`. Request i
    is group i % G of scrape first + i // G."""
    rec = {k: [] for k in REC}
    lock = threading.Lock()
    nxt = [0]
    G = len(gen.groups)
    cadence_ns = int(gen.cfg["cadence_s"]) * datagen.S

    def worker():
        time.sleep(max(0.0, (t0 - now_ns()) / 1e9))
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
                k, g = first + i // G, i % G
                if k >= end:
                    return
                ts = int(datagen.step_ts(gen.cfg, k))
                # the injected clock follows the newest scrape sent
                if ts + cadence_ns > gen.clock[0]:
                    gen.clock[0] = ts + cadence_ns
            sent = now_ns()
            if t1 is not None and sent >= t1:
                return
            lo, hi = gen.groups[g]
            body = gen.templates[g].fill(ts // 1_000_000, gen.vals[lo:hi, k])
            hdr = dict(HEADERS, **{"X-M3-Trace": "%d:1" % (i + 1)}) \
                if trace else HEADERS
            status, out = gen.client.fetch(PATH, body=body, headers=hdr)
            done = now_ns()
            wrote = 0
            if status == 200:
                wrote = int(json.loads(out).get("wrote", 0))
            else:
                gen.bodies[i] = (status, out)
            with lock:
                for key, v in zip(REC, (i, k, g, sent, done, status, wrote,
                                        hi - lo)):
                    rec[key].append(v)

    gen.threads(worker, int(gen.traffic["senders"]))
    return rec


def warm(gen) -> dict:
    rec = _send_scrapes(gen, gen.first_step, gen.first_step + 1, now_ns(),
                        None, False)
    bad = [[PATH, "status %d, wrote %d of %d: %s" % (
                s, n, w, gen.bodies.pop(i, (0, b""))[1][:300].decode(
                    errors="replace"))]
           for i, s, n, w in zip(rec["i"], rec["status"], rec["samples"],
                                 rec["want"]) if s != 200 or n != w]
    return {"ok": not bad, "errors": bad[:5]}


def run(gen, msg: dict) -> dict:
    t0 = int(msg["t0"])
    t1 = t0 + int(gen.seconds * 1e9)
    rec = _send_scrapes(gen, gen.first_step + 1,
                        gen.first_step + gen.max_steps, t0, t1,
                        bool(msg.get("trace")))
    return {"t0": t0, "t1": t1, **rec}


def keep_indices(cell, seed: int, seconds: float):
    """No answer is kept: what was acknowledged is read back."""
    return []
