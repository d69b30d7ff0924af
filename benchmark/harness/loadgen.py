"""The load generator: a child process that never imports JAX or the
program. It is started before the server process touches the chip,
talks to the server over localhost HTTP only, stamps every request on
its own monotonic clock (CLOCK_MONOTONIC, which the server process
shares, so spans and requests line up) and hands its samples back over
a pipe as JSON lines.

Open loop: requests are sent when they are due, by a pool of client
threads; latency is counted from when a request was due, so a stall
costs every request queued behind it. Closed loop: each client sends
its next request when the last one is answered. Remote-write: each
sender posts the next 500-sample WriteRequest when the last is acked."""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from harness import datagen, promwire, schedule, spec  # noqa: E402

now_ns = time.perf_counter_ns


class Client:
    """One request per connection: the server speaks HTTP/1.0."""

    def __init__(self, base: str, timeout: float):
        u = urllib.parse.urlsplit(base)
        self.host, self.port, self.timeout = u.hostname, u.port, timeout

    def fetch(self, path: str, body: bytes = None, headers: dict = None):
        """(status, body) — status 0 with the error text on any failure."""
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)
        try:
            conn.request("POST" if body is not None else "GET", path,
                         body=body, headers=headers or {})
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException) as e:
            return 0, repr(e).encode()
        finally:
            conn.close()


class Generator:
    def __init__(self, msg: dict):
        self.cell = msg["cell"]
        self.seed = int(msg["seed"])
        self.seconds = float(msg["seconds"])
        self.traffic = self.cell["traffic"]
        self.cfg = self.cell["config"]
        self.client = Client(msg["base"],
                             float(self.traffic["request_timeout_s"]))
        self.kind = self.traffic["kind"]
        self.bodies = {}
        if self.kind == "remote_write":
            self._init_ingest(msg)
        else:
            self._init_queries()

    # ---------------------------------------------------------------- queries

    def _init_queries(self):
        if self.traffic["loop"] == "open":
            self.due = schedule.arrivals(self.traffic, self.seconds)
        self.requests = schedule.requests_for(
            self.cell, self.seed,
            schedule.n_requests(self.traffic, self.seconds))

    def _class_requests(self, name: str, count: int, salt: int):
        """`count` requests of one class alone, on draws of their own."""
        cell = dict(self.cell, classes=[spec.load_class(name)],
                    traffic=dict(self.traffic, loop="closed",
                                 mix=[{"class": name, "cards": 1}]))
        return schedule.requests_for(cell, self.seed, count, salt=salt)

    def warm(self) -> dict:
        """One request at a time, on other draws than the window's but the
        same shapes, so what would compile compiles here: first what the
        traffic file names to fill the program's lazy state (a whole block
        is decoded at its first touch), then every class of the mix a few
        times."""
        reqs = []
        if self.kind != "remote_write":
            for w in self.traffic.get("warm_first", []):
                reqs += self._class_requests(w["class"], int(w["count"]), 2)
            for m in self.traffic["mix"]:
                reqs += self._class_requests(
                    m["class"], int(self.traffic["warm_per_class"]), 1)
        errors = []
        for r in reqs:
            status, body = self.client.fetch(r["path"])
            if status != 200:
                errors.append([r["path"][:200],
                               body[:300].decode(errors="replace")])
        return {"ok": not errors, "errors": errors[:5]}

    def _send(self, i: int, req: dict, due: int, rec: dict, keep, trace: bool):
        headers = {"X-M3-Trace": "%d:1" % (i + 1)} if trace else None
        sent = now_ns()
        status, body = self.client.fetch(req["path"], headers=headers)
        done = now_ns()
        rec["i"].append(i)
        rec["due"].append(due)
        rec["sent"].append(sent)
        rec["done"].append(done)
        rec["status"].append(status)
        rec["cls"].append(req["cls"])
        rec["bytes"].append(len(body))
        if status != 200 or keep is None or keep(i):
            self.bodies[i] = (status, body)

    def run_open(self, msg: dict) -> dict:
        keep_set = set(msg.get("keep", []))
        trace = bool(msg.get("trace"))
        due_ns = (self.due * 1e9).astype(np.int64)
        n = len(due_ns)
        rec = {k: [] for k in ("i", "due", "sent", "done", "status", "cls",
                               "bytes")}
        lock = threading.Lock()
        nxt = [0]
        t0 = now_ns() + 200_000_000

        def worker():
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= n:
                    return
                due = t0 + int(due_ns[i])
                wait = (due - now_ns()) / 1e9
                if wait > 0:
                    time.sleep(wait)
                mine = {k: [] for k in rec}
                self._send(i, self.requests[i], due, mine,
                           keep_set.__contains__, trace)
                with lock:
                    for k in rec:
                        rec[k] += mine[k]

        self._threads(worker, int(self.traffic["max_in_flight"]))
        return {"t0": t0, "t1": t0 + int(self.seconds * 1e9), **rec}

    def run_closed(self, msg: dict) -> dict:
        trace = bool(msg.get("trace"))
        rec = {k: [] for k in ("i", "due", "sent", "done", "status", "cls",
                               "bytes")}
        lock = threading.Lock()
        nxt = [0]
        t0 = now_ns() + 200_000_000
        t1 = t0 + int(self.seconds * 1e9)
        L = len(self.requests)

        def worker():
            time.sleep(max(0.0, (t0 - now_ns()) / 1e9))
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                start = now_ns()
                if start >= t1:
                    return
                mine = {k: [] for k in rec}
                # keep the newest answer of every replay entry: each is
                # compared with the reference once the window has closed
                self._send(i, self.requests[i % L], start, mine, None, trace)
                self.bodies[i % L] = self.bodies.pop(i)
                with lock:
                    for k in rec:
                        rec[k] += mine[k]

        self._threads(worker, int(self.traffic["clients"]))
        return {"t0": t0, "t1": t1, **rec}

    @staticmethod
    def _threads(fn, n: int):
        ts = [threading.Thread(target=fn, daemon=True) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    # ----------------------------------------------------------------- ingest

    def _init_ingest(self, msg: dict):
        t, cfg = self.traffic, self.cfg
        self.first_step = int(t["setup"]["load_steps"])
        self.max_steps = int(t["max_window_steps"])
        self.vals = datagen.walk(cfg, self.seed,
                                 self.first_step + self.max_steps)
        labels = datagen.series_labels(cfg, self.seed)
        per = int(t["samples_per_send"])
        self.groups = [(lo, min(lo + per, len(labels)))
                       for lo in range(0, len(labels), per)]
        self.templates = [promwire.Template(labels[lo:hi])
                          for lo, hi in self.groups]
        self.clock = np.memmap(msg["clock_file"], np.int64, "r+", shape=(1,))

    def run_ingest(self, msg: dict) -> dict:
        trace = bool(msg.get("trace"))
        rec = {k: [] for k in ("step", "group", "sent", "done", "status",
                               "samples", "want")}
        lock = threading.Lock()
        nxt = [0]
        G = len(self.groups)
        cadence_ns = int(self.cfg["cadence_s"]) * datagen.S
        headers = {"Content-Type": "application/x-protobuf",
                   "Content-Encoding": "snappy"}
        t0 = now_ns() + 200_000_000
        t1 = t0 + int(self.seconds * 1e9)

        def worker():
            time.sleep(max(0.0, (t0 - now_ns()) / 1e9))
            while True:
                with lock:
                    j = nxt[0]
                    nxt[0] += 1
                    k, g = self.first_step + j // G, j % G
                    if k >= self.first_step + self.max_steps:
                        return
                    ts = int(datagen.step_ts(self.cfg, k))
                    # the injected clock follows the newest scrape sent
                    if ts + cadence_ns > self.clock[0]:
                        self.clock[0] = ts + cadence_ns
                sent = now_ns()
                if sent >= t1:
                    return
                lo, hi = self.groups[g]
                body = self.templates[g].fill(ts // 1_000_000,
                                              self.vals[lo:hi, k])
                hdr = dict(headers, **{"X-M3-Trace": "%d:1" % (j + 1)}) \
                    if trace else headers
                status, out = self.client.fetch(
                    "/api/v1/prom/remote/write", body=body, headers=hdr)
                done = now_ns()
                wrote = 0
                if status == 200:
                    wrote = int(json.loads(out).get("wrote", 0))
                else:
                    self.bodies[j] = (status, out)
                with lock:
                    for key, v in zip(rec, (k, g, sent, done, status, wrote,
                                             hi - lo)):
                        rec[key].append(v)

        self._threads(worker, int(self.traffic["senders"]))
        return {"t0": t0, "t1": t1, **rec}

    # ---------------------------------------------------------------- control

    def run(self, msg: dict) -> dict:
        if self.kind == "remote_write":
            return self.run_ingest(msg)
        if self.traffic["loop"] == "open":
            return self.run_open(msg)
        return self.run_closed(msg)

    def get_bodies(self, msg: dict) -> dict:
        out = {}
        for i in msg["indices"]:
            if i in self.bodies:
                status, body = self.bodies[i]
                out[str(i)] = [status, body.decode(errors="replace")]
        return {"bodies": out}

    def errors(self) -> dict:
        bad = [[i, s, b[:300].decode(errors="replace")]
               for i, (s, b) in sorted(self.bodies.items()) if s != 200]
        return {"errors": bad[:10]}


def main():
    gen = None
    out = sys.stdout
    for line in sys.stdin:
        msg = json.loads(line)
        op = msg["op"]
        if op == "init":
            gen = Generator(msg)
            reply = {"ok": True}
        elif op == "warm":
            reply = gen.warm()
        elif op == "run":
            reply = gen.run(msg)
        elif op == "bodies":
            reply = gen.get_bodies(msg)
        elif op == "errors":
            reply = gen.errors()
        elif op == "exit":
            reply = {"ok": True}
        else:
            reply = {"error": "unknown op " + op}
        reply["jax_imported"] = "jax" in sys.modules
        reply["program_imported"] = "m3_tpu" in sys.modules
        out.write(json.dumps(reply) + "\n")
        out.flush()
        if op == "exit":
            return


if __name__ == "__main__":
    main()
