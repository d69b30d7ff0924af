"""Read traffic: PromQL over HTTP, requests made by the one general
generator (`harness/schedule.py`) from the mix's classes.

Open loop (`rate_per_s`, `schedule_seed`, `max_in_flight`,
`check_sample`): requests are sent when they are due, by a pool of
client threads; latency is counted from when a request was due, so a
stall costs every request queued behind it. Closed loop (`clients`,
`replay_len`): each client sends its next request when the last one is
answered, cycling a replay list.

`init`, `warm` and `run` are the load-generator child's; they import
neither JAX nor the program. `keep_indices` is the server side's."""

import threading
import time

import numpy as np

from harness import schedule, spec

CHECKS = ["query_answers", "served_path_verdict"]
REC = ("i", "due", "sent", "done", "status", "cls", "bytes")
now_ns = time.perf_counter_ns


def init(gen, msg: dict):
    if gen.traffic["loop"] == "open":
        gen.due = schedule.arrivals(gen.traffic, gen.seconds)
    gen.requests = schedule.requests_for(
        gen.cell, gen.seed, schedule.n_requests(gen.traffic, gen.seconds))


def _class_requests(gen, name: str, count: int, salt: int):
    """`count` requests of one class alone, on draws of their own."""
    cell = dict(gen.cell, classes=[spec.load_class(name)],
                traffic=dict(gen.traffic, loop="closed",
                             mix=[{"class": name, "cards": 1}]))
    return schedule.requests_for(cell, gen.seed, count, salt=salt)


def warm(gen) -> dict:
    """One request at a time, on other draws than the window's but the
    same shapes, so what would compile compiles here: first what the
    traffic file names to fill the program's lazy state (a whole block
    is decoded at its first touch), then every class of the mix a few
    times."""
    reqs = []
    for w in gen.traffic.get("warm_first", []):
        reqs += _class_requests(gen, w["class"], int(w["count"]), 2)
    for m in gen.traffic["mix"]:
        reqs += _class_requests(gen, m["class"],
                                int(gen.traffic["warm_per_class"]), 1)
    errors = []
    for r in reqs:
        status, body = gen.client.fetch(r["path"])
        if status != 200:
            errors.append([r["path"][:200],
                           body[:300].decode(errors="replace")])
    return {"ok": not errors, "errors": errors[:5]}


def _send(gen, i: int, req: dict, due: int, rec: dict, keep, trace: bool):
    headers = {"X-M3-Trace": "%d:1" % (i + 1)} if trace else None
    sent = now_ns()
    status, body = gen.client.fetch(req["path"], headers=headers)
    done = now_ns()
    for key, v in zip(REC, (i, due, sent, done, status, req["cls"],
                            len(body))):
        rec[key].append(v)
    if status != 200 or keep is None or keep(i):
        gen.bodies[i] = (status, body)


def _run_open(gen, msg: dict) -> dict:
    keep_set = set(msg.get("keep", []))
    trace = bool(msg.get("trace"))
    due_ns = (gen.due * 1e9).astype(np.int64)
    n = len(due_ns)
    rec = {k: [] for k in REC}
    lock = threading.Lock()
    nxt = [0]
    t0 = int(msg["t0"])

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
            _send(gen, i, gen.requests[i], due, mine, keep_set.__contains__,
                  trace)
            with lock:
                for k in rec:
                    rec[k] += mine[k]

    gen.threads(worker, int(gen.traffic["max_in_flight"]))
    return {"t0": t0, "t1": t0 + int(gen.seconds * 1e9), **rec}


def _run_closed(gen, msg: dict) -> dict:
    trace = bool(msg.get("trace"))
    rec = {k: [] for k in REC}
    lock = threading.Lock()
    nxt = [0]
    t0 = int(msg["t0"])
    t1 = t0 + int(gen.seconds * 1e9)
    L = len(gen.requests)

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
            _send(gen, i, gen.requests[i % L], start, mine, None, trace)
            gen.bodies[i % L] = gen.bodies.pop(i)
            with lock:
                for k in rec:
                    rec[k] += mine[k]

    gen.threads(worker, int(gen.traffic["clients"]))
    return {"t0": t0, "t1": t1, **rec}


def run(gen, msg: dict) -> dict:
    if gen.traffic["loop"] == "open":
        return _run_open(gen, msg)
    return _run_closed(gen, msg)


def keep_indices(cell, seed: int, seconds: float):
    """Which answers the child keeps for the comparison: a sample drawn
    from the seed (open loop) or every replay entry's newest."""
    t = cell.traffic
    if t["loop"] != "open":
        return list(range(int(t["replay_len"])))
    n = schedule.n_requests(t, seconds)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 23])
    return sorted(int(i) for i in rng.choice(
        n, min(n, int(t["check_sample"])), replace=False))
