"""The load generator: a child process that never imports JAX or the
program. It is started before the server process touches the chip,
talks to the server over localhost HTTP only, stamps every request on
its own monotonic clock (CLOCK_MONOTONIC, which the server process
shares, so spans and requests line up) and hands its samples back over
a pipe as JSON lines.

What it sends is the traffic file's `kind`: a file of that name under
`benchmark/traffic_kinds/`, which makes the requests (`init`), warms the
shapes they use (`warm`) and drives the window from the instant the
server names (`run`)."""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import spec  # noqa: E402


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
    """What every traffic kind works with: the cell as sent over the
    pipe, the seed, one client, and the answers kept for the checks."""

    def __init__(self, msg: dict):
        self.cell = msg["cell"]
        self.seed = int(msg["seed"])
        self.seconds = float(msg["seconds"])
        self.traffic = self.cell["traffic"]
        self.cfg = self.cell["config"]
        self.client = Client(msg["base"],
                             float(self.traffic["request_timeout_s"]))
        self.bodies = {}
        self.kind = spec.load_part("traffic_kinds", self.traffic["kind"])
        self.kind.init(self, msg)

    @staticmethod
    def threads(fn, n: int):
        ts = [threading.Thread(target=fn, daemon=True) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

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
            reply = gen.kind.warm(gen)
        elif op == "run":
            reply = gen.kind.run(gen, msg)
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
