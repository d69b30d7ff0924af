"""Randomized cluster chaos campaign — dtest scenarios with the op order
fuzzed (reference: cmd/tools/dtest/tests — add/remove/replace node,
seeded bootstrap — run as fixed sequences; here the sequence is drawn).

One round: a live multi-node cluster (real TCP node servers, shared KV,
quorum sessions) seeded with sealed data, then a random walk of settled
operations:

  * write burst      — quorum writes to random series at "now"
  * seal             — clock advance + tick (data moves to sealed blocks)
  * add_node         — placement add, peer-bootstrap the initializing
                       shards, mark available (the correct operator flow)
  * remove_up_node   — placement remove; new owners peer-bootstrap from
                       the surviving replicas, then mark available
  * replace_down     — SIGSTOP-equivalent (server close), placement
                       replace, peer-bootstrap the replacement

After EVERY operation, every series must be fully readable — exact
timestamps and values — through fresh quorum sessions at read
consistency ONE and MAJORITY. Any lost point, torn merge, or read
routed to a data-less owner fails the campaign (this is the invariant
whose violation surfaced the initializing-owner read-routing bug).

Usage: python scripts/fuzz_cluster.py --rounds 3 --ops 12
(forces the CPU jax backend; no TPU needed)
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from m3_tpu.client.session import Session, SessionOptions  # noqa: E402
from m3_tpu.cluster.placement import Instance, ShardState  # noqa: E402
from m3_tpu.storage.bootstrap import (BootstrapContext,  # noqa: E402
                                      BootstrapProcess)
from m3_tpu.storage.namespace import NamespaceOptions  # noqa: E402
from m3_tpu.testing.cluster import ClusterHarness  # noqa: E402
from m3_tpu.utils import xtime  # noqa: E402

NS = b"default"
S = 1_000_000_000


class Chaos:
    def __init__(self, rng, n_series=16):
        from m3_tpu.cluster.topology import ConsistencyLevel

        self.rng = rng
        self.h = ClusterHarness(n_nodes=4, replica_factor=3, num_shards=16,
                                ns_opts=NamespaceOptions(index_enabled=False))
        # Writes at ALL: the campaign's invariant is that consistency-ONE
        # reads are COMPLETE, which M3's model only guarantees once every
        # replica holds the point. At the default majority-ack level a
        # lagging third replica's queued write can be sealed away by the
        # simulated 2h clock jump, and a ONE read hitting that replica
        # legitimately misses it — consistency semantics, not data loss.
        self.session = Session(self.h.topology, SessionOptions(
            timeout_s=10, write_consistency=ConsistencyLevel.ALL))
        self.ids = [b"chaos.%d" % i for i in range(n_series)]
        self.expected = {sid: {} for sid in self.ids}  # sid -> {t: v}
        self.next_node = 100
        self.write_burst()
        self.seal()

    # -- operations --------------------------------------------------------

    def write_burst(self):
        now = self.h.clock()
        for sid in self.ids:
            if self.rng.random() < 0.7:
                k = int(self.rng.integers(1, 6))
                ts = [now - int(i) * xtime.SECOND for i in range(k)]
                vs = [float(self.rng.integers(0, 1000)) for _ in range(k)]
                self.session.write_batch(NS, [sid] * k, ts, vs)
                for t, v in zip(ts, vs):
                    self.expected[sid][t] = v

    def seal(self):
        self.h.clock.advance(2 * xtime.HOUR + 11 * xtime.MINUTE)
        self.h.tick_all()

    def _settle(self):
        """Peer-bootstrap every instance's INITIALIZING shards, then mark
        it available — the operator flow every placement change needs
        before the next one (the planner enforces it)."""
        p = self.h.placement_svc.get()
        for iid, inst in p.instances.items():
            init = [a.shard for a in inst.shards.values()
                    if a.state == ShardState.INITIALIZING]
            if not init:
                continue
            node = self.h.nodes[iid]
            proc = BootstrapProcess(
                chain=("peers", "uninitialized_topology"),
                ctx=BootstrapContext(session=self.session,
                                     placement=p, host_id=iid))
            res = proc.run(node.db, shard_ids=init)[NS]
            assert res.unfulfilled.is_empty(), (
                f"settle: {iid} could not bootstrap {init}: "
                f"{res.unfulfilled}")
            self.h.placement_svc.mark_instance_available(iid)

    def add_node(self):
        if len(self.h.nodes) >= 6:
            return "skip-add"
        node = self.h.add_node(f"node{self.next_node}")
        self.next_node += 1
        self._settle()
        return f"add {node.host_id}"

    def remove_up_node(self):
        if len(self.h.nodes) <= 4:
            return "skip-remove"
        victim = str(self.rng.choice(sorted(self.h.nodes)))
        self.h.remove_node(victim)
        self._settle()
        return f"remove {victim}"

    def replace_down(self):
        victim = str(self.rng.choice(sorted(self.h.nodes)))
        self.h.stop_node(victim)
        replacement = self.h._make_node(f"node{self.next_node}")
        self.next_node += 1
        self.h.placement_svc.replace_instance(
            victim, Instance(id=replacement.host_id,
                             endpoint=replacement.endpoint))
        del self.h.nodes[victim]
        self.h.nodes[replacement.host_id] = replacement
        # _settle bootstraps exactly the replacement's INITIALIZING
        # shards and marks it available — the same operator flow every
        # placement change uses.
        self._settle()
        return f"replace {victim} -> {replacement.host_id}"

    # -- invariant ---------------------------------------------------------

    def verify(self, tag):
        from m3_tpu.cluster.topology import ReadConsistencyLevel

        # Retention pruning: long campaigns (--ops >= ~22) push the
        # simulated clock past the namespace retention, and the shard
        # tick legitimately expires old blocks — drop them from the
        # expectation instead of reporting phantom data loss.
        now = self.h.clock()
        opts = self.h.ns_opts
        bsz = opts.block_size_ns
        horizon = now - opts.retention_ns
        for sid in self.ids:
            self.expected[sid] = {
                t: v for t, v in self.expected[sid].items()
                if (t - t % bsz) + bsz > horizon}
        for level in (ReadConsistencyLevel.ONE,
                      ReadConsistencyLevel.MAJORITY):
            sess = Session(self.h.topology, SessionOptions(
                timeout_s=10, read_consistency=level))
            try:
                for sid in self.ids:
                    want = self.expected[sid]
                    t, v = sess.fetch(NS, sid, 0, self.h.clock() + 1)
                    got = dict(zip(t.tolist(), v.tolist()))
                    assert got == want, (
                        f"[{tag} @ {level.name}] {sid}: "
                        f"missing={sorted(set(want) - set(got))[:3]} "
                        f"extra={sorted(set(got) - set(want))[:3]} "
                        f"({len(got)}/{len(want)} points)")
            finally:
                sess.close()

    def close(self):
        self.session.close()
        self.h.close()


def run_round(rng, ops):
    c = Chaos(rng)
    try:
        c.verify("seeded")
        choices = [c.add_node, c.remove_up_node, c.replace_down]
        for i in range(ops):
            # data churn between disruptions, always sealed before one
            c.write_burst()
            c.seal()
            op = choices[int(rng.integers(len(choices)))]
            tag = op()
            c.verify(f"op{i}:{tag}")
        return sum(len(m) for m in c.expected.values())
    finally:
        c.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--ops", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    pts = 0
    for r in range(args.rounds):
        pts += run_round(rng, args.ops)
        print(f"  round {r + 1}/{args.rounds} ok "
              f"({pts} expected points verified x2 levels, "
              f"{time.time() - t0:.0f}s)", flush=True)
    print(f"CLUSTER CHAOS PASS: {args.rounds} rounds x {args.ops} ops, "
          f"seed {args.seed}, {time.time() - t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
