"""`dbnode-restarted` (one dbnode with its embedded coordinator that can
be restarted in place) for a configuration whose coordinator has a
NAMESPACE LIST: `dbnode.coordinator.namespaces`, one `type:
unaggregated` and one `type: aggregated` with `downsample.all`, each a
namespace of the node's own `dbnode.namespaces`. The program builds its
resolver and its downsampler's targets from that list; nothing is wired
here.

The handle is `dbnode-restarted`'s, and names what the harness and the
set-up need of the two namespaces: `namespace` is the AGGREGATED one
(the harness counts its sealed blocks and filesets against
`setup.sealed_blocks`; the set-up's facts report the other's),
`unaggregated_namespace` where the coordinator writes what it is sent,
`resolution_ns` the aggregated namespace's, and `booted_at` the instant
the deployment was up (`time.perf_counter`).

A program that knows no namespace list refuses the configuration as the
node boots (`services/config.py`: unknown keys), before any set-up work."""

import os
import time

from harness import spec

_restarted = spec.load_part("deployments", "dbnode-restarted")


class Handle(_restarted.Handle):
    def _start(self, bootstrap: bool):
        from m3_tpu.services import load_dict

        super()._start(bootstrap)
        # the list as the program itself reads it
        members = load_dict(dict(self._node_cfg, bootstrap_enabled=bootstrap),
                            "dbnode").coordinator.namespaces
        raw = [m for m in members if not m.aggregated]
        agg = [m for m in members if m.downsample_all]
        if len(raw) != 1 or len(agg) != 1:
            raise SystemExit(
                "benchmark: dbnode-aggns takes a coordinator with one "
                "unaggregated namespace and one downsample.all aggregated")
        self.unaggregated_namespace = raw[0].namespace.encode()
        self.namespace = agg[0].namespace.encode()
        self.resolution_ns = agg[0].resolution_ns


def boot(cell, workdir: str, clock) -> Handle:
    node = dict(cell.config["dbnode"])
    node["data_dir"] = os.path.join(workdir, "data")
    node["coordinator"] = dict(node.get("coordinator") or {})
    handle = Handle(node, clock)
    handle.booted_at = time.perf_counter()
    return handle
