"""One dbnode with its embedded coordinator that can be RESTARTED in
place: booted empty through `services.run_dbnode` like
`dbnode-embedded`, and once its set-up has left on its disk what a node
that ran for hours leaves there, `handle.restart()` closes it and runs
`run_dbnode` again over the same data directory with
`bootstrap_enabled`: the program's own restart, its bootstrap chain
(filesystem -> commit log -> uninitialized topology) before the
listeners open. The coordinator comes back on the port it had, so the
endpoint the load generator was given stays the node's."""

import contextlib
import os
import sys


class Handle:
    """`base`, `db`, `persist`, `writer`, `namespace`, `close()` as every
    deployment's handle; `restart()` besides, after which `db`,
    `persist` and `writer` are the restarted node's."""

    def __init__(self, node_cfg: dict, clock):
        self._node_cfg, self._clock = node_cfg, clock
        self._start(bootstrap=False)
        self.base = self.node.coordinator.endpoint

    def _start(self, bootstrap: bool):
        from m3_tpu.services import load_dict, run_dbnode

        node = dict(self._node_cfg, bootstrap_enabled=bootstrap)
        cfg = load_dict(node, "dbnode")
        # run_dbnode prints its serving-ready line; standard output is
        # the result line's alone
        with contextlib.redirect_stdout(sys.stderr):
            self.node = run_dbnode(cfg, clock=self._clock)
        self.db, self.persist = self.node.db, self.node.persist
        self.writer = self.node.coordinator.writer
        self.namespace = cfg.coordinator.namespace.encode()

    def restart(self) -> dict:
        """Close the node and start it again over its own data
        directory, through its bootstrap. Returns the bootstrap's
        results by namespace (storage/bootstrap.BootstrapResult)."""
        port = self.base.rsplit(":", 1)[1]
        self.node.close()
        coord = dict(self._node_cfg["coordinator"])
        coord["listen_address"] = "127.0.0.1:" + port
        self._node_cfg = dict(self._node_cfg, coordinator=coord)
        self._start(bootstrap=True)
        if self.node.coordinator.endpoint != self.base:
            raise RuntimeError(
                f"the restarted coordinator listens on "
                f"{self.node.coordinator.endpoint}, not {self.base}")
        return self.node.bootstrap_results

    def close(self):
        self.node.close()


def boot(cell, workdir: str, clock) -> Handle:
    node = dict(cell.config["dbnode"])
    node["data_dir"] = os.path.join(workdir, "data")
    node["coordinator"] = dict(node.get("coordinator") or {})
    return Handle(node, clock)
