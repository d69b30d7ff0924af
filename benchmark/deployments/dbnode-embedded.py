"""One dbnode with its embedded coordinator, booted as its users boot
it: the normal entry point `services.run_dbnode` over the
configuration's `dbnode` block, under the injected clock. The
coordinator is what the configuration's own `dbnode.coordinator` block
says, and the program's defaults where it has none."""

import os


class Handle:
    """What the harness takes from a booted deployment: `base` (the HTTP
    endpoint the generator drives), `db`, `persist`, `writer` (the
    coordinator's ingest path, for a set-up that loads through it),
    `namespace` (where the coordinator reads and writes) and `close()`."""

    def __init__(self, node, namespace: bytes):
        self.node = node
        self.base = node.coordinator.endpoint
        self.db, self.persist = node.db, node.persist
        self.writer = node.coordinator.writer
        self.namespace = namespace

    def close(self):
        self.node.close()


def boot(cell, workdir: str, clock) -> Handle:
    from m3_tpu.services import load_dict, run_dbnode

    node = dict(cell.config["dbnode"])
    node["data_dir"] = os.path.join(workdir, "data")
    node["coordinator"] = dict(node.get("coordinator") or {})
    cfg = load_dict(node, "dbnode")
    return Handle(run_dbnode(cfg, clock=clock),
                  cfg.coordinator.namespace.encode())
