"""`db-write-batch` with Prometheus's scrape timing: every scrape of
every host written at the host's own timestamp, `T0 + offset[h] + k *
interval` (`harness/promoffsets.py`; whole milliseconds, a host's series
sharing it), straight into the dbnode through `Database.write_batch`.
The blocks such a store seals are at the MILLISECOND unit, and no two
hosts share a timestamp grid."""

from harness import datagen, promoffsets


def load(server, say) -> dict:
    from m3_tpu.metrics import id as metric_id

    tags = datagen.wire_tags(server.labels)
    name = server.cfg["schema"]["measurement"].encode()
    ids = [metric_id.encode(name, {k: v for k, v in t.items()
                                   if k != b"__name__"}) for t in tags]
    off = promoffsets.series_offsets_ns(server.cfg, server.seed)
    db, ns = server.handle.db, server.handle.namespace

    def write_scrape(k, ts, values):
        db.write_batch(ns, ids, ts + off, values, tags if k == 0 else None)

    server.replay_scrapes(write_scrape, say)
    say(f"offsets installed: {len(set(off.tolist()))} distinct of "
        f"{server.cfg['scale']} hosts, in ms")
    return {"series": len(ids),
            "samples": len(ids) * int(
                server.cell.traffic["setup"]["load_steps"])}
