"""The set-up's scrapes through the embedded coordinator's writer,
`DownsamplerAndWriter.write_batch`: the path a remote-write request
takes once it is decoded, in requests of `setup.batch_samples` rows. So
whatever the ingest path builds is built by set-up: the shard memo, the
series ids, rule matches and, in a deployment that has them, the
aggregated namespaces."""

from harness import datagen


def load(server, say) -> dict:
    setup = server.cell.traffic["setup"]
    per = int(setup["batch_samples"])
    tags = datagen.wire_tags(server.labels)
    n = len(tags)
    write_batch = server.handle.writer.write_batch

    def write_scrape(_k, ts, values):
        rows = [(t, ts, v) for t, v in zip(tags, values.tolist())]
        for lo in range(0, n, per):
            write_batch(rows[lo:lo + per])

    server.replay_scrapes(write_scrape, say)
    return {"series": n, "samples": n * int(setup["load_steps"])}
