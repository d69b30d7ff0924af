"""`db-write-batch` over counters: the seed's truth is not
`datagen.walk`'s gauges but the monotonic walks of
`harness/countergen.py` (TSBS `MWD`, as the configuration's
`schema.field_steps` states them). `Server.load` has already made the
gauges when it calls a set-up; this one puts the counters in their
place as `server.vals` before a scrape is replayed, then does what
`db-write-batch` does. Every check and reference reads `server.vals`,
so they follow."""

from harness import countergen, spec


def load(server, say) -> dict:
    server.vals = countergen.counters(server.cfg, server.seed,
                                      server.vals.shape[1])
    say(f"counters installed: {server.vals.shape}, the largest "
        f"{int(server.vals.max())}")
    return spec.load_part("setups", "db-write-batch").load(server, say)
