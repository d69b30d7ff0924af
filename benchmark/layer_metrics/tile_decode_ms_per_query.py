"""Client-side tile decode per query: `decode_ns` on
`client.fetch_tagged` — every `decode_tile` call's upload, device
program and copy back; one call per responder and tile geometry (a
responder's tiles, one per shard and sealed block, stack into it)."""

from harness import clusterspans


def read(m):
    return clusterspans.per_query(m, "decode_ns", 1e6)
