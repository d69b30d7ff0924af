"""Device dispatches of the client-side decode per query (`decode_n` on
`client.fetch_tagged`)."""

from harness import clusterspans


def read(m):
    return clusterspans.per_query(m, "decode_n")
