"""Bytes of node-RPC result frames the session took in per query
(`bytes_in` on `client.fetch_tagged`: the responders it waited for)."""

from harness import clusterspans


def read(m):
    return clusterspans.per_query(m, "bytes_in")
