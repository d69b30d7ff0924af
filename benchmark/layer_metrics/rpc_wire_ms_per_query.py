"""The node wire's own cost per query: the result frames' encode on the
nodes (`wire_encode_ns` on `rpc.fetch_tagged`) plus their decode on the
client (`wire_decode_ns` on `client.fetch_tagged`; spent on the fan-out
workers, inside the wait for coverage)."""

from harness import clusterspans, phases, spans


def read(m):
    client = clusterspans.per_query(m, "wire_decode_ns", 1e6)
    if client is None:
        return None
    n = len(spans.named(m.span_trees, "query.execute_range"))
    server = spans.named(m.span_trees, "rpc.fetch_tagged")
    return client + phases.cost(server, "wire_encode_ns") / n / 1e6
