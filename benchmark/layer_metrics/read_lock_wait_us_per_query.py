"""The read side's wait for the shard lock while a fleet's writes append
under it: `lock_wait_ns` of `query.fetch`, mean over the fetches that
carry the cost. A program whose fetch carries none gives nothing to
read. Until PR 50 a row of `checks/write_pace.py`."""

from harness import phases, spans


def read(m):
    fetches = [n for n in spans.named(m.span_trees, "query.fetch")
               if "lock_wait_ns" in n["costs"]]
    if not fetches:
        return None
    return phases.cost(fetches, "lock_wait_ns") / len(fetches) / 1e3
