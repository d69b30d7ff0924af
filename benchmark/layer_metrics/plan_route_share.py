"""Queries that ran on the compiled plan route, of the queries executed."""

from harness import reduce


def read(m):
    return reduce.share(m.moved("query.plan.executed"),
                        m.moved("query.executed"))
