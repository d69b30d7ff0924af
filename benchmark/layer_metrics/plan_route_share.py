"""Queries that ran on the compiled plan route, of the queries executed.

In `rf3-query-thin` the route runs on the coordinator's own device."""

from harness import reduce


def read(m):
    return reduce.share(m.moved("query.plan.executed"),
                        m.moved("query.executed"))
