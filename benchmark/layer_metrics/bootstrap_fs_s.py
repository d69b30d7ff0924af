"""Seconds of the restarted node's filesystem bootstrap: the
`bootstrap.filesystem` root span (index segments, every fileset
verified, its block installed, the series named), as the set-up read it
from the program's tracer; the restart's wall time where the program
opens no such span.

In `aggns-query-3d` the bootstrap runs over both namespaces (2,688
filesets, 42 block starts)."""


def read(m):
    return m.setup.get("bootstrap_fs_s")
