"""Smart replicating client (reference: src/dbnode/client)."""

from .decode import ConflictStrategy, merge_replica_points
from .session import (
    ConsistencyError,
    HostClient,
    RemoteError,
    Session,
    SessionOptions,
)

__all__ = [
    "ConflictStrategy",
    "ConsistencyError",
    "HostClient",
    "RemoteError",
    "Session",
    "SessionOptions",
    "merge_replica_points",
]
