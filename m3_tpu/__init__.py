"""m3_tpu: a TPU-native metrics platform (storage node, aggregator,
PromQL/Graphite query engine) with the capabilities of the M3 reference —
hot paths as batched JAX/XLA kernels, control plane on the host.

Package map (see README.md for the full reference parity table):
  ops/        device kernels: TSZ codec, window aggregation, temporal fns
  storage/    db -> namespace -> shard -> buffer/blocks, bootstrap, repair
  persist/    filesets + commitlog WAL
  index/      inverted tag index
  cluster/    KV, placement, elections, topology
  client/     replicating quorum session
  rpc/        framed binary wire + node server (+ http/json mirror)
  metrics/    types, policies, rules, matchers, pipelines, carbon
  aggregator/ windowed aggregation tier (+ raw TCP server, deploy)
  msg/        sharded pub/sub with acks
  collector/  rule-matched forwarding agent
  query/      PromQL + Graphite engines, storage adapters, federation
  coordinator/ HTTP API, ingest, downsampler, admin
  services/   yaml-config service binaries
  tools/      fileset/commitlog ops CLIs
  parallel/   mesh sharding + the flagship sharded ingest step
"""

__version__ = "0.1.0"

import os as _os

if _os.environ.get("M3_TPU_LOCKDEP", "") not in ("", "0"):
    # Runtime lock-order witness (utils/lockdep.py): must install BEFORE
    # any m3_tpu module allocates a lock, so the package init is the
    # one place early enough. Opt-in — costs nothing when unset.
    from .utils import lockdep as _lockdep

    _lockdep.install()

if _os.environ.get("M3_TPU_NUMERICS", "") not in ("", "0"):
    # Runtime numerics witness (utils/numwatch.py): arms the jit-builder
    # result observation points (plan compiler host finish, aggregator
    # quantile gather) and the exit dump. Smoke tiers only — observation
    # materializes padded planes. Opt-in — costs one bool read when off.
    from .utils import numwatch as _numwatch

    _numwatch.install()

if _os.environ.get("M3_TPU_RACEWATCH", "") not in ("", "0"):
    # Runtime race witness (utils/racewatch.py): arms attribute
    # instrumentation on registered shared-state attrs (installing
    # lockdep underneath for held-lock snapshots) and the exit dump.
    # Must install BEFORE product modules import so their register()
    # calls instrument immediately. Smoke tiers only — a watched attr
    # becomes a descriptor. Opt-in — costs one list append when off.
    from .utils import racewatch as _racewatch

    _racewatch.install()
