"""Mediator: the background lifecycle driver (reference:
src/dbnode/storage/mediator.go:112 Open -> :157 ongoingTick; tick.go,
flush.go, fs.go:115 flush/snapshot run, cleanup.go).

`run_once` is the deterministic unit tests call; `start` wraps it in a
ticker thread the service binary owns. Order per tick matches the
reference: tick (seal/expire) -> flush sealed blocks -> snapshot warm
buffers -> cleanup (expired filesets, old snapshots, rotated commitlog
files)."""

from __future__ import annotations

import dataclasses
import os
import shutil
import threading
import time
from typing import Dict, Optional

from ..parallel import scope as dscope
from ..persist.diskio import DiskWriteError
from ..persist.fs import PersistManager, fileset_complete
from ..storage.block import encode_block
from ..utils import tracing, xtime


@dataclasses.dataclass
class MediatorOptions:
    tick_interval_ns: int = 10 * xtime.SECOND
    snapshot_enabled: bool = True


class Mediator:
    def __init__(self, db, persist: Optional[PersistManager] = None,
                 opts: MediatorOptions = MediatorOptions()):
        self.db = db
        self.persist = persist
        self.opts = opts
        self._snapshot_version = 0
        self._version_seeded = False
        # snapshot volumes cleanup has seen complete (their paths)
        self._complete_snapshots: set = set()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.last_stats: Dict[str, int] = {}

    # ------------------------------------------------------------------ steps

    def run_once(self, now_ns: Optional[int] = None) -> Dict[str, int]:
        """One tick, under its own root span (`mediator.tick`, a
        background root: whoever drives the tick — `start`'s loop, a
        test, a benchmark's ticker — gets the same tree). One child per
        step; below them `encode.block` (storage/block.py) and
        `persist.write` (persist/fs.py) per block; the tick's stats are
        the root's tags."""
        with dscope.entered(self.db.scope), \
                tracing.background_span("mediator.tick") as root:
            root.set_tag("device", dscope.device_tag())
            now = now_ns if now_ns is not None else self.db.clock()
            with tracing.child_span("mediator.seal"):
                stats = dict(self.db.tick(now))
            if self.persist is not None:
                with tracing.child_span("mediator.flush"):
                    stats["flushed"] = self.db.flush(self.persist, now)
                if self.opts.snapshot_enabled:
                    with tracing.child_span("mediator.snapshot"):
                        stats["snapshotted"] = self.snapshot(now)
                with tracing.child_span("mediator.cleanup"):
                    stats["cleaned"] = self.cleanup(now)
            for k, v in stats.items():
                root.set_tag(k, v)
        self.last_stats = stats
        return stats

    def snapshot(self, now_ns: int) -> int:
        """Persist warm (still-mutable) buckets as snapshot filesets
        (storage/flush.go snapshot state; persist/fs snapshot volumes):
        those that took a row since their last snapshot; the others keep
        the one they have. Returns the buckets written.

        The commit log position is recorded ONCE, before any buffer is
        read: every WAL entry durable at-or-before it is provably
        visible to the buffer reads below, so recovery replays only the
        WAL tail past the position — the conservative overlap window
        dedups at read/seal, never loses. Sync writes land in the
        buffer BEFORE their commit log append; ASYNC new-series writes
        (write_new_series_async) sit in the insert queue with their WAL
        append already durable, so every queue is drained between
        taking the position and reading buffers — an entry whose chunk
        is at-or-before the position was enqueued before position() ran
        and therefore lands in the buffer the snapshot reads."""
        if not self._version_seeded:
            # Resume ABOVE any version already on disk: after a restart
            # a counter reset to 1 would lose every new snapshot to the
            # pre-kill generation's higher versions at cleanup.
            self._version_seeded = True
            for ns in list(self.db.namespaces.values()):
                for shard_id in ns.shards:
                    for _bs, version, _p in self.persist.list_snapshots(
                            ns.name, shard_id):
                        self._snapshot_version = max(
                            self._snapshot_version, version)
        self._snapshot_version += 1
        version = self._snapshot_version
        wal_position = None
        commitlog = getattr(self.db, "commitlog", None)
        if commitlog is not None:
            try:
                wal_position = commitlog.position()
            except ValueError:
                wal_position = None  # closed log: snapshot without one
        if wal_position is not None:
            with tracing.phase("drain"):
                for ns in list(self.db.namespaces.values()):
                    for shard in ns.shards.values():
                        shard.insert_queue.drain()
        count = 0
        for ns in list(self.db.namespaces.values()):
            if not ns.opts.snapshot_enabled:
                continue
            for shard in ns.shards.values():
                for bs in sorted(shard.buffer.buckets):
                    if bs in shard.blocks:
                        # The block start already has a sealed
                        # representation (a snapshot-recovered tile, or
                        # a seal racing a late drain): the BUFFER's
                        # content alone is a partial view, and a
                        # snapshot of it would record a WAL position
                        # claiming coverage of chunks whose data lives
                        # only in the block — a later restart would
                        # position-skip them and lose acked writes.
                        # These buckets stay WAL-replayable instead
                        # (the pre-existing snapshot, if any, remains
                        # the newest for this block start).
                        continue
                    # A bucket's columns only append: the row count
                    # its newest snapshot was cut at is its content.
                    # Nothing appended since leaves that snapshot the
                    # newest of its block start (cleanup keeps it; its
                    # older WAL position only means the replay looks
                    # through more chunks, which hold none of its rows).
                    # Read BEFORE the columns: an append between the two
                    # is in this snapshot and asks for the next.
                    bucket = shard.buffer.buckets.get(bs)
                    if bucket is None:      # sealed meanwhile
                        continue
                    rows = bucket.cols.n
                    if rows == bucket.snapshotted_rows:
                        tracing.count_cost("buckets_unchanged_n")
                        continue
                    with tracing.phase("buffer_snapshot"):
                        dense = shard.buffer.snapshot(bs)
                    if dense is None:
                        continue
                    tracing.count_cost("buckets_n")
                    series, tdense, vdense, npoints = dense
                    blk = encode_block(bs, series, tdense, vdense, npoints,
                                       min_rows=len(shard.registry))
                    try:
                        self.persist.write_snapshot(
                            ns.name, shard.shard_id, blk, shard.registry,
                            version, wal_position=wal_position)
                    except DiskWriteError:
                        # Typed snapshot failure: the bucket stays WAL-
                        # replayable (nothing is lost, recovery just
                        # replays more), health degrades, the sweep
                        # continues — the next tick re-attempts.
                        health = getattr(self.db, "disk_health", None)
                        if health is not None:
                            health.failure()
                        continue
                    bucket.snapshotted_rows = rows
                    count += 1
        return count

    def cleanup(self, now_ns: int) -> int:
        """cleanup.go: remove filesets past retention, superseded snapshots,
        and snapshots for blocks already flushed.

        One directory listing a shard, and a digest chain is read only
        where the answer decides something: a fileset that is up for
        removal or shares its block start with a snapshot, a snapshot
        this mediator has not yet seen complete (a complete volume is
        never written again under its name, so what was seen stays)."""
        removed = 0
        seen_complete = set()
        for ns in list(self.db.namespaces.values()):
            cutoff = now_ns - ns.opts.retention_ns
            block_size = ns.opts.block_size_ns
            for shard_id in ns.shards:
                shard_dir = os.path.join(self.persist.root, ns.name.decode(),
                                         f"shard-{shard_id:05d}")
                try:
                    names = os.listdir(shard_dir)
                except (FileNotFoundError, NotADirectoryError):
                    continue
                filesets: Dict[int, str] = {}
                snaps = []
                for name in names:
                    path = os.path.join(shard_dir, name)
                    if name.endswith(".tmp"):
                        # Mid-write crash residue (SIGKILL between
                        # the checkpoint write and os.replace):
                        # never servable, never auto-replaced.
                        shutil.rmtree(path, ignore_errors=True)
                        removed += 1
                    elif name.startswith("fileset-"):
                        filesets[int(name.split("-")[-1])] = path
                    elif name.startswith("snapshot-"):
                        _, version, bs = name.split("-")
                        if path in self._complete_snapshots \
                                or fileset_complete(path):
                            seen_complete.add(path)
                            snaps.append((int(bs), int(version), path))
                shard_removed = 0
                for bs in sorted(filesets):
                    if bs + block_size <= cutoff \
                            and fileset_complete(filesets[bs]):
                        shutil.rmtree(filesets.pop(bs), ignore_errors=True)
                        shard_removed += 1
                if shard_removed and getattr(self.db, "retriever", None) is not None:
                    # Cached listings/seekers/wired rows now point at deleted
                    # directories — drop them before the next cold read.
                    self.db.retriever.invalidate(ns.name, shard_id)
                removed += shard_removed
                newest: Dict[int, int] = {}
                for bs, version, _p in snaps:
                    newest[bs] = max(newest.get(bs, -1), version)
                flushed = {bs for bs in newest
                           if bs in filesets and fileset_complete(filesets[bs])}
                for bs, version, path in sorted(snaps):
                    stale = (version < newest[bs] or bs in flushed
                             or bs + block_size <= cutoff)
                    if stale:
                        shutil.rmtree(path, ignore_errors=True)
                        seen_complete.discard(path)
                        removed += 1
        self._complete_snapshots = seen_complete
        removed += self._trim_commitlog()
        return removed

    def _trim_commitlog(self) -> int:
        """Delete commit log files that can no longer contribute to any
        bootstrap (cleanup.go's commit log cleanup): a non-active file
        last written more than max-retention-plus-slack of WALL time ago
        holds only entries whose data timestamps (bounded by the
        acceptance window around their write time) are past every
        namespace's retention — replay would range-filter every one.
        Without this the WAL grows without bound and every restart
        replays history that can never be served."""
        commitlog = getattr(self.db, "commitlog", None)
        if commitlog is None:
            return 0
        namespaces = list(self.db.namespaces.values())
        retention = max((ns.opts.retention_ns for ns in namespaces),
                        default=0)
        if not retention:
            return 0
        # An entry written at file-mtime M carries a data timestamp of
        # at most M + buffer_future, so the slack must cover the widest
        # configured future window (plus an hour of margin) — a fixed
        # slack would delete still-in-retention entries under a large
        # buffer_future.
        slack = max((ns.opts.buffer_future_ns for ns in namespaces),
                    default=0) + xtime.HOUR
        # Wall clock, not the db clock: file mtimes are wall time (a
        # test driving a fake clock simply never trims — safe).
        horizon = time.time_ns() - retention - slack
        active = commitlog.active_file()
        removed = 0
        for path in commitlog.files():
            if path == active:
                continue
            try:
                if os.stat(path).st_mtime_ns < horizon:
                    os.remove(path)
                    removed += 1
            except OSError:
                continue
        return removed

    # ------------------------------------------------------------- background

    def start(self, interval_s: Optional[float] = None):
        iv = interval_s if interval_s is not None else self.opts.tick_interval_ns / 1e9
        self._stop.clear()

        def loop():
            while not self._stop.wait(iv):
                try:
                    self.run_once()
                except Exception:  # noqa: BLE001 — background loop survives
                    pass

        self._thread = threading.Thread(target=loop, name="mediator",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
