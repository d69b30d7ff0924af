"""Replicating smart client (reference: src/dbnode/client/session.go).

Session parity: topology-watching (session.go:536-543), per-host queues
with op batching (host_queue.go), connection pools
(connection_pool.go), write fanout to all shard replicas with quorum
wait (session.go:867 Write -> :903 writeAttempt, majority :609),
FetchTagged with consistency accumulation
(fetch_tagged_results_accumulator.go), and the AdminSession peer
metadata/block streaming used by bootstrap & repair
(FetchBootstrapBlocksFromPeers; docs/m3db/architecture/peer_streaming.md)."""

from __future__ import annotations

import dataclasses
import socket
import threading
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait as futures_wait
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..cluster.topology import (
    ConsistencyLevel,
    ReadConsistencyLevel,
    required_acks,
    required_reads,
)
from ..ops.decode_rows import decode_rows, decode_stacked
from ..parallel.sharding import ShardSet
from ..rpc import wire
from ..utils import tracing
from ..utils.instrument import ROOT
from ..utils.limits import ResourceExhausted
from ..utils.retry import (
    Breaker,
    BreakerOpen,
    BreakerOptions,
    Deadline,
    DeadlineExceeded,
    HostHealth,
    Retrier,
    RetryOptions,
)
from .decode import ConflictStrategy, merge_replica_points


class ConsistencyError(Exception):
    """Not enough replica acks/responses to satisfy the consistency level."""


# The typed ways a peer RPC fails without implicating this process's own
# logic: transport death (ConnectionError covers WireTruncated and
# BreakerOpen), socket/connect errors, an expired budget, or a deliberate
# shed by a healthy-but-overloaded peer. Peer-streaming paths classify on
# exactly this set — anything else is a programming error and propagates.
PEER_SKIP_ERRORS = (ConnectionError, OSError, DeadlineExceeded,
                    ResourceExhausted)

# AdminSession peer-streaming instrumentation (bootstrap/repair observe
# peer failures through these instead of silent except/continue).
_PEER_METRICS = ROOT.sub_scope("session.peers")


# ------------------------------------------------------------------ transport

# What one RPC cost on the wire, for the caller that asked: a fan-out
# worker or a host queue installs a dict here (`_wire_stats`) around its
# call and Connection.call adds the frame sizes and the encode / decode
# times to it. The caller folds them into its span's costs on its own
# thread: three workers adding to one span's dict would lose updates.
_WIRE = threading.local()
_clock = tracing.clock_ns


def _no_stats() -> dict:
    return {"encode_ns": 0, "decode_ns": 0, "bytes_out": 0, "bytes_in": 0}


class _wire_stats:
    """`with _wire_stats() as st:` around a HostClient call."""

    def __enter__(self) -> dict:
        self.prev = getattr(_WIRE, "stats", None)
        _WIRE.stats = st = _no_stats()
        return st

    def __exit__(self, *exc):
        _WIRE.stats = self.prev
        return False



class Connection:
    """One framed TCP connection (connection_pool.go conn)."""

    def __init__(self, endpoint: str, connect_timeout: float = 10.0,
                 request_timeout: float = 10.0):
        host, port = endpoint.rsplit(":", 1)
        self.endpoint = endpoint
        self.sock = socket.create_connection((host, int(port)),
                                             timeout=connect_timeout)
        self.sock.settimeout(request_timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.request_timeout = request_timeout
        self._msg_id = 0

    def call(self, method: str, args: dict,
             deadline: Optional[Deadline] = None,
             priority: Optional[str] = None):
        self._msg_id += 1
        req = {"m": method, "id": self._msg_id, "a": args}
        if priority is not None:
            # Admission hint for the server's gate ("bulk" sheds first at
            # the high watermark); rides the frame, not the args.
            req["pri"] = priority
        # Trace context rides the frame beside "d"/"pri" — only when a
        # SAMPLED span is active on this thread, so unsampled traffic
        # costs one thread-local read and no wire bytes. The server's
        # finished span tree comes back under "sp" and is grafted below.
        cur_span = tracing.TRACER.current()
        if cur_span is not None:
            req[wire.TRACE_KEY] = cur_span.context().to_wire()
        if deadline is not None:
            deadline.check(method)
            req[wire.DEADLINE_KEY] = deadline.to_wire()
            # The read must give up when the BUDGET does, not at the
            # connection's default request timeout past it.
            self.sock.settimeout(deadline.min_timeout(self.request_timeout))
        else:
            self.sock.settimeout(self.request_timeout)
        st = getattr(_WIRE, "stats", None) or _no_stats()  # nobody asked
        t0 = _clock()
        body = wire.encode(req)
        st["encode_ns"] += _clock() - t0
        st["bytes_out"] += len(body)
        wire.write_body(self.sock, body)
        try:
            while True:
                body = wire.read_body(self.sock)
                t0 = _clock()
                resp = wire.as_dict_frame(wire.decode(body))
                st["decode_ns"] += _clock() - t0
                st["bytes_in"] += len(body)
                rid = resp.get("id", self._msg_id)
                if rid == self._msg_id:
                    break
                if rid > self._msg_id:
                    # A response from the future: the stream is not
                    # request/response-paired anymore — unusable.
                    self.close()
                    raise ConnectionError(
                        f"node reply desync: got id {rid}, "
                        f"expected {self._msg_id}")
                # rid < current: a STALE response — a duplicated request
                # frame (at-least-once delivery) made the server answer
                # an earlier exchange twice. Discard and keep reading;
                # matching on id restores pairing instead of handing the
                # caller another method's result. Re-arm the socket
                # timeout to the REMAINING budget each iteration: stale
                # frames dripping in just under the timeout must not
                # extend a deadlined call past its budget (the unread
                # real response leaves the stream desynced — drop it).
                if deadline is not None:
                    if deadline.expired:
                        self.close()
                        raise DeadlineExceeded(
                            f"{method}: deadline exceeded draining "
                            "stale responses")
                    self.sock.settimeout(
                        deadline.min_timeout(self.request_timeout))
        except socket.timeout:
            # The response may still land later: this stream is desynced
            # for any further request/response pairing — drop it.
            self.close()
            if deadline is not None and deadline.expired:
                raise DeadlineExceeded(f"{method}: deadline exceeded "
                                       "waiting for reply")
            raise
        except ValueError as e:
            # malformed reply = desync: this connection is unusable; close
            # it and surface a CONNECTION error so quorum fanout treats
            # the node as failed instead of retrying on a broken stream.
            self.close()
            raise ConnectionError(f"node reply desync: {e}")
        if not resp.get("ok"):
            if resp.get("kind") == "deadline":
                raise DeadlineExceeded(resp.get("err", "deadline exceeded"))
            if resp.get("kind") == "resource_exhausted":
                # Server shed this request (query limit / admission gate).
                # ResourceExhausted is a RetryableError: the Retrier backs
                # off and re-attempts, because the overload clears on its
                # own — distinct from deadline, which stays non-retryable.
                raise ResourceExhausted(
                    resp.get("err", "server resource exhausted"))
            raise RemoteError(resp.get("err", "unknown remote error"))
        if cur_span is not None and cur_span.end_ns is None:
            # Graft the server-side tree under the calling span, tagged
            # with the endpoint it ran on — the cross-process hop becomes
            # one child in the caller's tree. A FINISHED span (quorum met
            # and returned while this replica straggled) never mutates:
            # it may already be published in the tracer's recent ring.
            sp = resp.get(wire.SPAN_KEY)
            if isinstance(sp, dict):
                sp.setdefault("tags", {})["endpoint"] = self.endpoint
                cur_span.attach(sp)
        return resp["r"]

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


class RemoteError(Exception):
    """Server-side failure passed back to the caller (not a transport error)."""


class HostClient:
    """Connection pool for one host (client/connection_pool.go) fronted
    by a circuit breaker and a retrier: transport failures retry with
    backoff, repeated failures trip the breaker so a dead host is shed
    instead of hammered, and a half-open probe restores it."""

    def __init__(self, endpoint: str, pool_size: int = 4, timeout: float = 10.0,
                 connect_timeout: Optional[float] = None,
                 retry_opts: RetryOptions = RetryOptions(),
                 breaker: Optional[Breaker] = None,
                 on_outcome: Optional[Callable[[bool], None]] = None):
        self.endpoint = endpoint
        self.timeout = timeout
        self.connect_timeout = timeout if connect_timeout is None else connect_timeout
        self.breaker = breaker if breaker is not None else Breaker(name=endpoint)
        self._on_outcome = on_outcome  # e.g. HostHealth.count
        self.retrier = Retrier(retry_opts)
        self._free: List[Connection] = []
        self._lock = threading.Lock()
        self._sema = threading.Semaphore(pool_size)
        # Counts failed attempts: a caller that keeps state about the
        # host (Session's tags-sent-once set) reads it before and after
        # a call, and trusts the state only if nothing failed between —
        # a host that restarted fails its old connections first.
        self.epoch = 0

    def _record(self, ok: bool):
        if ok:
            self.breaker.record_success()
        else:
            self.epoch += 1
            self.breaker.record_failure()
        if self._on_outcome is not None:
            self._on_outcome(ok)

    def call(self, method: str, _deadline: Optional[Deadline] = None,
             _priority: Optional[str] = None, **args):
        return self.retrier.attempt(self._call_once, method, args,
                                    _deadline, _priority, deadline=_deadline)

    def _call_once(self, method: str, args: dict, deadline: Optional[Deadline],
                   priority: Optional[str] = None):
        if self.breaker.state == Breaker.OPEN:
            # fast shed: no pool-slot wait, no grant claimed
            raise BreakerOpen(f"host {self.endpoint} shed by open breaker")
        with self._sema:
            # Claim the breaker grant only once a pool slot is held: a
            # half-open probe stuck waiting behind a busy pool would
            # otherwise hold the ONLY probe slot while doing no probe
            # I/O, shedding every other caller for the whole wait.
            if not self.breaker.allow():
                raise BreakerOpen(f"host {self.endpoint} shed by open breaker")
            # Past allow(), EVERY exit must settle the grant exactly
            # once — an unsettled exit leaks the half-open probe slot
            # and wedges the breaker half-open forever (allow()'s
            # contract).
            recorded = [False]

            def record(ok: bool):
                if not recorded[0]:
                    recorded[0] = True
                    self._record(ok)

            try:
                return self._call_on_conn(method, args, deadline, record,
                                          priority)
            except DeadlineExceeded as e:
                if getattr(e, "pre_io", False) and not recorded[0]:
                    # budget died in CLIENT-side queueing (retry backoff,
                    # connect gate) before any bytes reached the host:
                    # release the grant without blaming the endpoint
                    recorded[0] = True
                    self.breaker.cancel()
                else:
                    record(False)
                raise
            except BaseException:
                record(False)  # safety net for paths the branches miss
                raise

    def _call_on_conn(self, method: str, args: dict,
                      deadline: Optional[Deadline], record,
                      priority: Optional[str] = None):
        """One attempt on a pooled connection (pool semaphore + breaker
        grant both held by _call_once)."""
        with self._lock:
            conn = self._free.pop() if self._free else None
        if conn is None:
            # the connect phase consumes deadline budget too: a
            # blackholed host (SYN drop) must not stall a 100ms-budget
            # call for the full connect timeout
            ct = self.connect_timeout
            if deadline is not None:
                deadline.check(method)
                ct = deadline.min_timeout(ct)
            try:
                conn = Connection(self.endpoint, ct, self.timeout)
            except (OSError, ConnectionError):
                record(False)
                raise
        try:
            result = conn.call(method, args, deadline, priority)
        except RemoteError:
            # The HOST is healthy — it parsed, ran, and answered; the
            # application errored. Keep the connection and the breaker
            # must not trip on it.
            with self._lock:
                self._free.append(conn)
            record(True)
            raise
        except ResourceExhausted:
            # Deliberate shed by a healthy host: the stream is synced and
            # poolable, and the breaker must not trip (tripping it would
            # turn a load-shedding node into a "dead" one and dogpile its
            # replicas). The retrier above backs off and re-attempts —
            # exactly the producer behavior shedding asks for.
            with self._lock:
                self._free.append(conn)
            record(True)
            raise
        except DeadlineExceeded as e:
            # conn.call already dropped a desynced stream (reply never
            # read); a server-sent deadline frame leaves the stream
            # synced and poolable. The breaker records a failure when
            # the HOST burned the budget; a pre-I/O expiry (tagged by
            # Deadline.check — budget died before any bytes went out)
            # falls through for _call_once to cancel the grant.
            if conn.sock.fileno() != -1:
                with self._lock:
                    self._free.append(conn)
            if not getattr(e, "pre_io", False):
                record(False)
            raise
        except Exception:
            conn.close()
            record(False)
            raise
        with self._lock:
            self._free.append(conn)
        record(True)
        return result

    def health(self) -> bool:
        try:
            return bool(self.call("health")["ok"])
        except Exception:  # noqa: BLE001
            return False

    def close(self):
        with self._lock:
            for c in self._free:
                c.close()
            self._free.clear()


# ------------------------------------------------------------------- batching


class _Completion:
    """Quorum wait for one logical write (session writeState)."""

    __slots__ = ("required", "total", "acks", "errors", "errs", "_cond")

    def __init__(self, required: int, total: int):
        self.required = required
        self.total = total
        self.acks = 0
        self.errors = 0
        self.errs: List[str] = []
        self._cond = threading.Condition()

    def ack(self):
        with self._cond:
            self.acks += 1
            self._cond.notify_all()

    def error(self, err: str):
        with self._cond:
            self.errors += 1
            self.errs.append(err)
            self._cond.notify_all()

    def wait(self, timeout: float):
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                if self.acks >= self.required:
                    return
                if self.acks + self.errors >= self.total:
                    raise ConsistencyError(
                        f"{self.acks}/{self.total} acks, need {self.required}: {self.errs}"
                    )
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ConsistencyError(
                        f"timeout: {self.acks}/{self.total} acks, need {self.required}"
                    )
                self._cond.wait(remaining)


@dataclasses.dataclass
class _WriteOp:
    ns: bytes
    id: bytes
    t_ns: int
    value: float
    tags: Optional[dict]
    completion: _Completion
    # Wire admission hint ("bulk" backfill sheds first server-side); None
    # is NORMAL serving traffic.
    priority: Optional[str] = None


class _BatchCompletion:
    """Quorum wait for one columnar batch: one result per HOST, none per
    datapoint. `sets` are the distinct replica sets of the batch's
    shards, each with the acks it needs; the batch is acknowledged when
    every set has them, and fails as soon as one set no longer can."""

    __slots__ = ("sets", "acked", "failed", "_cond")

    def __init__(self, sets: List[Tuple[Tuple[str, ...], int]]):
        self.sets = sets
        self.acked: set = set()
        self.failed: Dict[str, str] = {}
        self._cond = threading.Condition()

    def done(self, host_id: str, err: Optional[str] = None):
        with self._cond:
            if err is None:
                self.acked.add(host_id)
            else:
                self.failed[host_id] = err
            self._cond.notify_all()

    def _settled(self) -> bool:
        """True when every set has its acks; raises when one cannot."""
        ok = True
        for hosts, required in self.sets:
            acks = sum(1 for h in hosts if h in self.acked)
            if acks >= required:
                continue
            ok = False
            if acks + sum(1 for h in hosts if h not in self.failed
                          and h not in self.acked) < required:
                raise ConsistencyError(
                    f"{acks}/{len(hosts)} acks, need {required}: "
                    f"{sorted(self.failed.items())}")
        return ok

    def wait(self, timeout: float):
        deadline = time.monotonic() + timeout
        with self._cond:
            while not self._settled():
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ConsistencyError(
                        f"timeout: {len(self.acked)} host acks, "
                        f"{sorted(self.failed.items())}")
                self._cond.wait(remaining)


class _BatchWrite:
    """One host's share of a columnar batch: ONE write_batch RPC. A
    series' tags ride only until this host has acknowledged them once
    (`known`, the session's set for the host): `tags` holds None for a
    row whose series the host is known to hold tagged, and is left out
    whole when every row is. A failed attempt between the send and the
    ack (HostClient.epoch) means the host may have restarted: the
    session forgets what it believed and the batch goes again with
    every tag (the same rows twice are the same points)."""

    __slots__ = ("ns", "ids", "ts", "vals", "tags", "shards", "priority",
                 "host_id", "known", "completion", "stats")

    def __init__(self, ns, ids, ts, vals, tags, shards, priority, host_id,
                 known: set, completion: _BatchCompletion):
        self.ns, self.ids, self.ts, self.vals = ns, ids, ts, vals
        self.tags, self.shards, self.priority = tags, shards, priority
        self.host_id, self.known, self.completion = host_id, known, completion
        self.stats: Optional[dict] = None   # set once the RPC has ended

    def _call(self, client: HostClient, tags):
        _WRITE_RPCS.inc()
        client.call("write_batch", _priority=self.priority, ns=self.ns,
                    ids=self.ids, ts=self.ts, vals=self.vals, tags=tags,
                    shards=self.shards)

    def send(self, client: HostClient):
        known, tags = self.known, self.tags
        fresh: List[bytes] = []
        sent = None
        if tags is not None:
            sent = [None if sid in known else tg
                    for sid, tg in zip(self.ids, tags)]
            fresh = [sid for sid, tg in zip(self.ids, sent) if tg]
            if not fresh:
                sent = None
        withheld = tags is not None and len(fresh) < len(self.ids)
        with _wire_stats() as st:
            try:
                epoch = client.epoch
                self._call(client, sent)
                if client.epoch != epoch and withheld:
                    known.clear()
                    fresh = [sid for sid, tg in zip(self.ids, tags) if tg]
                    self._call(client, list(tags))
            except Exception as e:  # noqa: BLE001 — propagate via completion
                known.clear()
                self.stats = st
                self.completion.done(self.host_id,
                                     f"{client.endpoint}: {e}")
                return
        if len(known) + len(fresh) > TAGGED_MAX:
            known.clear()
        known.update(fresh)
        self.stats = st
        self.completion.done(self.host_id)


# Series a session remembers, per host, as tagged there (ids come from
# clients before any validation: bounded, flushed whole when full).
TAGGED_MAX = 1 << 18

_WRITE_SCOPE = ROOT.sub_scope("client.write_batch")
_WRITE_SAMPLES = _WRITE_SCOPE.counter("samples")
_WRITE_RPCS = _WRITE_SCOPE.counter("rpcs")
_WRITE_SHORT = _WRITE_SCOPE.counter("acked_short_of_all")
_FETCH_SCOPE = ROOT.sub_scope("client.fetch_tagged")
_FETCH_REPLICAS = _FETCH_SCOPE.counter("replicas_merged")
_FETCH_DECODES = _FETCH_SCOPE.counter("decode_dispatches")
_FETCH_BYTES_IN = _FETCH_SCOPE.counter("bytes_in")


class HostQueue:
    """Per-host op queue: batches writes into write_batch RPCs
    (client/host_queue.go). Drains whatever is queued on each wake, so
    batching emerges under load without adding idle latency. A columnar
    batch's share for this host (_BatchWrite) is one op and one RPC of
    its own, in its place in the queue: a host sees a session's writes
    in the order the session made them."""

    def __init__(self, client: HostClient, max_batch: int = 256):
        self.client = client
        self.max_batch = max_batch
        self._ops: List[_WriteOp] = []
        self._cond = threading.Condition()
        self._closed = False
        self._busy = False
        self._thread = threading.Thread(
            target=self._run, name="host-queue", daemon=True)
        self._thread.start()

    def enqueue(self, op: _WriteOp):
        with self._cond:
            if self._closed:
                raise ConnectionError("host queue closed")
            self._ops.append(op)
            self._cond.notify()

    def _run(self):
        while True:
            with self._cond:
                while not self._ops and not self._closed:
                    self._cond.wait()
                if self._closed and not self._ops:
                    return
                batch, self._ops = self._ops[: self.max_batch], self._ops[self.max_batch :]
                self._busy = True
            try:
                self._flush(batch)
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()

    def wait_idle(self, timeout: float) -> bool:
        """True once nothing is queued or in flight (a straggler's share
        of an acknowledged batch included)."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._ops or self._busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
        return True

    def _flush(self, batch: list):
        run: List[_WriteOp] = []
        for op in batch:
            if op.__class__ is _BatchWrite:
                if run:
                    self._flush_ops(run)
                    run = []
                op.send(self.client)
            else:
                run.append(op)
        if run:
            self._flush_ops(run)

    def _flush_ops(self, batch: List[_WriteOp]):
        by_ns: Dict[Tuple[bytes, Optional[str]], List[_WriteOp]] = {}
        for op in batch:
            by_ns.setdefault((op.ns, op.priority), []).append(op)
        for (ns, pri), ops in by_ns.items():
            try:
                self.client.call(
                    "write_batch",
                    _priority=pri,
                    ns=ns,
                    ids=[o.id for o in ops],
                    ts=np.array([o.t_ns for o in ops], np.int64),
                    vals=np.array([o.value for o in ops], np.float64),
                    tags=[o.tags for o in ops],
                )
            except Exception as e:  # noqa: BLE001 — propagate via completion
                for o in ops:
                    o.completion.error(f"{self.client.endpoint}: {e}")
            else:
                for o in ops:
                    o.completion.ack()

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=5)


# -------------------------------------------------------------------- session


@dataclasses.dataclass
class SessionOptions:
    write_consistency: ConsistencyLevel = ConsistencyLevel.MAJORITY
    read_consistency: ReadConsistencyLevel = ReadConsistencyLevel.UNSTRICT_MAJORITY
    conflict_strategy: ConflictStrategy = ConflictStrategy.LAST_PUSHED
    timeout_s: float = 30.0
    pool_size: int = 4
    max_batch: int = 256
    # resilience knobs (no more hard-coded connect timeout): per-host
    # transport retries, breaker trip/recovery, and connection timeouts.
    # None = inherit timeout_s, preserving the pre-xresil behavior where
    # the per-RPC socket timeout WAS the session timeout — a user setting
    # only timeout_s must not be silently capped by a tighter default.
    connect_timeout_s: Optional[float] = None
    request_timeout_s: Optional[float] = None
    retry: RetryOptions = RetryOptions(max_attempts=3, initial_backoff_s=0.05)
    breaker: BreakerOptions = BreakerOptions()
    # Read-fanout worker pool: open-loop traffic with slow/faulted
    # replicas queues here before any socket — size it for the offered
    # concurrency, not just the host count.
    fanout_workers: int = 16

    @property
    def effective_request_timeout_s(self) -> float:
        return self.timeout_s if self.request_timeout_s is None \
            else self.request_timeout_s

    @property
    def effective_connect_timeout_s(self) -> float:
        return self.effective_request_timeout_s if self.connect_timeout_s \
            is None else self.connect_timeout_s


class _ReadCosts:
    """What one fetch_tagged's decodes and merges cost, summed on the
    calling thread and put on its span once."""

    __slots__ = ("decode_ns", "decode_n", "d2h_bytes", "merge_ns")

    def __init__(self):
        self.decode_ns = self.decode_n = self.d2h_bytes = self.merge_ns = 0


class Session:
    """client.Session: Write/WriteTagged/Fetch/FetchTagged over a topology."""

    def __init__(self, topology, opts: SessionOptions = SessionOptions()):
        self.topology = topology
        self.opts = opts
        self.health = HostHealth(opts.breaker)  # per-endpoint breakers/stats
        self._clients: Dict[str, HostClient] = {}
        self._queues: Dict[str, HostQueue] = {}
        self._lock = threading.RLock()  # _queue -> _client nest on this lock
        self._pool = ThreadPoolExecutor(max_workers=opts.fanout_workers,
                                        thread_name_prefix="fanout")
        self._shard_set: Optional[ShardSet] = None
        # host id -> ids of series whose tags that host has acknowledged
        self._tagged: Dict[str, set] = {}
        self._write_plan: Optional[tuple] = None  # (map, hosts, owns, sets)
        if hasattr(topology, "subscribe"):
            topology.subscribe(lambda _m: None)  # keep map fresh

    # ---------------------------------------------------------------- routing

    def _map(self):
        m = self.topology.get()
        if m is None:
            raise ConnectionError("no topology available")
        return m

    def _shards(self) -> ShardSet:
        m = self._map()
        if self._shard_set is None or self._shard_set.num_shards != m.num_shards:
            self._shard_set = ShardSet(m.num_shards)
        return self._shard_set

    def _client(self, host) -> HostClient:
        with self._lock:
            c = self._clients.get(host.id)
            if c is None or c.endpoint != host.endpoint:
                if c is not None:
                    c.close()  # endpoint moved: release the old socket pool
                ep = host.endpoint
                c = HostClient(ep, self.opts.pool_size,
                               self.opts.effective_request_timeout_s,
                               connect_timeout=self.opts.effective_connect_timeout_s,
                               retry_opts=self.opts.retry,
                               breaker=self.health.breaker(ep),
                               on_outcome=lambda ok, _ep=ep:
                                   self.health.count(_ep, ok))
                self._clients[host.id] = c
            return c

    def _queue(self, host) -> HostQueue:
        with self._lock:
            q = self._queues.get(host.id)
            if q is None or q.client.endpoint != host.endpoint:
                if q is not None:
                    q.close()
                    q.client.close()
                q = HostQueue(self._client(host), self.opts.max_batch)
                self._queues[host.id] = q
            return q

    # ----------------------------------------------------------------- writes

    def write(self, ns: bytes, id: bytes, t_ns: int, value: float,
              tags: Optional[dict] = None, priority: Optional[str] = None):
        """session.go:867 Write: fan out to all shard replicas, wait quorum."""
        m = self._map()
        shard = self._shards().lookup(id)
        hosts = m.route_shard(shard)
        if not hosts:
            raise ConsistencyError(f"no hosts own shard {shard}")
        required = required_acks(self.opts.write_consistency, m.replica_factor)
        completion = _Completion(required=min(required, len(hosts)), total=len(hosts))
        op = _WriteOp(ns, id, t_ns, value, tags, completion, priority)
        for h in hosts:
            self._queue(h).enqueue(op)
        completion.wait(self.opts.timeout_s)

    def write_tagged(self, ns: bytes, id: bytes, tags: dict, t_ns: int, value: float):
        self.write(ns, id, t_ns, value, tags)

    def _plan_writes(self, m) -> tuple:
        """What a batch needs of one topology map, worked out once a map:
        the hosts, which shards each takes writes for (bool [hosts,
        shards]) and each shard's replica set as a tuple of host ids."""
        plan = self._write_plan
        if plan is None or plan[0] is not m:
            hosts = sorted(m.hosts.values(), key=lambda h: h.id)
            row = {h.id: i for i, h in enumerate(hosts)}
            owns = np.zeros((len(hosts), m.num_shards), bool)
            sets: List[Tuple[str, ...]] = []
            for shard in range(m.num_shards):
                owners = m.route_shard(shard)
                sets.append(tuple(h.id for h in owners))
                for h in owners:
                    owns[row[h.id], shard] = True
            plan = self._write_plan = (m, hosts, owns, sets)
        return plan

    def write_batch(self, ns: bytes, ids: Sequence[bytes], ts, vals,
                    tags: Optional[Sequence[Optional[dict]]] = None,
                    priority: Optional[str] = None):
        """Columnar write: the batch is routed ONCE (the shard memo: a
        coordinator writes the same series every scrape), each host that
        owns any of its shards gets ONE write_batch RPC with its rows as
        columns (ids, int64 ts, f64 vals, the rows' shards; a series'
        tags only until that host has acknowledged them once), and the
        batch is acknowledged when every shard in it has the write
        consistency level's acks among its replicas. One completion a
        batch, one result a host; nothing per datapoint. A host that
        refuses the batch (its acceptance window refuses it whole) or
        cannot be reached counts as that host's failure toward every
        shard it owns; too few acks raise ConsistencyError. Hosts beyond
        the quorum finish behind the call (`drain` waits for them)."""
        n = len(ids)
        if not n:
            return
        ts = np.asarray(ts, np.int64)
        vals = np.asarray(vals, np.float64)
        with tracing.span("client.write_batch") as sp:
            t0 = _clock()
            ids = list(ids)
            m, hosts, owns, sets = self._plan_writes(self._map())
            shards = self._shards().lookup_memo(ids)
            required = required_acks(self.opts.write_consistency,
                                     m.replica_factor)
            quorums = []
            for shard in np.unique(shards).tolist():
                owners = sets[shard]
                if not owners:
                    raise ConsistencyError(f"no hosts own shard {shard}")
                quorums.append((owners, min(required, len(owners))))
            completion = _BatchCompletion(sorted(set(quorums)))
            ops = []
            for h, mine in zip(hosts, owns):
                rows = mine[shards]
                if rows.all():
                    share = (ids, ts, vals, tags, shards)
                elif rows.any():
                    at = np.flatnonzero(rows)
                    pick = at.tolist()
                    share = ([ids[i] for i in pick], ts[at], vals[at],
                             [tags[i] for i in pick] if tags else None,
                             shards[at])
                else:
                    continue
                op = _BatchWrite(ns, *share, priority, h.id,
                                 self._tagged.setdefault(h.id, set()),
                                 completion)
                ops.append(op)
                self._queue(h).enqueue(op)
            t1 = _clock()
            try:
                completion.wait(self.opts.timeout_s)
            finally:
                acks = len(completion.acked)
                _WRITE_SAMPLES.inc(n)
                if acks < len(ops):
                    _WRITE_SHORT.inc()
                if sp.sampled:
                    sp.set_tag("hosts", len(ops))
                    sp.set_tag("acks", acks)
                    sp.add_cost("samples_n", n)
                    sp.add_cost("route_ns", t1 - t0)
                    sp.add_cost("quorum_wait_ns", _clock() - t1)
                    sp.add_cost("wire_encode_ns", sum(
                        op.stats["encode_ns"] for op in ops
                        if op.stats is not None))

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Wait until no write is queued or in flight to any host: the
        replicas a quorum did not wait for have theirs too. True if so
        within the timeout."""
        deadline = time.monotonic() + (self.opts.timeout_s
                                       if timeout_s is None else timeout_s)
        with self._lock:
            queues = list(self._queues.values())
        return all(q.wait_idle(max(0.0, deadline - time.monotonic()))
                   for q in queues)

    # ------------------------------------------------------------------ reads

    def _traced_call(self, span, client: HostClient, method: str, **kwargs):
        """HostClient call with `span` active on the worker thread: the
        fanout pool's threads don't inherit the submitting thread's
        span stack, so propagation into the wire frames (and the graft
        of server spans back onto `span`) needs the explicit handoff."""
        with tracing.TRACER.activate(span):
            return client.call(method, **kwargs)

    def _measured_call(self, span, client: HostClient, method: str, **kwargs):
        """`_traced_call` that also returns what the call cost on the
        wire (`_wire_stats`), for the caller to put on its span."""
        with _wire_stats() as st:
            return self._traced_call(span, client, method, **kwargs), st

    def fetch(self, ns: bytes, id: bytes, start_ns: int, end_ns: int
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Fetch decoded + replica-merged datapoints for one series."""
        m = self._map()
        hosts = m.route_shard_readable(self._shards().lookup(id))
        required = min(required_reads(self.opts.read_consistency, m.replica_factor),
                       len(hosts)) or 1
        results, errs = [], []
        # One deadline bounds the whole quorum read and rides every RPC
        # frame: a faulted/slow replica returns DeadlineExceeded instead
        # of stalling past the caller's budget.
        dl = Deadline.after(self.opts.timeout_s)
        with tracing.span("client.fetch", replicas=len(hosts)) as csp:
            pending = {self._pool.submit(
                self._traced_call, csp, self._client(h), "fetch",
                _deadline=dl, ns=ns, id=id,
                start_ns=start_ns, end_ns=end_ns) for h in hosts}
            results, errs = self._await_quorum(pending, dl, required, results,
                                               errs)
        if len(results) < required:
            raise ConsistencyError(f"{len(results)}/{len(hosts)} reads, need {required}: {errs}")
        return merge_replica_points([r["t"] for r in results], [r["v"] for r in results],
                                    self.opts.conflict_strategy)

    def _await_quorum(self, pending, dl, required, results, errs):
        # Return as soon as the read consistency level is satisfied — a dead
        # replica must not stall a quorum-satisfiable read.
        while pending and len(results) < required:
            done, pending = futures_wait(
                pending, timeout=max(0.0, dl.remaining()),
                return_when=FIRST_COMPLETED)
            if not done:
                break
            for fut in done:
                try:
                    results.append(fut.result())
                except Exception as e:  # noqa: BLE001
                    errs.append(str(e))
        return results, errs

    def fetch_tagged(self, ns: bytes, query, start_ns: int, end_ns: int,
                     limit: int = 0) -> Dict[bytes, dict]:
        """session.go:1091 FetchTagged: fan out, accumulate per-shard
        consistency, then build the points from all the frames held once
        coverage is met in one pass (`_merged_points`: one decode
        dispatch a geometry for the whole fetch, one merge a series).
        Returns id -> {tags, t, v}."""
        m = self._map()
        q = wire.query_to_wire(query)
        hosts = list(m.hosts.values())
        required = required_reads(self.opts.read_consistency, m.replica_factor)

        def coverage_met(ok_ids):
            # Per-shard accumulation (fetch_tagged_results_accumulator.go):
            # every owned shard needs >= required responders among its
            # READABLE owners — an initializing owner has no data and
            # must neither count toward nor be awaited for coverage.
            for shard in range(m.num_shards):
                owners = m.route_shard_readable(shard)
                if not owners:
                    continue
                got = sum(1 for h in owners if h.id in ok_ids)
                if got < min(required, len(owners)):
                    return False
            return True

        results, errs = [], []
        ok_ids = set()
        dl = Deadline.after(self.opts.timeout_s)
        # The phases of a clustered read are costs of this span, never
        # children of it (its parent's self time is a reader's): the
        # wait for coverage, each responder's frame (bytes, decode), the
        # fetch's tile decode (device dispatches, bytes brought back)
        # and the merges. The workers hand their wire stats back; this
        # thread alone writes the span.
        with tracing.span("client.fetch_tagged", hosts=len(hosts)) as csp:
            t0 = _clock()
            pending = {self._pool.submit(
                self._measured_call, csp, self._client(h), "fetch_tagged",
                _deadline=dl, ns=ns,
                query=q, start_ns=start_ns, end_ns=end_ns,
                limit=limit): h for h in hosts}
            wire_ns = bytes_in = 0
            while pending and not coverage_met(ok_ids):
                done, _ = futures_wait(
                    set(pending), timeout=max(0.0, dl.remaining()),
                    return_when=FIRST_COMPLETED)
                if not done:
                    break
                for fut in done:
                    h = pending.pop(fut)
                    try:
                        r, st = fut.result()
                        results.append(r)
                        ok_ids.add(h.id)
                        wire_ns += st["decode_ns"]
                        bytes_in += st["bytes_in"]
                    except Exception as e:  # noqa: BLE001
                        errs.append(f"{h.id}: {e}")
            t1 = _clock()
            csp.set_tag("responders", len(ok_ids))
            if not coverage_met(ok_ids):
                raise ConsistencyError(
                    f"insufficient replica coverage ({len(ok_ids)} "
                    f"responders, need {required} per shard): {errs}")
            acc = _ReadCosts()
            merged = self._merged_points(results, acc)
            _FETCH_REPLICAS.inc(len(results))
            _FETCH_DECODES.inc(acc.decode_n)
            _FETCH_BYTES_IN.inc(bytes_in)
            if csp.sampled:
                csp.set_tag("replicas_merged", len(results))
                for kind, n in (("fanout_wait_ns", t1 - t0),
                                ("wire_decode_ns", wire_ns),
                                ("bytes_in", bytes_in),
                                ("decode_ns", acc.decode_ns),
                                ("decode_n", acc.decode_n),
                                ("d2h_bytes", acc.d2h_bytes),
                                ("merge_ns", acc.merge_ns),
                                ("series_n", len(merged))):
                    csp.add_cost(kind, n)
        return merged

    def _merged_points(self, frames: List[dict], acc: "_ReadCosts"
                       ) -> Dict[bytes, dict]:
        """id -> {tags, t, v} from the COLUMNAR fetch_tagged frames of
        the responders a fetch waited for, in arrival order. A vote
        (HIGHEST_FREQUENCY_VALUE) of per-responder votes is not the vote
        of all their parts, so that strategy keeps its fold: each
        responder's series built alone, then merged into what the
        earlier ones gave, a series at a time. Every other strategy
        picks the same point from a timestamp's candidates taken at once
        or pairwise (the last occurrence, the maximum, the minimum), and
        builds all the frames in one pass."""
        strategy = self.opts.conflict_strategy
        if strategy != ConflictStrategy.HIGHEST_FREQUENCY_VALUE:
            return self._one_pass_points(frames, acc)
        merged: Dict[bytes, dict] = {}
        for r in frames:
            points = self._one_pass_points([r], acc)
            t0 = _clock()
            for sid, new in points.items():
                cur = merged.get(sid)
                if cur is None:
                    merged[sid] = new
                    continue
                if not cur["tags"] and new["tags"]:
                    cur["tags"] = new["tags"]
                cur["t"], cur["v"] = merge_replica_points(
                    [cur["t"], new["t"]], [cur["v"], new["v"]], strategy)
            acc.merge_ns += _clock() - t0
        return merged

    def _one_pass_points(self, frames: List[dict], acc: "_ReadCosts"
                         ) -> Dict[bytes, dict]:
        """The frames' series in one pass: ONE decode a fetch and ONE
        merge a series, not one of each for every replica that answered.

        Slots: one a distinct series id over the frames' `series` lists
        (first sighting's order, the first non-empty tags kept); a
        frame's positions map to slots. Decode: every frame's
        sealed-block tiles go into one `decode_stacked` call, so the
        tiles of one geometry are one `decode_rows` call whichever
        replica sent them (the decode is row-independent: stacking
        changes no bit of a row). Merge: a slot's parts are laid down
        frame by frame in arrival order, inside a frame its sealed
        blocks (ascending start) and then its slice of the buffer
        sidecar (views of the concatenated columns) — the order a
        per-responder fold visits them in, so one stable
        `merge_replica_points` picks what the fold picks. `acc` takes
        what the decodes and the merges cost."""
        t0 = _clock()
        slot_of: Dict[bytes, int] = {}
        tags: List[dict] = []
        slots: List[np.ndarray] = []
        for r in frames:
            at = []
            for entry in r["series"]:
                slot = slot_of.setdefault(entry["id"], len(tags))
                if slot == len(tags):
                    tags.append(entry["tags"])
                elif not tags[slot] and entry["tags"]:
                    tags[slot] = entry["tags"]
                at.append(slot)
            slots.append(np.asarray(at, np.int64))
        acc.merge_ns += _clock() - t0

        # One decode a geometry for the whole fetch: a frame carries a
        # tile a block start, a call is its fixed cost and not its rows
        # (5.5 ms of the coordinator's host time for 54 us of device
        # time, ledger PR 34), and the decode is row-independent.
        def decode(words, npoints, window, unit_nanos):
            t0 = _clock()
            ran_on: list = []
            ts, vs, calls = decode_rows(words, npoints, window, unit_nanos,
                                        ran_on=ran_on)
            acc.decode_ns += _clock() - t0
            acc.decode_n += calls
            acc.d2h_bytes += ts.nbytes + vs.nbytes
            # counted where the result lies: the session's thread's
            # device (its scope's, parallel/scope.py)
            for dev in ran_on:
                ROOT.sub_scope("client.decode_tile", device=str(dev.id)
                               ).counter("dispatches").inc()
            return ts, vs, calls

        decoded: List[list] = [[] for _ in frames]
        for tile, ks, ts, vs in decode_stacked(
                [dict(tile, frame=f) for f, r in enumerate(frames)
                 for tile in r.get("tiles", ())], decode):
            decoded[tile["frame"]].append((tile, ks, ts, vs))
        parts_t: List[list] = [[] for _ in tags]
        parts_v: List[list] = [[] for _ in tags]
        for r, at, tiles in zip(frames, slots, decoded):
            for tile, ks, ts, vs in tiles:
                for j, (slot, k) in enumerate(zip(
                        at[np.asarray(tile["rows"])].tolist(), ks.tolist())):
                    parts_t[slot].append(ts[j, :k])
                    parts_v[slot].append(vs[j, :k])
            bufs = r.get("bufs")
            if bufs is None:
                continue
            t0 = _clock()
            offs = np.asarray(bufs["offs"]).tolist()
            bt, bv = bufs["t"], bufs["v"]
            for pos, slot in enumerate(at.tolist()):
                if offs[pos + 1] > offs[pos]:
                    parts_t[slot].append(bt[offs[pos]:offs[pos + 1]])
                    parts_v[slot].append(bv[offs[pos]:offs[pos + 1]])
            acc.merge_ns += _clock() - t0
        t0 = _clock()
        strategy = self.opts.conflict_strategy
        merged: Dict[bytes, dict] = {}
        for sid, slot in slot_of.items():
            t, v = merge_replica_points(parts_t[slot], parts_v[slot], strategy)
            merged[sid] = {"tags": tags[slot], "t": t, "v": v}
        acc.merge_ns += _clock() - t0
        return merged

    def aggregate(self, ns: bytes, query, start_ns: int, end_ns: int,
                  name_only: bool = False, field_filter=(),
                  term_limit: int = 0) -> Dict[bytes, set]:
        """session.go Aggregate: fan out the tags-only aggregate RPC and
        union-merge per-host field dictionaries (no datapoints cross the
        wire). Requires at least one responsive host; results are
        best-effort-complete like query_ids."""
        m = self._map()
        q = wire.query_to_wire(query)
        merged: Dict[bytes, set] = {}
        ok = 0
        errs: List[str] = []
        for h in m.hosts.values():
            try:
                r = self._client(h).call(
                    "aggregate", ns=ns, query=q, start_ns=start_ns,
                    end_ns=end_ns, name_only=name_only,
                    field_filter=list(field_filter), term_limit=term_limit)
            except Exception as e:  # noqa: BLE001
                errs.append(f"{h.id}: {e}")
                continue
            ok += 1
            for f in r["fields"]:
                merged.setdefault(f["name"], set()).update(f["values"])
        if not ok:
            raise ConsistencyError(f"aggregate: no hosts responded: {errs}")
        if term_limit:
            merged = {k: set(sorted(v)[:term_limit]) for k, v in merged.items()}
        return merged

    def query_ids(self, ns: bytes, query, start_ns: int, end_ns: int) -> Dict[bytes, dict]:
        """ids + tags only (thrift Query / FetchTagged fetchData=false)."""
        m = self._map()
        out: Dict[bytes, dict] = {}
        for h in m.hosts.values():
            try:
                r = self._client(h).call("query", ns=ns, query=wire.query_to_wire(query),
                                         start_ns=start_ns, end_ns=end_ns)
            except Exception:  # noqa: BLE001
                continue
            for s in r["series"]:
                out.setdefault(s["id"], {"tags": s["tags"]})
        return out

    # ------------------------------------------------------------------ admin

    def fetch_blocks_metadata_from_peers(self, ns: bytes, shard: int, start_ns: int,
                                         end_ns: int, exclude_host: Optional[str] = None,
                                         deadline: Optional[Deadline] = None,
                                         errors: Optional[Dict[str, str]] = None):
        """AdminSession peer metadata streaming: paged metadata from every
        replica of a shard -> {host_id: {series_id: {tags, blocks}}}.

        A peer that fails in one of the typed transport ways (connection
        death, expired budget, deliberate shed) or returns a server-side
        error is SKIPPED — counted in the `session.peers` scope and
        reported into `errors` (host_id -> message) when the caller passes
        a dict — so bootstrap/repair see partial coverage instead of a
        silently smaller quorum. Anything untyped propagates."""
        m = self._map()
        out: Dict[str, Dict[bytes, dict]] = {}
        # Peer streaming reads block data: only readable owners hold any
        # (an initializing peer is itself still bootstrapping).
        for h in m.route_shard_readable(shard):
            if h.id == exclude_host:
                continue
            series: Dict[bytes, dict] = {}
            token = 0
            try:
                while token is not None:
                    r = self._client(h).call(
                        "fetch_blocks_metadata", _deadline=deadline, ns=ns,
                        shard=shard, start_ns=start_ns, end_ns=end_ns,
                        page_token=token)
                    for s in r["series"]:
                        series[s["id"]] = {"tags": s["tags"],
                                           "blocks": s["blocks"]}
                    token = r["next_page_token"]
            except PEER_SKIP_ERRORS + (RemoteError,) as e:
                _PEER_METRICS.counter("metadata_peer_errors").inc()
                if errors is not None:
                    errors[h.id] = f"{type(e).__name__}: {e}"
                continue
            out[h.id] = series
        return out

    def fetch_block_metadata_tiles_from_peers(
            self, ns: bytes, shard: int, start_ns: int, end_ns: int,
            exclude_host: Optional[str] = None,
            deadline: Optional[Deadline] = None,
            errors: Optional[Dict[str, str]] = None) -> Dict[str, dict]:
        """Columnar peer metadata streaming: per responding host,
        {"ids": [...], "tags": [...], "blocks": [{"bs", "pos", "sums"}]}
        with pages concatenated (block `pos` re-based onto the combined
        ids list). Same typed skip/count semantics as the per-series
        form."""
        m = self._map()
        out: Dict[str, dict] = {}
        for h in m.route_shard_readable(shard):
            if h.id == exclude_host:
                continue
            ids: List[bytes] = []
            tags: List[dict] = []
            blocks: List[dict] = []
            token = 0
            try:
                while token is not None:
                    r = self._client(h).call(
                        "fetch_block_metadata_tiles", _deadline=deadline,
                        ns=ns, shard=shard, start_ns=start_ns,
                        end_ns=end_ns, page_token=token)
                    offset = len(ids)
                    ids.extend(r["ids"])
                    tags.extend(r["tags"])
                    for b in r["blocks"]:
                        pos = np.asarray(b["pos"], np.int64)
                        blocks.append({"bs": int(b["bs"]),
                                       "pos": pos + offset,
                                       "sums": np.asarray(b["sums"],
                                                          np.int64)})
                    token = r["next_page_token"]
            except PEER_SKIP_ERRORS + (RemoteError,) as e:
                _PEER_METRICS.counter("metadata_peer_errors").inc()
                if errors is not None:
                    errors[h.id] = f"{type(e).__name__}: {e}"
                continue
            out[h.id] = {"ids": ids, "tags": tags, "blocks": blocks}
        return out

    @staticmethod
    def plan_block_majority(meta: Dict[str, dict]):
        """Vectorized checksum-majority planning over COLUMNAR peer
        metadata: group every (series, block, checksum) observation,
        vote per (series, block), and return, per block start, the
        winning checksum + the lowest-ranked host actually holding it
        for every series — plus the per-checksum ranked host lists a
        consumer needs to build failover chains.

        Returns (tags_by_sid, sids, hosts_list, per_bs) where per_bs maps
        block_start -> {"gids": int64[], "sums": int64[] (majority
        checksum per gid), "primary": int64[] (host rank holding it),
        "by_sum": {checksum: [host_id ranked]}} and `sids[g]` resolves a
        gid back to its series id."""
        tags_by_sid: Dict[bytes, dict] = {}
        gmap: Dict[bytes, int] = {}
        sids: List[bytes] = []
        hosts_list = list(meta)
        per_bs_rows: Dict[int, List[Tuple[int, np.ndarray, np.ndarray]]] = {}
        for rank, (host_id, m) in enumerate(meta.items()):
            ids = m["ids"]
            for sid, tg in zip(ids, m["tags"]):
                if tg and not tags_by_sid.get(sid):
                    tags_by_sid[sid] = tg
            garr = np.empty(len(ids), np.int64)
            for j, sid in enumerate(ids):
                g = gmap.get(sid)
                if g is None:
                    g = gmap[sid] = len(sids)
                    sids.append(sid)
                garr[j] = g
            for b in m["blocks"]:
                per_bs_rows.setdefault(int(b["bs"]), []).append(
                    (rank, garr[np.asarray(b["pos"], np.int64)],
                     np.asarray(b["sums"], np.int64)))
        per_bs: Dict[int, dict] = {}
        for bs, entries in per_bs_rows.items():
            g_all = np.concatenate([g for _r, g, _s in entries])
            c_all = np.concatenate([s for _r, _g, s in entries])
            r_all = np.concatenate([np.full(len(g), r, np.int64)
                                    for r, g, _s in entries])
            order = np.lexsort((r_all, c_all, g_all))
            g, c, r = g_all[order], c_all[order], r_all[order]
            # (gid, checksum) runs: count = votes, first host = the
            # lowest-ranked holder of that copy.
            new = np.empty(len(g), bool)
            new[0] = True
            np.logical_or(g[1:] != g[:-1], c[1:] != c[:-1], out=new[1:])
            starts = np.flatnonzero(new)
            run_g = g[starts]
            run_c = c[starts]
            run_r0 = r[starts]
            run_n = np.diff(np.append(starts, len(g)))
            # Winner per gid: the run sorting LAST under (gid, count,
            # checksum) — max votes, deterministic checksum tie-break.
            sel = np.lexsort((run_c, run_n, run_g))
            gs = run_g[sel]
            last = np.empty(len(sel), bool)
            if len(sel):
                np.not_equal(gs[1:], gs[:-1], out=last[:-1])
                last[-1] = True
            win = sel[last]
            # Ranked host list per checksum (any row with that checksum
            # in this block) for failover chains.
            pairs = np.unique(np.stack([c_all, r_all], 1), axis=0)
            by_sum: Dict[int, List[str]] = {}
            for cc, rr in pairs:
                by_sum.setdefault(int(cc), []).append(hosts_list[int(rr)])
            per_bs[bs] = {"gids": run_g[win], "sums": run_c[win],
                          "primary": run_r0[win], "by_sum": by_sum,
                          # EVERY (gid, checksum) observation (not just
                          # the winner): repair merges all distinct peer
                          # copies so divergence converges in one sweep
                          # per node instead of pairwise over many.
                          "run_g": run_g, "run_c": run_c,
                          "run_r0": run_r0}
        return tags_by_sid, sids, hosts_list, per_bs

    @staticmethod
    def holder_chain_builder(p: dict, hosts_list: List[str],
                             cross_checksum_tail: bool):
        """Memoized failover-chain factory over one plan_block_majority
        block entry: chain(checksum, first_holder_rank) -> ranked host
        list ([first holder] + every other host with the SAME checksum,
        then — when `cross_checksum_tail` — every remaining holder of
        any copy: any copy beats no copy once the whole same-sum set is
        dead). Chains are SHARED per (checksum, rank) combo and the
        cross-checksum tail is built once per block: per-series list
        construction is quadratic when checksums are per-row distinct.
        The single definition both the bootstrap and repair planners
        rank holders with."""
        by_sum = p["by_sum"]
        all_hosts = [h for h in hosts_list
                     if any(h in hl for hl in by_sum.values())] \
            if cross_checksum_tail and len(by_sum) > 1 else []
        combos: Dict[Tuple[int, int], List[str]] = {}

        def chain(cc: int, rr: int) -> List[str]:
            key = (cc, rr)
            lst = combos.get(key)
            if lst is None:
                first = hosts_list[rr]
                lst = [first] + [h for h in by_sum[cc] if h != first]
                if all_hosts:
                    lst += [h for h in all_hosts if h not in lst]
                combos[key] = lst
            return lst

        return chain

    def fetch_block_tiles(self, ns: bytes, shard: int,
                     holders: Dict[Tuple[bytes, int], List[str]],
                     deadline: Optional[Deadline] = None,
                     errors: Optional[Dict[str, str]] = None):
        """Stream columnar block tiles for a holder plan, one wave per
        holder rank: rank-0 requests batch per host; anything a host
        failed to serve (typed transport error, shed, or a row that
        vanished server-side) re-plans onto each key's next holder. Only
        keys every holder failed come back in `failed`."""
        m = self._map()
        hosts = {h.id: h for h in m.hosts.values()}
        tiles: Dict[int, List[dict]] = {}
        remaining = dict.fromkeys(holders)
        max_rank = max((len(v) for v in holders.values()), default=0)
        for rank in range(max_rank):
            if not remaining:
                break
            wave: Dict[str, Dict[int, List[bytes]]] = {}
            for (sid, bs) in remaining:
                hlist = holders[(sid, bs)]
                if rank < len(hlist) and hlist[rank] in hosts:
                    wave.setdefault(hlist[rank], {}).setdefault(
                        bs, []).append(sid)
            for host_id, by_bs in wave.items():
                reqs = [{"bs": bs, "ids": sids} for bs, sids in by_bs.items()]
                try:
                    r = self._client(hosts[host_id]).call(
                        "fetch_block_tiles", _deadline=deadline, ns=ns,
                        shard=shard, blocks=reqs)
                except PEER_SKIP_ERRORS + (RemoteError,) as e:
                    # This host's whole wave re-plans onto the next
                    # holders (keys stay in `remaining`).
                    _PEER_METRICS.counter("block_fetch_peer_errors").inc()
                    if errors is not None:
                        errors[host_id] = f"{type(e).__name__}: {e}"
                    continue
                for tile in r["blocks"]:
                    ids = tile["ids"]
                    if not len(ids):
                        continue
                    tiles.setdefault(int(tile["bs"]), []).append(tile)
                    for sid in ids:
                        remaining.pop((sid, int(tile["bs"])), None)
        failed = sorted(remaining)
        if failed:
            _PEER_METRICS.counter("blocks_unfetchable").inc(len(failed))
        return tiles, failed

    def fetch_block_tiles_from_peers(self, ns: bytes, shard: int, start_ns: int,
                                     end_ns: int,
                                     exclude_host: Optional[str] = None,
                                     deadline: Optional[Deadline] = None,
                                     errors: Optional[Dict[str, str]] = None,
                                     meta_errors: Optional[Dict[str, str]]
                                     = None):
        """Columnar peer bootstrap streaming: diff peer metadata, plan by
        checksum majority, stream whole-block tiles ([rows, max_words]
        word matrices + per-row nbits/npoints columns — one ndarray per
        (host, block) instead of one dict per series).

        Returns (tiles, tags_by_sid, failed):
          tiles        {block_start: [tile dict]}
          tags_by_sid  {series_id: tags} from the metadata phase
          failed       [(series_id, block_start)] every holder failed —
                       the partial-coverage surface bootstrap subtracts
                       from its claim.

        `meta_errors` collects METADATA-phase peer failures separately
        from block-fetch failures (`errors`): a peer skipped during
        metadata may have held blocks nobody else has, so its loss means
        the plan itself — not just some fetches — is incomplete, and
        callers claiming coverage must treat it as such."""
        meta = self.fetch_block_metadata_tiles_from_peers(
            ns, shard, start_ns, end_ns, exclude_host, deadline,
            meta_errors if meta_errors is not None else errors)
        tags_by_sid, sids, hosts_list, per_bs = self.plan_block_majority(meta)
        holders: Dict[Tuple[bytes, int], List[str]] = {}
        for bs, p in per_bs.items():
            chain = self.holder_chain_builder(p, hosts_list,
                                              cross_checksum_tail=True)
            for gi, cc, rr in zip(p["gids"].tolist(), p["sums"].tolist(),
                                  p["primary"].tolist()):
                holders[(sids[gi], bs)] = chain(cc, rr)
        tiles, failed = self.fetch_block_tiles(ns, shard, holders, deadline,
                                               errors)
        return tiles, tags_by_sid, failed

    def fetch_bootstrap_blocks_from_peers(self, ns: bytes, shard: int, start_ns: int,
                                          end_ns: int, exclude_host: Optional[str] = None,
                                          deadline: Optional[Deadline] = None
                                          ) -> Dict[bytes, dict]:
        """Per-series view of peer bootstrap streaming (the session
        FetchBootstrapBlocksFromPeers shape, kept for callers that want
        row dicts): same tile fetch + holder fallback underneath.

        Returns {series_id: {"tags": .., "blocks": [wire block dicts]}}."""
        tiles, tags_by_sid, _failed = self.fetch_block_tiles_from_peers(
            ns, shard, start_ns, end_ns, exclude_host, deadline)
        out: Dict[bytes, dict] = {}
        for bs, tlist in sorted(tiles.items()):
            for tile in tlist:
                words = np.asarray(tile["words"])
                nbits = np.asarray(tile["nbits"])
                npoints = np.asarray(tile["npoints"])
                for i, sid in enumerate(tile["ids"]):
                    e = out.setdefault(
                        sid, {"tags": tags_by_sid.get(sid) or {}, "blocks": []})
                    e["blocks"].append({
                        "bs": bs, "words": words[i], "nbits": int(nbits[i]),
                        "npoints": int(npoints[i]),
                        "window": int(tile["window"]),
                        "time_unit": int(tile["time_unit"]),
                    })
        return out

    def fetch_block_tiles_from_host(self, host_id: str, ns: bytes, shard: int,
                                    blocks: List[dict],
                                    deadline: Optional[Deadline] = None) -> dict:
        """Columnar tiles from one specific replica (repair streams from
        the host holding the majority checksum); blocks =
        [{"bs": block_start, "ids": [series_id]}]."""
        m = self._map()
        host = m.hosts.get(host_id)
        if host is None:
            raise ConnectionError(f"unknown host {host_id}")
        return self._client(host).call("fetch_block_tiles", _deadline=deadline,
                                       ns=ns, shard=shard, blocks=blocks)

    def fetch_blocks_from_host(self, host_id: str, ns: bytes, shard: int,
                               requests: List[dict]) -> dict:
        """Raw encoded blocks from one specific replica (per-series
        request shape; the batched repair path uses
        fetch_block_tiles_from_host)."""
        m = self._map()
        host = m.hosts.get(host_id)
        if host is None:
            raise ConnectionError(f"unknown host {host_id}")
        return self._client(host).call("fetch_blocks", ns=ns, shard=shard,
                                       requests=requests)

    def truncate(self, ns: bytes) -> int:
        m = self._map()
        total = 0
        for h in m.hosts.values():
            total += self._client(h).call("truncate", ns=ns)
        return total

    def close(self):
        with self._lock:
            for q in self._queues.values():
                q.close()
            for c in self._clients.values():
                c.close()
            self._queues.clear()
            self._clients.clear()
        self._pool.shutdown(wait=False)
