"""Query storage interface + implementations (reference:
src/query/storage/types.go Storage, storage/m3/storage.go the dbnode
adapter, storage/fanout/storage.go the multi-store fanout).

fetch_raw(matchers, start_ns, end_ns) -> {series_id: {tags, t, v}} raw
datapoints; the executor grids them per query. Tag index queries compile
from label matchers via model.matchers_to_index_query."""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..storage.read_batch import read_many
from ..utils import tracing
from ..utils.instrument import ROOT
from ..utils.tracing import clock_ns as _clock
from .model import Matcher, matchers_to_index_query

# What a read costs, told apart by the namespace it served: the deltas of
# these tallies across one member's read_many land on the same span again
# as `<kind>{ns=<namespace>}` (a fetch that merges two namespaces reads
# both on one span).
_NS_COSTS = ("block_n", "cold_rows_n", "cold_dispatch_n", "block_cache_hit",
             "block_cache_miss")

_resolve_scope = ROOT.sub_scope("query.resolve")
_RESOLVED = {kind: _resolve_scope.counter(kind)
             for kind in ("unaggregated", "aggregated", "partial")}


class LocalStorage:
    """Direct adapter over an in-process storage.Database (the coordinator
    embedded in a dbnode, storage/m3/storage.go Fetch -> ReadEncoded)."""

    def __init__(self, db, namespace: bytes):
        self._db = db
        self._namespace = namespace

    def fetch_raw(self, matchers: Sequence[Matcher], start_ns: int,
                  end_ns: int) -> Dict[bytes, dict]:
        q = matchers_to_index_query(matchers)
        ids = self._db.query_ids(self._namespace, q, start_ns, end_ns)
        out: Dict[bytes, dict] = {}
        ns = self._db.namespace(self._namespace)
        # Under a detailed span (the caller's query.fetch) the read's
        # phases become costs of that span, never child spans: its self
        # time stays the read's whole time. One flag read per fetch.
        acc = tracing.detail()
        timed = acc is not None
        t_loop = _clock() if timed else 0
        before = [acc.costs.get(k, 0) for k in _NS_COSTS] if timed else ()
        # One routed sweep for all the ids (storage/read_batch.py): the
        # rows no cache holds are decoded one dispatch a geometry, not
        # one a (series, block).
        for sid, got in zip(ids, read_many(ns, self._db.shard_set, ids,
                                           start_ns, end_ns, acc)):
            if got is not None:
                out[sid] = {"tags": got[0] or {}, "t": got[1], "v": got[2]}
        if timed:
            acc.add_cost("series_n", len(ids))
            acc.add_cost("read_ns", _clock() - t_loop)
            tag = "{ns=%s}" % self._namespace.decode(errors="replace")
            for kind, was in zip(_NS_COSTS, before):
                moved = acc.costs.get(kind, 0) - was
                if moved:
                    acc.add_cost(kind + tag, moved)
        return out

    def write(self, series_id: bytes, tags: Dict[bytes, bytes], t_ns: int,
              value: float):
        self._db.write(self._namespace, series_id, t_ns, value, tags=tags)

    def write_batch(self, series_ids: Sequence[bytes], tags: Sequence[dict],
                    ts, vals):
        """Columnar write: one shard-routed db.write_batch append instead
        of a per-sample write loop (the coordinator ingest batch path).
        What a coordinator writes it writes again every scrape, so the
        rows are routed through the shard memo and the node hashes
        nothing."""
        series_ids = list(series_ids)
        shard_ids = self._db.shard_set.lookup_memo(series_ids)
        self._db.write_batch(self._namespace, series_ids, ts, vals,
                             tags=list(tags), shard_ids=shard_ids)

    def complete_tags(self, matchers: Sequence[Matcher], start_ns: int,
                      end_ns: int, name_only: bool = False,
                      filter_names: Sequence[bytes] = ()) -> Dict[bytes, set]:
        """storage/types.go CompleteTags: tag name -> distinct values for
        series matching the matchers, from the index — no datapoints read.
        name_only leaves the value sets empty (CompleteNameOnly)."""
        return self._db.aggregate_tags(
            self._namespace, matchers_to_index_query(matchers), start_ns,
            end_ns, name_only=name_only, filter_names=filter_names)


class SessionStorage:
    """Adapter over the replicating client session (storage/m3/storage.go
    Fetch -> session.FetchTagged, the coordinator's production path)."""

    def __init__(self, session, namespace: bytes):
        self._session = session
        self._namespace = namespace

    def fetch_raw(self, matchers: Sequence[Matcher], start_ns: int,
                  end_ns: int) -> Dict[bytes, dict]:
        q = matchers_to_index_query(matchers)
        return self._session.fetch_tagged(self._namespace, q, start_ns, end_ns)

    def write(self, series_id: bytes, tags: Dict[bytes, bytes], t_ns: int,
              value: float):
        self._session.write_tagged(self._namespace, series_id, tags, t_ns, value)

    def write_batch(self, series_ids: Sequence[bytes], tags: Sequence[dict],
                    ts, vals):
        """Columnar write through the cluster: one Session.write_batch —
        the batch routed once, one write_batch RPC a host, acknowledged
        at the session's write consistency level (the coordinator ingest
        batch path, as LocalStorage.write_batch is for an embedded
        node)."""
        self._session.write_batch(self._namespace, series_ids, ts, vals,
                                  tags=tags)

    def complete_tags(self, matchers: Sequence[Matcher], start_ns: int,
                      end_ns: int, name_only: bool = False,
                      filter_names: Sequence[bytes] = ()) -> Dict[bytes, set]:
        q = matchers_to_index_query(matchers)
        return self._session.aggregate(
            self._namespace, q, start_ns, end_ns, name_only=name_only,
            field_filter=filter_names)


def merge_points(parts_t: Sequence[np.ndarray], parts_v: Sequence[np.ndarray]
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """One series' runs from several stores as one run: ascending, one
    point a timestamp, the EARLIER part winning a duplicate (a caller
    lists the store it trusts most first: the finer resolution). One
    concatenate, one stable sort and one pass for the duplicates, however
    many stores answered."""
    if len(parts_t) == 1:
        return parts_t[0], parts_v[0]
    t = np.concatenate([np.asarray(p) for p in parts_t])
    v = np.concatenate([np.asarray(p) for p in parts_v])
    if t.size > 1 and not (t[1:] > t[:-1]).all():
        order = np.argsort(t, kind="stable")
        t, v = t[order], v[order]
        keep = np.ones(t.size, dtype=bool)
        keep[1:] = t[1:] != t[:-1]
        if not keep.all():
            t, v = t[keep], v[keep]
    return t, v


def fetch_merged(stores: Sequence, matchers: Sequence[Matcher], start_ns: int,
                 end_ns: int) -> Dict[bytes, dict]:
    """The stores' answers to one fetch, merged by series id: a series
    that one store holds is that store's entry, a series that several
    hold is one `merge_points` over all of them (its tags the first
    store's that has any)."""
    found: Dict[bytes, list] = {}
    for store in stores:
        for sid, entry in store.fetch_raw(matchers, start_ns, end_ns).items():
            found.setdefault(sid, []).append(entry)
    merged: Dict[bytes, dict] = {}
    for sid, entries in found.items():
        if len(entries) == 1:
            merged[sid] = dict(entries[0])
            continue
        t, v = merge_points([e["t"] for e in entries],
                            [e["v"] for e in entries])
        tags = next((e["tags"] for e in entries if e["tags"]),
                    entries[0]["tags"])
        merged[sid] = {"tags": tags, "t": t, "v": v}
    return merged


@dataclasses.dataclass(frozen=True)
class NamespaceAttrs:
    """What the resolver knows of one cluster namespace (the reference's
    ClusterNamespaceOptions.Attributes: metrics type, retention,
    resolution, and whether every metric is downsampled into it)."""

    name: bytes
    aggregated: bool = False
    retention_ns: int = 0
    resolution_ns: int = 0      # 0: the scrape's own (unaggregated)
    complete: bool = True       # aggregated: `downsample.all`


def resolve(attrs: Sequence[NamespaceAttrs], now_ns: int, start_ns: int
            ) -> Tuple[List[int], str]:
    """Which namespaces answer a fetch that starts at `start_ns`, as
    positions in `attrs`, finest resolution first, and how it was decided:
    "unaggregated", "aggregated" or "partial" (the rule of the
    reference's cluster_resolver.go, DIVERGENCES.md):

    1. the unaggregated namespace alone where its retention reaches back
       to the fetch's start (`now - retention <= start`);
    2. else, of the complete aggregated namespaces whose retention
       reaches back to it, the one of finest resolution (a tie: the
       longest retention) alone, with every partial aggregated namespace
       of finer resolution that reaches back too;
    3. else the unaggregated namespace and the aggregated namespace of
       longest retention, whatever they still hold: "partial"."""
    unagg = next(i for i, a in enumerate(attrs) if not a.aggregated)
    if now_ns - attrs[unagg].retention_ns <= start_ns:
        return [unagg], "unaggregated"
    agg = [i for i, a in enumerate(attrs) if a.aggregated]
    covers = [i for i in agg if now_ns - attrs[i].retention_ns <= start_ns]
    whole = [i for i in covers if attrs[i].complete]
    if whole:
        best = min(whole, key=lambda i: (attrs[i].resolution_ns,
                                         -attrs[i].retention_ns))
        finer = sorted((i for i in covers if not attrs[i].complete
                        and attrs[i].resolution_ns
                        < attrs[best].resolution_ns),
                       key=lambda i: attrs[i].resolution_ns)
        return finer + [best], "aggregated"
    longest = max(agg, key=lambda i: (attrs[i].retention_ns,
                                      -attrs[i].resolution_ns))
    return [unagg, longest], "partial"


class ResolvingStorage:
    """A coordinator's cluster namespaces behind one storage: every fetch
    is answered by the namespace(s) `resolve` names for its range against
    the coordinator's clock (storage/m3/cluster_resolver.go
    resolveClusterNamespacesForQuery); writes go to the unaggregated
    namespace. The members are `LocalStorage` or `SessionStorage` alike.
    A coordinator with one namespace holds that member itself and never
    builds this."""

    def __init__(self, members: Sequence[Tuple[NamespaceAttrs, object]],
                 clock: Optional[Callable[[], int]] = None):
        self.attrs = [a for a, _s in members]
        self._stores = [s for _a, s in members]
        raw = [s for a, s in members if not a.aggregated]
        if len(raw) != 1 or len(members) < 2:
            raise ValueError("a resolving storage takes one unaggregated "
                             "namespace and at least one aggregated")
        self._clock = clock or time.time_ns
        self._write_to = raw[0]

    def _resolved(self, start_ns: int) -> list:
        acc = tracing.detail()
        t0 = _clock() if acc is not None else 0
        picked, how = resolve(self.attrs, self._clock(), start_ns)
        _RESOLVED[how].inc()
        if acc is not None:
            acc.add_cost("resolve_ns", _clock() - t0)
            acc.add_cost("namespaces_n", len(picked))
        return [self._stores[i] for i in picked]

    def fetch_raw(self, matchers: Sequence[Matcher], start_ns: int,
                  end_ns: int) -> Dict[bytes, dict]:
        stores = self._resolved(start_ns)
        if len(stores) == 1:
            return stores[0].fetch_raw(matchers, start_ns, end_ns)
        return fetch_merged(stores, matchers, start_ns, end_ns)

    def write(self, series_id: bytes, tags: Dict[bytes, bytes], t_ns: int,
              value: float):
        self._write_to.write(series_id, tags, t_ns, value)

    def write_batch(self, series_ids: Sequence[bytes], tags: Sequence[dict],
                    ts, vals):
        self._write_to.write_batch(series_ids, tags, ts, vals)

    def complete_tags(self, matchers: Sequence[Matcher], start_ns: int,
                      end_ns: int, name_only: bool = False,
                      filter_names: Sequence[bytes] = ()) -> Dict[bytes, set]:
        stores = self._resolved(start_ns)
        if len(stores) == 1:
            return stores[0].complete_tags(
                matchers, start_ns, end_ns, name_only=name_only,
                filter_names=filter_names)
        return FanoutStorage(stores).complete_tags(
            matchers, start_ns, end_ns, name_only=name_only,
            filter_names=filter_names)


class FanoutStorage:
    """Fan out fetches across stores and merge by series id
    (storage/fanout/storage.go; replica-level merge already happened in the
    client, so cross-store merge is simple union preferring more points)."""

    def __init__(self, stores: Sequence):
        self._stores = list(stores)

    def fetch_raw(self, matchers: Sequence[Matcher], start_ns: int,
                  end_ns: int) -> Dict[bytes, dict]:
        return fetch_merged(self._stores, matchers, start_ns, end_ns)

    def write(self, series_id: bytes, tags, t_ns: int, value: float):
        for store in self._stores:
            store.write(series_id, tags, t_ns, value)

    def complete_tags(self, matchers: Sequence[Matcher], start_ns: int,
                      end_ns: int, name_only: bool = False,
                      filter_names: Sequence[bytes] = ()) -> Dict[bytes, set]:
        merged: Dict[bytes, set] = {}
        for store in self._stores:
            part = _store_complete_tags(store, matchers, start_ns, end_ns,
                                        name_only, filter_names)
            for name, vals in part.items():
                merged.setdefault(name, set()).update(vals)
        return merged


def _store_complete_tags(store, matchers, start_ns, end_ns, name_only,
                         filter_names) -> Dict[bytes, set]:
    """CompleteTags for any store: use the store's index-backed fast path
    when present, else derive from fetched series tags (the reference's
    remote storages similarly degrade to a series fetch)."""
    fn = getattr(store, "complete_tags", None)
    if fn is not None:
        return fn(matchers, start_ns, end_ns, name_only=name_only,
                  filter_names=filter_names)
    from ..storage.database import fold_tags

    ff = set(filter_names) if filter_names else None
    out: Dict[bytes, set] = {}
    for entry in store.fetch_raw(matchers, start_ns, end_ns).values():
        fold_tags(out, dict(entry["tags"]), ff, name_only)
    return out
