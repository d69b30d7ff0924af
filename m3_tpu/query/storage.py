"""Query storage interface + implementations (reference:
src/query/storage/types.go Storage, storage/m3/storage.go the dbnode
adapter, storage/fanout/storage.go the multi-store fanout).

fetch_raw(matchers, start_ns, end_ns) -> {series_id: {tags, t, v}} raw
datapoints; the executor grids them per query. Tag index queries compile
from label matchers via model.matchers_to_index_query."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..storage.read_batch import read_many
from ..utils import tracing
from ..utils.tracing import clock_ns as _clock
from .model import Matcher, matchers_to_index_query



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


class FanoutStorage:
    """Fan out fetches across stores and merge by series id
    (storage/fanout/storage.go; replica-level merge already happened in the
    client, so cross-store merge is simple union preferring more points)."""

    def __init__(self, stores: Sequence):
        self._stores = list(stores)

    def fetch_raw(self, matchers: Sequence[Matcher], start_ns: int,
                  end_ns: int) -> Dict[bytes, dict]:
        merged: Dict[bytes, dict] = {}
        for store in self._stores:
            for sid, entry in store.fetch_raw(matchers, start_ns, end_ns).items():
                cur = merged.get(sid)
                if cur is None:
                    merged[sid] = dict(entry)
                else:
                    t = np.concatenate([np.asarray(cur["t"]), np.asarray(entry["t"])])
                    v = np.concatenate([np.asarray(cur["v"]), np.asarray(entry["v"])])
                    order = np.argsort(t, kind="stable")
                    t, v = t[order], v[order]
                    keep = np.ones(t.size, dtype=bool)
                    keep[1:] = t[1:] != t[:-1]
                    cur["t"], cur["v"] = t[keep], v[keep]
                    if not cur["tags"] and entry["tags"]:
                        cur["tags"] = entry["tags"]
        return merged

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
