"""Prometheus remote write/read wire codecs: snappy block format +
hand-rolled protobuf for the remote-storage messages, so a real Prometheus
can speak to the coordinator with no external dependencies (reference:
src/query/api/v1/handler/prometheus/remote/write.go:46 ParseRequest ->
snappy.Decode -> proto Unmarshal prompb.WriteRequest; read.go for the
matching remote read path).

prompb messages implemented (proto3 field numbers per
prometheus/prompb/remote.proto and types.proto):
  WriteRequest { repeated TimeSeries timeseries = 1; }
  ReadRequest  { repeated Query queries = 1; }
  Query        { int64 start_timestamp_ms = 1; int64 end_timestamp_ms = 2;
                 repeated LabelMatcher matchers = 3; }
  ReadResponse { repeated QueryResult results = 1; }
  QueryResult  { repeated TimeSeries timeseries = 1; }
  TimeSeries   { repeated Label labels = 1; repeated Sample samples = 2; }
  Label        { string name = 1; string value = 2; }
  Sample       { double value = 1; int64 timestamp = 2; }   // ms
  LabelMatcher { Type type = 1; string name = 2; string value = 3; }
    (Type EQ=0 NEQ=1 RE=2 NRE=3 — numerically identical to
     m3_tpu.query.model.MatchType.)

Unknown fields are skipped (proto3 forward compatibility), so newer
Prometheus senders with exemplars/metadata fields still parse.
"""

from __future__ import annotations

import struct
import threading
from itertools import islice
from typing import Callable, Dict, List, Optional, Tuple

from ..query.model import Matcher, MatchType
from ..utils import tracing
from ..utils.instrument import ROOT

# ---------------------------------------------------------------------------
# snappy block format (github.com/google/snappy/blob/main/format_description.txt)
# ---------------------------------------------------------------------------


class SnappyError(ValueError):
    pass


def _read_uvarint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        if pos >= len(buf):
            raise SnappyError("truncated varint")
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7
        if shift > 63:
            raise SnappyError("varint too long")


def snappy_decompress(buf: bytes) -> bytes:
    """Decompress a snappy *block* (what Prometheus remote write sends)."""
    n, pos = _read_uvarint(buf, 0)
    out = bytearray()
    while pos < len(buf):
        tag = buf[pos]
        pos += 1
        kind = tag & 3
        if kind == 0:  # literal
            ln = tag >> 2
            if ln >= 60:
                nbytes = ln - 59
                if pos + nbytes > len(buf):
                    raise SnappyError("truncated literal length")
                ln = int.from_bytes(buf[pos:pos + nbytes], "little")
                pos += nbytes
            ln += 1
            if pos + ln > len(buf):
                raise SnappyError("truncated literal")
            out += buf[pos:pos + ln]
            pos += ln
            continue
        if kind == 1:  # copy with 1-byte offset
            ln = ((tag >> 2) & 7) + 4
            if pos >= len(buf):
                raise SnappyError("truncated copy-1")
            offset = ((tag >> 5) << 8) | buf[pos]
            pos += 1
        elif kind == 2:  # copy with 2-byte offset
            ln = (tag >> 2) + 1
            if pos + 2 > len(buf):
                raise SnappyError("truncated copy-2")
            offset = int.from_bytes(buf[pos:pos + 2], "little")
            pos += 2
        else:  # copy with 4-byte offset
            ln = (tag >> 2) + 1
            if pos + 4 > len(buf):
                raise SnappyError("truncated copy-4")
            offset = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        if offset == 0 or offset > len(out):
            raise SnappyError("copy offset out of range")
        start = len(out) - offset
        if offset >= ln:
            # Non-overlapping (the common label-dedup case): bulk slice.
            out += out[start:start + ln]
        else:
            # Overlapping forward copy (offset < length): byte-at-a-time
            # semantics, the run-length trick snappy uses for RLE.
            for i in range(ln):
                out.append(out[start + i])
    if len(out) != n:
        raise SnappyError(f"length mismatch: header {n}, decoded {len(out)}")
    return bytes(out)


def snappy_compress(data: bytes) -> bytes:
    """Spec-compliant literals-only snappy block (every snappy reader
    decodes it; we trade compression ratio for zero dependencies on the
    response path — requests are decompressed fully either way)."""
    out = bytearray()
    # uvarint length
    n = len(data)
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            break
    pos = 0
    while pos < len(data):
        chunk = data[pos:pos + 65536]
        ln = len(chunk) - 1  # <= 65535 by the chunk cap
        if ln < 60:
            out.append(ln << 2)
        elif ln < (1 << 8):
            out.append(60 << 2)
            out += ln.to_bytes(1, "little")
        else:
            out.append(61 << 2)
            out += ln.to_bytes(2, "little")
        out += chunk
        pos += len(chunk)
    return bytes(out)


# ---------------------------------------------------------------------------
# minimal protobuf wire codec
# ---------------------------------------------------------------------------


class ProtoError(ValueError):
    pass


def _fields(buf: memoryview):
    """Yield (field_number, wire_type, value) — value is int for varint/
    fixed, memoryview for length-delimited."""
    pos = 0
    n = len(buf)
    while pos < n:
        key, pos = _read_uvarint_mv(buf, pos)
        field, wt = key >> 3, key & 7
        if wt == 0:
            v, pos = _read_uvarint_mv(buf, pos)
            yield field, wt, v
        elif wt == 1:
            if pos + 8 > n:
                raise ProtoError("truncated fixed64")
            yield field, wt, int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wt == 2:
            ln, pos = _read_uvarint_mv(buf, pos)
            if pos + ln > n:
                raise ProtoError("truncated bytes field")
            yield field, wt, buf[pos:pos + ln]
            pos += ln
        elif wt == 5:
            if pos + 4 > n:
                raise ProtoError("truncated fixed32")
            yield field, wt, int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ProtoError(f"unsupported wire type {wt}")


def _read_uvarint_mv(buf: memoryview, pos: int) -> Tuple[int, int]:
    out = shift = 0
    n = len(buf)
    while True:
        if pos >= n:
            raise ProtoError("truncated varint")
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7
        if shift > 70:
            raise ProtoError("varint too long")


def _zigzag_i64(v: int) -> int:
    """proto int64 arrives as unsigned varint; reinterpret two's complement."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _f64(bits: int) -> float:
    return struct.unpack("<d", bits.to_bytes(8, "little"))[0]


# 65,536 label blocks (the shard memo's bound, parallel/sharding.py) of at
# most LABEL_MEMO_MAX_KEY bytes each; a longer block is decoded every time.
LABEL_MEMO_MAX_ENTRIES = 65536
LABEL_MEMO_MAX_KEY = 2048

_memo_scope = ROOT.sub_scope("coordinator.remote_write.label_memo")
_MEMO_HITS = _memo_scope.counter("hits")
_MEMO_MISSES = _memo_scope.counter("misses")

_F64 = struct.Struct("<d")


class LabelMemo:
    """A TimeSeries message's label block (the raw bytes of its leading
    `labels` fields) -> (tags, series id). A sender repeats a series'
    label bytes in every request, so a known block costs one dict probe
    instead of a decode per label and an id per series. The key is the
    bytes themselves and the value what the full decoder and `series_id`
    made of exactly those bytes, so a hit IS the slow path's answer.

    The tags dict of an entry is shared by every request that carries
    the block (and by whatever keeps a row's tags: a shard's registry):
    nothing downstream may mutate it.

    Probes are lock-free dict reads; the lock orders the miss path's
    bound check with its insert. A full memo drops its oldest eighth
    (insertion order): room is made once in 8,192 first sightings, and
    a series still being sent comes back on its next miss."""

    def __init__(self, series_id: Callable[[dict], bytes],
                 max_entries: int = LABEL_MEMO_MAX_ENTRIES):
        self._series_id = series_id
        self._max = max_entries
        self._entries: Dict[bytes, Tuple[dict, bytes]] = {}
        self._lock = threading.Lock()
        self.lookup = self._entries.get  # block -> entry | None, no lock

    def __len__(self) -> int:
        return len(self._entries)

    def resolve(self, tags: dict) -> Tuple[dict, bytes]:
        """The entry the full decoder's tags make; not remembered."""
        return tags, self._series_id(tags)

    def remember(self, block: bytes, tags: dict) -> Tuple[dict, bytes]:
        entry = self.resolve(tags)
        if len(block) <= LABEL_MEMO_MAX_KEY:
            entries = self._entries
            with self._lock:
                if len(entries) >= self._max:
                    for old in list(islice(entries, max(1, self._max // 8))):
                        del entries[old]
                entries[block] = entry
        return entry


def decode_write_request(data: bytes, memo: LabelMemo) -> Tuple[
        List[Tuple[dict, List[Tuple[int, float]]]], List[bytes]]:
    """prompb.WriteRequest -> ([(tags {bytes: bytes}, [(t_ms, value), ...])],
    [series id]), one of each per TimeSeries in the request's order.

    The request is walked at the level of field headers. A TimeSeries
    that is a run of `labels` fields followed by a run of `samples`
    fields and nothing else has its label block looked up in `memo` by
    its bytes (only the labels' LENGTHS are read) and its samples
    decoded in place; a first sighting decodes the block with the full
    decoder and is remembered. Any other layout takes the full decoder
    for that series, so this gives the rows the full decoder gives for
    the same bytes, or raises ProtoError where it does. Counts series in
    `coordinator.remote_write.label_memo.hits` / `.misses` (a series
    decoded in full is a miss) and, under a detailed span, in its
    `memo_hit_n` / `memo_miss_n`."""
    data = bytes(data)
    series: List[Tuple[dict, List[Tuple[int, float]]]] = []
    ids: List[bytes] = []
    probe = memo.lookup
    unpack = _F64.unpack_from
    hits = misses = 0
    pos, n = 0, len(data)
    while pos < n:
        if data[pos] == 0x0A:
            pos += 1
        else:
            key, pos = _read_uvarint_mv(data, pos)
            if key != 0x0A:  # not `timeseries`: skipped, as ever
                pos = _skip_value(data, pos, key & 7)
                continue
        if pos + 1 < n and data[pos + 1] < 0x80:  # one or two length bytes
            ln = data[pos]
            if ln < 0x80:
                start = pos + 1
            else:
                ln = (ln & 0x7F) | (data[pos + 1] << 7)
                start = pos + 2
        else:
            ln, start = _read_uvarint_mv(data, pos)
        pos = end = start + ln
        if end > n:
            raise ProtoError("truncated bytes field")
        entry = None
        try:
            p = start
            while p < end and data[p] == 0x0A:  # a label: its length only
                ln = data[p + 1]
                if ln < 0x80:
                    p += 2 + ln
                else:
                    ln, p = _read_uvarint_mv(data, p + 1)
                    p += ln
            labels_end = p
            samples = []
            while p < end and data[p] == 0x12 and data[p + 1] < 0x80:
                q = p + 2 + data[p + 1]
                # value then timestamp is what every sender writes
                t_ms = _stamp_ms(data, p + 12, q) if (
                    q - p > 12 and data[p + 2] == 0x09
                    and data[p + 11] == 0x10) else None
                if t_ms is not None:
                    samples.append((t_ms, unpack(data, p + 3)[0]))
                else:  # a default left out, another order, more fields
                    samples.append(_decode_sample(memoryview(data)[p + 2:q]))
                p = q
            if p == end:
                block = data[start:labels_end]
                entry = probe(block)
                if entry is None:
                    entry = memo.remember(
                        block, _decode_timeseries(memoryview(block))[0])
                    misses += 1
                else:
                    hits += 1
        except (IndexError, ProtoError):
            entry = None  # the full decoder says what is wrong with it
        if entry is None:
            tags, samples = _decode_timeseries(memoryview(data)[start:end])
            entry = memo.resolve(tags)
            misses += 1
        series.append((entry[0], samples))
        ids.append(entry[1])
    if hits:
        _MEMO_HITS.inc(hits)
    if misses:
        _MEMO_MISSES.inc(misses)
    acc = tracing.detail()
    if acc is not None:
        acc.add_cost("memo_hit_n", hits)
        acc.add_cost("memo_miss_n", misses)
    return series, ids


def _skip_value(buf: bytes, pos: int, wt: int) -> int:
    """Past one field's value, with `_fields`' own complaints."""
    n = len(buf)
    if wt == 0:
        return _read_uvarint_mv(buf, pos)[1]
    if wt == 2:
        ln, pos = _read_uvarint_mv(buf, pos)
        if pos + ln > n:
            raise ProtoError("truncated bytes field")
        return pos + ln
    if wt == 1 or wt == 5:
        pos += 8 if wt == 1 else 4
        if pos > n:
            raise ProtoError("truncated fixed64" if wt == 1
                             else "truncated fixed32")
        return pos
    raise ProtoError(f"unsupported wire type {wt}")


def _stamp_ms(buf: bytes, pos: int, end: int) -> Optional[int]:
    """buf[pos:end] as exactly one varint of at most ten bytes (an int64
    in two's complement), else None."""
    if not pos < end <= pos + 10:
        return None
    out = shift = 0
    for b in buf[pos:end - 1]:
        if b < 0x80:
            return None
        out |= (b & 0x7F) << shift
        shift += 7
    b = buf[end - 1]
    return None if b & 0x80 else _zigzag_i64(out | (b << shift))


def _decode_sample(buf: memoryview) -> Tuple[int, float]:
    val = 0.0
    t_ms = 0
    for field, wt, v in _fields(buf):
        if field == 1 and wt == 1:
            val = _f64(v)
        elif field == 2 and wt == 0:
            t_ms = _zigzag_i64(v)
    return t_ms, val


def _decode_timeseries(buf: memoryview):
    tags = {}
    samples: List[Tuple[int, float]] = []
    for field, wt, v in _fields(buf):
        if field == 1 and wt == 2:
            name = value = b""
            for f2, w2, v2 in _fields(v):
                if f2 == 1 and w2 == 2:
                    name = bytes(v2)
                elif f2 == 2 and w2 == 2:
                    value = bytes(v2)
            tags[name] = value
        elif field == 2 and wt == 2:
            samples.append(_decode_sample(v))
    return tags, samples


def decode_read_request(data: bytes) -> List[dict]:
    """prompb.ReadRequest -> [{"start_ms", "end_ms", "matchers": [Matcher]}]."""
    queries = []
    for field, wt, v in _fields(memoryview(data)):
        if field == 1 and wt == 2:
            q = {"start_ms": 0, "end_ms": 0, "matchers": []}
            for f2, w2, v2 in _fields(v):
                if f2 == 1 and w2 == 0:
                    q["start_ms"] = _zigzag_i64(v2)
                elif f2 == 2 and w2 == 0:
                    q["end_ms"] = _zigzag_i64(v2)
                elif f2 == 3 and w2 == 2:
                    mtype = 0
                    name = value = b""
                    for f3, w3, v3 in _fields(v2):
                        if f3 == 1 and w3 == 0:
                            mtype = v3
                        elif f3 == 2 and w3 == 2:
                            name = bytes(v3)
                        elif f3 == 3 and w3 == 2:
                            value = bytes(v3)
                    q["matchers"].append(
                        Matcher(MatchType(mtype), name, value))
            queries.append(q)
    return queries


# -- encoding ---------------------------------------------------------------


def _put_uvarint(out: bytearray, v: int):
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return


def _put_field_bytes(out: bytearray, field: int, data: bytes):
    _put_uvarint(out, (field << 3) | 2)
    _put_uvarint(out, len(data))
    out += data


def _encode_timeseries(tags: dict, samples: List[Tuple[int, float]]) -> bytes:
    ts = bytearray()
    for name, value in sorted(tags.items()):
        lbl = bytearray()
        _put_field_bytes(lbl, 1, name)
        _put_field_bytes(lbl, 2, value)
        _put_field_bytes(ts, 1, bytes(lbl))
    for t_ms, val in samples:
        smp = bytearray()
        _put_uvarint(smp, (1 << 3) | 1)
        smp += struct.pack("<d", val)
        _put_uvarint(smp, (2 << 3) | 0)
        _put_uvarint(smp, t_ms & ((1 << 64) - 1))
        _put_field_bytes(ts, 2, bytes(smp))
    return bytes(ts)


def encode_read_response(results: List[List[Tuple[dict, List[Tuple[int, float]]]]]) -> bytes:
    """[[(tags, [(t_ms, v)])] per query] -> prompb.ReadResponse bytes."""
    out = bytearray()
    for series_list in results:
        qr = bytearray()
        for tags, samples in series_list:
            _put_field_bytes(qr, 1, _encode_timeseries(tags, samples))
        _put_field_bytes(out, 1, bytes(qr))
    return bytes(out)


def encode_write_request(series: List[Tuple[dict, List[Tuple[int, float]]]]) -> bytes:
    """Inverse of decode_write_request (test fixtures + client use)."""
    out = bytearray()
    for tags, samples in series:
        _put_field_bytes(out, 1, _encode_timeseries(tags, samples))
    return bytes(out)
