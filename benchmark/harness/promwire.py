"""What a Prometheus remote_write sender puts on the wire, with no
dependency: a prompb.WriteRequest (protobuf) in a snappy block with real
back-references (label strings repeat from series to series, and a
server pays for decoding copies, not only literals).

One request carries one sample for each of a fixed group of series, so
its bytes differ from scrape to scrape only in the samples. A Template
compresses the group's request once, keeps every sample's bytes as
literals, and remembers where they lie in the compressed block; each
scrape then patches values and the timestamp in with numpy."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

SAMPLE_LEN = 18      # 0x12 0x10 | 0x09 f64 | 0x10 varint(6 bytes)
_TS_VARINT = 6       # unix milliseconds need 41 bits until the year 2109


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _lenfield(tag: int, data: bytes) -> bytes:
    return bytes([tag]) + _uvarint(len(data)) + data


def encode_group(labels: List[Dict[str, str]]) -> Tuple[bytes, np.ndarray]:
    """Uncompressed WriteRequest with zeroed samples, and the offset of
    each series' Sample field (SAMPLE_LEN bytes)."""
    out = bytearray()
    offs = []
    for lab in labels:
        ts = bytearray()
        for name in sorted(lab):   # Prometheus sorts labels by name
            ts += _lenfield(0x0A, _lenfield(0x0A, name.encode())
                            + _lenfield(0x12, lab[name].encode()))
        sample = (b"\x12\x10\x09" + b"\0" * 8 + b"\x10"
                  + b"\x80" * (_TS_VARINT - 1) + b"\0")
        assert len(sample) == SAMPLE_LEN
        head = b"\x0A" + _uvarint(len(ts) + SAMPLE_LEN)
        offs.append(len(out) + len(head) + len(ts))
        out += head + ts + sample
    return bytes(out), np.array(offs, np.int64)


def _emit_literal(out: bytearray, data: bytes, lo: int, hi: int) -> int:
    """Append data[lo:hi] as one literal; returns where its bytes start."""
    n = hi - lo - 1
    if n < 60:
        out.append(n << 2)
    else:
        nb = (n.bit_length() + 7) // 8
        out.append((59 + nb) << 2)
        out += n.to_bytes(nb, "little")
    at = len(out)
    out += data[lo:hi]
    return at


def snappy_compress(data: bytes, literal_only: np.ndarray,
                    span: int) -> Tuple[bytes, np.ndarray]:
    """Greedy snappy block compressor (4-byte hash, copies of 4..64
    bytes at offsets under 64 KiB). The `span` bytes at each offset of
    `literal_only` are never copied from or to; returns the block and
    where each of those spans starts inside it."""
    n = len(data)
    barrier = bytearray(n)
    for o in literal_only:
        barrier[o:o + span] = b"\1" * span
    out = bytearray(_uvarint(n))
    table: Dict[bytes, int] = {}
    where = {}
    i = lit = 0

    def flush(upto: int):
        if upto > lit:
            at = _emit_literal(out, data, lit, upto)
            for o in pending:
                where[o] = at + (o - lit)
            pending.clear()

    pending: List[int] = []
    starts = set(int(o) for o in literal_only)
    while i < n:
        if barrier[i]:
            if i in starts:
                pending.append(i)
            i += 1
            continue
        key = data[i:i + 4]
        cand = table.get(key)
        table[key] = i
        if (cand is not None and len(key) == 4 and i - cand < 65536
                and not (barrier[i + 1] | barrier[i + 2] | barrier[i + 3])):
            ln = 4
            while (ln < 64 and i + ln < n and not barrier[i + ln]
                   and data[cand + ln] == data[i + ln]):
                ln += 1
            flush(i)
            off = i - cand
            out.append(((ln - 1) << 2) | 2)
            out += off.to_bytes(2, "little")
            i += ln
            lit = i
        else:
            i += 1
    flush(n)
    return bytes(out), np.array([where[int(o)] for o in literal_only],
                                np.int64)


class Template:
    """One group's request, compressed once; `fill` makes a scrape."""

    def __init__(self, labels: List[Dict[str, str]]):
        raw, offs = encode_group(labels)
        block, at = snappy_compress(raw, offs, SAMPLE_LEN)
        self.block = np.frombuffer(block, np.uint8).copy()
        self.val_at = (at + 3)[:, None] + np.arange(8)
        self.ts_at = (at + 12)[:, None] + np.arange(_TS_VARINT)
        self.raw_len = len(raw)

    def fill(self, ts_ms: int, values: np.ndarray) -> bytes:
        body = self.block.copy()
        body[self.val_at] = np.ascontiguousarray(
            values, "<f8").view(np.uint8).reshape(-1, 8)
        tsb = [(ts_ms >> (7 * j)) & 0x7F | (0x80 if j < _TS_VARINT - 1 else 0)
               for j in range(_TS_VARINT)]
        body[self.ts_at] = np.array(tsb, np.uint8)
        return body.tobytes()
