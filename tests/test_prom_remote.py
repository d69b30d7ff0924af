"""Prometheus remote write/read: snappy block codec + prompb protobuf
(reference: src/query/api/v1/handler/prometheus/remote/write.go:46,
read.go). The end-to-end tests post real snappy-compressed protobuf bodies
over HTTP, exactly what a Prometheus remote_write/remote_read sends."""

import urllib.request

import numpy as np
import pytest

from m3_tpu.cluster import kv as cluster_kv
from m3_tpu.coordinator import promremote as pr
from m3_tpu.coordinator import run_embedded
from m3_tpu.coordinator.ingest import _series_id
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.parallel.sharding import ShardSet
from m3_tpu.query.model import MatchType, Matcher
from m3_tpu.storage.database import Database
from m3_tpu.storage.namespace import NamespaceOptions

S = 1_000_000_000
T0 = 1_700_000_000 * S


class TestSnappy:
    def test_roundtrip_literals(self):
        for payload in (b"", b"x", b"hello world" * 100, bytes(range(256)) * 300):
            assert pr.snappy_decompress(pr.snappy_compress(payload)) == payload

    def test_decompress_copy_elements(self):
        # Hand-crafted stream: literal "abc" + copy-1(offset=3, len=9) ->
        # overlapping RLE producing "abc" * 4.
        stream = bytes([12,              # uvarint uncompressed length = 12
                        0b000010_00,     # literal, len = 2+1 = 3
                        ord("a"), ord("b"), ord("c"),
                        0b000_101_01,    # copy-1: len = 5+4 = 9, offset hi = 0
                        3])              # offset low byte = 3
        assert pr.snappy_decompress(stream) == b"abcabcabcabc"

    def test_decompress_copy2(self):
        data = b"0123456789" * 10
        # literal of all 100 bytes, then copy-2 back 100 with len 20.
        stream = bytearray([120 & 0x7F | 0x80, 120 >> 7])  # uvarint 120
        stream.append(60 << 2)
        stream += (99).to_bytes(1, "little")
        stream += data
        stream.append(((20 - 1) << 2) | 2)
        stream += (100).to_bytes(2, "little")
        assert pr.snappy_decompress(bytes(stream)) == data + data[:20]

    def test_corrupt_streams_rejected(self):
        with pytest.raises(pr.SnappyError):
            pr.snappy_decompress(bytes([5, 0b000010_00, ord("a")]))  # short
        with pytest.raises(pr.SnappyError):
            pr.snappy_decompress(bytes([1, 0b000_000_01, 9]))  # bad offset


def _decode(data, memo=None):
    """The rows of a write request, by the one route there is: through a
    label memo (a new one unless given)."""
    if memo is None:     # an empty memo is falsy: it has a length
        memo = pr.LabelMemo(_series_id)
    series, ids = pr.decode_write_request(data, memo)
    assert ids == [_series_id(tags) for tags, _ in series]
    return series


class TestProto:
    def test_write_request_roundtrip(self):
        series = [
            ({b"__name__": b"up", b"job": b"api"}, [(1700000000000, 1.0),
                                                    (1700000015000, 0.0)]),
            ({b"__name__": b"lat", b"q": b"0.99"}, [(1700000000000, -3.25)]),
        ]
        enc = pr.encode_write_request(series)
        assert _decode(enc) == series

    def test_unknown_fields_skipped(self):
        series = [({b"n": b"v"}, [(123000, 4.5)])]
        enc = bytearray(pr.encode_write_request(series))
        # Append an unknown field 7 (varint) at top level + trailing bytes
        # field 9 — proto3 forward compat.
        enc += bytes([7 << 3, 42])
        enc += bytes([(9 << 3) | 2, 3]) + b"xyz"
        assert _decode(bytes(enc)) == series

    def test_negative_timestamp_and_values(self):
        series = [({b"n": b"v"}, [(-5000, -1.5)])]
        assert _decode(pr.encode_write_request(series)) == series

    def test_read_request_decode(self):
        # Build a ReadRequest by hand: one query, [start, end], two matchers.
        q = bytearray()
        pr._put_uvarint(q, (1 << 3) | 0)
        pr._put_uvarint(q, 1700000000000)
        pr._put_uvarint(q, (2 << 3) | 0)
        pr._put_uvarint(q, 1700003600000)
        for mtype, name, value in ((0, b"__name__", b"up"), (2, b"job", b"a.*")):
            m = bytearray()
            pr._put_uvarint(m, (1 << 3) | 0)
            pr._put_uvarint(m, mtype)
            pr._put_field_bytes(m, 2, name)
            pr._put_field_bytes(m, 3, value)
            pr._put_field_bytes(q, 3, bytes(m))
        req = bytearray()
        pr._put_field_bytes(req, 1, bytes(q))
        queries = pr.decode_read_request(bytes(req))
        assert len(queries) == 1
        assert queries[0]["start_ms"] == 1700000000000
        assert queries[0]["end_ms"] == 1700003600000
        ms = queries[0]["matchers"]
        assert ms[0] == Matcher(MatchType.EQUAL, b"__name__", b"up")
        assert ms[1] == Matcher(MatchType.REGEXP, b"job", b"a.*")


@pytest.fixture
def coord():
    now = {"t": T0}
    db = Database(ShardSet(8), clock=lambda: now["t"])
    db.create_namespace(b"default", NamespaceOptions(),
                        index=NamespaceIndex(clock=lambda: now["t"]))
    c = run_embedded(db, kv_store=cluster_kv.MemStore(),
                     clock=lambda: now["t"])
    c._now = now
    yield c
    c.close()


def _post(url: str, body: bytes):
    req = urllib.request.Request(url, data=body, method="POST")
    req.add_header("Content-Type", "application/x-protobuf")
    req.add_header("Content-Encoding", "snappy")
    with urllib.request.urlopen(req) as resp:
        return resp.read(), dict(resp.headers)


class TestRemoteWriteRead:
    def test_remote_write_then_query(self, coord):
        t0_ms = T0 // 1_000_000
        series = [
            ({b"__name__": b"rw_metric", b"host": b"a"},
             [(t0_ms + i * 10_000, float(i)) for i in range(5)]),
            ({b"__name__": b"rw_metric", b"host": b"b"},
             [(t0_ms + i * 10_000, 10.0 + i) for i in range(5)]),
        ]
        body = pr.snappy_compress(pr.encode_write_request(series))
        coord._now["t"] = T0 + 60 * S
        _post(coord.endpoint + "/api/v1/prom/remote/write", body)
        blk = coord.engine.execute_range(
            "rw_metric", T0 + 20 * S, T0 + 50 * S, 10 * S)
        assert blk.n_series == 2
        assert np.nanmax(blk.values) == 14.0

    def test_remote_read_roundtrip(self, coord):
        t0_ms = T0 // 1_000_000
        series = [({b"__name__": b"rr_metric", b"i": b"x"},
                   [(t0_ms + i * 10_000, float(i) * 2) for i in range(4)])]
        coord._now["t"] = T0 + 60 * S
        _post(coord.endpoint + "/api/v1/prom/remote/write",
              pr.snappy_compress(pr.encode_write_request(series)))

        q = bytearray()
        pr._put_uvarint(q, (1 << 3) | 0)
        pr._put_uvarint(q, t0_ms)
        pr._put_uvarint(q, (2 << 3) | 0)
        pr._put_uvarint(q, t0_ms + 60_000)
        m = bytearray()
        pr._put_uvarint(m, (1 << 3) | 0)
        pr._put_uvarint(m, 0)
        pr._put_field_bytes(m, 2, b"__name__")
        pr._put_field_bytes(m, 3, b"rr_metric")
        pr._put_field_bytes(q, 3, bytes(m))
        req = bytearray()
        pr._put_field_bytes(req, 1, bytes(q))

        body, headers = _post(coord.endpoint + "/api/v1/prom/remote/read",
                              pr.snappy_compress(bytes(req)))
        assert headers.get("Content-Type") == "application/x-protobuf"
        raw = pr.snappy_decompress(body)
        # Decode ReadResponse: results=1 -> timeseries=1 (same shape as a
        # WriteRequest one level down).
        results = [_decode(bytes(v))
                   for f, w, v in pr._fields(memoryview(raw)) if f == 1]
        assert len(results) == 1 and len(results[0]) == 1
        tags, samples = results[0][0]
        assert tags[b"__name__"] == b"rr_metric"
        assert [v for _, v in samples] == [0.0, 2.0, 4.0, 6.0]

    def test_bad_body_is_400(self, coord):
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(coord.endpoint + "/api/v1/prom/remote/write", b"not snappy")
        assert ei.value.code == 400


# ------------------------------------------- the label memo == full decoder
#
# decode_write_request walks field headers, looks a TimeSeries' label block
# up by its bytes and decodes the samples in place. The full decoder is
# what it falls back on (_decode_timeseries over _fields): for any bytes at
# all both give the same rows, or both raise ProtoError.


def _full(data):
    return [pr._decode_timeseries(v)
            for f, wt, v in pr._fields(memoryview(data)) if f == 1 and wt == 2]


def _outcome(decode, data):
    try:
        return decode(data)
    except pr.ProtoError:
        return "ProtoError"


def _lenfield(key: int, data: bytes) -> bytes:
    out = bytearray()
    pr._put_field_bytes(out, key >> 3, data)
    assert out[0] == key
    return bytes(out)


def _label(name: bytes, value: bytes) -> bytes:
    return _lenfield(0x0A, _lenfield(0x0A, name) + _lenfield(0x12, value))


def _varint(v: int) -> bytes:
    out = bytearray()
    pr._put_uvarint(out, v & ((1 << 64) - 1))
    return bytes(out)


def _sample(t_ms: int, value: float) -> bytes:
    """As a sender writes it: value (fixed64), then timestamp (varint)."""
    return _lenfield(0x12, b"\x09" + np.float64(value).tobytes()
                     + b"\x10" + _varint(t_ms))


def _ts(*fields: bytes) -> bytes:
    return _lenfield(0x0A, b"".join(fields))


def _scrape(hosts, fields, t_ms=1_700_000_000_000):
    """benchmark/harness/promwire.py's shape: labels sorted by name, one
    sample a series, the label bytes the same in every scrape."""
    out = bytearray()
    for h in range(hosts):
        for f in range(fields):
            lab = {b"__name__": b"cpu_usage_%d" % f, b"hostname": b"host_%d" % h,
                   b"region": b"eu-central-1", b"rack": b"%d" % (h % 7),
                   b"os": b"Ubuntu16.04LTS", b"arch": b"x86"}
            out += _ts(*(_label(k, lab[k]) for k in sorted(lab)),
                       _sample(t_ms, float(h * fields + f)))
    return bytes(out)


L1, L2 = _label(b"__name__", b"up"), _label(b"job", b"api")
S1, S2 = _sample(1_700_000_000_000, 1.5), _sample(1_700_000_015_000, -2.0)

LAYOUTS = {
    # (a) what a sender sends
    "scrape-1x1": _scrape(1, 1),
    "scrape-20x10": _scrape(20, 10),
    "several-samples-a-series": _ts(L1, L2, S1, S2, S1),
    # (b) what the walk must hand to the full decoder, or read with care
    "label-after-sample": _ts(L1, S1, L2),
    "label-between-samples": _ts(L1, S1, L2, S2) + _ts(L1, L2, S1),
    "unknown-field-in-series": _ts(L1, L2, S1, b"\x18\x07"),
    "unknown-field-before-labels": _ts(b"\x1a\x02hi", L1, S1),
    "unknown-fields-at-top": b"\x38\x2a" + _ts(L1, S1) + b"\x4a\x03xyz"
                             + b"\x3d\x01\x02\x03\x04" + _ts(L2, S2)
                             + b"\x31" + bytes(8),
    "field-1-as-a-varint-at-top": b"\x08\x05" + _ts(L1, S1),
    "timeseries-key-in-two-bytes": b"\x8a\x00" + _ts(L1, S1)[1:],
    "label-key-in-two-bytes": _ts(b"\x8a\x00" + L1[1:], S1),
    "sample-key-in-two-bytes": _ts(L1, b"\x92\x00" + S1[1:]),
    "no-samples": _ts(L1, L2),
    "no-labels": _ts(S1, S2),
    "empty-series": _ts() + _ts(L1, S1) + _ts(),
    "empty-request": b"",
    "duplicate-label-names": _ts(_label(b"a", b"1"), _label(b"a", b"2"), S1),
    "empty-label-name": _ts(_label(b"", b"v"), _label(b"", b""), S1),
    "unsorted-no-name": _ts(_label(b"z", b"1"), _label(b"b", b"2"), S1),
    "two-byte-label-length": _ts(L1, _label(b"path", b"/" + b"x" * 300), S1),
    "three-byte-series-length": _ts(*(_label(b"l%d" % i, b"v" * 100)
                                      for i in range(180)), S1),
    "one-byte-series-length": _ts(_label(b"a", b"b"), S1),
    "label-with-an-unknown-field": _ts(
        _lenfield(0x0A, _lenfield(0x0A, b"n") + b"\x18\x01"
                  + _lenfield(0x12, b"v")), S1),
    "label-name-after-value": _ts(
        _lenfield(0x0A, _lenfield(0x12, b"v") + _lenfield(0x0A, b"n")), S1),
    "label-of-the-wrong-wire-type": _ts(b"\x08\x01", L1, S1),
    "value-left-out": _ts(L1, _lenfield(0x12, b"\x10" + _varint(77))),
    "timestamp-left-out": _ts(L1, _lenfield(0x12, b"\x09" + bytes(8))),
    "empty-sample": _ts(L1, _lenfield(0x12, b""), S1),
    "timestamp-before-value": _ts(L1, _lenfield(
        0x12, b"\x10" + _varint(5) + b"\x09" + np.float64(2.5).tobytes())),
    "sample-with-an-unknown-field": _ts(L1, _lenfield(
        0x12, S1[2:] + b"\x18\x01")),
    "sample-with-two-timestamps": _ts(L1, _lenfield(
        0x12, S1[2:] + b"\x10\x05")),
    "timestamp-with-a-padded-varint": _ts(L1, _lenfield(
        0x12, S1[2:12] + b"\x85\x80\x00")),
    "negative-timestamp": _ts(L1, _sample(-5000, -0.0)),
    "timestamp-of-eleven-bytes": _ts(L1, _lenfield(
        0x12, S1[2:12] + b"\xff" * 10 + b"\x01")),
    "timestamp-of-twelve-bytes": _ts(L1, _lenfield(
        0x12, S1[2:12] + b"\xff" * 11 + b"\x01")),
    "nan-and-infinities": _ts(L1, _sample(1, float("inf")),
                              _sample(2, float("-inf")), _sample(3, 5e-324)),
    "label-overruns-its-series": _ts(L1[:-1], S1) + _ts(L2, S2),
    "sample-overruns-its-series": b"\x0a" + bytes([len(L1) + len(S1) - 3]) + L1 + S1,
    "series-overruns-the-request": _ts(L1, S1)[:-4],
    "length-cut-short": _ts(L1, S1) + b"\x0a",
    "length-of-two-bytes-cut-short": _ts(L1, S1) + b"\x0a\x85",
    "an-unsupported-wire-type": _ts(L1, S1) + b"\x0b",
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_the_memo_route_decodes_what_the_full_decoder_decodes(name):
    data = LAYOUTS[name]
    want = _outcome(_full, data)
    memo = pr.LabelMemo(_series_id)
    first = _outcome(lambda d: _decode(d, memo), data)
    again = _outcome(lambda d: _decode(d, memo), data)   # now with hits
    assert first == want and again == want
    if name.startswith("scrape") or name == "several-samples-a-series":
        # every series of the second pass was a hit: the very objects
        assert len(memo) == len(want)
        assert all(a[0] is b[0] for a, b in zip(first, again))


def test_a_nan_keeps_its_bits():
    bits = 0x7FF80000DEADBEEF
    data = _ts(L1, _lenfield(0x12, b"\x09" + bits.to_bytes(8, "little")
                             + b"\x10\x01"))
    (_, [(_, got)]), (_, [(_, want)]) = _decode(data)[0], _full(data)[0]
    assert np.float64(got).view(np.uint64) == np.float64(want).view(np.uint64)


@pytest.mark.parametrize("seed", range(8))
def test_truncations_and_byte_flips_end_alike_on_both_routes(seed):
    """A memo that has seen the sound request (and everything this seed
    has broken so far) is held to the full decoder on every variant."""
    rng = np.random.default_rng(seed)
    sound = _scrape(6, 3) + LAYOUTS["several-samples-a-series"] \
        + LAYOUTS["value-left-out"] + LAYOUTS["two-byte-label-length"]
    memo = pr.LabelMemo(_series_id)
    assert _decode(sound, memo) == _full(sound)
    raised = 0
    for _ in range(300):
        data = bytearray(sound)
        for _ in range(int(rng.integers(1, 4))):
            at = int(rng.integers(len(data)))
            kind = int(rng.integers(4))
            if kind == 0:
                data[at] ^= 1 << int(rng.integers(8))
            elif kind == 1:
                data[at] = int(rng.integers(256))
            elif kind == 2:
                del data[at]
            else:
                del data[at:]
        data = bytes(data)
        want = _outcome(_full, data)
        assert _outcome(lambda d: _decode(d, memo), data) == want, data.hex()
        raised += want == "ProtoError"
    assert 0 < raised < 300      # the seed broke some and spared some
    assert _decode(sound, memo) == _full(sound)
