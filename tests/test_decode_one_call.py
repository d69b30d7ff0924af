"""One decode call's protocol (ops/tsz.py::decode_plane): one upload, one
program and one fetch, the planes handed back as views of the one fetched
buffer — bit-equal to ops/ref_codec on every route (the XLA scan, the
Pallas kernel in interpret mode, a row mesh, the lone-row doubling) and
at every row bucket. A file of its own beside tests/test_codec_pallas.py
so that the two run on two workers. That the device-side weave lays the
buffer out C-ordered on a TPU is chip_smoke.py's few_row_twin_faults."""

import functools

import numpy as np
import pytest

from m3_tpu.ops import ref_codec, tsz
from m3_tpu.ops.decode_rows import ROW_BUCKETS
from m3_tpu.utils import instrument


# One decode call's protocol: rows x values x time unit x route, the
# rows a lone row, a pair and every rung a caller's rows are padded to.
# The shapes past 64 rows run on the XLA route only (interpret mode pays
# wall time by the row).
_PROTO_ROWS = (1, 2) + ROW_BUCKETS
_PROTO_CASES = [
    (rows, kind, unit, route)
    for rows in _PROTO_ROWS
    for kind in ("whole", "decimal", "float")
    for unit in (1, 10**9, 60 * 10**9)
    for route in ("xla", "pallas")
    if route == "xla" or rows <= 64]


_PROTO_W = 16


def _proto_plane(rows, kind, unit, w=_PROTO_W):
    """[rows, w] ticks whose nanoseconds cross a low-word wrap inside the
    row (half the points either side of a multiple of 2^32), and values
    of one kind: whole numbers (int mode, k = 0), two decimals (int
    mode, k > 0: the host's fix-up) or floats."""
    rng = np.random.default_rng([rows, unit % 997, len(kind)])
    step = 10 if unit > 1 else 1 << 20
    wrap = (np.int64(7) << 32) // unit + 1
    ts = wrap - (w // 2) * step + np.arange(w, dtype=np.int64)[None, :] * step \
        + rng.integers(0, 2, (rows, 1))
    if unit == 1:
        lo = (ts.astype(np.uint64) & np.uint64(0xFFFFFFFF))
        assert (np.diff(lo.astype(np.int64), axis=1) < 0).any(axis=1).all()
    walk = np.cumsum(rng.integers(-3, 4, (rows, w)), 1) + 50
    if kind == "whole":
        vals = walk.astype(np.float64)
    elif kind == "decimal":
        vals = np.round(walk + rng.random((rows, w)), 2)
    else:
        vals = rng.normal(0, 1, (rows, w))
    return ts, vals


@functools.lru_cache(maxsize=None)
def _proto_encoded(kind, unit):
    """The largest case's plane, encoded once: encoding is row by row,
    so a case of fewer rows is its first rows (and one encode program
    serves every case). Every fifth row is three points short."""
    rows, w = max(_PROTO_ROWS), _PROTO_W
    ts, vals = _proto_plane(rows, kind, unit)
    npoints = np.full(rows, w, np.int32)
    npoints[::5] = w - 3
    words, _ = tsz.encode(ts, vals, npoints, max_words=tsz.max_words_for(w))
    return ts, vals, npoints, np.asarray(words)


@functools.lru_cache(maxsize=None)
def _proto_ref_row(kind, unit, r):
    _ts, _vals, npoints, words = _proto_encoded(kind, unit)
    t, v = ref_codec.decode(ref_codec.EncodedBlock(
        words=words[r], nbits=0, npoints=int(npoints[r])))
    return np.asarray(t) * unit, np.asarray(v).view(np.uint64)


class TestOneCallProtocol:
    """tsz.decode_plane is one upload, one program and one fetch, and the
    planes it hands back are views of the one fetched buffer: bit-equal
    to ops/ref_codec on every route and at every row bucket."""

    @staticmethod
    def _moved(before):
        now = {k: instrument.ROOT.counter(f"codec.decode.{k}").value()
               for k in ("calls", "fetches", "uploads")}
        return {k: now[k] - before.get(k, 0) for k in now}

    @pytest.mark.parametrize("rows,kind,unit,route", _PROTO_CASES)
    def test_bit_identical_contiguous_one_fetch(self, rows, kind, unit,
                                                route, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1" if route == "pallas" else "0")
        w = _PROTO_W
        ts, vals, npoints, words = (a[:rows]
                                    for a in _proto_encoded(kind, unit))
        c0 = self._moved({})
        tsp, vsp = tsz.decode_plane(words, npoints, window=w,
                                    unit_nanos=unit)
        assert self._moved(c0) == {"calls": 1, "fetches": 1, "uploads": 1}
        assert tsp.shape == vsp.shape == (rows, w)
        assert tsp.dtype == np.int64 and vsp.dtype == np.float64
        for r in range(rows):
            n = int(npoints[r])
            t_ref, v_ref = _proto_ref_row(kind, unit, r)
            np.testing.assert_array_equal(t_ref, tsp[r, :n])
            np.testing.assert_array_equal(v_ref, vsp[r, :n].view(np.uint64))
        np.testing.assert_array_equal(tsp[0, :w - 3], ts[0, :w - 3] * unit)
        np.testing.assert_array_equal(vsp[0, :w - 3], vals[0, :w - 3])
        fixed = kind == "decimal"
        for plane in (tsp, vsp):
            assert plane.flags.c_contiguous
            cut = plane[:max(rows - 1, 1)]
            assert cut.flags.c_contiguous and np.shares_memory(cut, plane)
        # timestamps and values are views of ONE fetched buffer; a k > 0
        # fix-up is the one thing that copies, and only the values
        assert np.shares_memory(tsp.base, vsp) is not fixed
        # inputs already on the device: used where they are, no upload
        import jax

        held = jax.device_put((words, npoints))
        c1 = self._moved({})
        tsd, vsd = tsz.decode_plane(*held, window=w, unit_nanos=unit)
        assert self._moved(c1) == {"calls": 1, "fetches": 1, "uploads": 0}
        np.testing.assert_array_equal(tsd, tsp)
        np.testing.assert_array_equal(vsd.view(np.uint64),
                                      vsp.view(np.uint64))

    @pytest.mark.parametrize("route", ["xla", "pallas"])
    def test_f32_is_a_fetch_of_its_own(self, route, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1" if route == "pallas" else "0")
        ts, vals = _proto_plane(8, "decimal", 1)
        words, _ = tsz.encode(ts, vals, max_words=tsz.max_words_for(16))
        c0 = self._moved({})
        _t, v, f32 = tsz.decode_plane(np.asarray(words), np.full(8, 16),
                                      window=16, with_f32=True)
        assert self._moved(c0) == {"calls": 1, "fetches": 2, "uploads": 1}
        np.testing.assert_array_equal(f32, v.astype(np.float32))
        np.testing.assert_array_equal(v, vals)

    @pytest.mark.parametrize("route", ["xla", "pallas"])
    @pytest.mark.parametrize("kind", ["whole", "decimal"])
    def test_rows_over_a_mesh_answer_in_the_same_shape(self, route, kind,
                                                       monkeypatch):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("one device: no row mesh")
        monkeypatch.setenv("M3_TPU_PALLAS", "1" if route == "pallas" else "0")
        n_dev = 4 if len(devs) >= 4 else 2
        rows, w, unit = 8 * n_dev, 16, 10**9
        ts, vals = _proto_plane(rows, kind, unit, w)
        npoints = np.full(rows, w, np.int32)
        words, _ = tsz.encode(ts, vals, max_words=tsz.max_words_for(w))
        words = np.asarray(words)
        mesh = Mesh(np.array(devs[:n_dev]), ("rows",))
        sharded = jax.device_put(words, NamedSharding(mesh, P("rows", None)))
        assert tsz._row_mesh(sharded) is not None
        c0 = self._moved({})
        tsm, vsm = tsz.decode_plane(sharded, npoints, window=w,
                                    unit_nanos=unit)
        assert self._moved(c0) == {"calls": 1, "fetches": 1, "uploads": 1}
        assert tsm.flags.c_contiguous and vsm.flags.c_contiguous
        np.testing.assert_array_equal(tsm, ts * unit)
        np.testing.assert_array_equal(vsm, vals)
