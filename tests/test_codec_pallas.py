"""Pallas codec kernels (ops/pallas_codec.py): interpret-mode parity
against the XLA paths and the scalar reference codec.

This file is the `_PALLAS_ORACLE` the m3lint unguarded-pallas-dispatch
rule points at: every kernel (pack / decode / hash) is asserted
BIT-identical to its XLA twin and to ops/ref_codec.py over a property
corpus covering the codec's hostile regions — NaN holes, rewrite-window
churn past REWRITE_THRESHOLD, int/float mode mixes, wild f64 bit
patterns, and npoints 0/1 edges. On CPU the kernels run in interpret
mode: this proves the algebra. That they BUILD for a TPU is
tests/test_pallas_lowering.py; that the compiled kernels produce these
same bits on the chip is chip_smoke.py's phase_codec_twins."""

import os

import numpy as np
import pytest

from m3_tpu.ops import pallas_codec, ref_codec, tsz
from m3_tpu.parallel import telemetry
from m3_tpu.utils import hashing


def _corpus(seed, n, w):
    """Production mix + hostile kinds (fuzz_codec's adversarial menu,
    bounded so interpret mode stays inside the test budget)."""
    rng = np.random.default_rng(seed)
    base = np.int64(rng.choice([1_700_000_000, 2**40, 7]))
    step = int(rng.choice([1, 10, 1 << 20]))
    ts = base + np.arange(w, dtype=np.int64)[None, :] * step \
        + rng.integers(0, 2, (n, w))
    ts = np.sort(ts, axis=1)
    vals = np.empty((n, w), np.float64)
    for i in range(n):
        k = i % 7
        if k == 0:  # counter (int mode)
            vals[i] = np.cumsum(rng.poisson(5.0, w)).astype(np.float64)
        elif k == 1:  # gauge 2dp (scaled-int mode)
            vals[i] = np.round(rng.normal(100, 5, w), 2)
        elif k == 2:  # raw float noise: rewrite-window churn, every
            # XOR exceeds REWRITE_THRESHOLD reuse early on
            vals[i] = rng.normal(0, 1, w)
        elif k == 3:  # sparse NaN holes
            vals[i] = np.where(rng.random(w) < 0.1, np.nan,
                               np.round(rng.normal(10, 1, w), 3))
        elif k == 4:  # constant (zero XORs)
            vals[i] = float(rng.integers(0, 100))
        elif k == 5:  # signed zeros + denormals
            picks = rng.integers(0, 4, w)
            vals[i] = np.choose(picks, [0.0, -0.0, 5e-324, -5e-324])
        else:  # wild raw f64 bit patterns (infs, NaN payloads)
            vals[i] = rng.integers(0, 2**64, w, dtype=np.uint64).view(
                np.float64)
    npoints = rng.integers(1, w + 1, n).astype(np.int32)
    npoints[0] = 0
    npoints[1] = 1
    npoints[2] = w
    return ts, vals, npoints


def _encode_args(ts, vals, npoints):
    inp = tsz.prepare_encode_inputs(ts, vals, npoints)
    return dict(dt=inp["dt"], t0=inp["t0"], vhi=inp["vhi"],
                vlo=inp["vlo"], int_mode=inp["int_mode"], k=inp["k"],
                npoints=inp["npoints"], ts_regular=inp["ts_regular"],
                delta0=inp["delta0"])


def _assert_ref_parity(words, npoints, ts_plane, vs_plane, unit_nanos):
    words = np.asarray(words)
    for r in range(words.shape[0]):
        n = int(npoints[r])
        if n == 0:
            continue
        t_ref, v_ref = ref_codec.decode(ref_codec.EncodedBlock(
            words=words[r], nbits=0, npoints=n))
        np.testing.assert_array_equal(t_ref * unit_nanos,
                                      np.asarray(ts_plane[r, :n]))
        np.testing.assert_array_equal(
            np.asarray(v_ref).view(np.uint64),
            np.asarray(vs_plane[r, :n]).view(np.uint64))


SHAPES = [(16, 16), (24, 64)]


class TestPackParity:
    @pytest.mark.parametrize("n,w", SHAPES)
    def test_pallas_pack_bit_identical_to_both_xla_packers(self, n, w):
        ts, vals, npoints = _corpus(97 + w, n, w)
        kw = _encode_args(ts, vals, npoints)
        mw = tsz.max_words_for(w)
        outs = {p: tsz.encode_batch(**kw, max_words=mw, pack=p)
                for p in ("pallas", "scatter", "tree")}
        for p in ("scatter", "tree"):
            np.testing.assert_array_equal(
                np.asarray(outs["pallas"][0]), np.asarray(outs[p][0]),
                err_msg=f"pallas vs {p}: words")
            np.testing.assert_array_equal(
                np.asarray(outs["pallas"][1]), np.asarray(outs[p][1]),
                err_msg=f"pallas vs {p}: nbits")

    def test_pallas_pack_drop_semantics_match_scatter(self):
        # an undersized max_words drops the SAME bits on both packers
        ts, vals, npoints = _corpus(3, 16, 64)
        kw = _encode_args(ts, vals, npoints)
        mw = tsz.max_words_for(64) // 2
        wp, _ = tsz.encode_batch(**kw, max_words=mw, pack="pallas")
        ws, _ = tsz.encode_batch(**kw, max_words=mw, pack="scatter")
        np.testing.assert_array_equal(np.asarray(wp), np.asarray(ws))


class TestDecodeParity:
    @pytest.mark.parametrize("n,w", SHAPES)
    def test_decode_core_matches_xla_every_key(self, n, w):
        ts, vals, npoints = _corpus(11 + w, n, w)
        words, _ = tsz.encode(ts, vals, max_words=tsz.max_words_for(w))
        words = np.asarray(words)
        pc = pallas_codec.decode_core(words, npoints, window=w)
        xc = tsz._decode_core(words, npoints, window=w)
        assert set(pc) == set(xc)
        for key in xc:
            np.testing.assert_array_equal(
                np.asarray(pc[key]), np.asarray(xc[key]),
                err_msg=f"decode_core key {key!r}")

    def test_fused_decode_plane_vs_ref_codec(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        ts, vals, npoints = _corpus(5, 24, 64)
        words, _ = tsz.encode(ts, vals, max_words=tsz.max_words_for(64))
        tsp, vsp = tsz.decode_plane(np.asarray(words), npoints,
                                    window=64, unit_nanos=10**9)
        _assert_ref_parity(words, npoints, tsp, vsp, 10**9)

    def test_pallas_roundtrip_vs_ref_codec(self, monkeypatch):
        # pallas pack -> pallas decode, judged against the scalar oracle
        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        ts, vals, npoints = _corpus(7, 16, 16)
        kw = _encode_args(ts, vals, npoints)
        words, _ = tsz.encode_batch(**kw, max_words=tsz.max_words_for(16),
                                    pack="pallas")
        tsp, vsp = tsz.decode_plane(np.asarray(words), npoints,
                                    window=16, unit_nanos=1)
        _assert_ref_parity(words, npoints, tsp, vsp, 1)


class TestHashParity:
    def test_hash_words_matches_scalar_murmur3(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        rng = np.random.default_rng(13)
        ids = [bytes(rng.integers(0, 256, ln, dtype=np.uint8))
               for ln in list(rng.integers(1, 40, 200)) + [1, 2, 3, 4, 5]]
        got = hashing.hash_batch(ids)
        ref = np.array([hashing.murmur3_32(i) for i in ids], np.uint32)
        np.testing.assert_array_equal(got, ref)

    def test_hash_batch_empty_and_oversize_fall_back(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        assert hashing.hash_batch([]).shape == (0,)
        big = [b"x" * (4 * pallas_codec.HASH_MAX_COLS + 8)]
        assert int(hashing.hash_batch(big)[0]) == hashing.murmur3_32(big[0])


class TestDispatchGate:
    def test_env_semantics(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        assert pallas_codec.enabled() is True
        monkeypatch.setenv("M3_TPU_PALLAS", "0")
        assert pallas_codec.enabled() is False
        monkeypatch.delenv("M3_TPU_PALLAS")
        import jax
        assert pallas_codec.enabled() is (jax.default_backend() == "tpu")

    def test_route_counters_prove_dispatch(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        before = telemetry.snapshot().get(
            "telemetry.codec.pallas_decode", 0)
        ts, vals, npoints = _corpus(17, 16, 16)
        words, _ = tsz.encode(ts, vals, max_words=tsz.max_words_for(16))
        tsz.decode_plane(np.asarray(words), npoints, window=16,
                         unit_nanos=1)
        after = telemetry.snapshot().get(
            "telemetry.codec.pallas_decode", 0)
        assert after == before + 1

    def test_kill_switch_routes_to_xla(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "0")
        before = telemetry.snapshot().get("telemetry.codec.xla_decode", 0)
        ts, vals, npoints = _corpus(19, 16, 16)
        words, _ = tsz.encode(ts, vals, max_words=tsz.max_words_for(16))
        tsz.decode_plane(np.asarray(words), npoints, window=16,
                         unit_nanos=1)
        after = telemetry.snapshot().get("telemetry.codec.xla_decode", 0)
        assert after == before + 1


class TestCursorOverflow:
    def test_encode_block_raises_on_undersized_bound(self):
        from m3_tpu.storage import block as blk
        ts, vals, npoints = _corpus(23, 16, 64)
        npoints = np.maximum(npoints, 1)
        with pytest.raises(tsz.CursorOverflowError):
            blk.encode_block(0, np.arange(16), ts * 10**9, vals, npoints,
                             max_words=2)

    def test_encode_raises_on_undersized_bound(self):
        ts, vals, npoints = _corpus(31, 16, 64)
        with pytest.raises(tsz.CursorOverflowError):
            tsz.encode(ts, vals, max_words=2)

    def test_max_words_for_is_sufficient(self):
        # the derived bound never trips the overflow check
        ts, vals, npoints = _corpus(29, 16, 16)
        words, nbits = tsz.encode(ts, vals,
                                  max_words=tsz.max_words_for(16))
        assert int(np.max(np.asarray(nbits))) <= 32 * tsz.max_words_for(16)


class TestGuardRouteMatrix:
    """The M3_TPU_PALLAS route matrix under the guard's per-kernel kill
    switches: guard.set_disabled("codec.<kernel>") flips each codec
    kernel's route independently, MID-PROCESS (no env churn, no cache
    surgery — the route pickers resolve outside jit per call), with the
    route counters proving the dispatch actually moved and bit-identity
    holding on both sides of every flip."""

    KERNELS = ("encode", "decode", "hash")

    @pytest.fixture(autouse=True)
    def _clean_guard(self):
        from m3_tpu.parallel import guard
        guard.reset()
        yield
        guard.reset()

    def _counts(self):
        snap = telemetry.snapshot()
        return {k: snap.get(f"telemetry.codec.{k}", 0)
                for k in ("pallas_encode", "xla_encode", "pallas_decode",
                          "xla_decode", "pallas_hash", "xla_hash")}

    @staticmethod
    def _bits(a):
        a = np.asarray(a)
        return a.view(np.uint64) if a.dtype == np.float64 else a

    def test_per_kernel_kill_switch_matrix(self, monkeypatch):
        from m3_tpu.parallel import guard
        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        ts, vals, npoints = _corpus(41, 16, 16)
        kw = _encode_args(ts, vals, npoints)
        mw = tsz.max_words_for(16)
        rng = np.random.default_rng(43)
        ids = [bytes(rng.integers(0, 256, ln, dtype=np.uint8))
               for ln in rng.integers(1, 33, 64)]

        def run_all():
            words, nbits = tsz.encode_batch(**kw, max_words=mw)
            tsp, vsp = tsz.decode_plane(np.asarray(words), npoints,
                                        window=16, unit_nanos=1)
            return (np.asarray(words), np.asarray(nbits),
                    np.asarray(tsp), np.asarray(vsp),
                    hashing.hash_batch(ids))

        base = self._counts()
        ref = run_all()  # all three kernels on the pallas route
        after = self._counts()
        for kern in self.KERNELS:
            assert after[f"pallas_{kern}"] == base[f"pallas_{kern}"] + 1

        for kern in self.KERNELS:  # flip ONE switch at a time
            guard.set_disabled(f"codec.{kern}", True)
            before = self._counts()
            got = run_all()
            now = self._counts()
            # the killed kernel re-routed to its XLA/host twin...
            assert now[f"xla_{kern}"] == before[f"xla_{kern}"] + 1
            # ...the other two kept their pallas route (independence)...
            for other in self.KERNELS:
                if other != kern:
                    assert now[f"pallas_{other}"] == \
                        before[f"pallas_{other}"] + 1, (kern, other)
            # ...and every output is bit-identical across the flip.
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(self._bits(a),
                                              self._bits(b), err_msg=kern)
            guard.set_disabled(f"codec.{kern}", False)

        before = self._counts()  # all switches restored: pallas again
        got = run_all()
        now = self._counts()
        for kern in self.KERNELS:
            assert now[f"pallas_{kern}"] == before[f"pallas_{kern}"] + 1
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(self._bits(a), self._bits(b))


class TestFewRowPlanes:
    """Planes of 1-16 rows on the Pallas route (interpret mode here; the
    same function runs compiled in chip_smoke.py's codec twins): equal
    to the XLA twin and to the written samples over block starts either
    side of many low-word wraps, and encoded alone as among sixteen."""

    @pytest.mark.parametrize("rows", [1, 2, 3, 5, 8, 16])
    def test_few_rows_equal_twin_and_written(self, rows, monkeypatch):
        import chip_smoke

        monkeypatch.setenv("M3_TPU_PALLAS", "1")
        before = telemetry.snapshot().get("telemetry.codec.pallas_decode", 0)
        assert chip_smoke.few_row_twin_faults(32 + rows, rows=(rows,),
                                              starts=3) == []
        assert telemetry.snapshot().get(
            "telemetry.codec.pallas_decode", 0) > before

    @pytest.mark.parametrize("route,rows_built", [("pallas", 2), ("xla", 1)])
    def test_a_lone_row_goes_twice_on_the_pallas_route_only(
            self, route, rows_built, monkeypatch):
        monkeypatch.setenv("M3_TPU_PALLAS", "1" if route == "pallas" else "0")
        ts, vals, npoints = _corpus(3, 4, 16)
        words, _ = tsz.encode(ts, vals, max_words=tsz.max_words_for(16))
        words = np.asarray(words)
        built = []
        real = tsz._decode_fused_jit

        def spy(*key):
            run = real(*key)
            return lambda w, n: (built.append((key[3], w.shape[0])),
                                 run(w, n))[1]

        monkeypatch.setattr(tsz, "_decode_fused_jit", spy)
        one = tsz.decode_plane(words[2:3], npoints[2:3], window=16,
                               unit_nanos=1, with_f32=True)
        assert built == [(route, rows_built)]
        monkeypatch.setattr(tsz, "_decode_fused_jit", real)
        four = tsz.decode_plane(words, npoints, window=16, unit_nanos=1,
                                with_f32=True)
        for a, b in zip(one, four):
            assert a.shape[0] == 1
            np.testing.assert_array_equal(a.view(np.uint8),
                                          b[2:3].view(np.uint8))
