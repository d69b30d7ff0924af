"""Codec-kernel smoke: the <5s check_all tier for the Pallas bitstream
kernels (ops/pallas_codec.py) and their dispatch gate. Asserts, not
just times:

  1. with M3_TPU_PALLAS=1 every kernel actually DISPATCHES — the
     telemetry.codec.pallas_{encode,decode,hash} route counters must
     move (a silent fallback that still produces right answers would
     otherwise pass every parity test while benchmarking the wrong
     code);
  2. pack / fused-decode / hash outputs on the Pallas route are
     BIT-identical to the XLA/numpy twins and the scalar reference
     codec (ops/ref_codec.py) on a small production-mix corpus — the
     cheap always-on slice of tests/test_codec_pallas.py;
  3. the kill switch (M3_TPU_PALLAS=0) routes back to XLA, counted on
     the xla_* route counters.

The corpus stays tiny (interpret mode on CPU is orders of magnitude
slower than compiled Mosaic); wall budget via CODEC_SMOKE_BUDGET_S.

Usage: JAX_PLATFORMS=cpu python scripts/codec_smoke.py
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Force the Pallas route BEFORE any m3_tpu import resolves the gate.
os.environ["M3_TPU_PALLAS"] = "1"

from m3_tpu.ops import pallas_codec, ref_codec, tsz  # noqa: E402
from m3_tpu.parallel import telemetry  # noqa: E402
from m3_tpu.utils import hashing  # noqa: E402

BUDGET_S = float(os.environ.get("CODEC_SMOKE_BUDGET_S", "30"))


def _counter(name: str) -> int:
    return int(telemetry.snapshot().get(f"telemetry.codec.{name}", 0))


def _corpus(rng, n, w):
    ts = (1_700_000_000 + np.arange(w, dtype=np.int64)[None, :] * 10
          + rng.integers(0, 2, (n, w)))
    ts = np.sort(ts, axis=1)
    vals = rng.normal(100, 5, (n, w))
    vals[rng.random((n, w)) < 0.1] = np.nan      # NaN holes
    vals[: n // 4] = np.round(vals[: n // 4], 2)  # scaled-int rows
    vals[n // 4] = 7.0                            # constant row
    npoints = rng.integers(1, w + 1, n).astype(np.int32)
    npoints[0] = 0
    npoints[1] = 1
    npoints[2] = w
    return ts, vals, npoints


def main() -> int:
    t_start = time.perf_counter()
    assert pallas_codec.enabled(), "M3_TPU_PALLAS=1 must enable the gate"
    rng = np.random.default_rng(7)
    n, w = 16, 32
    ts, vals, npoints = _corpus(rng, n, w)
    mw = tsz.max_words_for(w)

    # 1+2. encode: pallas pack dispatches and is bit-identical to scatter
    inp = tsz.prepare_encode_inputs(ts, vals, npoints)
    kw = dict(dt=inp["dt"], t0=inp["t0"], vhi=inp["vhi"], vlo=inp["vlo"],
              int_mode=inp["int_mode"], k=inp["k"],
              npoints=inp["npoints"], ts_regular=inp["ts_regular"],
              delta0=inp["delta0"])
    enc0 = _counter("pallas_encode")
    wp, nbp = tsz.encode_batch(**kw, max_words=mw)  # gate picks pallas
    assert _counter("pallas_encode") == enc0 + 1, \
        "pallas_encode route counter did not move — encode fell back"
    ws, nbs = tsz.encode_batch(**kw, max_words=mw, pack="scatter")
    assert np.array_equal(np.asarray(wp), np.asarray(ws)), \
        "pallas pack != scatter pack (words)"
    assert np.array_equal(np.asarray(nbp), np.asarray(nbs)), \
        "pallas pack != scatter pack (nbits)"
    words = np.asarray(wp)

    # 1+2. decode: fused plane on the pallas route, vs the scalar oracle
    dec0 = _counter("pallas_decode")
    tsp, vsp = tsz.decode_plane(words, npoints, window=w, unit_nanos=10**9)
    assert _counter("pallas_decode") == dec0 + 1, \
        "pallas_decode route counter did not move — decode fell back"
    for r in range(n):
        m = int(npoints[r])
        if m == 0:
            continue
        t_ref, v_ref = ref_codec.decode(ref_codec.EncodedBlock(
            words=words[r], nbits=0, npoints=m))
        assert np.array_equal(t_ref * 10**9, np.asarray(tsp[r, :m])), \
            f"decode ts mismatch row {r}"
        assert np.array_equal(np.asarray(v_ref).view(np.uint64),
                              np.asarray(vsp[r, :m]).view(np.uint64)), \
            f"decode value bits mismatch row {r}"

    # 1+2. hash: lane-parallel murmur3 dispatches, vs the scalar hash
    ids = [bytes(rng.integers(0, 256, ln, dtype=np.uint8))
           for ln in list(rng.integers(1, 40, 100)) + [1, 2, 3, 4]]
    h0 = _counter("pallas_hash")
    hb = hashing.hash_batch(ids)
    assert _counter("pallas_hash") == h0 + 1, \
        "pallas_hash route counter did not move — hash fell back"
    ref = np.array([hashing.murmur3_32(i) for i in ids], np.uint32)
    assert np.array_equal(hb, ref), "pallas hash != scalar murmur3"

    # 3. kill switch: =0 routes everything back to XLA, and is counted
    os.environ["M3_TPU_PALLAS"] = "0"
    try:
        x0 = _counter("xla_decode")
        ts2, vs2 = tsz.decode_plane(words, npoints, window=w,
                                    unit_nanos=10**9)
        assert _counter("xla_decode") == x0 + 1, \
            "xla_decode route counter did not move under the kill switch"
        assert np.array_equal(np.asarray(tsp), np.asarray(ts2))
        assert np.array_equal(np.asarray(vsp).view(np.uint64),
                              np.asarray(vs2).view(np.uint64))
    finally:
        os.environ["M3_TPU_PALLAS"] = "1"

    compiles = _counter("compiles")
    wall = time.perf_counter() - t_start
    print(f"CODEC SMOKE PASS: {n}x{w} corpus, {len(ids)} ids, "
          f"{compiles} kernel compiles, routes proven "
          f"(pallas encode/decode/hash + xla kill-switch), {wall:.1f}s")
    if wall > BUDGET_S:
        print(f"CODEC SMOKE FAIL: wall {wall:.1f}s > budget {BUDGET_S}s",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
