"""Randomized TTSZ codec campaign — the fuzz tier for the flagship kernel.

Each round draws an adversarial workload (the unit tests' production mix
PLUS wild f64 bit patterns, wide-header t0/delta0/v0 magnitudes, ragged
1..w point counts, NaN holes) and asserts, per shape bucket:

  1. batched encode (both packers) -> decode is BIT-exact on timestamps
     and value bit patterns (sign of zero and NaN payloads included);
  2. a random subsample of series is bit-exact vs the scalar oracle
     (m3_tpu/ops/ref_codec.py) — stream words and nbits;
  3. seal/concat merge equivalence: the workload split into two sealed
     half-blocks, merged through the eligibility partition
     (tsz_concat.concat_regular_batch for the regular fast path,
     _merge_by_recode for the rest), decodes to the original points, and
     int-mode concat outputs are bit-identical to directly encoding the
     full window.

Shapes are drawn from a bounded bucket set so XLA compiles each program
once per campaign and the rounds vary DATA, not trace shapes (on TPU a
fresh shape costs a 20-40s compile; on CPU seconds — either way the
budget goes to inputs, not recompiles).

Usage:
    python scripts/fuzz_codec.py --rounds 150 --seed 1      # CPU or TPU
    JAX_PLATFORMS=cpu python scripts/fuzz_codec.py ...      # force host

Reference analog: the reference fuzzes its codec with generative
roundtrip property tests (src/dbnode/encoding/m3tsz/roundtrip_test.go);
this campaign is the batched-kernel equivalent with the merge path
folded in.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from m3_tpu.ops import bits64 as b64  # noqa: E402
from m3_tpu.ops import ref_codec as rc  # noqa: E402
from m3_tpu.ops import tsz  # noqa: E402
from m3_tpu.ops import tsz_concat  # noqa: E402

# (n series, window) buckets: one compile each, all rounds reuse them.
SHAPES = [(64, 16), (128, 60), (96, 120), (48, 240)]


def make_adversarial(rng, n, w):
    """The unit-test production mix plus hostile kinds."""
    base = np.int64(rng.choice([1_700_000_000, 2**40, -(2**40), 7]))
    step = int(rng.choice([1, 10, 1 << 20]))
    ts = base + np.arange(w, dtype=np.int64)[None, :] * step \
        + rng.integers(0, 2, (n, w))
    ts = np.sort(ts, axis=1)
    kinds = rng.integers(0, 8, n)
    vals = np.empty((n, w), dtype=np.float64)
    for i in range(n):
        k = kinds[i]
        if k == 0:  # counter
            vals[i] = np.cumsum(rng.poisson(5.0, w)).astype(np.float64)
        elif k == 1:  # gauge, 2dp
            vals[i] = np.round(rng.normal(100, 5, w), 2)
        elif k == 2:  # constant
            vals[i] = float(rng.integers(0, 100))
        elif k == 3:  # raw float noise
            vals[i] = rng.normal(0, 1, w)
        elif k == 4:  # sparse NaN gauge
            vals[i] = np.where(rng.random(w) < 0.05, np.nan,
                               np.round(rng.normal(10, 1, w), 3))
        elif k == 5:  # huge integers (wide int-mode headers)
            vals[i] = (float(2**40) + np.cumsum(
                rng.integers(0, 5, w))).astype(np.float64)
        elif k == 6:  # signed zeros and tiny denormals
            picks = rng.integers(0, 4, w)
            vals[i] = np.choose(picks, [0.0, -0.0, 5e-324, -5e-324])
        else:  # wild raw f64 bit patterns (incl. infs, NaN payloads)
            vals[i] = rng.integers(0, 2**64, w, dtype=np.uint64).view(
                np.float64)
    return ts, vals


def assert_bits_equal(a, b, msg):
    ab = np.asarray(a, np.float64).view(np.uint64)
    bb = np.asarray(b, np.float64).view(np.uint64)
    if not (ab == bb).all():
        bad = np.argwhere(ab != bb)
        raise AssertionError(f"{msg}: first mismatch at {bad[0]}: "
                             f"{ab[tuple(bad[0])]:#x} != {bb[tuple(bad[0])]:#x}")


@functools.lru_cache(maxsize=None)
def _encoder(w, pack):
    import jax

    return jax.jit(functools.partial(
        tsz.encode_batch, max_words=tsz.max_words_for(w), pack=pack))


def run_round(rng, n, w, oracle_sample=6):
    ts, vals = make_adversarial(rng, n, w)
    # Exactly one quarter full-window (the merge-phase input), the rest
    # strictly ragged: the per-bucket SHAPES stay identical across
    # rounds, so XLA compiles each program once for the whole campaign.
    npoints = rng.integers(1, w, n).astype(np.int32)
    npoints[: n // 4] = w
    inp = tsz.prepare_encode_inputs(ts, vals, npoints)
    args = (inp["dt"], inp["t0"], inp["vhi"], inp["vlo"], inp["int_mode"],
            inp["k"], inp["npoints"], inp["ts_regular"], inp["delta0"])
    # The Pallas pack kernel joins the parity set only when the dispatch
    # switch is on (M3_TPU_PALLAS=1): interpret mode on CPU is orders of
    # magnitude slower than the XLA packers, so default campaigns keep
    # their round budget on data variation.
    from m3_tpu.ops import pallas_codec
    pack_names = ("scatter", "tree") + (
        ("pallas",) if pallas_codec.enabled() else ())
    packs = {}
    for pack in pack_names:
        words, nbits = _encoder(w, pack)(*args)
        packs[pack] = (np.asarray(words), np.asarray(nbits))
    (words, nbits) = packs["scatter"]
    for other in pack_names[1:]:
        assert np.array_equal(words, packs[other][0]), \
            f"packers disagree ({other}): words"
        assert np.array_equal(nbits, packs[other][1]), \
            f"packers disagree ({other}): nbits"

    # 1. roundtrip, bit-exact (padding beyond npoints is unspecified)
    t2, v2 = tsz.decode(words, npoints, w)
    for i in range(n):
        m = npoints[i]
        assert np.array_equal(ts[i, :m], t2[i, :m]), f"ts roundtrip s{i}"
        assert_bits_equal(vals[i, :m], v2[i, :m], f"vals roundtrip s{i}")

    # 2. oracle parity on a subsample
    for i in rng.choice(n, size=min(oracle_sample, n), replace=False):
        blk = rc.encode(ts[i, : npoints[i]], vals[i, : npoints[i]])
        assert nbits[i] == blk.nbits, f"oracle nbits s{i}"
        nwords = (blk.nbits + 31) // 32
        assert np.array_equal(words[i, :nwords], blk.words), f"oracle words s{i}"

    # 3. seal/concat merge equivalence on the full-window quarter
    full = np.flatnonzero(npoints == w)
    if w >= 4 and w % 2 == 0 and full.size:
        _merge_check(ts[full], vals[full], w)
    return n


def _half_inputs(inp, ts, lo, hi):
    """Slice the FULL-window prepared columns for one sealed half — the
    seal-time contract the storage layer and bench follow: mantissa
    columns (vhi/vlo) and the int-mode/k decision come from the full
    window's preparation, so both halves and the direct full-window
    encode agree on the value path; only the timestamp head fields
    (t0, delta0, ts_regular) are per-half."""
    n = len(ts)
    dt = np.asarray(inp["dt"])[:, lo:hi].copy()
    dt[:, 0] = 0
    t0 = b64.from_u64_np(ts[:, lo].astype(np.int64))
    delta0 = dt[:, 1].copy() if hi - lo > 1 else np.zeros(n, dt.dtype)
    ts_regular = ((dt[:, 1:] == delta0[:, None]).all(axis=1)
                  if hi - lo > 1 else np.ones(n, bool))
    return (dt, t0, np.asarray(inp["vhi"])[:, lo:hi],
            np.asarray(inp["vlo"])[:, lo:hi], np.asarray(inp["int_mode"]),
            np.asarray(inp["k"]), np.full(n, hi - lo, np.int32),
            ts_regular, delta0)


def _merge_check(ts, vals, w):
    n, half = len(ts), w // 2
    npts = np.full(n, w, np.int32)
    inp = tsz.prepare_encode_inputs(ts, vals, npts)
    int_mode = np.asarray(inp["int_mode"])
    enc = _encoder(half, "scatter")
    h1 = _half_inputs(inp, ts, 0, half)
    h2 = _half_inputs(inp, ts, half, w)
    w1, nb1 = map(np.asarray, enc(*h1))
    w2, nb2 = map(np.asarray, enc(*h2))
    npts_half = np.full(n, half, np.int32)
    boundary = (ts[:, half] - ts[:, half - 1]).astype(np.int32)

    bmeta = tsz.boundary_metadata({
        "dt": h1[0], "t0": h1[1], "vhi": h1[2], "vlo": h1[3],
        "int_mode": int_mode, "npoints": npts_half})
    last_v = b64.from_u64_np(bmeta["last_v_bits"])
    last_vd = b64.from_u64_np(bmeta["last_vdelta_bits"])

    hdr1, hdr2 = tsz_concat.parse_header(w1), tsz_concat.parse_header(w2)
    ok = np.asarray(tsz_concat.concat_eligible(
        hdr1, hdr2, npts_half, npts_half, boundary))
    fast, slow = np.flatnonzero(ok), np.flatnonzero(~ok)
    mw_full = tsz.max_words_for(w)
    merged_w = np.zeros((n, mw_full), np.uint32)
    merged_nb = np.zeros(n, np.int32)

    def _padded(idx):
        # Pad every partition to the full n rows (repeating the first
        # index) so both merge programs keep ONE compile per bucket
        # instead of one per (round, partition-size); callers slice the
        # outputs back to idx.size.
        return np.concatenate(
            [idx, np.full(n - idx.size, idx[0], idx.dtype)])

    if fast.size:
        p = _padded(fast)
        fw, fnb = tsz_concat.concat_regular_batch(
            w1[p], nb1[p], npts_half[p], w2[p], nb2[p], npts_half[p],
            tuple(a[p] for a in last_v),
            tuple(a[p] for a in last_vd), max_words=mw_full)
        merged_w[fast] = np.asarray(fw)[: fast.size]
        merged_nb[fast] = np.asarray(fnb)[: fast.size]
    if slow.size:
        p = _padded(slow)
        sw, snb = tsz_concat._merge_by_recode(
            w1[p], npts_half[p], w2[p], npts_half[p],
            boundary[p], half_window=half, max_words=mw_full)
        merged_w[slow] = np.asarray(sw)[: slow.size]
        merged_nb[slow] = np.asarray(snb)[: slow.size]
    dts, dv = tsz.decode(merged_w, npts, window=w)
    assert np.array_equal(dts, ts), "merge ts decode"
    assert_bits_equal(vals, dv, "merge vals decode")
    # int-mode concat streams must equal the direct full-window encode
    int_fast = fast[int_mode[fast]]
    if int_fast.size:
        ref_w, ref_nb = map(np.asarray, _encoder(w, "scatter")(
            inp["dt"], inp["t0"], inp["vhi"], inp["vlo"], inp["int_mode"],
            inp["k"], inp["npoints"], inp["ts_regular"], inp["delta0"]))
        assert np.array_equal(merged_nb[int_fast], ref_nb[int_fast]), \
            "concat nbits != direct encode"
        assert np.array_equal(merged_w[int_fast], ref_w[int_fast]), \
            "concat words != direct encode"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    import jax

    backend = jax.default_backend()
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    total = 0
    for r in range(args.rounds):
        n, w = SHAPES[r % len(SHAPES)]
        total += run_round(rng, n, w)
        if (r + 1) % 10 == 0:
            print(f"  round {r + 1}/{args.rounds} "
                  f"({total} series checked, {time.time() - t0:.0f}s)",
                  flush=True)
    print(f"FUZZ PASS: {args.rounds} rounds, {total} series, backend "
          f"{backend}, seed {args.seed}, {time.time() - t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
