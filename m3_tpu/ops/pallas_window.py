"""Pallas TPU kernel for strided sliding-window moments — the
*_over_time hot loop (reference: src/query/functions/temporal/
aggregation.go walks a per-series iterator per step; the XLA path in
ops/temporal.py reduces EVERY window with reduce_window and strides the
result AFTER, paying W work per grid cell even when the query step only
needs every stride-th window).

This kernel computes exactly the strided windows: one grid program per
8-row tile keeps its [8, K] slice of the residual grid in VMEM and loops
the T_out output steps, each reducing its [8, W] window slice on the VPU
and storing one output lane. Work drops from O(S*K*W) to
O(S*T_out*W) = O(S*K*W/stride), and the stat+count pair comes out of one
launch (the XLA path builds a separate masked volume per moment).

Semantics match temporal._window_stat (masked by finiteness, m2 in the
two-pass mean-then-deviation form that survives f32): the parity tests
run both over the same grids, NaN holes included; accumulated stats
(sum/m2) may differ from the XLA path by reduction-order ULPs.

STATUS: opt-in only (M3_TPU_PALLAS=1). The one timing ever taken of it
on hardware (July 2026, 10k x 438 grid, W=30, stride=3, a toolchain and
chip access that no longer exist) had it slower than the XLA path on
every stat; on the current installation it is known to BUILD for a v5e
(tests/test_pallas_lowering.py) and its speed on the chip is not
measured. The plausible reason it would lose again: each output step
reduces an [8, W] tile that fills 30 of 128 VPU lanes and pays a layout
change for its unaligned static offset, while XLA's fused reduce_window
streams full [8, 128] tiles. ROADMAP C4 decides whether it stays.
Its structure became the template for the codec kernels
(ops/pallas_codec.py): they inherit the VMEM-tiling half (lane-tiled
BlockSpecs, lru_cached `_build(..., interpret)` seams, interpret-mode
parity as the CPU oracle) but NOT the strided-window-scheduling half —
their inner loop walks a data-dependent bit cursor, so there is no
MAX_UNROLL_STEPS analog.

Opt-in wiring: temporal._window_stat_strided dispatches here when
M3_TPU_PALLAS=1 (interpret mode backs the kernel on CPU so the tests
stay correct).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_F32 = jnp.float32

# Where this module's interpret-vs-XLA parity is asserted (the m3lint
# unguarded-pallas-dispatch rule checks the declared oracle exists;
# tests/test_pallas_lowering.py builds the kernel for TPU from the CPU).
_PALLAS_ORACLE = "tests/test_temporal.py"

# Row tile: f32 VMEM tiling is (8, 128); eight series rows per program
# keeps the window slice a native sublane group.
_BS = 8

STATS = ("count", "sum", "min", "max", "last", "m2")

# The kernel statically unrolls its output-step loop (Mosaic alignment,
# see _kernel); callers must not dispatch shapes whose unroll would blow
# up trace/compile time — an unstrided 10k-column grid would unroll ~10k
# window reductions into one program. Past this bound the XLA
# reduce_window path (constant program size) is the right tool anyway.
MAX_UNROLL_STEPS = 512


def _kernel(x_ref, o_ref, c_ref, *, W: int, stride: int, T_out: int,
            stat: str):
    # STATIC unroll over the output steps: Mosaic requires dynamic lane
    # slices to start at provable multiples of 128, and a window start of
    # i*stride from a fori_loop counter is not — the dynamic-slice form
    # fails TPU compilation outright ("cannot statically prove that index
    # in dimension 1 is a multiple of 128"; interpret mode on CPU never
    # sees the constraint). Constant offsets lower fine (Mosaic inserts
    # the layout changes), and T_out is a query's output step count
    # (~100s), so the unrolled loop stays a modest program.
    x = x_ref[:, :]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (_BS, W), 1)
    for i in range(T_out):
        win = x[:, i * stride: i * stride + W]          # [BS, W], static
        mask = jnp.isfinite(win)
        cnt = jnp.sum(mask.astype(_F32), axis=1)
        if stat == "count":
            out = cnt
        elif stat == "sum":
            out = jnp.sum(jnp.where(mask, win, 0.0), axis=1)
        elif stat == "min":
            out = jnp.min(jnp.where(mask, win, jnp.inf), axis=1)
        elif stat == "max":
            out = jnp.max(jnp.where(mask, win, -jnp.inf), axis=1)
        elif stat == "last":
            last_i = jnp.max(jnp.where(mask, iota_w, -1), axis=1)
            hit = iota_w == last_i[:, None]
            out = jnp.sum(jnp.where(hit & mask, win, 0.0), axis=1)
        elif stat == "m2":
            s = jnp.sum(jnp.where(mask, win, 0.0), axis=1)
            mu = s / jnp.maximum(cnt, 1.0)
            dev = jnp.where(mask, win - mu[:, None], 0.0)
            out = jnp.sum(dev * dev, axis=1)
        else:  # pragma: no cover - guarded by caller
            raise ValueError(stat)
        o_ref[:, i] = out
        c_ref[:, i] = cnt


@functools.lru_cache(maxsize=256)
def _build(S: int, K: int, W: int, stride: int, stat: str,
           interpret: bool):
    T_out = (K - W) // stride + 1
    grid = ((S + _BS - 1) // _BS,)
    kern = functools.partial(_kernel, W=W, stride=stride, T_out=T_out,
                             stat=stat)
    call = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((_BS, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((_BS, T_out), lambda i: (i, 0)),
                   pl.BlockSpec((_BS, T_out), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, T_out), _F32),
                   jax.ShapeDtypeStruct((S, T_out), _F32)],
        interpret=interpret,
    )
    return jax.jit(call)


def window_stat(resid, W: int, stride: int, stat: str):
    """(stat [S, T_out] f32, count [S, T_out] f32) over the strided
    windows of `resid` ([S, K] f32, NaN = missing sample); window t reads
    columns [t*stride, t*stride+W).

    Matches temporal._window_stat followed by [..., ::stride] at every
    cell with count > 0 — which is the whole caller contract: both
    finishes mask count==0 to NaN. Where count == 0 the raw planes may
    differ ('last' yields 0.0 here vs the XLA gather's clipped-index
    artifact), and a selected -0.0 comes back as +0.0 (the one-hot
    sum); neither is observable through *_over_time.

    Runs in interpret mode off-TPU — fine for tests, pathologically
    slow in serving, which is why temporal._window_stat_strided only
    dispatches here on a real tpu backend."""
    if stat not in STATS:
        raise ValueError(f"unknown pallas window stat {stat!r}")
    S, K = resid.shape
    if K < W:
        raise ValueError(
            f"grid has {K} columns < window {W}; callers fall back to the "
            "XLA path for the empty result (temporal._window_stat_strided)")
    t_out = (K - W) // stride + 1
    if t_out > MAX_UNROLL_STEPS:
        raise ValueError(
            f"{t_out} output steps would unroll past MAX_UNROLL_STEPS="
            f"{MAX_UNROLL_STEPS}; callers fall back to the XLA path "
            "(temporal._window_stat_strided)")
    interpret = jax.default_backend() != "tpu"
    return _build(S, K, W, stride, stat, interpret)(resid)
