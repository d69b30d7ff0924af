"""Every `pl.pallas_call` in the tree builds for TPU — checked from the
CPU, no chip needed, in seconds.

Two depths, both with interpret=False:

  1. cross-lowering (`.trace(...).lower(lowering_platforms=("tpu",))`)
     runs the Pallas -> Mosaic lowering and catches primitives the
     installed lowering has no rule for (PR 20 shipped three kernels that
     died here: `dynamic_slice` on a loaded value, a (1,128)-index
     `take_along_axis`);
  2. when the installed libtpu can describe a v5e topology without a
     chip (compile-only client), the lowered module is also COMPILED,
     which runs Mosaic itself and catches what it refuses to legalize
     (unsigned min, multi-vreg sublane gathers, unsigned reductions).

What this cannot show, and chip_smoke.py therefore checks on the chip:
that the compiled kernel computes the right bits, fits VMEM at run time
next to its neighbours, and that anything the compile-only client
accepts the attached chip's runtime accepts too.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from m3_tpu.ops import pallas_codec as pc
from m3_tpu.ops import tsz


@functools.lru_cache(maxsize=1)
def _compile_only_device():
    """A v5e device of a compile-only topology, or None when this
    installation cannot make one without hardware."""
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception:  # noqa: BLE001 — any reason means "lower only"
        return None


def build_for_tpu(fn, *shapes):
    dev = _compile_only_device()
    sharding = SingleDeviceSharding(dev) if dev is not None else None
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    if dev is not None:
        lowered.compile()


# (rows, window): the smoke's served tile, a one-row retriever read, and
# one geometry whose max_words / slot count / window are not multiples
# of 8 (37 -> 139 words, 74 slots)
GEOMETRIES = [(2048, 128), (1, 128), (100, 37)]
U32, I32 = jnp.uint32, jnp.int32


@pytest.mark.parametrize("rows,window", GEOMETRIES)
def test_pack_chunks_builds(rows, window):
    mw = tsz.max_words_for(window)
    sp, mwp = pc._ceil_to(2 * window, 8), pc._ceil_to(mw, 8)
    tiles = pc._tiles_for(rows)
    cols = tiles * pc._LANES
    build_for_tpu(pc._build_pack(sp, mwp, tiles, False),
                  *[((sp, cols), U32)] * 3, ((sp, cols), I32))


@pytest.mark.parametrize("rows,window", GEOMETRIES)
def test_decode_core_builds(rows, window):
    mw = tsz.max_words_for(window)
    mwp, wp = pc._ceil_to(mw, 8), pc._ceil_to(window, 8)
    tiles = pc._tiles_for(rows)
    cols = tiles * pc._LANES
    build_for_tpu(pc._build_decode(mwp, mw, wp, window, tiles, False),
                  ((mwp, cols), U32), ((1, cols), I32))


@pytest.mark.parametrize("n_ids,id_cols", [(100_000, 10), (5, 3)])
def test_hash_words_builds(n_ids, id_cols):
    cp, tiles = pc._ceil_to(id_cols, 8), pc._tiles_for(n_ids)
    cols = tiles * pc._LANES
    build_for_tpu(pc._build_hash(cp, tiles, 0, False),
                  ((cp, cols), U32), ((1, cols), I32))


def test_whole_codec_programs_build(monkeypatch):
    """The kernels inside the jitted programs that serve: the encode
    program with the Pallas packer, the fused decode with the Pallas
    scan."""
    # the wrappers pick interpret mode from the DEFAULT backend (cpu
    # here); the program under test is the one a TPU backend builds
    monkeypatch.setattr(pc, "_interpret", lambda: False)
    tsz._decode_fused_jit.cache_clear()
    rows, window = 2048 + 128, 128  # a row count no CPU test encodes
    mw = tsz.max_words_for(window)
    plane, col = ((rows, window), U32), ((rows,), I32)
    enc = functools.partial(tsz._encode_batch, max_words=mw, pack="pallas")
    build_for_tpu(lambda dt, t0h, t0l, vhi, vlo, im, k, n, reg, d0: enc(
        dt, (t0h, t0l), vhi, vlo, im, k, n, reg, d0),
        ((rows, window), I32), ((rows,), U32), ((rows,), U32), plane, plane,
        ((rows,), jnp.bool_), col, col, ((rows,), jnp.bool_), col)
    build_for_tpu(tsz._decode_fused_jit(window, 10**9, True, "pallas"),
                  ((rows, mw), U32), col)
    # drop the compiled-mode traces: the CPU backend cannot run them
    tsz._decode_fused_jit.cache_clear()
    tsz._encode_batch.clear_cache()


def test_the_program_a_lone_row_gets_holds_no_degenerate_reduce(monkeypatch):
    """decode_plane sends a lone row twice on the Pallas route, so the
    program it builds is the two-row one, and that one holds no reduce
    at all. The one-row program cuts the kernel's tile to one lane, and
    XLA:TPU lowers the degenerate reshapes that follow ([window, 1] ->
    [1, window] -> [1, window, 2]) as u32 reduce-adds over a one-wide
    dimension inside the fused unit multiply; on a v5e that read 12 of
    a row's 128 timestamps ~2^31 ns low (PR 32)."""
    dev = _compile_only_device()
    if dev is None:
        pytest.skip("no compile-only TPU topology in this installation")
    monkeypatch.setattr(pc, "_interpret", lambda: False)
    tsz._decode_fused_jit.cache_clear()
    window = 128
    mw = tsz.max_words_for(window)
    reduces = {}
    for rows in (2, 8):
        args = [jax.ShapeDtypeStruct(s, d, sharding=SingleDeviceSharding(dev))
                for s, d in (((rows, mw), U32), ((rows,), I32))]
        text = tsz._decode_fused_jit(window, 10**9, False, "pallas").trace(
            *args).lower(lowering_platforms=("tpu",)).compile().as_text()
        reduces[rows] = text.count(" reduce(")
    tsz._decode_fused_jit.cache_clear()
    assert reduces == {2: 0, 8: 0}
