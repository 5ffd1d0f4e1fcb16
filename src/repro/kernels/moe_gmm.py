"""Grouped expert matmuls (MoE expert FFN hot spot).

Two formulations:

* ``moe_gmm`` — the capacity-padded baseline: y[e] = x[e] @ w[e] over an
  ``(E, C, D)`` buffer, every capacity slot matmul'd (padding included).
  Blocked for the MXU: grid (E, C/Cb, F/Fb, D/Db) with a VMEM fp32
  accumulator tile. Non-block-divisible dims are padded up to the block
  multiple and the result sliced back.

* ``moe_gmm_ragged`` — the group-sized ragged GMM: x is a single
  ``(Np, D)`` buffer of tokens sorted by expert with block-aligned group
  starts (see ``kernels/moe_dispatch.ragged_dispatch``). Per-group row
  offsets/sizes sit in SMEM (scalar prefetch). Each grid step multiplies
  one row tile by its owning expert's whole ``(D, F)`` weight slab in one
  ``dot_general`` with an f32 result; an expert too wide for the VMEM
  budget is cut into the widest ``(D, fb)`` column slabs that fit
  (``weight_slab``, from the shape and itemsize alone). The grid is
  ``(F / fb, row tiles)`` with row tiles innermost, and the weight block
  follows only the tile's expert, so consecutive tiles of one expert keep
  the block and each touched expert's slab crosses HBM once per call;
  untouched experts own no tiles. Tiles past the last real group (and
  pad-only boundary tiles) skip the product and write zeros, and partial
  boundary tiles are iota-masked. Issued FLOPs scale with actual
  tokens-per-expert, not E*C padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(n: int, b: int) -> int:
    return -(-n // b) * b


def _kernel(x_ref, w_ref, o_ref, *, n_d):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                                     # (Cb, Db)
    w = w_ref[0]                                     # (Db, Fb)
    o_ref[0] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def moe_gmm(x, w, *, c_block: int = 128, f_block: int = 128,
            d_block: int = 256, interpret: bool = False):
    """x: (E, C, D), w: (E, D, F) -> (E, C, F) fp32-accumulated.

    Odd shapes are handled by zero-padding C/D/F up to the block multiple
    (zero rows/cols contribute nothing to the accumulation) and slicing the
    result back to (E, C, F).
    """
    E, C, D = x.shape
    _, _, F = w.shape
    cb, fb, db = min(c_block, C), min(f_block, F), min(d_block, D)
    Cp, Fp, Dp = _round_up(C, cb), _round_up(F, fb), _round_up(D, db)
    if (Cp, Dp) != (C, D):
        x = jnp.pad(x, ((0, 0), (0, Cp - C), (0, Dp - D)))
    if (Dp, Fp) != (D, F):
        w = jnp.pad(w, ((0, 0), (0, Dp - D), (0, Fp - F)))
    grid = (E, Cp // cb, Fp // fb, Dp // db)
    kernel = functools.partial(_kernel, n_d=Dp // db)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cb, db), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, db, fb), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, cb, fb), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, Fp), jnp.float32),
        interpret=interpret,
    )(x, w)
    if (Cp, Fp) != (C, F):
        y = y[:, :C, :F]
    return y


# VMEM bytes of one weight slab. The pipeline double-buffers it, so two
# slabs take at most half of the 16 MiB scoped default and leave the rest
# for the row and output tiles and the f32 product.
SLAB_BYTES = 4 * 1024 * 1024


def weight_slab(d_in: int, d_out: int, itemsize: int,
                budget: int = SLAB_BYTES) -> int:
    """Columns of an expert's (d_in, d_out) weight that one grid step reads.

    The whole expert where it fits the budget; else the widest multiple of
    128 that divides d_out (padded up to 128) and fits, and 128 at least.
    """
    if d_in * d_out * itemsize <= budget:
        return d_out
    dp = _round_up(d_out, 128)
    fits = [fb for fb in range(128, dp + 1, 128)
            if dp % fb == 0 and d_in * fb * itemsize <= budget]
    return max(fits, default=128)


def weight_block_index(f, n, te, sz, po):
    """Weight block of grid step (f, n): slab f of row tile n's expert. It
    depends on the tile only through its expert, so consecutive tiles of
    one expert keep the block and the pipeline fetches it once."""
    return te[n], 0, f


def _ragged_kernel(tile_e_ref, sizes_ref, poff_ref, x_ref, w_ref, o_ref, *,
                   n_block):
    n = pl.program_id(1)
    e = tile_e_ref[n]
    row0 = n * n_block
    n_groups = sizes_ref.shape[0]
    used = poff_ref[n_groups]            # rows past this are dead tail
    # tiles wholly outside any group's real row range (past the last
    # group, or entirely inside the owning group's alignment padding)
    # write zeros and skip the product
    live = (row0 < used) & (row0 < poff_ref[e] + sizes_ref[e])

    @pl.when(live)
    def _product():
        # boundary tiles: mask rows past the group's real size
        local = (row0 - poff_ref[e]
                 + jax.lax.broadcasted_iota(jnp.int32, (n_block, 1), 0))
        keep = local < sizes_ref[e]
        x = jnp.where(keep, x_ref[...], 0)
        o_ref[...] = jax.lax.dot_general(
            x, w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def moe_gmm_ragged(x, w, tile_expert, group_sizes, padded_offsets, *,
                   n_block: int, slab_bytes: int = SLAB_BYTES,
                   interpret: bool = False):
    """Group-sized ragged GMM: x (Np, D) sorted-by-expert block-aligned rows,
    w (E, D, F), -> (Np, F) fp32.

    tile_expert (Np//n_block,) int32: owning expert per row tile;
    group_sizes (E,) int32: real rows per expert;
    padded_offsets (E+1,) int32: block-aligned group starts (see
    kernels/moe_dispatch). All three ride in SMEM via scalar prefetch so
    the weight BlockSpec can select each tile's expert slab directly.
    ``slab_bytes`` is the VMEM budget of one weight slab (``weight_slab``).
    """
    Np, D = x.shape
    E, _, F = w.shape
    nb = n_block
    assert Np % nb == 0, f"rows {Np} not aligned to n_block {nb}"
    fb = weight_slab(D, F, w.dtype.itemsize, slab_bytes)
    Fp = _round_up(F, fb)
    if Fp != F:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Fp - F)))
    grid = (Fp // fb, Np // nb)          # row tiles innermost
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, D), lambda f, n, te, sz, po: (n, 0)),
            pl.BlockSpec((1, D, fb), weight_block_index),
        ],
        out_specs=pl.BlockSpec((nb, fb), lambda f, n, te, sz, po: (n, f)),
    )
    y = pl.pallas_call(
        functools.partial(_ragged_kernel, n_block=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Np, Fp), jnp.float32),
        interpret=interpret,
        name="moe_gmm_ragged",      # the custom call's name in traces
    )(tile_expert.astype(jnp.int32), group_sizes.astype(jnp.int32),
      padded_offsets.astype(jnp.int32), x, w)
    if Fp != F:
        y = y[:, :F]
    return y
