"""Paged decode over a latent page pool (multi-head latent attention, absorbed).

In latent attention (DeepSeek-V2/V3) a token keeps one row in the cache: the
normed ``rank``-wide latent ``c`` and one roped key ``k_pe`` shared by every
head, ``W = rank + rope`` values. At decode the key up-projection is folded
into the query (``q_lat = q_nope . W_UK`` per head), so each head attends
over the rows themselves: its score is ``[q_lat | q_pe] . row`` and its
output the probability-weighted sum of the rows' latent part, still in
latent space (the caller applies ``W_UV``). That is multi-query attention
over one ``W``-wide key whose first ``rank`` columns are also the value.

The pool is ``(n_pages, page_size, Wp)``: each page holds ``page_size``
rows, each the ``W`` values a token keeps, then zeros up to ``Wp``, the
next multiple of 128 (:func:`padded_width`). TPU tiles the last axis to 128
lanes, so a row of ``W = 576`` (4.5 x 128) takes 640 lanes of HBM whatever
the array's shape, and a DMA moves whole tiles: the zeros make that
padding explicit, so the kernel reads a page in one copy and scores a row
in one product. At 576 they cost 11% more bytes than the values.

The kernel walks only each lane's live pages with the block walk and the
double-buffered page copies of :mod:`repro.kernels.paged_decode`
(:class:`~repro.kernels.paged_decode.LiveBlocks`): one grid step per lane,
blocks of ``pages_per_block`` block-table slots up to ``ctx_lens``, one DMA
per live page, the next block's copies in flight while one is computed.
Conventions as there: page 0 is the garbage page, nothing past
``ctx_lens`` is read, and a lane with ``ctx_lens == 0`` returns zeros.

Its scalar operands are ``ctx_lens``, then the block table, then the next
live lane: the call's first operand is a vector, so a trace reader that
knows the GQA kernel by a (lanes, slots) table in first place does not take
this one for it. ``mla_decode_xla`` is the gather fallback for the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_decode import (NEG_INF, LiveBlocks, next_live_lanes,
                                        pages_per_block)


def padded_width(width: int) -> int:
    """Lanes a latent row takes in the pool: ``width`` up to a multiple of
    128."""
    return -(-width // 128) * 128


def _kernel(ctx_ref, bt_ref, nxt_ref, q_ref, kv_hbm, o_ref, buf, sems,
            slot_ref, *, scale, ppb, rank):
    _, _, ps, W = buf.shape
    walk = LiveBlocks(
        bt_ref, ctx_ref, nxt_ref, slot_ref,
        lambda page, slot, i: [pltpu.make_async_copy(
            kv_hbm.at[page], buf.at[slot, i], sems.at[slot])],
        ppb=ppb, ps=ps)
    bk = walk.bk
    ctx, n_blocks, next_lane = walk.begin()
    q = q_ref[0]                                  # (H, W)
    H = q.shape[0]

    def block(i, carry):
        m_prev, l_prev, acc = carry               # (H,), (H,), (H, rank)
        slot = walk.fetch(i, n_blocks, next_lane)
        # rows past the context were not fetched (stale VMEM): select, never
        # multiply, them away
        live = i * bk + jax.lax.broadcasted_iota(jnp.int32, (H, bk), 1) < ctx
        vlive = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, rank),
                                                  0) < ctx
        rows = buf[slot].reshape(bk, W)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, NEG_INF)
        m = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(live, jnp.exp(s - m[:, None]), 0.0)
        corr = jnp.exp(m_prev - m)
        v = jnp.where(vlive, rows[:, :rank].astype(jnp.float32), 0.0)
        return (m, l_prev * corr + jnp.sum(p, axis=-1),
                acc * corr[:, None] + jnp.dot(
                    p, v, preferred_element_type=jnp.float32))

    m0 = jnp.full((H,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H,), jnp.float32)
    acc0 = jnp.zeros((H, rank), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def mla_decode_pallas(q, pages, block_tables, ctx_lens, *, rank: int,
                      scale: float, interpret: bool = False):
    """q (B, H, Wp) absorbed queries ``[q_lat | q_pe | 0]``; pages (P, ps,
    Wp) latent rows ``[c | k_pe | 0]``; block_tables (B, NB) int32 page ids
    (0-filled past the context); ctx_lens (B,) int32. Returns (B, H, rank):
    each head's attention output in latent space."""
    B, H, W = q.shape
    P, ps, _ = pages.shape
    _, NB = block_tables.shape
    ppb = pages_per_block(ps, NB)
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, -NB % ppb)))
    ctx = jnp.minimum(ctx_lens.astype(jnp.int32), NB * ps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # ctx_lens, block table, nxt
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, W), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),    # one a buffer slot
            pltpu.SMEM((1,), jnp.int32),      # slot of the block in flight
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, ppb=ppb, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),   # lanes hand DMAs on
        interpret=interpret,
        name="mla_decode",          # the custom call's name in traces
    )(ctx, bt, next_live_lanes(ctx), q, pages)


def mla_decode_xla(q, pages, block_tables, ctx_lens, *, rank: int,
                   scale: float):
    """Gather fallback: each lane's dense view of its rows, then masked
    softmax attention in fp32 (the kernel's math)."""
    B, H, W = q.shape
    _, ps, _ = pages.shape
    L = block_tables.shape[1] * ps
    rows = pages[block_tables.astype(jnp.int32)].reshape(B, L, W) \
        .astype(jnp.float32)
    valid = jnp.arange(L, dtype=jnp.int32)[None] < ctx_lens[:, None]
    s = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), rows) * scale
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jnp.where(valid[:, None], jnp.exp(s - jnp.max(s, -1, keepdims=True)),
                  0.0)
    acc = jnp.einsum("bhl,blr->bhr", p, rows[..., :rank])
    out = acc / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    return out.astype(q.dtype)


def mla_decode(q, pages, block_tables, ctx_lens, *, rank: int, scale: float,
               backend: str = "auto", interpret: bool = False):
    """Absorbed latent decode over the live pages. backend: auto | pallas |
    xla (``auto``: the kernel on TPU, the gather elsewhere)."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "pallas":
        return mla_decode_pallas(q, pages, block_tables, ctx_lens, rank=rank,
                                 scale=scale, interpret=interpret)
    return mla_decode_xla(q, pages, block_tables, ctx_lens, rank=rank,
                          scale=scale)
