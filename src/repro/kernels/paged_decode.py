"""Paged flash-decode attention kernel (block-table KV, single-token decode).

The KV cache lives in a physical page pool ``(n_pages, page_size, Hkv, hd)``
shared by every request; each request owns a *block table* — the ordered list
of page ids holding its context. The kernel extends ``flash_decode``'s
running-softmax structure: grid (B, n_blocks) iterates a request's logical
pages sequentially, the block table rides in SMEM via scalar prefetch so the
K/V BlockSpec index maps fetch physical page ``bt[b, j]`` directly from HBM —
no gather materialisation, working set one (page_size, Hkv, hd) tile.

Conventions shared with ``serving/paged.py``:

* page id 0 is the reserved garbage page — allocators never hand it out, and
  masked/inactive writes land there;
* unused block-table entries are 0 (valid index, masked by ``ctx_lens``);
* ``ctx_lens[b]`` is the number of live tokens — rows with ``ctx_lens == 0``
  produce a zero output vector.

``paged_decode_xla`` is the gather-based fallback used on CPU and under SPMD
partitioning (identical math, materialises the dense view).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, *rest,
            scale, page_size, n_blocks, quant):
    # args after v_ref: [k_scale_ref, v_scale_ref (quant only)], o_ref,
    # then the three scratch buffers
    if quant:
        ks_ref, vs_ref, o_ref = rest[0], rest[1], rest[2]
    else:
        o_ref = rest[0]
    m_scr, l_scr, acc_scr = rest[-3], rest[-2], rest[-1]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                  # (Hq, hd)
    k = k_ref[0].astype(jnp.float32)              # (ps, Hkv, hd)
    v = v_ref[0].astype(jnp.float32)
    if quant:                                     # int8 pages: dequant on read
        k = k * ks_ref[0][..., None]              # scales (ps, Hkv)
        v = v * vs_ref[0][..., None]
    ctx = ctx_ref[pl.program_id(0)]               # scalar: live tokens

    Hq, hd = q.shape
    ps, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(Hkv, G, hd)

    # logical positions covered by this page; mask dead tail + garbage pages
    kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (ps,), 0)
    valid = kpos < ctx

    s = jnp.einsum("kgd,lkd->kgl", qg.astype(jnp.float32),
                   k) * scale                          # (Hkv, G, ps)
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_scr[...]                                # (Hkv, G)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(valid[None, None, :], p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jnp.einsum(
        "kgl,lkd->kgd", p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(Hq, hd).astype(o_ref.dtype)


def paged_decode_pallas(q, k_pages, v_pages, block_tables, ctx_lens, *,
                        k_scales=None, v_scales=None,
                        interpret: bool = False):
    """q (B, Hq, hd); pages (P, ps, Hkv, hd); block_tables (B, NB) int32
    physical page ids (0-filled past the context); ctx_lens (B,) int32.
    ``k_scales``/``v_scales`` (P, ps, Hkv) fp32 mark int8 pages — the
    kernel dequantizes each fetched page tile in-register (kv_pack.py)."""
    B, Hq, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    _, NB = block_tables.shape
    scale = 1.0 / np.sqrt(hd)
    G = Hq // Hkv
    bt = block_tables.astype(jnp.int32)
    quant = k_scales is not None

    kernel = functools.partial(_kernel, scale=scale, page_size=ps,
                               n_blocks=NB, quant=quant)
    # index maps see (b, j, block table, ctx_lens): both ride in SMEM via
    # scalar prefetch (a (1, 1) VMEM block of ctx_lens would break the TPU
    # tiling rule on the last two block dims)
    in_specs = [
        pl.BlockSpec((1, Hq, hd), lambda b, j, bt, cl: (b, 0, 0)),
        pl.BlockSpec((1, ps, Hkv, hd),
                     lambda b, j, bt, cl: (bt[b, j], 0, 0, 0)),
        pl.BlockSpec((1, ps, Hkv, hd),
                     lambda b, j, bt, cl: (bt[b, j], 0, 0, 0)),
    ]
    args = [bt, ctx_lens.astype(jnp.int32), q, k_pages, v_pages]
    if quant:
        in_specs += [
            pl.BlockSpec((1, ps, Hkv),
                         lambda b, j, bt, cl: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, ps, Hkv),
                         lambda b, j, bt, cl: (bt[b, j], 0, 0)),
        ]
        args += [k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # block table, ctx_lens
        grid=(B, NB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, hd), lambda b, j, bt, cl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),        # running max m
            pltpu.VMEM((Hkv, G), jnp.float32),        # running sum l
            pltpu.VMEM((Hkv, G, hd), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        interpret=interpret,
        name="paged_decode",        # the custom call's name in traces
    )(*args)


def paged_decode_xla(q, k_pages, v_pages, block_tables, ctx_lens,
                     k_scales=None, v_scales=None):
    """Gather fallback: materialise each request's dense KV view, then do the
    masked-softmax attention in fp32 (identical math to the kernel)."""
    B, Hq, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    _, NB = block_tables.shape
    L = NB * ps
    bt = block_tables.astype(jnp.int32)
    kd = k_pages[bt].reshape(B, L, Hkv, hd).astype(jnp.float32)
    vd = v_pages[bt].reshape(B, L, Hkv, hd).astype(jnp.float32)
    if k_scales is not None:                      # int8 pages: dequant on read
        kd = kd * k_scales[bt].reshape(B, L, Hkv)[..., None]
        vd = vd * v_scales[bt].reshape(B, L, Hkv)[..., None]
    kpos = jnp.arange(L, dtype=jnp.int32)[None]        # (1, L)
    valid = kpos < ctx_lens[:, None]                   # (B, L)
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,blkd->bkgl", qg, kd) / np.sqrt(hd)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                    # (B, Hkv, G, 1)
    p = jnp.where(valid[:, None, None, :], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)                    # (B, Hkv, G, 1)
    acc = jnp.einsum("bkgl,blkd->bkgd", p, vd)                # (B, Hkv, G, hd)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, Hq, hd).astype(q.dtype)


def paged_decode(q, k_pages, v_pages, block_tables, ctx_lens, *,
                 k_scales=None, v_scales=None,
                 backend: str = "auto", interpret: bool = False):
    """Block-table flash decode. backend: auto | pallas | xla.

    ``auto`` picks the Pallas kernel on TPU and the XLA gather path
    elsewhere (CPU, or when the caches are SPMD-partitioned arrays whose
    page axis Pallas cannot follow). Passing ``k_scales``/``v_scales``
    (P, ps, Hkv) enables the int8-page dequant-on-read path."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "pallas":
        return paged_decode_pallas(q, k_pages, v_pages, block_tables,
                                   ctx_lens, k_scales=k_scales,
                                   v_scales=v_scales, interpret=interpret)
    return paged_decode_xla(q, k_pages, v_pages, block_tables, ctx_lens,
                            k_scales, v_scales)
