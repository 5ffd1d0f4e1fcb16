"""Paged flash-decode attention kernel (block-table KV, single-token decode).

The KV cache lives in a physical page pool ``(n_pages, page_size, Hkv, hd)``
shared by every request; each request owns a *block table* — the ordered list
of page ids holding its context. The kernel keeps ``flash_decode``'s
running softmax and walks only each lane's live pages: the grid is one step
per lane, and a lane loops over blocks of ``pages_per_block`` consecutive
block-table slots (~256 tokens) up to its ``ctx_lens``. The block table and
``ctx_lens`` ride in SMEM via scalar prefetch; the pool stays in HBM, and each
live page of a block is copied into a double-buffered VMEM block by its own
DMA. The next block's copies start before this block is computed, and a
lane's last block starts the first block of the next lane with a context, so
the DMAs run ahead across lanes. Work per call scales with the live pages,
not with lanes x table slots; slots past the context are never fetched.

Conventions shared with ``serving/paged.py``:

* page id 0 is the reserved garbage page — allocators never hand it out, and
  masked/inactive writes land there;
* unused block-table entries are 0; nothing past ``ctx_lens`` is read;
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
BLOCK_TOKENS = 256          # tokens per block of a lane's walk


def pages_per_block(page_size: int, n_slots: int) -> int:
    """Block-table slots walked per block: ~``BLOCK_TOKENS`` tokens, never
    more than the table holds."""
    return max(1, min(n_slots, BLOCK_TOKENS // page_size))


def next_live_lanes(ctx):
    """The walk's ``nxt`` operand from ``ctx`` (lanes,): ``nxt[0]`` is the
    first lane with a context, ``nxt[b + 1]`` the first such lane after b,
    the lane count where there is none."""
    B = ctx.shape[0]
    lane = jnp.where(ctx > 0, jnp.arange(B, dtype=jnp.int32), B)
    return jnp.append(jax.lax.cummin(lane, reverse=True), B).astype(jnp.int32)


class LiveBlocks:
    """One lane's walk over its live pages, a block of ``ppb`` block-table
    slots at a time, with the copies of the next block in flight while one
    is computed. Built inside a kernel whose grid is one step per lane and
    whose scalar prefetch holds the block table ``bt_ref`` (lanes, slots),
    ``ctx_ref`` (lanes,) and ``nxt_ref`` (:func:`next_live_lanes`).
    ``page_copies(page, slot, i)`` gives the DMAs that bring page ``page``
    into position ``i`` of double-buffer ``slot``; ``slot_ref`` (SMEM (1,))
    holds the slot of the block in flight. The paged decode kernels share
    it."""

    def __init__(self, bt_ref, ctx_ref, nxt_ref, slot_ref, page_copies, *,
                 ppb: int, ps: int):
        self.b = pl.program_id(0)
        self.n_lanes = pl.num_programs(0)
        self.bt_ref, self.ctx_ref, self.nxt_ref = bt_ref, ctx_ref, nxt_ref
        self.slot_ref, self.page_copies = slot_ref, page_copies
        self.ppb, self.ps, self.bk = ppb, ps, ppb * ps   # bk: tokens a block

    def _dmas(self, lane, blk, slot, i):
        """The copies of page ``i`` of block ``blk`` into buffer ``slot``."""
        return self.page_copies(self.bt_ref[lane, blk * self.ppb + i], slot,
                                i)

    def _live_pages(self, lane, blk):   # pages past the context: never read
        return jnp.minimum(self.ppb, pl.cdiv(
            self.ctx_ref[lane] - blk * self.bk, self.ps))

    def _start(self, lane, blk, slot):
        @pl.loop(0, self._live_pages(lane, blk))
        def _(i):
            for c in self._dmas(lane, blk, slot, i):
                c.start()

    def _wait(self, lane, blk, slot):
        @pl.loop(0, self._live_pages(lane, blk))
        def _(i):
            for c in self._dmas(lane, blk, slot, i):
                c.wait()

    def begin(self):
        """Start this lane's walk: the first live lane fetches its own first
        block; later lanes find theirs in flight, started by the lane
        before. Returns (context, blocks, next live lane or ``n_lanes``)."""
        b = self.b

        @pl.when(b == self.nxt_ref[0])
        def _first():
            self.slot_ref[0] = 0
            self._start(b, 0, 0)

        ctx = self.ctx_ref[b]
        return ctx, pl.cdiv(ctx, self.bk), self.nxt_ref[b + 1]

    def fetch(self, i, n_blocks, next_lane):
        """Start the copies of the block after ``i`` (or of the next live
        lane's first block), wait for block ``i``'s, and return the buffer
        slot that holds it."""
        slot = self.slot_ref[0]

        @pl.when(i + 1 < n_blocks)
        def _():
            self._start(self.b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & (next_lane < self.n_lanes))
        def _():
            self._start(next_lane, 0, 1 - slot)

        self.slot_ref[0] = 1 - slot
        self._wait(self.b, i, slot)
        return slot


def _kernel(bt_ref, ctx_ref, nxt_ref, q_ref, k_hbm, v_hbm, *rest,
            scale, ppb, quant):
    if quant:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sems, slot_ref = rest
    else:
        o_ref, k_buf, v_buf, sems, slot_ref = rest
    _, _, ps, Hkv, hd = k_buf.shape
    walk = LiveBlocks(
        bt_ref, ctx_ref, nxt_ref, slot_ref,
        lambda page, slot, i: [
            pltpu.make_async_copy(src.at[page], dst.at[slot, i],
                                  sems.at[kv, slot])
            for kv, (src, dst) in enumerate([(k_hbm, k_buf),
                                             (v_hbm, v_buf)])],
        ppb=ppb, ps=ps)
    bk = walk.bk
    ctx, n_blocks, next_lane = walk.begin()
    Hq = q_ref.shape[1]
    G = Hq // Hkv
    q = q_ref[0]                                  # (Hq, hd)
    # bf16 x bf16 products are exact in f32: Q.K^T may take bf16 operands
    dt = jnp.float32 if quant else jnp.promote_types(q.dtype, k_buf.dtype)

    def block(i, carry):
        m_prev, l_prev, acc = carry     # (Hkv, G), (Hkv, G), (Hkv, G, hd)
        slot = walk.fetch(i, n_blocks, next_lane)

        # slots past the context were not fetched (stale VMEM): select, never
        # multiply, them away
        live = i * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1) < ctx
        vlive = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, hd), 0) < ctx
        k_blk = k_buf[slot].reshape(bk, Hkv, hd)
        v_blk = v_buf[slot].reshape(bk, Hkv, hd)
        if quant:                   # int8 pages: scales (Hkv, bk) of the block
            off = 0 if ks_ref.shape[-1] == bk else pl.multiple_of(i * bk, bk)
            k_sc = ks_ref[0, :, pl.ds(off, bk)]
            v_sc = vs_ref[0, :, pl.ds(off, bk)]
        ms, ls, accs = [], [], []
        for h in range(Hkv):            # G query heads share KV head h
            s = jax.lax.dot_general(
                q[h * G:(h + 1) * G].astype(dt), k_blk[:, h].astype(dt),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (G, bk)
            if quant:
                s = s * k_sc[h:h + 1]
            s = jnp.where(live, s, NEG_INF)
            m = jnp.maximum(m_prev[h], jnp.max(s, axis=-1))
            p = jnp.where(live, jnp.exp(s - m[:, None]), 0.0)
            corr = jnp.exp(m_prev[h] - m)
            ls.append(l_prev[h] * corr + jnp.sum(p, axis=-1))
            if quant:
                p = jnp.where(live, p * v_sc[h:h + 1], 0.0)
            v = jnp.where(vlive, v_blk[:, h].astype(jnp.float32), 0.0)
            accs.append(acc[h] * corr[:, None] + jnp.dot(
                p, v, preferred_element_type=jnp.float32))
            ms.append(m)
        return jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)

    m0 = jnp.full((Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G), jnp.float32)
    acc0 = jnp.zeros((Hkv, G, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # ctx == 0: zeros
    o_ref[0] = out.reshape(Hq, hd).astype(o_ref.dtype)


def paged_decode_pallas(q, k_pages, v_pages, block_tables, ctx_lens, *,
                        k_scales=None, v_scales=None,
                        interpret: bool = False):
    """q (B, Hq, hd); pages (P, ps, Hkv, hd); block_tables (B, NB) int32
    physical page ids (0-filled past the context); ctx_lens (B,) int32.
    ``k_scales``/``v_scales`` (P, ps, Hkv) fp32 mark int8 pages
    (kv_pack.py): each token's scales multiply its scores and its
    probabilities in-kernel."""
    B, Hq, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    _, NB = block_tables.shape
    ppb = pages_per_block(ps, NB)
    pad = -NB % ppb
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, pad)))
    ctx = jnp.minimum(ctx_lens.astype(jnp.int32), NB * ps)
    nxt = next_live_lanes(ctx)
    quant = k_scales is not None

    kernel = functools.partial(_kernel, scale=1.0 / np.sqrt(hd), ppb=ppb,
                               quant=quant)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, Hq, hd), lambda b, *_: (b, 0, 0)), hbm, hbm]
    args = [bt, ctx, nxt, q, k_pages, v_pages]
    if quant:
        # a page's (ps, Hkv) scales are narrower than a vector lane row, which
        # no DMA can slice: gather each lane's scales, token-major per KV head
        # (B, Hkv, NB' * ps), into VMEM beside its lane
        L = bt.shape[1] * ps
        for sc in (k_scales, v_scales):
            args.append(sc.astype(jnp.float32)[bt].transpose(0, 3, 1, 2)
                        .reshape(B, Hkv, L))
            in_specs.append(pl.BlockSpec((1, Hkv, L), lambda b, *_: (b, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # block table, ctx_lens, nxt
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, hd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, Hkv, hd), k_pages.dtype),
            pltpu.VMEM((2, ppb, ps, Hkv, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K or V, buffer slot)
            pltpu.SMEM((1,), jnp.int32),      # slot of the block in flight
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),   # lanes hand DMAs on
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
