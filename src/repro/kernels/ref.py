"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def source_expert_count_ref(expert_idx, source_ids, *, n_experts: int,
                            n_sources: int):
    """Scatter-add reference. expert_idx (T, K); source_ids (T,)."""
    flat = expert_idx.reshape(-1)
    valid = flat >= 0
    b = jnp.zeros((n_experts,), jnp.int32).at[
        jnp.where(valid, flat, 0)].add(valid.astype(jnp.int32))
    k = expert_idx.shape[-1]
    src = jnp.repeat(source_ids, k)
    sv = valid & (src >= 0)
    a = jnp.zeros((n_sources, n_experts), jnp.int32).at[
        jnp.where(sv, src, 0), jnp.where(sv, flat, 0)].add(
        sv.astype(jnp.int32))
    return b, a


def moe_gmm_ref(x, w):
    """x (E, C, D) @ w (E, D, F) -> (E, C, F) in fp32."""
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32))


def moe_gmm_ragged_ref(x, w, group_sizes, padded_offsets):
    """Ragged GMM oracle: row r of x belongs to the expert whose
    block-aligned range [padded_offsets[e], padded_offsets[e+1]) contains r,
    and is live iff it lies within the group's real size. Dead rows -> 0."""
    Np, _ = x.shape
    E = group_sizes.shape[0]
    rows = jnp.arange(Np, dtype=jnp.int32)
    e_of = jnp.clip(jnp.searchsorted(padded_offsets[1:], rows, side="right"),
                    0, E - 1)
    live = rows < padded_offsets[e_of] + group_sizes[e_of]
    y = jnp.einsum("nd,ndf->nf", x.astype(jnp.float32),
                   w[e_of].astype(jnp.float32))
    return jnp.where(live[:, None], y, 0.0)


def paged_decode_ref(q, k_pages, v_pages, block_tables, ctx_lens):
    """Paged decode oracle: gather the dense view from the block table, then
    route through the trusted dense oracle. Rows with ctx_lens == 0 -> 0."""
    B, Hq, hd = q.shape
    _, ps, Hkv, _ = k_pages.shape
    NB = block_tables.shape[1]
    L = NB * ps
    bt = block_tables.astype(jnp.int32)
    kd = k_pages[bt].reshape(B, L, Hkv, hd)
    vd = v_pages[bt].reshape(B, L, Hkv, hd)
    pos = jnp.arange(L, dtype=jnp.int32)[None]
    k_pos = jnp.where(pos < ctx_lens[:, None], pos, -1)
    q_pos = jnp.maximum(ctx_lens - 1, 0).astype(jnp.int32)
    out = flash_decode_ref(q, kd, vd, k_pos, q_pos)
    return jnp.where((ctx_lens > 0)[:, None, None], out, 0.0).astype(q.dtype)


def flash_decode_ref(q, k_cache, v_cache, k_pos, q_pos):
    """Masked softmax attention oracle. q (B, Hq, hd)."""
    B, Hq, hd = q.shape
    _, L, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    k = k_cache.astype(jnp.float32)
    v = v_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,blkd->bkgl", qg, k) / np.sqrt(hd)
    valid = (k_pos >= 0) & (k_pos <= q_pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgl,blkd->bkgd", p, v)
    return out.reshape(B, Hq, hd).astype(q.dtype)


def mla_decode_ref(q, pages, block_tables, ctx_lens, *, rank: int,
                   scale: float):
    """Latent decode oracle, one lane at a time: softmax over the lane's
    first ``ctx_lens`` rows of scores ``q . row * scale``, weighting the
    rows' first ``rank`` values. Lanes with ctx_lens == 0 -> 0."""
    _, ps, _ = pages.shape
    out = []
    for b in range(q.shape[0]):
        n = int(ctx_lens[b])
        if n == 0:
            out.append(jnp.zeros((q.shape[1], rank), jnp.float32))
            continue
        rows = pages[block_tables[b]].reshape(-1, pages.shape[-1])[:n]
        rows = rows.astype(jnp.float32)
        p = jax.nn.softmax(q[b].astype(jnp.float32) @ rows.T * scale, -1)
        out.append(p @ rows[:, :rank])
    return jnp.stack(out).astype(q.dtype)
