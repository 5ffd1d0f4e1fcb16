"""Blocked (flash-style) attention in pure JAX.

One implementation serves every attention variant in the zoo:
  * causal / bidirectional (encoder) / sliding-window (gemma2/3, hymba)
  * GQA grouping, logit softcapping, explicit position arrays
  * prefill (Sq large, double-blocked scan) and decode (Sq=1, KV-block scan)

Memory is O(q_block * kv_block) regardless of sequence length, which is what
lets the 32k prefill and 500k decode cells lower on a 16 GB/chip budget. The
kv-block scan step is rematerialized so training does not store per-block
scores. Invalid KV slots are encoded as k_pos < 0 (ring buffers, padding).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import softcap as _softcap

NEG_INF = -1e30


def _attend_one_kv_block(q, kb, vb, qpos, kpos, *, scale, causal, window, cap,
                         m, l, acc, ks=None, vs=None):
    """One running-softmax update.

    q: (B, Sq, Hkv, G, hd)  kb/vb: (B, Bk, Hkv, hd)
    qpos: (B, Sq) kpos: (B, Bk) | m,l: (B, Hkv, G, Sq) acc: (B, Hkv, G, Sq, hd)
    ks/vs: optional (B, Bk, Hkv) dequant scales for int8 KV caches.
    """
    if ks is not None:
        kb = (kb.astype(jnp.float32) * ks[..., None]).astype(jnp.bfloat16)
    if vs is not None:
        vb = (vb.astype(jnp.float32) * vs[..., None]).astype(jnp.bfloat16)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, kb,
                   preferred_element_type=jnp.float32) * scale
    if cap > 0.0:
        s = _softcap(s, cap)
    valid = (kpos[:, None, None, None, :] >= 0)
    if causal:
        valid &= kpos[:, None, None, None, :] <= qpos[:, None, None, :, None]
    if window > 0:
        valid &= kpos[:, None, None, None, :] > (
            qpos[:, None, None, :, None] - window)
    s = jnp.where(valid, s, NEG_INF)

    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard: rows with everything masked keep m at NEG_INF; exp(0)=1 handled by l
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def flash_attention(q, k, v, *, q_pos, k_pos, causal: bool = True,
                    window: int = 0, softcap_val: float = 0.0,
                    q_block: int = 512, kv_block: int = 512,
                    scale: Optional[float] = None,
                    k_scale=None, v_scale=None):
    """q, k: (B, Sq | Skv, Hq | Hkv, hd), v: (B, Skv, Hkv, hd_v) ->
    (B, Sq, Hq, hd_v); ``scale`` defaults to ``hd ** -0.5``.

    q_pos: (B, Sq) int32 absolute positions; k_pos: (B, Skv) int32 absolute
    positions with -1 marking invalid slots. ``window`` may be a traced scalar
    (0 disables); pass window as python int 0 to skip the term entirely.
    k_scale/v_scale: (B, Skv, Hkv) dequant scales for int8 KV caches.
    """
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    hd_v = v.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    dtype = q.dtype

    qg = q.reshape(B, Sq, Hkv, G, hd)

    # ---- pad KV to a block multiple (invalid slots get k_pos = -1)
    n_kv = -(-Skv // kv_block)
    pad_kv = n_kv * kv_block - Skv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_kv)), constant_values=-1)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_kv), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_kv), (0, 0)))
    kb = k.reshape(B, n_kv, kv_block, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_kv, kv_block, Hkv, hd_v).transpose(1, 0, 2, 3, 4)
    kpb = k_pos.reshape(B, n_kv, kv_block).transpose(1, 0, 2)
    quant = k_scale is not None
    if quant:
        ksb = k_scale.reshape(B, n_kv, kv_block, Hkv).transpose(1, 0, 2, 3)
        vsb = v_scale.reshape(B, n_kv, kv_block, Hkv).transpose(1, 0, 2, 3)
    else:  # dummy zero-size scans are not allowed; reuse kpb as placeholder
        ksb = vsb = kpb

    window_i = int(window) if not isinstance(window, jax.Array) else -1
    use_window = (window_i != 0)
    win_val = window if window_i == -1 else window_i

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def kv_step(carry, xs):
        m, l, acc = carry
        kblk, vblk, kpos_blk, ks_blk, vs_blk, qcur, qpos_cur = xs
        m, l, acc = _attend_one_kv_block(
            qcur, kblk, vblk, qpos_cur, kpos_blk, scale=scale, causal=causal,
            window=win_val if use_window else 0, cap=softcap_val,
            m=m, l=l, acc=acc,
            ks=ks_blk if quant else None, vs=vs_blk if quant else None)
        return (m, l, acc), None

    def attend_q_block(qcur, qpos_cur):
        # qcur: (B, Bq, Hkv, G, hd)
        Bq = qcur.shape[1]
        m0 = jnp.full((B, Hkv, G, Bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, Bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, Bq, hd_v), jnp.float32)

        def step(carry, xs):
            kblk, vblk, kpos_blk, ks_blk, vs_blk = xs
            return kv_step(carry, (kblk, vblk, kpos_blk, ks_blk, vs_blk,
                                   qcur, qpos_cur))

        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      (kb, vb, kpb, ksb, vsb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).astype(dtype)  # (B, Bq, Hkv, G, hd)

    if Sq <= q_block:
        out = attend_q_block(qg, q_pos)
        return out.reshape(B, Sq, Hq, hd_v)

    # ---- outer scan over q blocks
    n_q = -(-Sq // q_block)
    pad_q = n_q * q_block - Sq
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), mode="edge")
    qblocks = qg.reshape(B, n_q, q_block, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qpos_blocks = q_pos.reshape(B, n_q, q_block).transpose(1, 0, 2)

    def q_step(_, xs):
        qcur, qpos_cur = xs
        return None, attend_q_block(qcur, qpos_cur)

    _, outs = jax.lax.scan(q_step, None, (qblocks, qpos_blocks))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, n_q * q_block, Hq,
                                                   hd_v)
    return out[:, :Sq]


def decode_attention(q, k_cache, v_cache, *, q_pos, k_pos,
                     window: int = 0, softcap_val: float = 0.0,
                     kv_block: int = 1024, k_scale=None, v_scale=None):
    """Single-token decode attention against a (ring-buffered) KV cache.

    q: (B, 1, Hq, hd); caches: (B, Skv, Hkv, hd); k_pos: (B, Skv) with -1
    marking never-written slots.
    """
    return flash_attention(
        q, k_cache, v_cache, q_pos=q_pos, k_pos=k_pos, causal=True,
        window=window, softcap_val=softcap_val, kv_block=kv_block,
        k_scale=k_scale, v_scale=v_scale)


def ring_positions(write_pos, cache_len: int):
    """Positions held by a ring buffer after ``write_pos + 1`` total writes.

    Slot i holds absolute position p = last p <= write_pos with p % L == i,
    or -1 if that slot was never written. write_pos: (B,) int32.
    Returns (B, L) int32.
    """
    i = jnp.arange(cache_len)[None, :]
    wp = write_pos[:, None]
    p = wp - ((wp - i) % cache_len)
    return jnp.where(p >= 0, p, -1)
