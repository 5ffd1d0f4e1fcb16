"""Decoder-only transformer assembly (dense / MoE / VLM / hybrid families).

Layers are grouped into *super-blocks* so heterogeneous per-layer patterns
(MoE interleave, local:global attention, hybrid attn+mamba) become homogeneous
stacks that ``jax.lax.scan`` can iterate — this keeps 512-device SPMD compiles
small and fast regardless of depth. Period P = lcm(moe_every, local_ratio+1);
params/caches are stacked (n_super, ...) per within-period position.

Three entry points per model: ``loss`` (train), ``prefill`` (S tokens, builds
KV cache, emits Gimbal MoE statistics), ``decode`` (1 token against the
cache). MoE layers take the Gimbal expert ``placement`` (n_moe_layers, E) as a
runtime input and emit per-layer A[s,e] / B[e] statistics as outputs.

Leading dense layers (``cfg.n_dense_lead``, DeepSeek's
``first_k_dense_replace``) sit in a stack of their own, ``params["lead"]``
with its own pool entry ``pages["lead"]``, and run one by one before the
scan; placement rows, statistics and expert migration cover the scanned
MoE layers only. Latent attention (``cfg.mla``) keeps one latent row per
token in the paged pool and has two paths: chunked prefill up-projects the
gathered latents into per-head keys and values; decode folds the key
up-projection into the query and attends over the latents
(``kernels/mla_decode``).
"""
from __future__ import annotations

import dataclasses
from math import gcd
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.attention import (decode_attention, flash_attention,
                                    ring_positions)
from repro.models.layers import (apply_rope, apply_rope_interleaved,
                                 cross_entropy, dense_init, embed_tokens,
                                 init_embed, init_mlp, lm_logits, mlp,
                                 rms_norm)
from repro.models.ssm import init_mamba, mamba_block, mamba_state_init


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    local: bool = False    # sliding-window attention
    moe: bool = False      # MoE FFN instead of dense
    hybrid: bool = False   # parallel attn + mamba branches (hymba)


def _lcm(a: int, b: int) -> int:
    return a * b // gcd(a, b)


def period_descriptors(cfg: ModelConfig) -> List[LayerDesc]:
    """The repeating period of the layers after the leading dense ones."""
    moe_p = cfg.moe.moe_every if cfg.moe.enabled else 1
    loc_p = (cfg.local_global_ratio + 1) if cfg.local_global_ratio > 0 else 1
    P = _lcm(moe_p, loc_p)
    lead = cfg.n_dense_lead
    if (cfg.n_layers - lead) % P:
        raise ValueError(
            f"{cfg.name}: n_layers={cfg.n_layers} less {lead} leading dense "
            f"not divisible by period {P}")
    if lead and (cfg.family == "hybrid" or loc_p > 1):
        raise NotImplementedError("leading dense layers in a hybrid or "
                                  "windowed stack")
    descs = []
    for j in range(P):
        descs.append(LayerDesc(
            local=cfg.is_local_layer(j),
            moe=cfg.is_moe_layer(lead + j),
            hybrid=(cfg.family == "hybrid"),
        ))
    return descs


def n_super_blocks(cfg: ModelConfig) -> int:
    return (cfg.n_layers - cfg.n_dense_lead) // len(period_descriptors(cfg))


LEAD = LayerDesc()                     # a leading dense layer


# ------------------------------------------------------------------ init
def init_attention(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], (d, cfg.q_dim), 0, dt),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), 0, dt),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), 0, dt),
        "wo": dense_init(ks[3], (cfg.q_dim, d), 0, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), jnp.float32)
    return p


def init_mla(key, cfg: ModelConfig):
    """Latent attention without low-rank queries: ``wq`` (D -> H x (nope +
    rope)), ``wkv_a`` (D -> rank + rope), an RMSNorm on the latent,
    ``wkv_b`` (rank -> H x (nope + v)) and ``wo`` (H x v -> D)."""
    a, H, d = cfg.mla, cfg.n_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": dense_init(ks[0], (d, H * a.qk_head_dim), 0, dt),
        "wkv_a": dense_init(ks[1], (d, a.latent_dim), 0, dt),
        "kv_norm": jnp.zeros((a.kv_lora_rank,), jnp.float32),
        "wkv_b": dense_init(ks[2], (a.kv_lora_rank,
                                    H * (a.qk_nope_head_dim + a.v_head_dim)),
                            0, dt),
        "wo": dense_init(ks[3], (H * a.v_head_dim, d), 0, dt),
    }


def init_layer(key, cfg: ModelConfig, desc: LayerDesc):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "ln_attn": jnp.zeros((cfg.d_model,), jnp.float32),
        "attn": (init_mla(ks[0], cfg) if cfg.mla.enabled
                 else init_attention(ks[0], cfg)),
        "ln_ffn": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if desc.moe:
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, jnp.dtype(cfg.dtype))
    if desc.hybrid:
        p["mamba"] = init_mamba(ks[2], cfg.d_model, cfg.ssm.state_dim,
                                cfg.ssm.conv_width, cfg.ssm.expand,
                                jnp.dtype(cfg.dtype))
        p["attn_out_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["mamba_out_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if cfg.post_norms:
        p["post_attn_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["post_ffn_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def init_params(key, cfg: ModelConfig):
    descs = period_descriptors(cfg)
    ns = n_super_blocks(cfg)
    k_embed, k_blocks = jax.random.split(key)
    blocks = {}
    for j, desc in enumerate(descs):
        keys = jax.random.split(jax.random.fold_in(k_blocks, j), ns)
        stacked = jax.vmap(lambda k: init_layer(k, cfg, desc))(keys)
        blocks[f"pos{j}"] = stacked
    params = {
        "embed": init_embed(k_embed, cfg),
        "blocks": blocks,
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if cfg.n_dense_lead:
        keys = jax.random.split(jax.random.fold_in(key, 1 << 20),
                                cfg.n_dense_lead)
        params["lead"] = jax.vmap(lambda k: init_layer(k, cfg, LEAD))(keys)
    return params


# ------------------------------------------------------------------ cache
def kv_len_for(cfg: ModelConfig, desc: LayerDesc, max_len: int) -> int:
    if desc.local and cfg.sliding_window > 0:
        return min(cfg.sliding_window, max_len)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               kv_dtype: str = "bfloat16"):
    """kv_dtype='int8' stores quantized KV + per-(token, head) scales —
    needed to fit e.g. the MHA 32k x 128 decode cell in 16 GB/chip."""
    if cfg.mla.enabled or cfg.n_dense_lead:
        raise NotImplementedError("latent attention and leading dense "
                                  "layers are served from the paged pool "
                                  "(init_paged_cache)")
    descs = period_descriptors(cfg)
    ns = n_super_blocks(cfg)
    quant = kv_dtype == "int8"
    dt = jnp.int8 if quant else jnp.dtype(cfg.dtype)
    cache = {}
    for j, desc in enumerate(descs):
        L = kv_len_for(cfg, desc, max_len)
        c = {
            "k": jnp.zeros((ns, batch, L, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((ns, batch, L, cfg.n_kv_heads, cfg.head_dim), dt),
        }
        if quant:
            c["k_scale"] = jnp.zeros((ns, batch, L, cfg.n_kv_heads),
                                     jnp.float32)
            c["v_scale"] = jnp.zeros((ns, batch, L, cfg.n_kv_heads),
                                     jnp.float32)
        if desc.hybrid:
            d_in = cfg.ssm.expand * cfg.d_model
            c["mamba_h"] = jnp.zeros((ns, batch, d_in, cfg.ssm.state_dim),
                                     jnp.float32)
            c["mamba_conv"] = jnp.zeros(
                (ns, batch, cfg.ssm.conv_width - 1, d_in), jnp.float32)
        cache[f"pos{j}"] = c
    return cache


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     kv_dtype: str = "auto"):
    """Physical KV page pool: per super-block position k/v arrays of shape
    (n_super, n_pages, page_size, Hkv, hd). Page 0 is the reserved garbage
    page (see serving/paged.py) — allocators hand out ids >= 1, and masked
    writes land in page 0. Request state (block tables, lengths) lives
    outside the pytree and is passed per call.

    ``kv_dtype="int8"`` stores quantized pages plus per-(token, head) fp32
    scale arrays (kernels/kv_pack.py) — the same pool bytes hold roughly
    ``2*hd/(hd+4)`` times the tokens of the fp layout; attention reads
    dequantize on the fly. ``"auto"`` keeps the model dtype (bit-exact).

    Latent attention keeps ``{"latent": (n_super, n_pages, page_size,
    Wp)}`` instead: a token's normed latent and roped key, zero-padded to
    whole 128-lane tiles (``kernels/mla_decode.padded_width``). Leading
    dense layers get the entry ``"lead"``, stacked over those layers.
    """
    if cfg.family == "hybrid":
        raise NotImplementedError("paged KV: mamba state is not paged")
    if cfg.local_global_ratio > 0 or cfg.sliding_window > 0:
        raise NotImplementedError("paged KV: sliding-window layers "
                                  "use the dense ring cache")
    if cfg.attn_logit_softcap:
        # the paged decode kernel has no softcap term yet; admitting such a
        # config would make decode diverge from the softcapped prefill
        raise NotImplementedError("paged KV: attn_logit_softcap "
                                  "unsupported in paged_decode")
    quant = kv_dtype == "int8"
    if quant and cfg.mla.enabled:
        raise NotImplementedError("int8 pages of latent attention are not "
                                  "implemented")
    dt = jnp.int8 if quant else jnp.dtype(cfg.dtype)

    def entry(ns):
        if cfg.mla.enabled:
            from repro.kernels.mla_decode import padded_width
            width = padded_width(cfg.mla.latent_dim)
            return {"latent": jnp.zeros((ns, n_pages, page_size, width), dt)}
        shape = (ns, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
        c = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        if quant:
            sshape = (ns, n_pages, page_size, cfg.n_kv_heads)
            c["k_scale"] = jnp.zeros(sshape, jnp.float32)
            c["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return c

    ns = n_super_blocks(cfg)
    out = {f"pos{j}": entry(ns) for j in range(len(period_descriptors(cfg)))}
    if cfg.n_dense_lead:
        out["lead"] = entry(cfg.n_dense_lead)
    return out


def paged_cache_page_nbytes(pages) -> int:
    """Device bytes per page across every super-block slice and leaf
    (values + scales): the transfer size one swapped page costs the
    host tier (``serving/kv_tier.py`` byte accounting)."""
    return sum(leaf.nbytes // leaf.shape[1]
               for leaf in jax.tree.leaves(pages))


def gather_pages(pages, page_ids):
    """Gather whole page rows across the pool pytree -> a payload pytree
    of shape (ns, len(page_ids), ...) per leaf. Device side of a KV tier
    swap-out; the caller moves the result to host memory."""
    ids = jnp.asarray(page_ids, jnp.int32)
    return jax.tree.map(lambda a: a[:, ids], pages)


def scatter_pages(pages, payload, page_ids):
    """Scatter a :func:`gather_pages` payload back into (possibly
    different) page rows — the device side of a KV tier swap-in."""
    ids = jnp.asarray(page_ids, jnp.int32)
    return jax.tree.map(
        lambda a, p: a.at[:, ids].set(jnp.asarray(p, a.dtype)),
        pages, payload)


def copy_pages(pages, copies):
    """Apply copy-on-write page copies to the physical pool: row ``dst``
    := row ``src`` for every (src, dst) pair, across every super-block
    position and k/v array. One vectorized gather-then-scatter, so a src
    page recycled as a later dst within the same batch still contributes
    its pre-batch contents.
    """
    if not copies:
        return pages
    src = jnp.asarray([s for s, _ in copies], jnp.int32)
    dst = jnp.asarray([d for _, d in copies], jnp.int32)
    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pages)


# ------------------------------------------------------------------ attention
def _quantize_kv(t):
    """(B, S, H, hd) -> (int8 values, (B, S, H) fp32 scales)."""
    s = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
    q = jnp.round(t.astype(jnp.float32)
                  / jnp.maximum(s, 1e-8)[..., None]).astype(jnp.int8)
    return q, s


def _qkv(lp, cfg, xn, positions):
    B, S, _ = xn.shape
    q = jnp.einsum("bsd,df->bsf", xn, lp["wq"])
    k = jnp.einsum("bsd,df->bsf", xn, lp["wk"])
    v = jnp.einsum("bsd,df->bsf", xn, lp["wv"])
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _pool_slots(bt, valid, positions, page_size):
    """Page and offset in the pool of each row a call writes, flattened;
    rows not ``valid`` (chunk padding, inactive lanes) go to garbage page
    0. bt (B, NB) int32; valid, positions (B, S)."""
    bidx = jnp.arange(bt.shape[0])[:, None]
    blk = jnp.clip(positions // page_size, 0, bt.shape[1] - 1)
    page = jnp.where(valid, bt[bidx, blk], 0).reshape(-1)
    off = jnp.where(valid, positions % page_size, 0).reshape(-1)
    return page, off


def _paged_attention(cfg, q, k, v, positions, cache, mode, paged):
    """Paged-KV attention: scatter the new k/v into physical pages via the
    block table, then attend through the block table.

    cache: {"k": (P, ps, Hkv, hd), "v": ...} — one super-block slice of the
    page pool. paged: {"block_tables" (B, NB), "valid" (B, S) rows to write,
    "ctx_lens" (B,) live tokens incl. this chunk, "backend", "interpret"}.
    Invalid rows (chunk padding / inactive decode lanes) write to garbage
    page 0 and attend to nothing.

    int8 pools (``"k_scale" in cache``) quantize each written row through
    ``kernels/kv_pack`` and scatter the per-(token, head) scales alongside;
    reads dequantize on the fly (in-kernel for decode, post-gather for
    chunked prefill).
    """
    from repro.kernels.kv_pack import pack_kv
    from repro.kernels.paged_decode import paged_decode

    B, S = positions.shape
    pk, pv = cache["k"], cache["v"]
    ps = pk.shape[1]
    bt = paged["block_tables"].astype(jnp.int32)       # (B, NB)
    NB = bt.shape[1]
    ctx = paged["ctx_lens"].astype(jnp.int32)          # (B,)
    page, off = _pool_slots(bt, paged["valid"], positions, ps)
    Hkv, hd = pk.shape[2], pk.shape[3]
    quant = "k_scale" in cache
    backend = paged.get("backend", "auto")
    interpret = paged.get("interpret", False)
    if quant:
        kw, ksc = pack_kv(k, backend=backend, interpret=interpret)
        vw, vsc = pack_kv(v, backend=backend, interpret=interpret)
    else:
        kw, vw, ksc, vsc = k, v, None, None
    ck = pk.at[page, off].set(kw.reshape(B * S, Hkv, hd).astype(pk.dtype))
    cv = pv.at[page, off].set(vw.reshape(B * S, Hkv, hd).astype(pv.dtype))
    new_cache = dict(cache, k=ck, v=cv)
    cks = cvs = None
    if quant:
        cks = cache["k_scale"].at[page, off].set(ksc.reshape(B * S, Hkv))
        cvs = cache["v_scale"].at[page, off].set(vsc.reshape(B * S, Hkv))
        new_cache["k_scale"], new_cache["v_scale"] = cks, cvs

    if mode == "paged_decode":                         # S == 1, kernel path
        out = paged_decode(q[:, 0], ck, cv, bt, ctx,
                           k_scales=cks, v_scales=cvs,
                           backend=backend, interpret=interpret)
        return out[:, None], new_cache
    # chunked prefill: dense gather of the request's pages (prior context +
    # the chunk just written), causal mask via absolute positions
    L = NB * ps
    kd = ck[bt].reshape(B, L, Hkv, hd)
    vd = cv[bt].reshape(B, L, Hkv, hd)
    if quant:                                          # dequant the gather
        kd = (kd.astype(jnp.float32)
              * cks[bt].reshape(B, L, Hkv)[..., None]).astype(q.dtype)
        vd = (vd.astype(jnp.float32)
              * cvs[bt].reshape(B, L, Hkv)[..., None]).astype(q.dtype)
    kpos = jnp.arange(L, dtype=jnp.int32)[None]
    kpos = jnp.where(kpos < ctx[:, None], kpos, -1)
    out = flash_attention(q, kd, vd, q_pos=positions, k_pos=kpos,
                          causal=True, window=0,
                          softcap_val=cfg.attn_logit_softcap)
    dm = paged.get("decode_mask")
    if dm is not None:
        # mixed fused step: rows flagged decode are 1-token lanes whose
        # attention must be bit-exact with decode_step_paged. The prefill
        # flash path casts softmax weights to the KV dtype before the
        # value product while the decode kernel keeps them f32, so the
        # two differ in low bits — recompute those rows' position-0
        # output through the decode kernel and select per row.
        dec = paged_decode(q[:, 0], ck, cv, bt, ctx,
                           k_scales=cks, v_scales=cvs,
                           backend=backend, interpret=interpret)
        out = out.at[:, 0].set(jnp.where(dm[:, None, None], dec, out[:, 0]))
    return out, new_cache


def attention_block(lp, cfg, desc, x, positions, cache, mode, policy=None,
                    paged=None):
    """x: (B, S, D); positions (B, S). Returns (attn_out, new_cache)."""
    B, S, _ = x.shape
    xn = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
    q, k, v = _qkv(lp["attn"], cfg, xn, positions)
    window = cfg.sliding_window if desc.local else 0
    if policy is not None:
        q, k, v = policy.shard_heads(q), policy.shard_heads(k), \
            policy.shard_heads(v)

    new_cache = cache
    quant = cache is not None and "k_scale" in cache
    if mode in ("paged_prefill", "paged_decode"):
        out, new_cache = _paged_attention(cfg, q, k, v, positions, cache,
                                          mode, paged)
    elif mode == "train":
        out = flash_attention(q, k, v, q_pos=positions, k_pos=positions,
                              causal=True, window=window,
                              softcap_val=cfg.attn_logit_softcap)
    elif mode == "prefill":
        out = flash_attention(q, k, v, q_pos=positions, k_pos=positions,
                              causal=True, window=window,
                              softcap_val=cfg.attn_logit_softcap)
        kw, ks = _quantize_kv(k) if quant else (k, None)
        vw, vs = _quantize_kv(v) if quant else (v, None)
        L = cache["k"].shape[1]  # (B, L, Hkv, hd) — superblock slice
        if L >= S:
            upd = lambda name, val: jax.lax.dynamic_update_slice_in_dim(
                cache[name], val, 0, axis=1)
            new_cache = dict(cache, k=upd("k", kw), v=upd("v", vw))
            if quant:
                new_cache["k_scale"] = upd("k_scale", ks)
                new_cache["v_scale"] = upd("v_scale", vs)
        else:  # ring: keep last L tokens at slots pos % L
            tail_pos = positions[:, S - L:]
            slots = tail_pos % L                       # (B, L)
            bidx = jnp.arange(B)[:, None]
            upd = lambda name, val: cache[name].at[bidx, slots].set(
                val[:, S - L:])
            new_cache = dict(cache, k=upd("k", kw), v=upd("v", vw))
            if quant:
                new_cache["k_scale"] = upd("k_scale", ks)
                new_cache["v_scale"] = upd("v_scale", vs)
        if policy is not None:
            new_cache = dict(new_cache,
                             k=policy.shard_kv_cache(new_cache["k"]),
                             v=policy.shard_kv_cache(new_cache["v"]))
            if quant:
                new_cache["k_scale"] = policy.shard_kv_scale(
                    new_cache["k_scale"])
                new_cache["v_scale"] = policy.shard_kv_scale(
                    new_cache["v_scale"])
    else:  # decode: S == 1
        L = cache["k"].shape[1]
        pos = positions[:, 0]                          # (B,) current position
        slot = pos % L
        bidx = jnp.arange(B)
        kw, ks = _quantize_kv(k) if quant else (k, None)
        vw, vs = _quantize_kv(v) if quant else (v, None)
        ck = cache["k"].at[bidx, slot].set(kw[:, 0])
        cv = cache["v"].at[bidx, slot].set(vw[:, 0])
        if policy is not None:
            ck, cv = policy.shard_kv_cache(ck), policy.shard_kv_cache(cv)
        new_cache = dict(cache, k=ck, v=cv)
        cks = cvs = None
        if quant:
            cks = cache["k_scale"].at[bidx, slot].set(ks[:, 0])
            cvs = cache["v_scale"].at[bidx, slot].set(vs[:, 0])
            new_cache["k_scale"], new_cache["v_scale"] = cks, cvs
        k_pos = ring_positions(pos, L)                 # (B, L), -1 invalid
        n_split = policy.kv_split if policy is not None else 1
        out = _split_decode(q, ck, cv, positions, k_pos, window,
                            cfg.attn_logit_softcap, n_split, cks, cvs)

    out = out.reshape(B, S, cfg.q_dim)
    out = jnp.einsum("bsf,fd->bsd", out, lp["attn"]["wo"])
    if cfg.post_norms:
        out = rms_norm(out, lp["post_attn_norm"], cfg.norm_eps)
    return out, new_cache


def _mla_project(lp, cfg, xn, positions):
    """xn (B, S, D) -> (q (B, S, H, nope + rope), latent rows (B, S, W)):
    a row is the normed latent, then the roped key shared by the heads."""
    a = cfg.mla
    B, S, _ = xn.shape
    nope, r = a.qk_nope_head_dim, a.kv_lora_rank
    q = jnp.einsum("bsd,df->bsf", xn, lp["wq"]).reshape(
        B, S, cfg.n_heads, a.qk_head_dim)
    q_pe = apply_rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    kv = jnp.einsum("bsd,df->bsf", xn, lp["wkv_a"])
    c = rms_norm(kv[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope_interleaved(kv[..., None, r:], positions,
                                  cfg.rope_theta)[..., 0, :]
    return q, jnp.concatenate([c, k_pe], axis=-1)


def _mla_up(lp, cfg, rows):
    """Non-absorbed keys and values of latent rows (B, L, >= W):
    (k (B, L, H, nope + rope), v (B, L, H, v))."""
    a = cfg.mla
    nope, r, H = a.qk_nope_head_dim, a.kv_lora_rank, cfg.n_heads
    B, L = rows.shape[:2]
    kv = jnp.einsum("blr,rf->blf", rows[..., :r], lp["wkv_b"]).reshape(
        B, L, H, nope + a.v_head_dim)
    k_pe = jnp.broadcast_to(rows[..., None, r:a.latent_dim],
                            (B, L, H, a.qk_rope_head_dim))
    return jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]


def _paged_mla(lp, cfg, q, rows, positions, cache, mode, paged):
    """Latent attention over the paged pool: write this call's rows, then
    attend. ``cache``: {"latent": (P, ps, Wp)}; ``paged`` as in
    :func:`_paged_attention`. Decode (S == 1) is the absorbed form in
    ``kernels/mla_decode``; chunked prefill gathers the request's rows
    through the block table and up-projects them (dense gather over the
    whole table, as the GQA path does)."""
    from repro.kernels.mla_decode import mla_decode
    if paged.get("decode_mask") is not None:
        raise NotImplementedError("mixed steps with latent attention")
    a = cfg.mla
    nope, r, H = a.qk_nope_head_dim, a.kv_lora_rank, cfg.n_heads
    B, S = positions.shape
    pool = cache["latent"]
    ps, Wp = pool.shape[1], pool.shape[2]
    bt = paged["block_tables"].astype(jnp.int32)       # (B, NB)
    NB = bt.shape[1]
    ctx = paged["ctx_lens"].astype(jnp.int32)
    page, off = _pool_slots(bt, paged["valid"], positions, ps)
    pad = ((0, 0), (0, 0), (0, Wp - a.latent_dim))
    pool = pool.at[page, off].set(
        jnp.pad(rows, pad).reshape(B * S, Wp).astype(pool.dtype))
    new_cache = dict(cache, latent=pool)
    scale = 1.0 / np.sqrt(a.qk_head_dim)
    if mode == "paged_decode":                         # S == 1, absorbed
        w = lp["wkv_b"].reshape(r, H, nope + a.v_head_dim)
        q_lat = jnp.einsum("bhn,rhn->bhr", q[:, 0, :, :nope], w[..., :nope],
                           preferred_element_type=jnp.float32)
        qa = jnp.concatenate([q_lat.astype(q.dtype), q[:, 0, :, nope:]], -1)
        qa = jnp.pad(qa, pad)
        o = mla_decode(qa, pool, bt, ctx, rank=r, scale=scale,
                       backend=paged.get("backend", "auto"),
                       interpret=paged.get("interpret", False))
        out = jnp.einsum("bhr,rhv->bhv", o, w[..., nope:])
        return out[:, None], new_cache
    L = NB * ps
    k, v = _mla_up(lp, cfg, pool[bt].reshape(B, L, Wp))
    kpos = jnp.arange(L, dtype=jnp.int32)[None]
    kpos = jnp.where(kpos < ctx[:, None], kpos, -1)
    out = flash_attention(q, k, v, q_pos=positions, k_pos=kpos, causal=True,
                          window=0, scale=scale)
    return out, new_cache


def mla_attention_block(lp, cfg, x, positions, cache, mode, paged=None):
    """Latent attention (DeepSeek-V3, no low-rank queries) through the
    paged latent pool. x: (B, S, D)."""
    if mode not in ("paged_prefill", "paged_decode"):
        raise NotImplementedError("latent attention is served from the "
                                  "paged pool")
    B, S, _ = x.shape
    xn = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
    q, rows = _mla_project(lp["attn"], cfg, xn, positions)
    out, cache = _paged_mla(lp["attn"], cfg, q, rows, positions, cache,
                            mode, paged)
    out = out.reshape(B, S, cfg.n_heads * cfg.mla.v_head_dim)
    return jnp.einsum("bsf,fd->bsd", out, lp["attn"]["wo"]), cache


def _split_decode(q, ck, cv, positions, k_pos, window, cap, n_split,
                  k_scale=None, v_scale=None):
    """Flash-decode with KV split across ``n_split`` shards (split-K SP)."""
    B, one, Hq, hd = q.shape
    L = ck.shape[1]
    if n_split <= 1 or L % n_split:
        return decode_attention(q, ck, cv, q_pos=positions, k_pos=k_pos,
                                window=window, softcap_val=cap,
                                k_scale=k_scale, v_scale=v_scale)
    Ls = L // n_split
    Hkv = ck.shape[2]
    spl = lambda t: t.reshape(B, n_split, Ls, Hkv, hd).transpose(1, 0, 2, 3, 4)
    cks_v, cvs_v = spl(ck), spl(cv)
    kps = k_pos.reshape(B, n_split, Ls).transpose(1, 0, 2)
    quant = k_scale is not None
    spl_s = lambda t: t.reshape(B, n_split, Ls, Hkv).transpose(1, 0, 2, 3)
    kss = spl_s(k_scale) if quant else kps
    vss = spl_s(v_scale) if quant else kps

    def partial_attn(kc, vc, kp, ks, vs):
        from repro.models.attention import _attend_one_kv_block, NEG_INF
        G = Hq // Hkv
        qg = q.reshape(B, 1, Hkv, G, hd)
        m0 = jnp.full((B, Hkv, G, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, 1), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, 1, hd), jnp.float32)
        m, l, acc = _attend_one_kv_block(
            qg, kc, vc, positions, kp, scale=1.0 / np.sqrt(hd), causal=True,
            window=window, cap=cap, m=m0, l=l0, acc=a0,
            ks=ks if quant else None, vs=vs if quant else None)
        return m, l, acc

    ms, ls, accs = jax.vmap(partial_attn)(cks_v, cvs_v, kps, kss, vss)
    m_star = jnp.max(ms, axis=0)
    w = jnp.exp(ms - m_star)
    l_tot = jnp.sum(ls * w, axis=0)
    acc_tot = jnp.sum(accs * w[..., None], axis=0)
    out = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
    # (B, Hkv, G, 1, hd) -> (B, 1, Hq, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, 1, Hq, hd).astype(q.dtype)


# ------------------------------------------------------------------ layer
def decoder_layer(lp, cfg, desc, x, positions, cache, mode, placement_row,
                  source_ids, n_sources, policy=None, collect_stats=True,
                  paged=None):
    """Returns (x, new_cache, stats_or_None)."""
    if cfg.mla.enabled:
        if policy is not None:
            raise NotImplementedError("latent attention under a sharding "
                                      "policy")
        attn_out, new_cache = mla_attention_block(lp, cfg, x, positions,
                                                  cache, mode, paged)
    else:
        attn_out, new_cache = attention_block(lp, cfg, desc, x, positions,
                                              cache, mode, policy, paged)
    if desc.hybrid:
        xn = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        state = None
        if mode == "decode":
            state = {"h": cache["mamba_h"], "conv": cache["mamba_conv"]}
        m_out, m_state = mamba_block(
            lp["mamba"], xn, cfg.ssm.state_dim, cfg.ssm.conv_width,
            state=state, chunk=cfg.ssm.chunk_size, return_state=True)
        if mode in ("prefill", "decode"):
            new_cache = dict(new_cache, mamba_h=m_state["h"],
                             mamba_conv=m_state["conv"])
        # hymba: branch-normalized mean fusion
        fused = 0.5 * (rms_norm(attn_out, lp["attn_out_norm"], cfg.norm_eps)
                       + rms_norm(m_out, lp["mamba_out_norm"], cfg.norm_eps))
        x = x + fused
    else:
        x = x + attn_out
    if policy is not None:
        x = policy.shard_resid(x)

    xn = rms_norm(x, lp["ln_ffn"], cfg.norm_eps)
    stats = None
    if desc.moe:
        # paged runs carry padding rows / inactive lanes: keep them out of
        # the routing statistics so the coordinator sees only real load
        mask = paged["valid"] if paged is not None else None
        y, stats = moe_mod.moe_layer(
            lp["moe"], cfg, xn, placement_row, source_ids=source_ids,
            n_sources=n_sources, policy=policy, collect_stats=collect_stats,
            token_mask=mask)
    else:
        y = mlp(lp["mlp"], xn, policy)
    if cfg.post_norms:
        y = rms_norm(y, lp["post_ffn_norm"], cfg.norm_eps)
    x = x + y
    if policy is not None:
        x = policy.shard_resid(x)
    return x, new_cache, stats


# ------------------------------------------------------------------ model
def _moe_positions(descs) -> List[int]:
    return [j for j, d in enumerate(descs) if d.moe]


def identity_placement(cfg: ModelConfig):
    n_moe = cfg.n_moe_layers
    if n_moe == 0:
        return jnp.zeros((0, 0), jnp.int32)
    return jnp.tile(jnp.arange(cfg.moe.n_experts, dtype=jnp.int32),
                    (n_moe, 1))


def migrate_params_for_placement(params, cfg, old_placement, new_placement):
    """Reorder the stacked physical expert weights after a placement update.

    ``placement`` rows are (n_moe_layers, E) = (ns * mp, E); layer l lives at
    super-block l // mp, moe-position index l % mp. Must be applied whenever
    a data-plane engine adopts a new placement, or logical experts would
    execute another expert's physical weights (see moe.migrate_expert_weights
    for the per-layer permutation and its cost accounting).
    """
    descs = period_descriptors(cfg)
    moe_pos = _moe_positions(descs)
    mp = len(moe_pos)
    if mp == 0:
        return params
    ns = n_super_blocks(cfg)
    old_r = jnp.asarray(old_placement, jnp.int32).reshape(ns, mp, -1)
    new_r = jnp.asarray(new_placement, jnp.int32).reshape(ns, mp, -1)
    blocks = dict(params["blocks"])
    for mi, j in enumerate(moe_pos):
        blk = dict(blocks[f"pos{j}"])
        blk["moe"] = jax.vmap(moe_mod.migrate_expert_weights)(
            blk["moe"], old_r[:, mi], new_r[:, mi])
        blocks[f"pos{j}"] = blk
    return dict(params, blocks=blocks)


def expert_weight_bytes(cfg) -> int:
    """Bytes of ONE expert's stacked FFN weights (w_gate + w_up + w_down):
    what an asynchronous prefetch moves per (layer, expert) relocation.
    Sizes ``PrefetchConfig.bytes_per_expert`` from the real model config."""
    m = cfg.moe
    if not m.enabled:
        return 0
    return 3 * cfg.d_model * m.d_expert * jnp.dtype(cfg.dtype).itemsize


def stage_expert_prefetch(params, cfg, cur_placement, target_placement):
    """Double-buffered expert-weight prefetch: build the params tree the
    model will need under ``target_placement`` WITHOUT touching the live
    ``params`` (``migrate_params_for_placement`` is functional — the staged
    copy and the serving copy coexist until the pointer flip adopts the
    staged one). The serving path never blocks on the copy; the flip is a
    pointer swap."""
    return migrate_params_for_placement(params, cfg, cur_placement,
                                        target_placement)


def superblock_forward(blk_params, cfg, descs, x, positions, blk_cache,
                       mode, blk_placement, source_ids, n_sources, policy,
                       collect_stats, paged=None):
    """One super-block (period of layers). Module-level so the roofline
    analyzer can lower it standalone (scan bodies are counted once by
    XLA cost analysis — launch/roofline.py scales by trip count)."""
    new_blk_cache = {} if blk_cache is not None else None
    stats_list = []
    mi = 0
    for j, desc in enumerate(descs):
        lp = blk_params[f"pos{j}"]
        c = blk_cache[f"pos{j}"] if blk_cache is not None else None
        prow = None
        if desc.moe:
            prow = (blk_placement[mi] if blk_placement is not None
                    else jnp.arange(cfg.moe.n_experts, dtype=jnp.int32))
            mi += 1
        x, nc, st = decoder_layer(
            lp, cfg, desc, x, positions, c, mode, prow, source_ids,
            n_sources, policy, collect_stats, paged)
        if blk_cache is not None:
            new_blk_cache[f"pos{j}"] = nc
        if st is not None:
            stats_list.append(st)
    stats = None
    if stats_list and collect_stats:
        stats = {k: jnp.stack([s[k] for s in stats_list])
                 for k in stats_list[0]}
    return x, new_blk_cache, stats


def _stack_forward(params, cfg, x, positions, cache, mode, placement,
                   source_ids, n_sources, policy, collect_stats, remat,
                   paged=None):
    """Scan over super-blocks. x: (B, S, D)."""
    descs = period_descriptors(cfg)
    ns = n_super_blocks(cfg)
    moe_pos = _moe_positions(descs)
    mp = len(moe_pos)

    placement_r = None
    if mp and placement is not None and placement.size:
        placement_r = placement.reshape(ns, mp, -1)

    def body(x, xs):
        blk_params, blk_cache, blk_placement = xs
        x, new_blk_cache, stats = superblock_forward(
            blk_params, cfg, descs, x, positions, blk_cache, mode,
            blk_placement, source_ids, n_sources, policy, collect_stats,
            paged)
        return x, (new_blk_cache, stats)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (params["blocks"], cache, placement_r)
    x, (new_cache, stats) = jax.lax.scan(body, x, xs)
    if stats is not None:
        stats = {k: v.reshape((ns * mp,) + v.shape[2:])
                 for k, v in stats.items()}
    return x, new_cache, stats


def _lead_forward(params, cfg, x, positions, cache, mode, policy,
                  paged=None):
    """The leading dense layers, one at a time, before the scan. Their
    stack ``params["lead"]`` (and pool entry) is indexed by layer; at one
    layer the index is a reshape, not a copy."""
    new = []
    for i in range(cfg.n_dense_lead):
        at = lambda a, i=i: a[i]
        c = None if cache is None else jax.tree.map(at, cache)
        x, nc, _ = decoder_layer(jax.tree.map(at, params["lead"]), cfg, LEAD,
                                 x, positions, c, mode, None, None, 0,
                                 policy, False, paged)
        new.append(nc)
    if cache is None:
        return x, None
    return x, jax.tree.map(lambda *a: jnp.stack(a), *new)


def _forward(params, cfg, x, positions, cache, mode, placement, source_ids,
             n_sources, policy, collect_stats, remat, paged=None):
    """The leading dense layers (if any), then the scan over super-blocks.
    Returns (x, new_cache, stats of the scanned MoE layers)."""
    if not cfg.n_dense_lead:
        return _stack_forward(params, cfg, x, positions, cache, mode,
                              placement, source_ids, n_sources, policy,
                              collect_stats, remat, paged)
    blk_cache = lead_cache = None
    if cache is not None:
        blk_cache = {k: v for k, v in cache.items() if k != "lead"}
        lead_cache = cache["lead"]
    x, lead_cache = _lead_forward(params, cfg, x, positions, lead_cache,
                                  mode, policy, paged)
    x, blk_cache, stats = _stack_forward(
        params, cfg, x, positions, blk_cache, mode, placement, source_ids,
        n_sources, policy, collect_stats, remat, paged)
    new_cache = None if cache is None else dict(blk_cache, lead=lead_cache)
    return x, new_cache, stats


def _inputs_to_embed(params, cfg, batch):
    if cfg.input_mode == "embeddings" and "embeddings" in batch:
        return batch["embeddings"]
    return embed_tokens(params["embed"], cfg, batch["tokens"])


def loss_fn(params, cfg: ModelConfig, batch, *, placement=None,
            policy=None, aux_weight: float = 0.01):
    """batch: {tokens|embeddings, labels, (mask)} -> (loss, metrics)."""
    x = _inputs_to_embed(params, cfg, batch)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if policy is not None:
        x = policy.shard_resid(x)
    if placement is None:
        placement = identity_placement(cfg)
    x, _, stats = _forward(
        params, cfg, x, positions, None, "train", placement, None, 0,
        policy, collect_stats=cfg.moe.enabled, remat=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params["embed"], cfg, x)
    mask = batch.get("mask")
    ce = cross_entropy(logits, batch["labels"], mask)
    aux = jnp.asarray(0.0, jnp.float32)
    if stats is not None and "aux_loss" in stats:
        aux = jnp.mean(stats["aux_loss"])
    metrics = {"ce": ce, "aux": aux}
    return ce + aux_weight * aux, metrics


def prefill(params, cfg: ModelConfig, batch, cache, *, placement=None,
            source_ids=None, n_sources: int = 0, policy=None,
            collect_stats: bool = True):
    """batch: {tokens|embeddings (B,S), lengths (B,)} -> (logits, cache, stats)."""
    x = _inputs_to_embed(params, cfg, batch)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if policy is not None:
        x = policy.shard_resid(x)
    if placement is None:
        placement = identity_placement(cfg)
    x, cache, stats = _forward(
        params, cfg, x, positions, cache, "prefill", placement, source_ids,
        n_sources, policy, collect_stats, remat=False)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    lengths = batch.get("lengths")
    if lengths is None:
        last = x[:, -1]
    else:
        last = x[jnp.arange(B), jnp.clip(lengths - 1, 0, S - 1)]
    logits = lm_logits(params["embed"], cfg, last)
    return logits, cache, stats


def prefill_chunk_paged(params, cfg: ModelConfig, batch, pages, *,
                        block_tables, placement=None, source_ids=None,
                        n_sources: int = 0, collect_stats: bool = True,
                        attn_backend: str = "auto", interpret: bool = False):
    """Chunked prefill into the paged KV pool.

    batch: {tokens (B, S), chunk_starts (B,), chunk_lens (B,)} — row b
    prefills prompt positions [chunk_starts[b], chunk_starts[b] + chunk_lens[b])
    (rows past chunk_lens are padding and write to the garbage page).
    Earlier chunks' KV is read back through the block table, so attention is
    exact across chunk boundaries. Returns (logits_at_chunk_end (B, V),
    pages, stats) — logits are only meaningful when the chunk completes the
    prompt.
    """
    x = _inputs_to_embed(params, cfg, batch)
    B, S = x.shape[:2]
    starts = batch["chunk_starts"].astype(jnp.int32)
    lens = batch["chunk_lens"].astype(jnp.int32)
    positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    paged = {"block_tables": block_tables,
             "valid": jnp.arange(S, dtype=jnp.int32)[None] < lens[:, None],
             "ctx_lens": starts + lens,
             "decode_mask": batch.get("decode_mask"),
             "backend": attn_backend, "interpret": interpret}
    if placement is None:
        placement = identity_placement(cfg)
    x, pages, stats = _forward(
        params, cfg, x, positions, pages, "paged_prefill", placement,
        source_ids, n_sources, None, collect_stats, remat=False, paged=paged)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[jnp.arange(B), jnp.clip(lens - 1, 0, S - 1)]
    logits = lm_logits(params["embed"], cfg, last)
    return logits, pages, stats


def mixed_step_paged(params, cfg: ModelConfig, batch, pages, *,
                     block_tables, placement=None, source_ids=None,
                     n_sources: int = 0, collect_stats: bool = True,
                     attn_backend: str = "auto", interpret: bool = False):
    """One fused mixed dispatch: prefill chunk lanes AND 1-token decode
    lanes in the same ragged (B, S) batch — one model call, one MoE
    all-to-all, for a whole StepPlan mixed group.

    batch extends the :func:`prefill_chunk_paged` contract with
    ``decode_mask (B,) bool``: a decode row has ``chunk_lens == 1``,
    ``chunk_starts`` at the request's written KV length, and its last
    sampled token at ``tokens[b, 0]``. Decode rows write KV to the same
    page slot a split decode step would and their logits come out
    bit-exact with :func:`decode_step_paged` (the row-0 attention output
    is recomputed through the paged decode kernel — the prefill flash
    path's bf16 softmax-weight cast would otherwise diverge in low
    bits). Prefill rows are untouched, so the whole call is bit-exact
    with the split decode+prefill dispatches it replaces. MoE B/A stats
    mask padding exactly as batched prefill does (decode rows contribute
    their one real token).

    Returns (logits (B, V), pages, stats): row b's logits are the
    next-token distribution for decode rows and for prompt-completing
    chunks, as in the split entry points.
    """
    assert "decode_mask" in batch, "mixed step needs batch['decode_mask']"
    return prefill_chunk_paged(
        params, cfg, batch, pages, block_tables=block_tables,
        placement=placement, source_ids=source_ids, n_sources=n_sources,
        collect_stats=collect_stats, attn_backend=attn_backend,
        interpret=interpret)


def decode_step_paged(params, cfg: ModelConfig, tokens, pages, lengths, *,
                      block_tables, active=None, placement=None,
                      source_ids=None, n_sources: int = 0,
                      collect_stats: bool = True, attn_backend: str = "auto",
                      interpret: bool = False):
    """One batched decode token against the paged KV pool.

    tokens (B,) int32; lengths (B,) current context per lane (the new token
    is written at position lengths[b]); block_tables (B, NB); active (B,)
    bool marks live lanes — inactive lanes write to the garbage page and
    emit zero attention.
    """
    x = embed_tokens(params["embed"], cfg, tokens[:, None])   # (B, 1, D)
    lengths = lengths.astype(jnp.int32)
    positions = lengths[:, None]
    if active is None:
        active = jnp.ones((tokens.shape[0],), bool)
    paged = {"block_tables": block_tables,
             "valid": active[:, None],
             "ctx_lens": jnp.where(active, lengths + 1, 0),
             "backend": attn_backend, "interpret": interpret}
    if placement is None:
        placement = identity_placement(cfg)
    x, pages, stats = _forward(
        params, cfg, x, positions, pages, "paged_decode", placement,
        source_ids, n_sources, None, collect_stats, remat=False, paged=paged)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params["embed"], cfg, x[:, 0])
    return logits, pages, stats


def decode_step(params, cfg: ModelConfig, tokens, cache, lengths, *,
                placement=None, source_ids=None, n_sources: int = 0,
                policy=None, collect_stats: bool = True):
    """tokens (B,) int32; lengths (B,) current context length per row."""
    x = embed_tokens(params["embed"], cfg, tokens[:, None])   # (B, 1, D)
    positions = lengths[:, None].astype(jnp.int32)
    if placement is None:
        placement = identity_placement(cfg)
    x, cache, stats = _forward(
        params, cfg, x, positions, cache, "decode", placement, source_ids,
        n_sources, policy, collect_stats, remat=False)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params["embed"], cfg, x[:, 0])
    return logits, cache, stats
