"""Shared model layers: norms, rotary embeddings, MLPs, initializers."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------- init utils
def dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    """Truncated-normal fan-in init (what LM stacks actually use)."""
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------- norms
def rms_norm(x, scale, eps: float = 1e-6):
    """RMSNorm in fp32 accumulation, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def softcap(x, cap: float):
    """Logit soft-capping (gemma2): cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------- rotary
def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim)).astype(np.float32)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32. Interleaved-pair RoPE."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta))          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin = jnp.sin(angles)[..., None, :]                        # (..., S, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x, positions, theta: float):
    """RoPE over (even, odd) pairs (DeepSeek's ``rope_interleave``): the
    pairs are gathered into halves, evens first, and rotated as
    :func:`apply_rope` rotates halves. The result stays in that order, as
    Hugging Face's ``apply_rotary_pos_emb_interleave`` leaves it."""
    hd = x.shape[-1]
    halves = x.reshape(*x.shape[:-1], hd // 2, 2).swapaxes(-1, -2)
    return apply_rope(halves.reshape(x.shape), positions, theta)


# ---------------------------------------------------------------- MLP
def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), 0, dtype),
        "w_up": dense_init(k2, (d_model, d_ff), 0, dtype),
        "w_down": dense_init(k3, (d_ff, d_model), 0, dtype),
    }


def mlp(params, x, policy=None):
    """SwiGLU MLP. x: (..., D)."""
    gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    if policy is not None:
        h = policy.shard_ffn_act(h)
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# ---------------------------------------------------------------- embedding
def init_embed(key, cfg):
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"embedding": embed_init(k1, (cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), 0, dt)
    return p


def embed_tokens(params, cfg, tokens):
    x = jnp.take(params["embedding"], tokens, axis=0)
    if cfg.family in ("dense", "moe", "vlm") or cfg.tie_embeddings:
        # gemma-style sqrt(d) embedding scale is applied for tied-embedding
        # families; harmless rescale elsewhere is avoided.
        if cfg.tie_embeddings:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(params, cfg, x):
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embedding"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["lm_head"])
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits


def cross_entropy(logits, labels, mask=None):
    """Mean token cross-entropy in fp32. logits (..., V), labels (...) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
