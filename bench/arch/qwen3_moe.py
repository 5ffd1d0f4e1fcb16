"""Qwen3-MoE (hf:Qwen/Qwen3-30B-A3B), and dense Qwen3 where a
configuration has no experts: what the harness knows of the block.

Every layer is alike: pre-RMSNorm, grouped-query attention with an
RMSNorm over each head of q and k, and a feed-forward that is top-k of
``num_experts`` SwiGLU experts, or one SwiGLU where ``num_experts`` is 0.
The program's param tree, as :func:`leaves` lists it (period one, so one
stack ``blocks/pos0`` over all ``L`` layers)::

    embed/embedding (V, D), embed/lm_head (D, V)           bfloat16
    blocks/pos0/ln_attn, ln_ffn (L, D)                     float32
    blocks/pos0/attn/wq (L, D, Hq*hd), wk, wv (L, D, Hkv*hd),
                    wo (L, Hq*hd, D)                       bfloat16
    blocks/pos0/attn/q_norm, k_norm (L, hd)                float32
    blocks/pos0/moe/router (L, D, E)                       float32
    blocks/pos0/moe/w_gate, w_up (L, E, D, F), w_down (L, E, F, D)
    blocks/pos0/mlp/w_gate, w_up (L, D, F), w_down (L, F, D)
    final_norm (D,)                                        float32

Scales follow the program's own initializers (truncated normal of std
``1/sqrt(fan_in)``, embedding normal 0.02); norm scales, which the program
keeps as an offset from one, are drawn at std 0.1 so that they matter.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

from harness.weights import BF16, F32, Leaf
from reference.qwen3 import served_logits  # noqa: F401  (the reference)


def is_moe(m: dict) -> bool:
    return int(m.get("num_experts", 0)) > 0


def program_config(m: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig
    moe = is_moe(m)
    if m.get("mlp_only_layers") or int(m.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("every layer must be alike (all MoE or all dense)")
    return ModelConfig(
        name=m["name"], family="moe" if moe else "dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        d_ff=m["moe_intermediate_size"] if moe else m["intermediate_size"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        moe=MoEConfig(n_experts=m["num_experts"],
                      top_k=m["num_experts_per_tok"],
                      d_expert=m["moe_intermediate_size"], moe_every=1)
        if moe else MoEConfig(),
        qk_norm=True, rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), dtype="bfloat16")


def leaves(m: dict) -> List[Leaf]:
    D, V, hd = m["hidden_size"], m["vocab_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    s = lambda n: 1.0 / np.sqrt(n)
    layer = [("ln_attn", (D,), F32, "normal", 0.1),
             ("ln_ffn", (D,), F32, "normal", 0.1),
             ("attn/wq", (D, q), BF16, "trunc", s(D)),
             ("attn/wk", (D, kv), BF16, "trunc", s(D)),
             ("attn/wv", (D, kv), BF16, "trunc", s(D)),
             ("attn/wo", (q, D), BF16, "trunc", s(q)),
             ("attn/q_norm", (hd,), F32, "normal", 0.1),
             ("attn/k_norm", (hd,), F32, "normal", 0.1)]
    if is_moe(m):
        E, F = m["num_experts"], m["moe_intermediate_size"]
        layer += [("moe/router", (D, E), F32, "trunc", s(D)),
                  ("moe/w_gate", (E, D, F), BF16, "trunc", s(D)),
                  ("moe/w_up", (E, D, F), BF16, "trunc", s(D)),
                  ("moe/w_down", (E, F, D), BF16, "trunc", s(F))]
    else:
        F = m["intermediate_size"]
        layer += [("mlp/w_gate", (D, F), BF16, "trunc", s(D)),
                  ("mlp/w_up", (D, F), BF16, "trunc", s(D)),
                  ("mlp/w_down", (F, D), BF16, "trunc", s(F))]
    every = tuple(range(m["num_hidden_layers"]))
    return ([Leaf("embed/embedding", (V, D), BF16, "normal", 0.02),
             Leaf("embed/lm_head", (D, V), BF16, "trunc", s(D)),
             Leaf("final_norm", (D,), F32, "normal", 0.1)]
            + [Leaf(*lf, layers=every, stack="blocks/pos0") for lf in layer])


def token_flops(m: dict, layer: int) -> float:
    """Weight products of one token in one layer (attention projections
    and the feed-forward, top-k experts only)."""
    D, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    proj = 2.0 * D * (q + 2 * kv) + 2.0 * q * D
    if is_moe(m):
        ffn = (2.0 * D * m["num_experts"]
               + m["num_experts_per_tok"] * 6.0 * D
               * m["moe_intermediate_size"])
    else:
        ffn = 6.0 * D * m["intermediate_size"]
    return proj + ffn


def attention_flops(m: dict, layer: int, ctx: np.ndarray) -> np.ndarray:
    """Scores and weighted sum of one query token over ``ctx`` tokens."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] * ctx


def paged_decode_cost(ctx_lens: Sequence[int], m: dict, itemsize: int = 2):
    """One call of the paged decode kernel (one layer): attention of one
    query token per lane over its live context. Bytes: the live K and V
    tokens, the queries and the outputs."""
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    ctx = np.asarray(ctx_lens, np.float64)
    ops = float(np.sum(4.0 * hq * hd * ctx))
    kv = float(np.sum(ctx)) * 2 * hkv * hd * itemsize
    qo = len(ctx) * 2 * hq * hd * itemsize
    return ops, kv + qo
