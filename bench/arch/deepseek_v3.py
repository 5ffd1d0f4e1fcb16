"""DeepSeek-V3's block without low-rank queries (``model_type``
``deepseek_v3``; hf:kakaocorp/kanana-2-30b-a3b-instruct-2601): what the
harness knows of it.

Every layer is pre-RMSNorm latent attention (MLA): queries of ``nope +
rope`` a head from ``wq``; one ``kv_lora_rank`` latent, RMS-normed, and one
roped key shared by the heads from ``wkv_a``; per-head keys and values
up-projected from the latent by ``wkv_b``. The first
``first_k_dense_replace`` layers have a SwiGLU of ``intermediate_size``;
the rest route each token to ``num_experts_per_tok`` of
``n_routed_experts`` SwiGLU experts by sigmoid scores, chosen with a
per-expert correction bias, gated by the renormalized unbiased scores
times ``routed_scaling_factor``, beside ``n_shared_experts`` shared ones.
The program's param tree, as :func:`leaves` lists it (``lead`` stacks the
``Ld`` leading dense layers, ``blocks/pos0`` the ``Lm`` MoE layers)::

    embed/embedding (V, D), embed/lm_head (D, V)            bfloat16
    final_norm (D,)                                         float32
    <stack>/ln_attn, ln_ffn (L, D); attn/kv_norm (L, r)     float32
    <stack>/attn/wq (L, D, H*(nope+rope)), wkv_a (L, D, r+rope),
            wkv_b (L, r, H*(nope+v)), wo (L, H*v, D)        bfloat16
    lead/mlp/w_gate, w_up (Ld, D, I), w_down (Ld, I, D)     bfloat16
    blocks/pos0/moe/router (Lm, D, E), router_bias (Lm, E)  float32
    blocks/pos0/moe/w_gate, w_up (Lm, E, D, F), w_down (Lm, E, F, D)
    blocks/pos0/moe/shared/w_gate, w_up (Lm, D, S*F), w_down (Lm, S*F, D)

Scales follow the program's initializers (truncated normal of std
``1/sqrt(fan_in)``, embedding normal 0.02); norm scales, kept as an
offset from one, are drawn at std 0.1. The correction bias is learned in
the published model and not in its config: it is drawn normal at the std
the configuration's ``assumed`` gives.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

from harness.weights import BF16, F32, Leaf
from reference.deepseek_v3 import served_logits  # noqa: F401  (the reference)


def program_config(m: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    if m.get("rope_scaling") is not None or not m["rope_interleave"]:
        raise ValueError("RoPE must be interleaved and unscaled")
    if not m["norm_topk_prob"] or m["scoring_func"] != "sigmoid":
        raise ValueError("the router must be sigmoid, renormalized")
    return ModelConfig(
        name=m["name"], family="moe",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        head_dim=m["v_head_dim"],
        moe=MoEConfig(n_experts=m["n_routed_experts"],
                      top_k=m["num_experts_per_tok"],
                      d_expert=m["moe_intermediate_size"],
                      n_shared_experts=m["n_shared_experts"],
                      d_shared=m["moe_intermediate_size"],
                      moe_every=m["moe_layer_freq"], scoring="sigmoid",
                      correction_bias=m["topk_method"] == "noaux_tc",
                      routed_scaling_factor=float(
                          m["routed_scaling_factor"]),
                      n_group=m["n_group"], topk_group=m["topk_group"]),
        mla=MLAConfig(kv_lora_rank=m["kv_lora_rank"],
                      qk_nope_head_dim=m["qk_nope_head_dim"],
                      qk_rope_head_dim=m["qk_rope_head_dim"],
                      v_head_dim=m["v_head_dim"],
                      q_lora_rank=m["q_lora_rank"] or 0),
        n_dense_lead=m["first_k_dense_replace"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), dtype="bfloat16")


def _dense(m: dict, layer: int) -> bool:
    return layer < m["first_k_dense_replace"]


def leaves(m: dict) -> List[Leaf]:
    D, V, H = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    r, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, v = m["qk_nope_head_dim"], m["v_head_dim"]
    E, F, I = m["n_routed_experts"], m["moe_intermediate_size"], \
        m["intermediate_size"]
    SF = m["n_shared_experts"] * F
    s = lambda n: 1.0 / np.sqrt(n)
    attn = [("ln_attn", (D,), F32, "normal", 0.1),
            ("ln_ffn", (D,), F32, "normal", 0.1),
            ("attn/wq", (D, H * (nope + rope)), BF16, "trunc", s(D)),
            ("attn/wkv_a", (D, r + rope), BF16, "trunc", s(D)),
            ("attn/kv_norm", (r,), F32, "normal", 0.1),
            ("attn/wkv_b", (r, H * (nope + v)), BF16, "trunc", s(r)),
            ("attn/wo", (H * v, D), BF16, "trunc", s(H * v))]
    dense = [("mlp/w_gate", (D, I), BF16, "trunc", s(D)),
             ("mlp/w_up", (D, I), BF16, "trunc", s(D)),
             ("mlp/w_down", (I, D), BF16, "trunc", s(I))]
    moe = [("moe/router", (D, E), F32, "trunc", s(D)),
           ("moe/router_bias", (E,), F32, "normal",
            float(m["assumed"]["e_score_correction_bias_std"])),
           ("moe/w_gate", (E, D, F), BF16, "trunc", s(D)),
           ("moe/w_up", (E, D, F), BF16, "trunc", s(D)),
           ("moe/w_down", (E, F, D), BF16, "trunc", s(F)),
           ("moe/shared/w_gate", (D, SF), BF16, "trunc", s(D)),
           ("moe/shared/w_up", (D, SF), BF16, "trunc", s(D)),
           ("moe/shared/w_down", (SF, D), BF16, "trunc", s(SF))]
    layers = range(m["num_hidden_layers"])
    lead = tuple(l for l in layers if _dense(m, l))
    rest = tuple(l for l in layers if not _dense(m, l))
    out = [Leaf("embed/embedding", (V, D), BF16, "normal", 0.02),
           Leaf("embed/lm_head", (D, V), BF16, "trunc", s(D)),
           Leaf("final_norm", (D,), F32, "normal", 0.1)]
    if lead:
        out += [Leaf(*lf, layers=lead, stack="lead") for lf in attn + dense]
    return out + [Leaf(*lf, layers=rest, stack="blocks/pos0")
                  for lf in attn + moe]


def token_flops(m: dict, layer: int) -> float:
    """Weight products of one token in one layer, in the published
    (non-absorbed) form: the query, latent, up and output projections, and
    the dense feed-forward or the router, the top-k experts and the shared
    experts."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    r, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, v = m["qk_nope_head_dim"], m["v_head_dim"]
    proj = 2.0 * D * (H * (nope + rope) + r + rope) \
        + 2.0 * r * H * (nope + v) + 2.0 * H * v * D
    if _dense(m, layer):
        return proj + 6.0 * D * m["intermediate_size"]
    F = m["moe_intermediate_size"]
    return proj + 2.0 * D * m["n_routed_experts"] \
        + (m["num_experts_per_tok"] + m["n_shared_experts"]) * 6.0 * D * F


def attention_flops(m: dict, layer: int, ctx: np.ndarray) -> np.ndarray:
    """Scores over ``nope + rope`` and the weighted sum over ``v`` of one
    query token over ``ctx`` tokens, every head."""
    return 2.0 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]) * ctx


def paged_decode_cost(ctx_lens: Sequence[int], m: dict, itemsize: int = 2):
    """One call of the latent decode kernel (one layer), absorbed: each
    head scores a query of ``r + rope`` against every live row and sums the
    rows' ``r`` latent values. Bytes: each live row's ``r + rope`` values
    read once, the queries and the outputs."""
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    W = r + m["qk_rope_head_dim"]
    ctx = np.asarray(ctx_lens, np.float64)
    ops = float(np.sum(2.0 * H * (W + r) * ctx))
    rows = float(np.sum(ctx)) * W * itemsize
    qo = len(ctx) * H * (W + r) * itemsize
    return ops, rows + qo
