"""Plain float32 forward of a DeepSeek-V3 configuration without low-rank
queries (Kanana-2 and the like).

It follows the published DeepSeek-V3 layer equations (DeepSeek-V3
technical report, sections 2.1.1-2.1.2; ``DeepseekV3`` in Hugging Face
transformers): pre-RMSNorm blocks. Attention is multi-head latent
attention in its plain, non-absorbed form: per-head queries
``q = h W_q`` of ``nope + rope``; ``[c | k_pe] = h W_kv_a``; the latent
``c`` RMS-normed and up-projected to per-head keys and values,
``[k_nope | v] = c W_kv_b``; RoPE over interleaved pairs
(``rope_interleave``: the pairs gathered into halves, then rotated) on the
queries' last ``rope`` values and on ``k_pe``, which every head shares;
causal softmax at scale ``(nope + rope) ** -0.5``; ``W_o`` on the heads'
values. The first ``first_k_dense_replace`` layers have a SwiGLU; the
others a router of sigmoid scores ``s = sigmoid(h W_r)``, the top
``num_experts_per_tok`` of ``s + b`` chosen (``noaux_tc`` with one group;
``b`` the correction bias), gated by ``s`` over the chosen renormalized
(``norm_topk_prob``) times ``routed_scaling_factor``, plus the shared
experts' SwiGLU of width ``n_shared_experts * moe_intermediate_size``; a
final RMSNorm and an untied head.

Departures, none of which changes the mathematics: norm weights are kept
as an offset from one (``weight = 1 + scale``), as the weight maker draws
them; every expert is computed for every token and masked by its gate,
which is plain and costs ``n_routed_experts / num_experts_per_tok`` times
the arithmetic of routing.

Everything is float32 at ``jax.default_matmul_precision("highest")``,
computed as ``reference/qwen3.py`` computes it: sequences packed into one
stream with segment ids, one layer at a time with that layer's weights
made from the seed, queries in blocks. ``quant="fp8"`` is the control:
every weight matrix product takes float8 (e4m3) operands, the precision
below the configuration's bfloat16. Nothing of the program is imported.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W
from reference.qwen3 import (HEAD_ROWS, NEG, _frozen, _head_jit, _mm, _pack,
                             _rms)

Q_BLOCK = 256        # query rows per attention block


def _rope_interleaved(x, pos, theta):
    """x (T, H, d): pairs (x[2i], x[2i+1]) gathered into halves, evens
    first, then rotated by position as halves are."""
    T, H, d = x.shape
    x = x.reshape(T, H, d // 2, 2).swapaxes(-1, -2).reshape(T, H, d)
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, pos, seg, scale):
    """Causal attention within each segment, one block of queries at a
    time. q, k (T, H, dk); v (T, H, dv) -> (T, H * dv)."""
    T, H, dk = q.shape
    nb = T // Q_BLOCK

    def one(args):
        qi, pi, si = args
        s = jnp.einsum("qhd,thd->hqt", qi, k) * scale
        ok = (si[:, None] == seg[None, :]) & (pos[None, :] <= pi[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], s, NEG), axis=-1)
        return jnp.einsum("hqt,thd->qhd", p, v)

    out = jax.lax.map(one, (q.reshape(nb, Q_BLOCK, H, dk),
                            pos.reshape(nb, Q_BLOCK),
                            seg.reshape(nb, Q_BLOCK)))
    return out.reshape(T, -1)


def _swiglu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def _ffn(h, w, m, quant):
    if "mlp/w_gate" in w:                         # a leading dense layer
        return _swiglu(h, w["mlp/w_gate"], w["mlp/w_up"], w["mlp/w_down"],
                       quant)
    T = h.shape[0]
    E, K = m["n_routed_experts"], m["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(h, w["moe/router"], quant))
    _, idx = jax.lax.top_k(s + w["moe/router_bias"], K)
    top = jnp.take_along_axis(s, idx, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) \
        * m["routed_scaling_factor"]
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].set(top)

    def expert(acc, xs):
        wg, wu, wd, ge = xs
        return acc + ge[:, None] * _swiglu(h, wg, wu, wd, quant), None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"],
                           gates.T))
    return acc + _swiglu(h, w["moe/shared/w_gate"], w["moe/shared/w_up"],
                         w["moe/shared/w_down"], quant)


def _layer(x, pos, seg, w, m, quant):
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    T = x.shape[0]
    h = _rms(x, w["ln_attn"], eps)
    q = _mm(h, w["attn/wq"], quant).reshape(T, H, nope + rope)
    kv = _mm(h, w["attn/wkv_a"], quant)
    c = _rms(kv[:, :r], w["attn/kv_norm"], eps)
    k_pe = _rope_interleaved(kv[:, None, r:], pos, theta)
    up = _mm(c, w["attn/wkv_b"], quant).reshape(T, H, nope + dv)
    q = jnp.concatenate(
        [q[..., :nope], _rope_interleaved(q[..., nope:], pos, theta)], -1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe, (T, H, rope))], -1)
    att = _attend(q, k, up[..., nope:], pos, seg,
                  1.0 / np.sqrt(nope + rope))
    x = x + _mm(att, w["attn/wo"], quant)
    return x + _ffn(_rms(x, w["ln_ffn"], eps), w, m, quant)


@functools.partial(jax.jit, static_argnames=("mf", "quant"))
def _layer_jit(x, pos, seg, w, *, mf, quant):
    with jax.default_matmul_precision("highest"):
        return _layer(x, pos, seg, w, dict(mf), quant)


def final_rows(m: dict, seed: int, seqs: Sequence[Sequence[int]],
               rows: np.ndarray, quant: str = "f32") -> jax.Array:
    """Last hidden state at packed ``rows`` of the packed ``seqs``."""
    toks, pos, seg = _pack(seqs)
    g = W.make_global(m, seed)
    x = jnp.take(g["embed/embedding"], jnp.asarray(toks), axis=0) \
        .astype(jnp.float32)
    pos, seg = jnp.asarray(pos), jnp.asarray(seg)
    mf = _frozen(m)
    for layer in range(m["num_hidden_layers"]):
        w = W.make_layer(m, seed, layer)
        x = _layer_jit(x, pos, seg, w, mf=mf, quant=quant)
        del w
    return x[jnp.asarray(rows)]


def logits(m: dict, seed: int, seqs: Sequence[Sequence[int]],
           rows: np.ndarray) -> np.ndarray:
    """Float32 logits at packed ``rows`` of the packed ``seqs``."""
    g = W.make_global(m, seed)
    h = final_rows(m, seed, seqs, rows)
    with jax.default_matmul_precision("highest"):
        z = _mm(_rms(h, g["final_norm"], m["rms_norm_eps"]),
                g["embed/lm_head"], "f32")
    return np.asarray(z)


def served_logits(m: dict, seed: int, prompts: Sequence[Sequence[int]],
                  outputs: Sequence[Sequence[int]],
                  control: bool = False) -> Dict[str, List[np.ndarray]]:
    """Teacher-forced reference over each prompt and its served tokens.

    Returns, per request, arrays over its served tokens: ``best`` (the
    reference's largest logit), ``served`` (its logit of the served
    token) and, with ``control``, ``control`` (its logit of the token the
    float8 control puts first)."""
    seqs = [list(p) + list(o[:-1]) for p, o in zip(prompts, outputs)]
    rows, offset = [], 0
    for p, o, s in zip(prompts, outputs, seqs):
        rows += [offset + len(p) - 1 + j for j in range(len(o))]
        offset += len(s)
    rows = np.asarray(rows, np.int64)
    served = np.concatenate([np.asarray(o, np.int32) for o in outputs])
    g = W.make_global(m, seed)
    mf = _frozen(m)

    def head(h, quant, toks):
        best, arg, tok = [], [], []
        for i in range(0, h.shape[0], HEAD_ROWS):
            hb, sb = h[i:i + HEAD_ROWS], toks[i:i + HEAD_ROWS]
            k = hb.shape[0]
            if k < HEAD_ROWS:
                hb = jnp.pad(hb, ((0, HEAD_ROWS - k), (0, 0)))
                sb = np.pad(sb, (0, HEAD_ROWS - k))
            b, a, t = _head_jit(hb, g["final_norm"], g["embed/lm_head"],
                                jnp.asarray(sb), mf=mf, quant=quant)
            best.append(np.asarray(b)[:k])
            arg.append(np.asarray(a)[:k])
            tok.append(np.asarray(t)[:k])
        return np.concatenate(best), np.concatenate(arg), np.concatenate(tok)

    h = final_rows(m, seed, seqs, rows)
    best, _, served_logit = head(h, "f32", served)
    out = {"best": best, "served": served_logit}
    if control:
        hq = final_rows(m, seed, seqs, rows, quant="fp8")
        _, arg_q, _ = head(hq, "fp8", served)
        # the float32 reference's logit of the control's first choice
        out["control"] = head(h, "f32", arg_q)[2]
    cuts = np.cumsum([len(o) for o in outputs])[:-1]
    return {k: np.split(v, cuts) for k, v in out.items()}
