"""Plain float32 forward of a Qwen3 configuration, dense or MoE.

It follows the published Qwen3 architecture (Qwen3 and Qwen3-MoE in
Hugging Face transformers): pre-RMSNorm blocks; grouped-query attention
with an RMSNorm over each head of q and k before rotary embedding
(split-half rotation, ``rope_theta`` from the configuration), causal
softmax at scale ``1/sqrt(head_dim)``; a SwiGLU feed-forward, or in MoE
layers a softmax router over all experts, the top ``num_experts_per_tok``
renormalized (``norm_topk_prob``), and the gate-weighted sum of those
experts' SwiGLU outputs; a final RMSNorm and an untied head.

Departures, none of which changes the mathematics: norm weights are kept
as an offset from one (``weight = 1 + scale``), as the weight maker draws
them; every expert is computed for every token and masked by its gate,
which costs sixteen times the arithmetic of routing and is plain.

Everything is float32 at ``jax.default_matmul_precision("highest")``.
Sequences are packed into one stream with segment ids, padded to a
multiple of ``BLOCK``, and run one layer at a time with that layer's
weights made from the seed, so that a few long requests fit one chip.
The weights are the benchmark's own (``harness/weights.py``); nothing of
the program is imported.

``quant="fp8"`` is the control: every weight matrix product takes
float8 (e4m3) operands, activations scaled per row and weights per output
column, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W

BLOCK = 512          # query rows per attention block, and packing unit
HEAD_ROWS = 256      # output rows per head product
NEG = -1e30


E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 after scaling the largest magnitude along
    ``axis`` to the format's largest finite value."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return a @ w


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, pos, seg):
    """Causal attention within each segment, one block of queries at a
    time. q (T, Hq, hd); k, v (T, Hkv, hd)."""
    T, Hq, hd = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    nb = T // BLOCK
    qb = q.reshape(nb, BLOCK, Hkv, G, hd)

    def one(args):
        qi, pi, si = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / np.sqrt(hd)
        ok = (si[:, None] == seg[None, :]) & (pos[None, :] <= pi[:, None])
        s = jnp.where(ok[None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(one, (qb, pos.reshape(nb, BLOCK),
                            seg.reshape(nb, BLOCK)))
    return out.reshape(T, Hq * hd)


def _ffn(h, w, m, quant):
    if not W.is_moe(m):
        g = _mm(h, w["mlp/w_gate"], quant)
        u = _mm(h, w["mlp/w_up"], quant)
        return _mm(jax.nn.silu(g) * u, w["mlp/w_down"], quant)
    T = h.shape[0]
    E, K = m["num_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(h, w["moe/router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, K)
    if m.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].set(top)

    def expert(acc, xs):
        wg, wu, wd, ge = xs
        y = jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant)
        return acc + ge[:, None] * _mm(y, wd, quant), None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w["moe/w_gate"], w["moe/w_up"],
                           w["moe/w_down"], gates.T))
    return acc


def _layer(x, pos, seg, w, m, quant):
    eps, hd = m["rms_norm_eps"], m["head_dim"]
    T = x.shape[0]
    h = _rms(x, w["ln_attn"], eps)
    q = _mm(h, w["attn/wq"], quant).reshape(T, -1, hd)
    k = _mm(h, w["attn/wk"], quant).reshape(T, -1, hd)
    v = _mm(h, w["attn/wv"], quant).reshape(T, -1, hd)
    q = _rope(_rms(q, w["attn/q_norm"], eps), pos, m["rope_theta"])
    k = _rope(_rms(k, w["attn/k_norm"], eps), pos, m["rope_theta"])
    x = x + _mm(_attend(q, k, v, pos, seg), w["attn/wo"], quant)
    return x + _ffn(_rms(x, w["ln_ffn"], eps), w, m, quant)


def _frozen(m: dict) -> Tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.partial(jax.jit, static_argnames=("mf", "quant"))
def _layer_jit(x, pos, seg, w, *, mf, quant):
    with jax.default_matmul_precision("highest"):
        return _layer(x, pos, seg, w, dict(mf), quant)


@functools.partial(jax.jit, static_argnames=("mf", "quant"))
def _head_jit(h, final_norm, lm_head, served, *, mf, quant):
    """Rows of the last hidden state -> (max logit, argmax, logit of the
    served token) per row."""
    m = dict(mf)
    with jax.default_matmul_precision("highest"):
        z = _mm(_rms(h, final_norm, m["rms_norm_eps"]), lm_head, quant)
    return (jnp.max(z, -1), jnp.argmax(z, -1).astype(jnp.int32),
            jnp.take_along_axis(z, served[:, None], -1)[:, 0])


def _pack(seqs: Sequence[Sequence[int]]):
    toks, pos, seg = [], [], []
    for i, s in enumerate(seqs):
        toks += list(s)
        pos += list(range(len(s)))
        seg += [i] * len(s)
    T = -(-len(toks) // BLOCK) * BLOCK
    pad = T - len(toks)
    return (np.asarray(toks + [0] * pad, np.int32),
            np.asarray(pos + [0] * pad, np.int32),
            np.asarray(seg + [-1] * pad, np.int32))


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
        n = h.shape[0]
        for i in range(0, n, HEAD_ROWS):
            hb = h[i:i + HEAD_ROWS]
            sb = toks[i:i + HEAD_ROWS]
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
