"""Peaks of the chips, and the operations and bytes the algorithm needs.

The peaks are the published ones, keyed by ``device_kind`` as JAX
reports it; a chip that is not in the table is an error, never a
default. Operations and bytes are computed from each call's real inputs
(live context lengths, real routed rows), never from padded shapes, so a
roofline share cannot pass 100% by counting work the algorithm does not
need.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def min_time_s(ops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])


def paged_decode_cost(ctx_lens: Sequence[int], m: dict,
                      itemsize: int = 2):
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


def gmm_cost(rows: int, touched: int, d_in: int, d_out: int,
             in_itemsize: int = 2, out_itemsize: int = 4,
             w_itemsize: int = 2):
    """One grouped matrix product of the MoE layer: ``rows`` routed rows
    of width ``d_in`` against the ``touched`` experts' (d_in, d_out)
    weights. Bytes: those experts' weights, the rows in and the rows out."""
    ops = 2.0 * rows * d_in * d_out
    nbytes = (touched * d_in * d_out * w_itemsize
              + rows * (d_in * in_itemsize + d_out * out_itemsize))
    return ops, nbytes


def _per_token_layer_flops(m: dict) -> float:
    """Weight products of one token in one layer (attention projections
    and the feed-forward, top-k experts only)."""
    D, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    proj = 2.0 * D * (q + 2 * kv) + 2.0 * q * D
    if int(m.get("num_experts", 0)) > 0:
        ffn = (2.0 * D * m["num_experts"]
               + m["num_experts_per_tok"] * 6.0 * D
               * m["moe_intermediate_size"])
    else:
        ffn = 6.0 * D * m["intermediate_size"]
    return proj + ffn


def decode_flops(ctx_lens: Sequence[int], m: dict) -> float:
    """Model FLOPs one decode call needs: one token per live lane, with
    attention over its context, and the head for each lane."""
    L, hq, hd = m["num_hidden_layers"], m["num_attention_heads"], \
        m["head_dim"]
    ctx = np.asarray(ctx_lens, np.float64)
    per = L * (_per_token_layer_flops(m) + 4.0 * hq * hd * ctx)
    return float(np.sum(per) + len(ctx) * 2.0 * m["hidden_size"]
                 * m["vocab_size"])


def prefill_flops(lanes: Sequence[tuple], m: dict) -> float:
    """Model FLOPs one prefill call needs. ``lanes`` are (start, chunk,
    completes): a chunk of ``chunk`` prompt tokens at ``start``, causal
    attention over everything before each token, and the head only where
    the chunk completes the prompt."""
    L, hq, hd = m["num_hidden_layers"], m["num_attention_heads"], \
        m["head_dim"]
    total = 0.0
    for start, n, completes in lanes:
        attn = 4.0 * hq * hd * (n * start + n * (n + 1) / 2.0)
        total += L * (n * _per_token_layer_flops(m) + attn)
        if completes:
            total += 2.0 * m["hidden_size"] * m["vocab_size"]
    return total


def gmm_min_time(expert_counts: Optional[np.ndarray], m: dict,
                 peak: Dict[str, float]) -> float:
    """Least time of the three grouped products (gate, up, down) of every
    MoE layer of one call, from its (layers, experts) routed-row counts."""
    if expert_counts is None:
        return 0.0
    D, F = m["hidden_size"], m["moe_intermediate_size"]
    t = 0.0
    for counts in np.asarray(expert_counts):
        rows, touched = int(counts.sum()), int((counts > 0).sum())
        for d_in, d_out in ((D, F), (D, F), (F, D)):
            t += min_time_s(*gmm_cost(rows, touched, d_in, d_out), peak)
    return t
