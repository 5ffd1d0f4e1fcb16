"""Peaks of the chips, and the operations and bytes the algorithm needs.

The peaks are the published ones, keyed by ``device_kind`` as JAX
reports it; a chip that is not in the table is an error, never a
default. Operations and bytes are computed from each call's real inputs
(live context lengths, real routed rows), never from padded shapes, so a
roofline share cannot pass 100% by counting work the algorithm does not
need. What depends on the block (attention, the weight products of a
token, the paged decode kernel) the configuration's architecture module
counts, layer by layer.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from harness.spec import arch_of

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
    """One call of the paged decode kernel (one layer) over each lane's
    live context: (operations, bytes), as the architecture counts them."""
    return arch_of(m).paged_decode_cost(ctx_lens, m, itemsize)


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


def decode_flops(ctx_lens: Sequence[int], m: dict) -> float:
    """Model FLOPs one decode call needs: one token per live lane, with
    attention over its context, and the head for each lane."""
    arch = arch_of(m)
    ctx = np.asarray(ctx_lens, np.float64)
    per = sum(arch.token_flops(m, layer) + arch.attention_flops(m, layer, ctx)
              for layer in range(m["num_hidden_layers"]))
    return float(np.sum(per) + len(ctx) * 2.0 * m["hidden_size"]
                 * m["vocab_size"])


def prefill_flops(lanes: Sequence[tuple], m: dict) -> float:
    """Model FLOPs one prefill call needs. ``lanes`` are (start, chunk,
    completes): a chunk of ``chunk`` prompt tokens at ``start``, causal
    attention over everything before each token, and the head only where
    the chunk completes the prompt."""
    arch = arch_of(m)
    total = 0.0
    for start, n, completes in lanes:
        ctx = start + np.arange(1, n + 1, dtype=np.float64)
        for layer in range(m["num_hidden_layers"]):
            total += n * arch.token_flops(m, layer) \
                + float(np.sum(arch.attention_flops(m, layer, ctx)))
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
