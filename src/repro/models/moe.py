"""Mixture-of-Experts layer with placement-aware dispatch + Gimbal statistics.

Design (TPU adaptation of the paper's vLLM/PPLX stack — see DESIGN.md §3):

* Expert weights are stored in **physical slot order**; the Gimbal expert
  placement is a logical->physical permutation passed as a runtime input
  (``placement``), so migrating experts never recompiles the serving step.
* Dispatch is scatter-based (capacity-bounded): tokens are scattered into an
  ``(E, C, D)`` buffer sharded over the EP axis, experts run as one batched
  einsum, and results gather back. This keeps HLO FLOPs ~= useful FLOPs
  (capacity_factor overhead only) — unlike one-hot einsum dispatch whose fake
  FLOPs would destroy the roofline ratio.
* The layer emits the paper's two statistics along the normal dispatch path:
  aggregate expert load ``B[e]`` and the source-DP-to-expert matrix
  ``A[s, e]`` (logical expert ids). ``kernels/source_expert_count`` provides
  the fused Pallas fast path used by the serving engine; the in-graph
  scatter-add here is the shardable XLA formulation.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.moe_dispatch import (pick_row_block, ragged_combine,
                                        ragged_dispatch, ragged_gmm)
from repro.models.layers import dense_init

# §Perf toggles — flipped by launch/perf_run.py to measure the before/after
# of each hillclimbing iteration (EXPERIMENTS.md §Perf). Defaults = optimized.
PERF = {
    "decode_regroup": True,        # iteration B2: one dispatch group at S==1
    "dispatch_constraints": True,  # iteration A2: a2a-friendly buffer specs
    "vmap_scatter": True,          # iteration A3: per-row scatter/gather so
                                   # the partitioner keeps dispatch shard-local
                                   # (explicit batch indices force a global
                                   # scatter = full all-gather of updates)
    "ragged_dispatch": True,       # iteration D1: sort-based dropless dispatch
                                   # + group-sized ragged GMM — useful FLOPs
                                   # ~= issued FLOPs, no capacity drops
                                   # (EXPERIMENTS.md §Perf iteration D1)
}


def init_moe(key, cfg, d_model: Optional[int] = None):
    m = cfg.moe
    d = d_model or cfg.d_model
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "router": dense_init(ks[0], (d, m.n_experts), 0, jnp.float32),
        "w_gate": dense_init(ks[1], (m.n_experts, d, m.d_expert), 1, dt),
        "w_up": dense_init(ks[2], (m.n_experts, d, m.d_expert), 1, dt),
        "w_down": dense_init(ks[3], (m.n_experts, m.d_expert, d), 1, dt),
    }
    if m.n_shared_experts:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d, m.n_shared_experts * m.d_shared, dt)
    if m.correction_bias:
        p["router_bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    return p


def route(params, cfg, x2d):
    """Router: x2d (..., D) -> (gates (..., K), ids (..., K), probs (..., E)).

    Softmax scoring takes the top-k probabilities. Sigmoid scoring
    (DeepSeek-V3's ``noaux_tc`` with one group) takes the top-k of the
    scores plus ``router_bias`` where the config has one, and gates by the
    unbiased scores. Either way the gates are renormalized over the chosen
    experts, then scaled by ``routed_scaling_factor``."""
    m = cfg.moe
    logits = jnp.einsum("...d,de->...e", x2d.astype(jnp.float32),
                        params["router"])
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + params["router_bias"] if m.correction_bias \
            else scores
        _, expert_idx = jax.lax.top_k(choice, m.top_k)
        gate_vals = jnp.take_along_axis(scores, expert_idx, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
    gates = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    if m.routed_scaling_factor != 1.0:
        gates = gates * m.routed_scaling_factor
    return gates, expert_idx, probs


def expert_statistics(expert_idx, n_experts: int, source_ids=None,
                      n_sources: int = 0, token_mask=None):
    """B[e] and A[s, e] by scatter-add (logical ids). expert_idx: (T, K).
    token_mask (broadcastable to expert_idx[..., 0]): tokens counted with
    weight 0 are excluded — padding/inactive lanes must not register load."""
    k = expert_idx.shape[-1]
    flat = expert_idx.reshape(-1)
    if token_mask is None:
        w = jnp.ones_like(flat)
    else:
        w = jnp.repeat(token_mask.reshape(-1).astype(jnp.int32), k)
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat].add(w)
    stats = {"expert_counts": counts}
    if source_ids is not None and n_sources > 0:
        src = jnp.repeat(source_ids.reshape(-1), k)
        a = jnp.zeros((n_sources, n_experts), jnp.int32)
        stats["source_expert"] = a.at[src, flat].add(w)
    return stats


def _ragged_moe_ffn(params, x, gates, logical_idx, placement, E, K, policy,
                    src2d, n_sources: int, collect_stats: bool,
                    token_mask=None):
    """Sort-based dropless expert FFN [§Perf iteration D1].

    Pipeline: argsort physical ids -> per-expert group_sizes (bincount; this
    IS the B[e] statistic, so stats collection rides the dispatch pass) ->
    gather tokens into one contiguous block-aligned (Np, D) buffer ->
    group-sized ragged GMM (Pallas off-policy, blocked-XLA under SPMD) ->
    unsort + gate-weighted combine. No capacity, no drops, no trash row;
    issued FLOPs scale with actual tokens-per-expert.
    """
    B, S, D = x.shape
    T = B * S
    x2d = x.reshape(T, D)
    phys = placement[logical_idx].reshape(T, K)
    nb = pick_row_block(T * K, E)
    disp = ragged_dispatch(x2d, phys, E, row_block=nb)

    stats = {}
    if collect_stats:
        if token_mask is None:
            # physical slot placement[l] holds logical expert l, so the
            # logical load B[e] is a gather of the sort pass's bincount —
            # zero extra work
            stats["expert_counts"] = jnp.take(disp.group_sizes, placement)
        else:
            # masked tokens still dispatch (static shapes) but must not
            # register load: count the logical ids under the mask instead
            w = jnp.repeat(token_mask.reshape(T).astype(jnp.int32), K)
            stats["expert_counts"] = jnp.zeros((E,), jnp.int32).at[
                logical_idx.reshape(T * K)].add(w)
        if src2d is not None and n_sources > 0:
            if policy is None:
                # fused Pallas stats kernel on the sorted ids (same pass)
                from repro.kernels import ops
                lg = logical_idx.reshape(T * K)[disp.sort_idx] \
                    .astype(jnp.int32)
                ss = src2d.reshape(T)[disp.sort_idx // K].astype(jnp.int32)
                if token_mask is not None:
                    # source -1 matches no one-hot column in the kernel
                    vs = token_mask.reshape(T)[disp.sort_idx // K]
                    ss = jnp.where(vs, ss, -1)
                _, a = ops.source_expert_count(
                    lg[:, None], ss, n_experts=E, n_sources=n_sources)
                stats["source_expert"] = a
            else:
                # shardable XLA scatter-add (same formulation as the
                # padded path)
                stats["source_expert"] = expert_statistics(
                    logical_idx, E, src2d, n_sources,
                    token_mask=token_mask)["source_expert"]

    use_kernel = policy is None
    xs = disp.xs
    if policy is not None:
        xs = policy.shard_sorted_rows(xs)
    args = (disp.tile_expert, disp.group_sizes, disp.padded_offsets, nb,
            use_kernel)
    gate = ragged_gmm(xs, params["w_gate"], *args)
    up = ragged_gmm(xs, params["w_up"], *args)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    if policy is not None:
        h = policy.shard_ffn_act(h)
    ys = ragged_gmm(h, params["w_down"], *args)
    if policy is not None:
        ys = policy.shard_sorted_rows(ys)
    y2d = ragged_combine(ys, disp.dest, gates.reshape(T, K))
    return y2d.reshape(B, S, D).astype(x.dtype), stats


def moe_layer(params, cfg, x, placement, *, source_ids=None, n_sources: int = 0,
              policy=None, collect_stats: bool = True,
              capacity_factor: Optional[float] = None,
              ragged: Optional[bool] = None, token_mask=None):
    """x: (B, S, D) -> (y (B, S, D), stats dict).

    placement: (E,) int32 logical->physical slot permutation.
    source_ids: (B,) int32 DP-source id per batch row (for A[s, e]).
    ragged: override for PERF["ragged_dispatch"] (None = use the toggle).
    token_mask: (B, S) bool — tokens to EXCLUDE from the routing statistics
    (padding rows, inactive decode lanes). Compute is unaffected (static
    shapes route everything); only the reported load is masked.

    Two dispatch formulations:

    * **ragged** (default, [§Perf iteration D1]): sort-based dropless
      dispatch + group-sized GMM — see ``_ragged_moe_ffn``.
    * **padded** (the A/B baseline): dispatch bookkeeping **grouped per
      batch row** (GShard grouping): each row computes its own capacity
      queue locally, so the one-hot cumsum is O(S*K*E) per row instead of
      O(B*S*K*E) globally and stays shard-local on the DP axes — matching
      the paper's per-DP-engine dispatch semantics. Tokens past an expert's
      capacity C are dropped.
    """
    m = cfg.moe
    B, S, D = x.shape
    K = m.top_k
    E = m.n_experts
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor

    gates, logical_idx, probs = route(params, cfg, x)   # (B,S,K),(B,S,K),(B,S,E)

    use_ragged = PERF["ragged_dispatch"] if ragged is None else ragged
    src = None
    if source_ids is not None:
        src = jnp.broadcast_to(source_ids[:, None], (B, S))

    if use_ragged:
        y, stats = _ragged_moe_ffn(params, x, gates, logical_idx, placement,
                                   E, K, policy, src, n_sources,
                                   collect_stats, token_mask=token_mask)
        return _moe_epilogue(params, cfg, x, y, stats, gates, logical_idx,
                             probs, B, S, E, K, policy)

    stats = {}
    if collect_stats:
        stats = expert_statistics(logical_idx, E, src, n_sources,
                                  token_mask=token_mask)

    # Decode (S == 1): per-row grouping would give every row its own
    # capacity-4 expert buffer (64x flop waste at batch 128); treat the whole
    # batch as ONE dispatch group instead. [§Perf iteration B2]
    decode_regroup = S == 1 and B > 1 and PERF["decode_regroup"]
    if decode_regroup:
        orig_B = B
        x = x.reshape(1, B, D)
        gates = gates.reshape(1, B, K)
        logical_idx = logical_idx.reshape(1, B, K)
        probs = probs.reshape(1, B, E)
        B, S = 1, B

    C = max(int(-(-S * K * cf // E)), 4)           # per-row expert capacity

    phys_idx = placement[logical_idx]                        # (B, S, K)

    # ---- per-row position within each physical expert's capacity queue
    oh = jax.nn.one_hot(phys_idx.reshape(B, S * K), E, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=1) - oh                        # (B, S*K, E)
    pos = jnp.take_along_axis(
        pos, phys_idx.reshape(B, S * K, 1), axis=2)[..., 0]  # (B, S*K)
    within = pos < C
    flat_e = phys_idx.reshape(B, S * K)
    dest = jnp.where(within, flat_e * C + pos, E * C)        # (B, S*K)

    # ---- scatter tokens into per-row expert buffers (trash row catches drops)
    updates = jnp.broadcast_to(x[:, :, None, :],
                               (B, S, K, D)).reshape(B, S * K, D)
    use_dc = policy is not None and PERF["dispatch_constraints"]
    if use_dc:
        updates = policy.shard_dispatch_rows(updates)
    if PERF["vmap_scatter"]:
        buf = jax.vmap(lambda u, d: jnp.zeros(
            (E * C + 1, D), x.dtype).at[d].set(u))(updates, dest)
    else:
        bidx = jnp.arange(B)[:, None]
        buf = jnp.zeros((B, E * C + 1, D), x.dtype).at[bidx, dest].set(
            updates)
    if use_dc:
        buf = policy.shard_dispatch_rows(buf)
    xe = buf[:, : E * C].reshape(B, E, C, D).transpose(1, 0, 2, 3) \
        .reshape(E, B * C, D)                                # all-to-all here
    if policy is not None:
        xe = policy.shard_expert_act(xe)

    # ---- batched expert SwiGLU
    gate = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    if policy is not None:
        h = policy.shard_expert_ffn(h)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    # ---- return path: back to per-row layout, gather + weighted combine
    ye_rows = ye.reshape(E, B, C, D).transpose(1, 0, 2, 3).reshape(B, E * C, D)
    if use_dc:
        ye_rows = policy.shard_dispatch_rows(ye_rows)
    ybuf = jnp.concatenate(
        [ye_rows, jnp.zeros((B, 1, D), ye.dtype)], axis=1)
    if PERF["vmap_scatter"]:
        ytok = jax.vmap(lambda yb, d: yb[d])(ybuf, dest).reshape(B, S, K, D)
    else:
        ytok = ybuf[jnp.arange(B)[:, None], dest].reshape(B, S, K, D)
    y = jnp.sum(ytok * gates[..., None].astype(ytok.dtype), axis=2)

    y, stats = _moe_epilogue(params, cfg, x, y, stats, gates, logical_idx,
                             probs, B, S, E, K, policy)
    if decode_regroup:
        y = y.reshape(orig_B, 1, D)
    return y, stats


def _moe_epilogue(params, cfg, x, y, stats, gates, logical_idx, probs,
                  B, S, E, K, policy=None):
    """Shared-expert branch + router aux loss (both dispatch paths)."""
    if cfg.moe.n_shared_experts:
        from repro.models.layers import mlp
        y = y + mlp(params["shared"], x, policy)

    # router aux loss (train-time load balancing), from routing probs
    probs_mean = jnp.mean(probs.reshape(B * S, E), axis=0)
    frac = jnp.mean(jax.nn.one_hot(
        logical_idx.reshape(B * S, K), E, dtype=jnp.float32).sum(1), axis=0)
    stats["aux_loss"] = E * jnp.sum(probs_mean * frac)
    return y, stats


def moe_layer_ref(params, cfg, x, placement):
    """Dropless dense oracle (tiny models only): every expert sees every token.

    Used by tests as the ground truth for the dispatch path (with a capacity
    factor large enough that nothing drops, outputs must match).
    """
    m = cfg.moe
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    gates, logical_idx, _ = route(params, cfg, x2d)
    phys = placement[logical_idx]                            # (T, K)

    def one_expert(wg, wu, wd):
        h = jax.nn.silu(jnp.einsum("td,df->tf", x2d, wg).astype(
            jnp.float32)).astype(x.dtype) * jnp.einsum("td,df->tf", x2d, wu)
        return jnp.einsum("tf,fd->td", h, wd)

    all_out = jax.vmap(one_expert)(
        params["w_gate"], params["w_up"], params["w_down"])  # (E, T, D)
    sel = all_out[phys.T, jnp.arange(x2d.shape[0])[None, :]]  # (K, T, D)
    y = jnp.sum(sel * gates.T[..., None].astype(sel.dtype), axis=0)
    if m.n_shared_experts:
        from repro.models.layers import mlp
        y = y + mlp(params["shared"], x2d)
    return y.reshape(B, S, D)


def migrate_expert_weights(params, old_placement, new_placement):
    """Reorder physical expert weights when the placement changes.

    weights[new_phys] = weights[old_phys] for each logical expert. On a real
    mesh this lowers to an expert-axis collective-permute; bytes moved are
    accounted by the placement manager's migration cost.
    """
    E = old_placement.shape[0]
    inv_old = jnp.zeros_like(old_placement).at[old_placement].set(
        jnp.arange(E, dtype=old_placement.dtype))
    # physical slot p_new holds logical expert inv_new[p_new]; source slot is
    # old_placement[inv_new[p_new]]
    inv_new = jnp.zeros_like(new_placement).at[new_placement].set(
        jnp.arange(E, dtype=new_placement.dtype))
    src = old_placement[inv_new]
    out = dict(params)
    for name in ("w_gate", "w_up", "w_down"):
        out[name] = params[name][src]
    return out
