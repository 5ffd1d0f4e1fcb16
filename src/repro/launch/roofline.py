"""Roofline analysis (assignment §Roofline).

Three terms per (arch x shape x mesh) cell, all in seconds:

  compute    = HLO_FLOPs    / peak_FLOPs_per_chip       (197 TFLOP/s bf16)
  memory     = HLO_bytes    / HBM_bw_per_chip           (819 GB/s)
  collective = coll_bytes   / link_bw_per_chip          (~50 GB/s/link)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes (per-device SPMD
module) and compiled-HLO text parsing for collective bytes
(launch/hlo_analysis.py).

Scan correction: XLA cost analysis counts ``while`` bodies ONCE (verified
empirically — see EXPERIMENTS.md §Roofline methodology), so per-cell terms
are ``full_graph + (n_super - 1) * superblock_body``, with the super-block
body lowered standalone under the same mesh/shardings. For training cells
the body is the rematerialized value-and-grad of one super-block (what the
backward scan executes per iteration). xLSTM is unrolled (no correction);
enc-dec corrects each stack separately.
"""
from __future__ import annotations

import dataclasses
import json
from math import prod
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ModelConfig, ShapeConfig, get_config, get_shape
from repro.launch.cells import build_cell, cell_is_skipped, lower_cell
from repro.launch.hlo_analysis import (collective_bytes_from_text,
                                       total_collective_bytes)

# hardware constants (assignment): TPU v5e-class chip
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops: float               # per-device
    bytes_accessed: float
    collective_bytes: float
    n_chips: int
    model_flops: float         # 6*N*D train / 2*N*D serve (global)
    skip: Optional[str] = None

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips) — remat/redundancy waste."""
        total = self.flops * self.n_chips
        return self.model_flops / total if total > 0 else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / dominant-term time (the score)."""
        useful_s = self.model_flops / (self.n_chips * PEAK_FLOPS)
        dom = max(self.compute_s, self.memory_s, self.collective_s)
        return useful_s / dom if dom > 0 else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "flops_per_chip": self.flops,
            "bytes_per_chip": self.bytes_accessed,
            "collective_bytes_per_chip": self.collective_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "skip": self.skip,
        }


def model_flops_for(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6*N*D (train, fwd+bwd) or 2*N*D (serving fwd) with N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch      # decode: 1 token per row


def _analyze(lowered):
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes_from_text(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            float(total_collective_bytes(coll)))


def _superblock_cell(cfg, shape, mesh, policy):
    """Lowerable one-super-block function + abstract args (serve or train)."""
    from repro.distributed.sharding import make_param_specs
    from repro.models import transformer as tr

    descs = tr.period_descriptors(cfg)
    ns = tr.n_super_blocks(cfg)
    B, S = shape.global_batch, shape.seq_len
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    n_src = prod(mesh.shape[a] for a in data_axes)

    # abstract per-superblock params: strip the leading ns dim
    fns_params = jax.eval_shape(
        lambda: tr.init_params(jax.random.PRNGKey(0), cfg))
    blk_params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
        fns_params["blocks"])
    from repro.distributed.sharding import make_param_specs as mps
    pspec_full = mps(fns_params, cfg, policy)["blocks"]
    blk_pspec = jax.tree.map(lambda s: P(*s[1:]) if len(s) else P(),
                             pspec_full,
                             is_leaf=lambda x: isinstance(x, P))

    mode = {"train": "train", "prefill": "prefill",
            "decode": "decode"}[shape.kind]
    Sq = 1 if mode == "decode" else S
    x = jax.ShapeDtypeStruct((B, Sq, cfg.d_model), jnp.dtype(cfg.dtype))
    positions = jax.ShapeDtypeStruct((B, Sq), jnp.int32)
    src = jax.ShapeDtypeStruct((B,), jnp.int32)
    mp = sum(1 for d in descs if d.moe)
    placement = jax.ShapeDtypeStruct((max(mp, 1), cfg.moe.n_experts),
                                     jnp.int32) if mp else None

    blk_cache = None
    cspec = None
    if mode in ("prefill", "decode"):
        full_cache = jax.eval_shape(lambda: tr.init_cache(cfg, B, S))
        blk_cache = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), full_cache)
        from repro.distributed.sharding import cache_specs_tree
        cfull = cache_specs_tree(cfg, policy, full_cache)
        cspec = jax.tree.map(lambda s: P(*s[1:]) if len(s) else P(), cfull,
                             is_leaf=lambda x: isinstance(x, P))

    def fwd(bp, xx, pos, bc, plc, sid):
        out, nc, st = tr.superblock_forward(
            bp, cfg, descs, xx, pos, bc, mode, plc, sid, n_src, policy,
            cfg.moe.enabled)
        return out, nc, st

    ba = policy.batch_axes or None
    xspec = P(ba, None, None)
    pspec = P(ba, None)
    sspec = P(ba)

    if mode == "train":
        def body(bp, xx, pos, plc, sid):
            def loss(bp_, xx_):
                out, _, _ = tr.superblock_forward(
                    bp_, cfg, descs, xx_, pos, None, "train", plc, sid,
                    n_src, policy, False)
                return jnp.sum(out.astype(jnp.float32))
            f = jax.checkpoint(loss, prevent_cse=False)
            (_, grads) = jax.value_and_grad(f, argnums=(0, 1))(bp, xx)
            return grads
        args = (blk_params, x, positions, placement, src)
        shardings = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), blk_pspec,
                         is_leaf=lambda t: isinstance(t, P)),
            NamedSharding(mesh, xspec), NamedSharding(mesh, pspec),
            NamedSharding(mesh, P()), NamedSharding(mesh, sspec))
        if placement is None:
            args = (blk_params, x, positions,
                    jax.ShapeDtypeStruct((0, 0), jnp.int32), src)
        return body, args, shardings, ns

    args = (blk_params, x, positions, blk_cache,
            placement if placement is not None
            else jax.ShapeDtypeStruct((0, 0), jnp.int32), src)
    shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), blk_pspec,
                     is_leaf=lambda t: isinstance(t, P)),
        NamedSharding(mesh, xspec), NamedSharding(mesh, pspec),
        jax.tree.map(lambda s: NamedSharding(mesh, s), cspec,
                     is_leaf=lambda t: isinstance(t, P)),
        NamedSharding(mesh, P()), NamedSharding(mesh, sspec))

    def body(bp, xx, pos, bc, plc, sid):
        return fwd(bp, xx, pos, bc, plc, sid)

    return body, args, shardings, ns


def n_chips_guess(mesh) -> int:
    return prod(mesh.shape.values())


def roofline_cell(arch: str, shape_name: str, mesh,
                  mesh_name: str, *, policy_overrides=None,
                  donate_cache: bool = False) -> RooflineTerms:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    skip = cell_is_skipped(cfg, shape)
    mf = model_flops_for(cfg, shape)
    if skip:
        return RooflineTerms(arch, shape_name, mesh_name, 0, 0, 0,
                             prod(mesh.shape.values()), mf, skip=skip)

    cell = build_cell(arch, shape_name, mesh,
                      policy_overrides=policy_overrides)
    lowered = lower_cell(cell, donate_cache=donate_cache)
    fl, by, co = _analyze(lowered)

    # scan-body correction for the transformer families
    if cfg.family in ("dense", "moe", "vlm", "hybrid"):
        try:
            body, args, shardings, ns = _superblock_cell(
                cfg, shape, mesh, cell.policy)
            with mesh:
                lb = jax.jit(body, in_shardings=shardings).lower(*args)
            bfl, bby, bco = _analyze(lb)
            fl += (ns - 1) * bfl
            by += (ns - 1) * bby
            co += (ns - 1) * bco
        except Exception as e:  # pragma: no cover — fall back to raw terms
            print(f"[roofline] body lowering failed for {arch}/{shape_name}:"
                  f" {type(e).__name__}: {e}; using uncorrected terms")
    elif cfg.family == "encdec":
        # enc/dec stacks scan with bodies counted once; the only heavy
        # outside-scan op is the LM head — separate it analytically, scale
        # the remainder by the (shared) stack depth.
        tokens = (shape.global_batch
                  if shape.kind == "decode"
                  else shape.global_batch * shape.seq_len)
        mult = 3.0 if shape.kind == "train" else 1.0
        head_fl = 2.0 * tokens * cfg.d_model * cfg.vocab_size * mult \
            / n_chips_guess(mesh)
        n_l = max(cfg.enc_layers, 1)
        fl = head_fl + (max(fl - head_fl, 0.0)) * n_l
        by *= n_l
        co *= n_l
    # ssm (xlstm) is unrolled: raw terms are already exact

    n_chips = prod(mesh.shape.values())
    return RooflineTerms(arch, shape_name, mesh_name, fl, by, co, n_chips, mf)
