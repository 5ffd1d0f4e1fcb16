"""Seeded random weights, in the layout the program's param tree has.

Each leaf is drawn from its own key, ``fold_in(fold_in(run_key, leaf),
layer)``, so the whole tree is one jitted call for the program and the
reference can make one layer at a time and get the same numbers. Scales
follow the program's own initializers (truncated normal of std
``1/sqrt(fan_in)``, embedding normal 0.02); norm scales, which the program
keeps as an offset from one, are drawn at std 0.1 so that they matter.

Layout (period one: every layer of a configuration is alike)::

    embed/embedding (V, D), embed/lm_head (D, V)           bfloat16
    blocks/pos0/ln_attn, ln_ffn (L, D)                     float32
    blocks/pos0/attn/wq (L, D, Hq*hd), wk, wv (L, D, Hkv*hd),
                    wo (L, Hq*hd, D)                       bfloat16
    blocks/pos0/attn/q_norm, k_norm (L, hd)                float32
    blocks/pos0/moe/router (L, D, E)                       float32
    blocks/pos0/moe/w_gate, w_up (L, E, D, F), w_down (L, E, F, D)
    blocks/pos0/mlp/w_gate, w_up (L, D, F), w_down (L, F, D)
    final_norm (D,)                                        float32
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32


def run_key(seed: int):
    """A threefry key from any non-negative whole number (more than 32
    bits are kept)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def is_moe(m: dict) -> bool:
    return int(m.get("num_experts", 0)) > 0


def layer_leaves(m: dict) -> List[Tuple[str, Tuple[int, ...], object, str,
                                        float]]:
    """(path, per-layer shape, dtype, kind, std) of every layered leaf."""
    D, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    s = lambda n: 1.0 / np.sqrt(n)
    out = [("ln_attn", (D,), F32, "normal", 0.1),
           ("ln_ffn", (D,), F32, "normal", 0.1),
           ("attn/wq", (D, q), BF16, "trunc", s(D)),
           ("attn/wk", (D, kv), BF16, "trunc", s(D)),
           ("attn/wv", (D, kv), BF16, "trunc", s(D)),
           ("attn/wo", (q, D), BF16, "trunc", s(q)),
           ("attn/q_norm", (hd,), F32, "normal", 0.1),
           ("attn/k_norm", (hd,), F32, "normal", 0.1)]
    if is_moe(m):
        E, F = m["num_experts"], m["moe_intermediate_size"]
        out += [("moe/router", (D, E), F32, "trunc", s(D)),
                ("moe/w_gate", (E, D, F), BF16, "trunc", s(D)),
                ("moe/w_up", (E, D, F), BF16, "trunc", s(D)),
                ("moe/w_down", (E, F, D), BF16, "trunc", s(F))]
    else:
        F = m["intermediate_size"]
        out += [("mlp/w_gate", (D, F), BF16, "trunc", s(D)),
                ("mlp/w_up", (D, F), BF16, "trunc", s(D)),
                ("mlp/w_down", (F, D), BF16, "trunc", s(F))]
    return out


def global_leaves(m: dict):
    D, V = m["hidden_size"], m["vocab_size"]
    return [("embed/embedding", (V, D), BF16, "normal", 0.02),
            ("embed/lm_head", (D, V), BF16, "trunc", 1.0 / np.sqrt(D)),
            ("final_norm", (D,), F32, "normal", 0.1)]


def _draw(key, shape, dtype, kind, std):
    if kind == "trunc":
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
    else:
        x = jax.random.normal(key, shape, F32)
    return (x * std).astype(dtype)


def _leaf_key(key, index: int):
    return jax.random.fold_in(key, index)


def layer_leaf(key, index: int, layer, shape, dtype, kind, std):
    """One layer's slice of layered leaf ``index``."""
    k = jax.random.fold_in(_leaf_key(key, 100 + index), layer)
    return _draw(k, shape, dtype, kind, std)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _one_layer_leaf(key, layer, index, shape, dtype, kind, std):
    return layer_leaf(key, index, layer, shape, dtype, kind, std)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _one_global_leaf(key, index, shape, dtype, kind, std):
    return _draw(_leaf_key(key, index), shape, dtype, kind, std)


def _set(tree: Dict, path: str, value) -> None:
    *parents, last = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[last] = value


def make_params(m: dict, seed: int):
    """The program's whole param tree for ``seed``, in one jitted call."""
    L = m["num_hidden_layers"]

    def build(key):
        tree: Dict = {}
        for i, (path, shape, dt, kind, std) in enumerate(global_leaves(m)):
            _set(tree, path, _draw(_leaf_key(key, i), shape, dt, kind, std))
        pos0: Dict = {}
        for i, (path, shape, dt, kind, std) in enumerate(layer_leaves(m)):
            one = lambda l, i=i, shape=shape, dt=dt, kind=kind, std=std: \
                layer_leaf(key, i, l, shape, dt, kind, std)
            _set(pos0, path, jax.vmap(one)(jnp.arange(L)))
        tree["blocks"] = {"pos0": pos0}
        return tree

    return jax.jit(build)(run_key(seed))


def make_layer(m: dict, seed: int, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves, flat by path, equal to ``make_params``'s slice."""
    key = run_key(seed)
    return {path: _one_layer_leaf(key, layer, i, shape, dt, kind, std)
            for i, (path, shape, dt, kind, std)
            in enumerate(layer_leaves(m))}


def make_global(m: dict, seed: int) -> Dict[str, jax.Array]:
    key = run_key(seed)
    return {path: _one_global_leaf(key, i, shape, dt, kind, std)
            for i, (path, shape, dt, kind, std)
            in enumerate(global_leaves(m))}
