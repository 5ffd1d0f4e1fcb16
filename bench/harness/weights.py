"""Seeded random weights, in the layout the program's param tree has.

The configuration's architecture module (``bench/arch/<model_type>.py``)
lists the leaves; this module draws them. Each leaf is drawn from its own
key: a global leaf ``i`` from ``fold_in(run_key, i)``, layered leaf ``i``
of layer ``l`` from ``fold_in(fold_in(run_key, 100 + i), l)``, counting
global and layered leaves apart in the order the module lists them. So
the whole tree is one jitted call for the program, and the reference can
make one layer at a time and get the same numbers. A layered leaf is
stacked over the layers it lists, under its stack's path, so layers that
differ sit in stacks of their own.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import arch_of

BF16, F32 = jnp.bfloat16, jnp.float32


class Leaf(NamedTuple):
    path: str                   # in the tree; a layered leaf's in its stack
    shape: Tuple[int, ...]      # a layered leaf's for one layer
    dtype: object
    kind: str                   # "trunc" (within two std) or "normal"
    std: float
    layers: Optional[Tuple[int, ...]] = None   # stacked over; None: global
    stack: str = ""             # path of the stack in the tree


def run_key(seed: int):
    """A threefry key from any non-negative whole number (more than 32
    bits are kept)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _leaves(m: dict) -> Tuple[List[Leaf], List[Leaf]]:
    """(global leaves, layered leaves) of the configuration."""
    out = arch_of(m).leaves(m)
    return ([lf for lf in out if lf.layers is None],
            [lf for lf in out if lf.layers is not None])


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
    glob, layered = _leaves(m)

    def build(key):
        tree: Dict = {}
        for i, lf in enumerate(glob):
            _set(tree, lf.path,
                 _draw(_leaf_key(key, i), lf.shape, lf.dtype, lf.kind,
                       lf.std))
        for i, lf in enumerate(layered):
            one = lambda l, i=i, lf=lf: layer_leaf(
                key, i, l, lf.shape, lf.dtype, lf.kind, lf.std)
            _set(tree, lf.stack + "/" + lf.path,
                 jax.vmap(one)(jnp.asarray(lf.layers, jnp.int32)))
        return tree

    return jax.jit(build)(run_key(seed))


def make_layer(m: dict, seed: int, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves, flat by their path in the stack, equal to
    ``make_params``'s slices."""
    key = run_key(seed)
    return {lf.path: _one_layer_leaf(key, layer, i, lf.shape, lf.dtype,
                                     lf.kind, lf.std)
            for i, lf in enumerate(_leaves(m)[1]) if layer in lf.layers}


def make_global(m: dict, seed: int) -> Dict[str, jax.Array]:
    key = run_key(seed)
    return {lf.path: _one_global_leaf(key, i, lf.shape, lf.dtype, lf.kind,
                                      lf.std)
            for i, lf in enumerate(_leaves(m)[0])}
