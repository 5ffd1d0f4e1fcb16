"""The serving path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) checks the math but none of the
TPU compiler's rules: block tiling, SMEM/VMEM limits, what Mosaic can
lower. Here each kernel is compiled at the Qwen3-30B-A3B widths the
one-chip configuration serves (d_model 2048, d_expert 768, 128 experts,
top-8, 32/4 heads of 128), for one chip of a described ``v5e:2x2``
topology — no chip is needed, and nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist worker
imports this file. The kernel modules are called with
``interpret=False`` directly (``kernels.ops`` would pick interpret mode
from the CPU backend).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moe_dispatch import padded_rows
from repro.kernels.moe_gmm import moe_gmm_ragged
from repro.kernels.paged_decode import paged_decode_pallas
from repro.kernels.source_expert_count import source_expert_count

D, F, E, K = 2048, 768, 128, 8          # d_model, d_expert, experts, top-k
HQ, HKV, HD = 32, 4, 128                # query heads, KV heads, head_dim


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without a chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_decode_compiles(one_chip, page_size):
    B, NB, P = 8, 72, 4097
    text = _compiled_text(
        lambda q, k, v, bt, ctx: paged_decode_pallas(q, k, v, bt, ctx,
                                                     interpret=False),
        _spec((B, HQ, HD), jnp.bfloat16, one_chip),
        _spec((P, page_size, HKV, HD), jnp.bfloat16, one_chip),
        _spec((P, page_size, HKV, HD), jnp.bfloat16, one_chip),
        _spec((B, NB), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("row_block", [8, 128])
def test_moe_gmm_ragged_compiles(one_chip, row_block):
    T = 512                              # tokens routed per dispatch
    Np = padded_rows(T * K, E, row_block)
    text = _compiled_text(
        lambda x, w, te, gs, po: moe_gmm_ragged(x, w, te, gs, po,
                                                n_block=row_block,
                                                interpret=False),
        _spec((Np, D), jnp.bfloat16, one_chip),
        _spec((E, D, F), jnp.bfloat16, one_chip),
        _spec((Np // row_block,), jnp.int32, one_chip),
        _spec((E,), jnp.int32, one_chip),
        _spec((E + 1,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_source_expert_count_compiles(one_chip):
    T = 512 * K                          # sorted (token, k) slots, K=1 view
    text = _compiled_text(
        lambda e, s: source_expert_count(e, s, n_experts=E, n_sources=2,
                                         interpret=False),
        _spec((T, 1), jnp.int32, one_chip),
        _spec((T,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text
