"""The serving path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) checks the math but none of the
TPU compiler's rules: block tiling, SMEM/VMEM limits, what Mosaic can
lower. Here each kernel is compiled at the Qwen3-30B-A3B widths the
one-chip configuration serves (d_model 2048, d_expert 768, 128 experts,
top-8, 32/4 heads of 128), and the latent decode kernel at Kanana-2's
(32 heads over rows of 512 + 64), for one chip of a described
``v5e:2x2`` topology — no chip is needed, and nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist worker
imports this file. The kernel modules are called with
``interpret=False`` directly (``kernels.ops`` would pick interpret mode
from the CPU backend).
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.mla_decode import mla_decode_pallas
from repro.kernels.moe_dispatch import padded_rows
from repro.kernels.moe_gmm import moe_gmm_ragged
from repro.kernels.paged_decode import (paged_decode_pallas,
                                        pages_per_block)
from repro.kernels.source_expert_count import source_expert_count

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
from harness import trace  # noqa: E402

D, F, E, K = 2048, 768, 128, 8          # d_model, d_expert, experts, top-k
HQ, HKV, HD = 32, 4, 128                # query heads, KV heads, head_dim
# Kanana-2-30B-A3B's latent rows: 512 latent + 64 roped key, padded to 640
RANK, LATENT_PAD = 512, 640


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


@pytest.mark.parametrize("B,NB,page_size,kv_dtype", [
    pytest.param(8, 72, 8, jnp.bfloat16, id="8"),
    pytest.param(8, 72, 16, jnp.bfloat16, id="16"),
    # the reasoning cell's decode: 32 lanes, a 256-slot table of 16-token
    # pages (16-page blocks)
    pytest.param(32, 256, 16, jnp.bfloat16, id="qwen3-32x256"),
    # 250 slots are not a multiple of the 16-page block: padded to 256
    pytest.param(32, 250, 16, jnp.bfloat16, id="qwen3-32x250"),
    pytest.param(8, 72, 16, jnp.int8, id="int8-16"),
])
def test_paged_decode_compiles(one_chip, B, NB, page_size, kv_dtype):
    P = 4097
    pages = _spec((P, page_size, HKV, HD), kv_dtype, one_chip)
    specs = [_spec((B, HQ, HD), jnp.bfloat16, one_chip), pages, pages,
             _spec((B, NB), jnp.int32, one_chip),
             _spec((B,), jnp.int32, one_chip)]
    if kv_dtype == jnp.int8:
        specs += [_spec((P, page_size, HKV), jnp.float32, one_chip)] * 2
    text = _compiled_text(
        lambda q, k, v, bt, ctx, *sc: paged_decode_pallas(
            q, k, v, bt, ctx, k_scales=sc[0] if sc else None,
            v_scales=sc[1] if sc else None, interpret=False),
        *specs)
    assert "tpu_custom_call" in text
    # the benchmark's trace reader finds the kernel by its first operand,
    # the (lanes, slots) block table padded to whole blocks
    ppb = pages_per_block(page_size, NB)
    nb = -(-NB // ppb) * ppb
    assert f"operand_layout_constraints={{s32[{B},{nb}]" in text


@pytest.mark.parametrize("B,NB", [
    # the long-document cell's decode: 16 lanes, a 1040-slot table of
    # 16-token pages (prompts to 16384 plus 256 answer tokens)
    pytest.param(16, 1040, id="kanana-16x1040"),
    pytest.param(8, 72, id="8x72"),
])
def test_mla_decode_compiles(one_chip, B, NB):
    specs = [_spec((B, HQ, LATENT_PAD), jnp.bfloat16, one_chip),
             _spec((10241, 16, LATENT_PAD), jnp.bfloat16, one_chip),
             _spec((B, NB), jnp.int32, one_chip),
             _spec((B,), jnp.int32, one_chip)]
    text = _compiled_text(
        lambda q, p, bt, ctx: mla_decode_pallas(
            q, p, bt, ctx, rank=RANK, scale=(128 + 64) ** -0.5,
            interpret=False), *specs)
    line = next(l for l in text.splitlines()
                if re.match(r"\s*(ROOT )?%mla_decode", l))
    # the first operand is ctx_lens, a vector: the trace reader's GQA
    # pattern, a (lanes, slots) table in first place, does not match
    assert f"operand_layout_constraints={{s32[{B}]{{0}}, " in line
    # the operation as a profiler trace names it: the result, then each
    # operand's shape before its name
    result = line.strip().removeprefix("ROOT ").split(" custom-call(")[0]
    shapes = re.findall(r"\w+\[[\d,]*\]\{[\d,]*\}", line.split(
        "operand_layout_constraints=")[1].split(", frontend_attributes")[0])
    assert len(shapes) == 5
    operands = ", ".join(f"{sh} %arg{i}" for i, sh in enumerate(shapes))
    op = (f'{result} custom-call({operands}), '
          f'custom_call_target="tpu_custom_call"')
    assert trace.kernel_of(op) == "mla_decode"


@pytest.mark.parametrize("d_in,d_out,row_block", [
    pytest.param(D, F, 8, id="8"),
    pytest.param(D, F, 128, id="128"),
    # the published widths at the row blocks both cells dispatch: gate
    # and up (d_model -> d_expert), down (d_expert -> d_model)
    pytest.param(D, F, 16, id="gate-16"),
    pytest.param(F, D, 8, id="down-8"),
    pytest.param(F, D, 16, id="down-16"),
])
def test_moe_gmm_ragged_compiles(one_chip, d_in, d_out, row_block):
    T = 512                              # tokens routed per dispatch
    Np = padded_rows(T * K, E, row_block)
    text = _compiled_text(
        lambda x, w, te, gs, po: moe_gmm_ragged(x, w, te, gs, po,
                                                n_block=row_block,
                                                interpret=False),
        _spec((Np, d_in), jnp.bfloat16, one_chip),
        _spec((E, d_in, d_out), jnp.bfloat16, one_chip),
        _spec((Np // row_block,), jnp.int32, one_chip),
        _spec((E,), jnp.int32, one_chip),
        _spec((E + 1,), jnp.int32, one_chip))
    # the benchmark's trace reader finds the kernel by its call's name
    line = next(l for l in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in l)
    op = line.strip().removeprefix("ROOT ")
    assert f"f32[{Np},{d_out}]" in op
    assert trace.kernel_of(op) == "moe_gmm"


def test_source_expert_count_compiles(one_chip):
    T = 512 * K                          # sorted (token, k) slots, K=1 view
    text = _compiled_text(
        lambda e, s: source_expert_count(e, s, n_experts=E, n_sources=2,
                                         interpret=False),
        _spec((T, 1), jnp.int32, one_chip),
        _spec((T,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text
