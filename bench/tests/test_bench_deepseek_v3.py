"""The DeepSeek-V3 block (``bench/arch/deepseek_v3.py``) and the
long-document cell of Kanana-2-30B-A3B, at a tiny size on the CPU.

The module's leaves are the program's param tree; its costs are the
published form's, counted here by hand; the program's paged path (chunked
prefill over gathered latents, then absorbed decode over the latent pool,
through the XLA path and the kernel in interpret mode) agrees in float32
with the plain float32 reference, and a run of the harness on a tiny cell
of this block is correct where its float8 control is not. The new cell
reads its seven metrics and leaves the reasoning cell's list as it was.
"""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

from arch import deepseek_v3 as arch
from harness import costs
from harness import weights as W
from harness.driver import CallRecord
from harness.readings import Context
from harness.spec import load_cell
from harness.trace import Summary
from reference import deepseek_v3 as reference

with open(os.path.join(tiny.BENCH, "configs",
                       "kanana-2-30b-a3b-5l.json")) as _f:
    KANANA = json.load(_f)

# Kanana-2's keys at widths a CPU runs in seconds: one dense layer, then
# two MoE layers of 8 experts, top-2, and 2 shared experts
MODEL = dict(
    KANANA, name="tiny-dsv3", hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    qk_head_dim=24, v_head_dim=16, head_dim=8, vocab_size=512,
    assumed={"e_score_correction_bias_std": 0.3},
    engine=dict(tiny.MODEL["engine"]),
    check={"limits": {"logit_gap_mean": 0.01}, "max_tokens": 2048,
           "min_served": 48})

LONG = dict(tiny.BACKLOG, n_requests=5,
            prompt_len={"dist": "uniform", "min": 20, "max": 44})

NEW_METRICS = ["mla_decode_roofline.longdoc", "prefill_step_ms.longdoc",
               "decode_step_ms.longdoc", "step_mfu.longdoc",
               "moe_gmm_roofline.longdoc", "kv_peak_share.longdoc",
               "device_idle_share.longdoc"]


def test_leaves_are_the_program_param_tree():
    from repro.models import transformer as tfm
    cfg = arch.program_config(MODEL)
    prog = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    drawn = jax.eval_shape(lambda: W.make_params(MODEL, 3))
    flat = lambda t: {jax.tree_util.keystr(p): (x.shape, x.dtype)
                      for p, x in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(drawn) == flat(prog)
    assert "['lead']['mlp']['w_gate']" in flat(drawn)
    assert "['blocks']['pos0']['moe']['router_bias']" in flat(drawn)


def test_program_config_keeps_the_published_widths():
    cfg = arch.program_config(KANANA)
    assert (cfg.d_model, cfg.n_heads, cfg.vocab_size, cfg.d_ff) == \
        (2048, 32, 128256, 6144)
    assert (cfg.mla.kv_lora_rank, cfg.mla.qk_nope_head_dim,
            cfg.mla.qk_rope_head_dim, cfg.mla.v_head_dim) == (512, 128, 64,
                                                              128)
    m = cfg.moe
    assert (m.n_experts, m.d_expert, m.top_k, m.n_shared_experts,
            m.scoring, m.correction_bias, m.routed_scaling_factor) == \
        (128, 768, 6, 2, "sigmoid", True, 2.448)
    assert (cfg.n_layers, cfg.n_dense_lead, cfg.n_moe_layers) == (5, 1, 4)


def test_cost_hooks_count_the_published_form():
    m = KANANA
    D, H = 2048, 32
    proj = (2 * D * H * 192 + 2 * D * 576       # wq, wkv_a
            + 2 * 512 * H * 256 + 2 * H * 128 * D)   # wkv_b, wo
    assert arch.token_flops(m, 0) == proj + 6 * D * 6144
    assert arch.token_flops(m, 1) == proj + 2 * D * 128 + 8 * 6 * D * 768
    np.testing.assert_array_equal(
        arch.attention_flops(m, 3, np.array([1.0, 10.0])),
        [2 * H * 320, 2 * H * 3200])
    ops, nbytes = arch.paged_decode_cost([100, 0, 28], m)
    assert ops == 2 * H * (576 + 512) * 128
    assert nbytes == 128 * 576 * 2 + 3 * H * (576 + 512) * 2
    # the harness sums the module's layers: one dense, four MoE
    per_token = proj * 5 + 6 * D * 6144 + 4 * (2 * D * 128
                                               + 8 * 6 * D * 768)
    assert costs.decode_flops([1], m) == pytest.approx(
        per_token + 5 * 2 * H * 320 + 2 * D * 128256)


def test_longdoc_cell_reads_every_new_metric():
    cell = load_cell("kanana-2-30b-a3b-5l.longdoc")
    assert cell.arch.__name__ == "bench_arch_deepseek_v3"
    assert [m.name for m in cell.end_to_end] == ["output_tok_s", "setup_s"]
    assert [m.name for m in cell.per_layer] == NEW_METRICS
    assert cell.traffic["n_requests"] == 256
    m = cell.config
    calls = [CallRecord("decode", ctx=np.array([4000, 9000]),
                        expert_counts=np.full((4, 128), 3)),
             CallRecord("prefill", lanes=[(0, 512, False)],
                        expert_counts=np.full((4, 128), 24))]
    summary = Summary(window_s=2.0, busy_s=1.5,
                      call_kinds=["decode", "prefill"],
                      call_device_s=[0.04, 0.2],
                      kernel_s={"mla_decode": 0.001, "moe_gmm": 0.02},
                      device_ops=[], idle_gaps=[])
    ctx = Context(model=m, peak=costs.peaks("TPU v5 lite"), setup_s=1.0,
                  t0=0.0, t1=2.0, records=[], kv_peak=0.5,
                  decode_lanes=[2], calls=calls, trace=summary)
    got = {mt.name: mt.read(ctx) for mt in cell.per_layer}
    ideal = 5 * costs.min_time_s(*arch.paged_decode_cost([4000, 9000], m),
                                 ctx.peak)
    assert got["mla_decode_roofline.longdoc"] == pytest.approx(
        100 * ideal / 0.001)
    assert got["prefill_step_ms.longdoc"] == pytest.approx(200.0)
    assert got["decode_step_ms.longdoc"] == pytest.approx(40.0)
    assert got["kv_peak_share.longdoc"] == 50.0
    assert got["device_idle_share.longdoc"] == pytest.approx(25.0)
    assert all(v is not None and v > 0 for v in got.values())
    # without the kernel in the trace (a program that lacks it) the reader
    # finds nothing and says so
    ctx.trace = dataclasses.replace(summary, kernel_s={"moe_gmm": 0.02})
    assert cell.per_layer[0].read(ctx) is None


def test_reasoning_cell_metrics_as_before():
    cell = load_cell("qwen3-30b-a3b-4l.reasoning")
    assert [m.name for m in cell.end_to_end] == ["output_tok_s", "setup_s"]
    assert [m.name for m in cell.per_layer] == [
        "decode_lanes_mean.reasoning", "kv_peak_share.reasoning",
        "decode_step_ms.reasoning", "step_mfu.reasoning",
        "paged_decode_roofline.reasoning", "moe_gmm_roofline.reasoning",
        "device_idle_share.reasoning"]


def _float32_program(backend):
    from repro.serving.paged_engine import PagedEngineConfig, \
        PagedModelRunner
    cfg = dataclasses.replace(arch.program_config(MODEL), dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          W.make_params(MODEL, 9))
    ecfg = PagedEngineConfig(page_size=8, n_pages=64, max_blocks_per_req=8,
                             max_batch=2, token_budget=16,
                             chunk_buckets=(16,), max_prefill_lanes=2,
                             lane_buckets=(1, 2), attn_backend=backend,
                             interpret=backend == "pallas")
    return cfg, params, ecfg, PagedModelRunner(cfg, params, ecfg,
                                               n_sources=1)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_path_in_float32_agrees_with_the_reference(backend):
    """Logits of one request: a prompt of 21 in chunks of 16, then decode
    through the latent pool, against the reference's full forward. Both
    are float32 at the highest matmul precision; 1e-4 is rounding on
    logits of magnitude ~1 (a bfloat16 ulp there is ~4e-3). Then the
    engine serves two requests the reference's best token throughout."""
    from repro.models.transformer import identity_placement
    from repro.serving.paged_engine import PagedRealEngine
    from repro.serving.request import Request
    cfg, params, ecfg, runner = _float32_program(backend)
    placement = identity_placement(cfg)
    seq = np.random.default_rng(1).integers(0, 512, 30).tolist()
    P = 21
    pages = runner.init_pages()
    bt = jnp.asarray([[2, 4, 6, 8, 0, 0, 0, 0]], jnp.int32)
    got = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, P, 16):
            n = min(16, P - start)
            toks = np.zeros((1, 16), np.int32)
            toks[0, :n] = seq[start:start + n]
            lg, pages, _ = runner.prefill_chunk(
                {"tokens": jnp.asarray(toks),
                 "chunk_starts": jnp.asarray([start]),
                 "chunk_lens": jnp.asarray([n])}, pages, bt, placement,
                jnp.zeros((1,), jnp.int32))
        got.append(np.asarray(lg[0]))
        for t in range(P, len(seq)):
            lanes = np.zeros(2, np.int32)
            lanes[0] = t
            lg, pages, _ = runner.decode(
                jnp.asarray([seq[t], 0]), pages, jnp.asarray(lanes),
                jnp.concatenate([bt, jnp.zeros_like(bt)]),
                jnp.asarray([True, False]), placement,
                jnp.zeros((2,), jnp.int32))
            got.append(np.asarray(lg[0]))
    want = reference.logits(MODEL, 9, [seq], np.arange(P - 1, len(seq)))
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4)

    eng = PagedRealEngine(0, cfg, None, ecfg, runner=runner, n_sources=1)
    rng = np.random.default_rng(0)
    reqs = []
    for i, n in enumerate((21, 9)):
        toks = rng.integers(0, MODEL["vocab_size"], n).tolist()
        reqs.append(Request(req_id=i, prompt_len=n, max_new_tokens=6,
                            arrival_time=0.0, prompt_tokens=toks))
        eng.enqueue(reqs[-1], 0.0)
    with jax.default_matmul_precision("highest"):
        while eng.has_work:
            eng.step(0.0)
    ref = reference.served_logits(MODEL, 9, [r.prompt_tokens for r in reqs],
                                  [r.output_tokens for r in reqs])
    gap = np.concatenate(ref["best"]) - np.concatenate(ref["served"])
    assert gap.max() < 1e-4


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("dsv3")), model=MODEL,
                          mixes={"long": LONG})


@pytest.mark.parametrize("seed", [2**40 + 7, 12])
def test_harness_run_is_correct_and_its_control_is_not(tiny_root, seed):
    """The whole run as the benchmark drives it (the look for a chip
    skipped): bfloat16 program, float32 reference, float8 control."""
    from harness.cell import Setup, verify
    cell = load_cell("tiny.long", tiny_root)
    s = Setup(cell, seed, require_chip=False)
    ctx = s.window(cell.traffic, 3.0, t_start=time.perf_counter())
    s.free()
    ok, checks, readings, control_ok = verify(ctx, seed, control=True)
    assert ok, checks
    assert control_ok is False, readings
    assert ctx.kv_peak > 0 and ctx.decode_lanes
