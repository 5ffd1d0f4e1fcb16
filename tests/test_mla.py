"""Latent attention (MLA), the sigmoid router with a correction bias and
leading dense layers: the pieces DeepSeek-V3-shaped models (Kanana-2)
need, at a tiny size on the CPU.

The paged path (chunked prefill, then decode through the latent pool) is
checked against the benchmark's float32 reference in
``bench/tests/test_bench_deepseek_v3.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.kernels import ref
from repro.kernels.mla_decode import (mla_decode_pallas, mla_decode_xla,
                                      padded_width)
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.serving.paged_engine import (PagedEngineConfig, PagedModelRunner,
                                        PagedRealEngine)

CFG = ModelConfig(
    name="tiny-dsv3", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, vocab_size=256, head_dim=16,
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared_experts=2,
                  d_shared=16, scoring="sigmoid", correction_bias=True,
                  routed_scaling_factor=2.5),
    mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    n_dense_lead=1, rope_theta=1e4, dtype="float32")


@pytest.fixture(scope="module")
def params():
    p = jax.jit(lambda k: tfm.init_params(k, CFG))(jax.random.PRNGKey(0))
    # a bias that matters: as large as the spread of the scores
    bias = jax.random.normal(jax.random.PRNGKey(5), (2, 8)) * 0.3
    p["blocks"]["pos0"]["moe"]["router_bias"] = bias
    return p


# ------------------------------------------------------------ the kernel
def _rows(rng, P, ps, W, dtype):
    """A pool of latent rows, zero past ``W`` as the model writes them."""
    rows = np.zeros((P, ps, padded_width(W)), np.float32)
    rows[..., :W] = rng.normal(size=(P, ps, W))
    return jnp.asarray(rows, dtype)


@pytest.mark.parametrize("B,H,rank,rope,ps,NB,ctx,dtype", [
    pytest.param(4, 4, 16, 8, 8, 5, [0, 1, 8, 37], jnp.float32, id="f32"),
    # blocks of 16 pages at ps 16; 20 slots are not a whole block; lanes of
    # no context between live ones
    pytest.param(5, 8, 64, 32, 16, 20, [0, 256, 0, 320, 17], jnp.bfloat16,
                 id="blocks-bf16"),
    pytest.param(3, 8, 128, 64, 16, 3, [48, 0, 47], jnp.bfloat16,
                 id="576-wide"),
])
def test_mla_decode_matches_the_xla_path_and_the_oracle(B, H, rank, rope, ps,
                                                        NB, ctx, dtype):
    W = rank + rope
    P = B * NB + 4
    rng = np.random.default_rng(7)
    pages = _rows(rng, P, ps, W, dtype)
    q = jnp.asarray(np.pad(rng.normal(size=(B, H, W)),
                           ((0, 0), (0, 0), (0, padded_width(W) - W))), dtype)
    ctx = jnp.asarray(ctx, jnp.int32)
    bt = np.zeros((B, NB), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b in range(B):
        for j in range(-(-int(ctx[b]) // ps)):
            bt[b, j] = free.pop()
    bt = jnp.asarray(bt)
    scale = 1.0 / np.sqrt(rank // 4 + rope)
    o_ref = ref.mla_decode_ref(q, pages, bt, ctx, rank=rank, scale=scale)
    o_pal = mla_decode_pallas(q, pages, bt, ctx, rank=rank, scale=scale,
                              interpret=True)
    o_xla = mla_decode_xla(q, pages, bt, ctx, rank=rank, scale=scale)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2    # one bf16 output ulp
    for got in (o_pal, o_xla):
        assert got.shape == (B, H, rank)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   rtol=tol, atol=tol)
    assert not np.asarray(o_pal[np.asarray(ctx) == 0]).any()


def test_mla_decode_reads_no_dead_row_and_ignores_the_garbage_page():
    """Page 0 (where masked writes land), every page past a lane's context
    and the rows past it in its last page hold NaN: the kernel neither
    fetches nor multiplies them."""
    B, H, rank, rope, ps, NB = 4, 4, 16, 8, 8, 6
    W, P = rank + rope, B * NB + 2
    rng = np.random.default_rng(2)
    pages = np.array(_rows(rng, P, ps, W, jnp.float32))
    q = jnp.asarray(np.pad(rng.normal(size=(B, H, W)),
                           ((0, 0), (0, 0), (0, padded_width(W) - W))),
                    jnp.float32)
    ctx = np.array([NB * ps - 1, 0, 9, 1], np.int32)
    bt = np.zeros((B, NB), np.int32)
    nxt = iter(range(1, P))
    for b in range(B):
        for j in range(-(-ctx[b] // ps)):
            bt[b, j] = next(nxt)
    o_ref = ref.mla_decode_ref(q, jnp.asarray(pages), jnp.asarray(bt),
                               jnp.asarray(ctx), rank=rank, scale=0.2)
    live = np.zeros((P, ps), bool)
    for b in range(B):
        for t in range(ctx[b]):
            live[bt[b, t // ps], t % ps] = True
    assert not live[0].any()
    pages[~live] = np.nan
    o_pal = mla_decode_pallas(q, jnp.asarray(pages), jnp.asarray(bt),
                              jnp.asarray(ctx), rank=rank, scale=0.2,
                              interpret=True)
    assert np.isfinite(np.asarray(o_pal)).all()
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ the model
def test_router_chooses_by_score_plus_bias_and_weights_by_score():
    cfg = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, top_k=3))
    E, D = 8, 4
    router = np.zeros((D, E), np.float32)
    router[0] = [2.0, 1.5, 1.0, 0.5, 0.0, -0.5, -1.0, -1.5]
    bias = np.zeros(E, np.float32)
    bias[6] = 10.0                       # the bias alone picks expert 6
    params = {"router": jnp.asarray(router), "router_bias": jnp.asarray(bias)}
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]])
    gates, idx, _ = moe_mod.route(params, cfg, x)
    s = 1.0 / (1.0 + np.exp(-router[0]))
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 6]
    want = s[[0, 1, 6]] / s[[0, 1, 6]].sum() * 2.5
    got = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(gates[0])))
    np.testing.assert_allclose([got[0], got[1], got[6]], want, rtol=1e-6)
    # the dense oracle gates its experts the same way
    p = moe_mod.init_moe(jax.random.PRNGKey(3), cfg, d_model=D)
    p.update(params)
    xs = jax.random.normal(jax.random.PRNGKey(4), (1, 5, D))
    y = moe_mod.moe_layer_ref(p, cfg, xs, jnp.arange(E, dtype=jnp.int32))
    g, i, _ = moe_mod.route(p, cfg, xs.reshape(5, D))
    assert (np.asarray(i) == 6).any(axis=1).all()
    h = lambda e: jax.nn.silu(xs[0] @ p["w_gate"][e]) * (xs[0] @ p["w_up"][e])
    manual = sum(g[:, k, None] * jnp.stack(
        [h(int(e))[t] @ p["w_down"][int(e)] for t, e in enumerate(i[:, k])])
        for k in range(3))
    from repro.models.layers import mlp
    manual = manual + mlp(p["shared"], xs[0])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(manual),
                               rtol=1e-5, atol=1e-5)


def test_softmax_router_is_unchanged():
    """The default router is softmax, top-k, renormalized, unscaled."""
    cfg = dataclasses.replace(CFG, moe=MoEConfig(n_experts=8, top_k=2,
                                                 d_expert=32))
    router = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    gates, idx, probs = moe_mod.route({"router": router}, cfg, x)
    p = jax.nn.softmax(x @ router, axis=-1)
    top, want_idx = jax.lax.top_k(p, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(p), rtol=1e-6)


def test_placement_and_statistics_cover_the_moe_layers_only(params):
    assert CFG.n_moe_layers == 2 and tfm.n_super_blocks(CFG) == 2
    assert not CFG.is_moe_layer(0) and CFG.is_moe_layer(1)
    assert tfm.identity_placement(CFG).shape == (2, 8)
    pages = tfm.init_paged_cache(CFG, 8, 4)
    assert pages["lead"]["latent"].shape == (1, 8, 4, 128)
    assert pages["pos0"]["latent"].shape == (2, 8, 4, 128)
    _, _, stats = tfm.decode_step_paged(
        params, CFG, jnp.array([3, 4]), pages, jnp.array([0, 2]),
        block_tables=jnp.array([[1, 0], [2, 0]]),
        source_ids=jnp.array([0, 1]), n_sources=2)
    assert stats["expert_counts"].shape == (2, 8)
    assert stats["source_expert"].shape == (2, 2, 8)
    # top-2 of 2 tokens in each of the 2 MoE layers
    assert np.asarray(stats["expert_counts"]).sum(axis=1).tolist() == [4, 4]


def test_page_bytes_come_from_the_latent_width(params):
    """What one page costs the host tier: every layer's rows of 128 lanes
    (16 latent + 8 roped key, zero-padded), float32 here."""
    from repro.serving.kv_tier import HostKVTier
    ecfg = PagedEngineConfig(page_size=8, n_pages=16, max_blocks_per_req=4,
                             max_batch=2, token_budget=16,
                             chunk_buckets=(16,), attn_backend="xla")
    tier = HostKVTier(capacity_pages=4)
    eng = PagedRealEngine(0, CFG, params, ecfg, n_sources=1, tier=tier)
    assert tier.page_nbytes == tfm.paged_cache_page_nbytes(eng.pages) \
        == 3 * 8 * 128 * 4


def test_migration_leaves_the_dense_layer_untouched(params):
    ident = np.asarray(tfm.identity_placement(CFG))
    new = ident.copy()
    new[1] = np.random.default_rng(0).permutation(8)
    moved = tfm.migrate_params_for_placement(params, CFG, ident, new)
    assert moved["lead"] is params["lead"]
    w = params["blocks"]["pos0"]["moe"]["w_gate"]
    mw = moved["blocks"]["pos0"]["moe"]["w_gate"]
    np.testing.assert_array_equal(np.asarray(mw[0]), np.asarray(w[0]))
    inv = np.argsort(new[1])
    np.testing.assert_array_equal(np.asarray(mw[1]), np.asarray(w[1][inv]))


def test_configs_without_a_leading_layer_have_no_lead_stack():
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    p = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert set(p) == {"embed", "blocks", "final_norm"}
    assert set(tfm.init_paged_cache(cfg, 4, 4)) == {"pos0"}


# ------------------------------------------------------------ refusals
@pytest.mark.parametrize("make,match", [
    (lambda: MLAConfig(kv_lora_rank=16, q_lora_rank=32), "q_lora_rank"),
    (lambda: MoEConfig(n_experts=8, top_k=2, n_group=2), "group-limited"),
    (lambda: MoEConfig(n_experts=8, top_k=2, topk_group=2), "group-limited"),
], ids=["q-lora", "n-group", "topk-group"])
def test_out_of_scope_configs_are_refused(make, match):
    with pytest.raises(NotImplementedError, match=match):
        make()


def test_int8_latent_pages_are_refused():
    with pytest.raises(NotImplementedError, match="int8 pages of latent"):
        tfm.init_paged_cache(CFG, 8, 4, kv_dtype="int8")


def test_mixed_steps_with_latent_attention_are_refused(params):
    ecfg = PagedEngineConfig(page_size=8, n_pages=16, max_blocks_per_req=4,
                             max_batch=2, token_budget=16,
                             chunk_buckets=(16,), attn_backend="xla")
    runner = PagedModelRunner(CFG, params, ecfg, n_sources=1)
    with pytest.raises(NotImplementedError, match="mixed steps"):
        PagedRealEngine(0, CFG, None,
                        dataclasses.replace(ecfg, mixed_steps=True),
                        runner=runner, n_sources=1)
