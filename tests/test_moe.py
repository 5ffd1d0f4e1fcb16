"""MoE layer: dispatch-vs-oracle equivalence, placement/migration identities,
and routing-statistics correctness (property-based)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import moe as moe_mod

KEY = jax.random.PRNGKey(1)


def _cfg(top_k=2, cf=8.0):
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=top_k, capacity_factor=cf))


def test_dispatch_matches_dropless_oracle():
    cfg = _cfg(cf=float(8))   # capacity >= everything -> no drops
    params = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.bfloat16)
    placement = jnp.arange(cfg.moe.n_experts, dtype=jnp.int32)
    y, stats = jax.jit(lambda p, x: moe_mod.moe_layer(p, cfg, x, placement))(
        params, x)
    y_ref = jax.jit(lambda p, x: moe_mod.moe_layer_ref(p, cfg, x, placement))(
        params, x)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_placement_permutation_invariance():
    """Permuting expert placement while permuting the physical weights the
    same way must leave outputs unchanged (the migration correctness law)."""
    cfg = _cfg()
    params = moe_mod.init_moe(KEY, cfg)
    E = cfg.moe.n_experts
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.bfloat16)
    ident = jnp.arange(E, dtype=jnp.int32)
    perm = jnp.asarray(np.random.default_rng(0).permutation(E), jnp.int32)

    y0, _ = moe_mod.moe_layer(params, cfg, x, ident)
    moved = moe_mod.migrate_expert_weights(params, ident, perm)
    y1, _ = moe_mod.moe_layer(moved, cfg, x, perm)
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y1, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_capacity_drops_tokens_not_crash():
    cfg = _cfg(cf=0.25)       # force drops
    params = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model), jnp.bfloat16)
    placement = jnp.arange(cfg.moe.n_experts, dtype=jnp.int32)
    y, _ = moe_mod.moe_layer(params, cfg, x, placement)
    assert bool(jnp.isfinite(y.astype(jnp.float32)).all())


def test_statistics_match_routing():
    cfg = _cfg()
    params = moe_mod.init_moe(KEY, cfg)
    B, S = 3, 16
    x = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.bfloat16)
    placement = jnp.arange(cfg.moe.n_experts, dtype=jnp.int32)
    src = jnp.asarray([0, 1, 1], jnp.int32)
    _, stats = moe_mod.moe_layer(params, cfg, x, placement, source_ids=src,
                                 n_sources=2)
    counts = np.asarray(stats["expert_counts"])
    a = np.asarray(stats["source_expert"])
    assert counts.sum() == B * S * cfg.moe.top_k
    np.testing.assert_array_equal(a.sum(axis=0), counts)  # B is A's marginal
    assert a[0].sum() == S * cfg.moe.top_k                # row 0 -> source 0
    assert a[1].sum() == 2 * S * cfg.moe.top_k


# ------------------------------------------------- ragged dispatch (D1)
def test_ragged_matches_dropless_oracle():
    """Ragged dispatch is dropless by construction: it must match the dense
    oracle even at a capacity factor that would drop tokens when padded."""
    cfg = _cfg(cf=0.5)
    params = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.bfloat16)
    placement = jnp.arange(cfg.moe.n_experts, dtype=jnp.int32)
    y, _ = jax.jit(lambda p, x: moe_mod.moe_layer(p, cfg, x, placement,
                                                  ragged=True))(params, x)
    y_ref = moe_mod.moe_layer_ref(params, cfg, x, placement)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_ragged_matches_padded_at_high_capacity():
    cfg = _cfg(cf=float(8))   # dropless padded == ragged
    params = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (3, 8, cfg.d_model), jnp.bfloat16)
    placement = jnp.arange(cfg.moe.n_experts, dtype=jnp.int32)
    y_r, _ = moe_mod.moe_layer(params, cfg, x, placement, ragged=True)
    y_p, _ = moe_mod.moe_layer(params, cfg, x, placement, ragged=False)
    np.testing.assert_allclose(np.asarray(y_r, np.float32),
                               np.asarray(y_p, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_ragged_decode_regroup_equivalent():
    """Decode (S == 1, B > 1): ragged flattens the whole batch into one
    dispatch group; must match the padded decode-regroup path at dropless
    capacity."""
    cfg = _cfg(cf=float(8))
    params = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (16, 1, cfg.d_model), jnp.bfloat16)
    placement = jnp.arange(cfg.moe.n_experts, dtype=jnp.int32)
    y_r, _ = jax.jit(lambda p, x: moe_mod.moe_layer(p, cfg, x, placement,
                                                    ragged=True))(params, x)
    assert y_r.shape == x.shape
    y_p, _ = moe_mod.moe_layer(params, cfg, x, placement, ragged=False)
    np.testing.assert_allclose(np.asarray(y_r, np.float32),
                               np.asarray(y_p, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_ragged_placement_migration_roundtrip():
    """Non-identity placements + a migrate_expert_weights round-trip leave
    ragged outputs unchanged (the migration correctness law, ragged form)."""
    cfg = _cfg()
    params = moe_mod.init_moe(KEY, cfg)
    E = cfg.moe.n_experts
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.bfloat16)
    ident = jnp.arange(E, dtype=jnp.int32)
    rng = np.random.default_rng(3)
    perm1 = jnp.asarray(rng.permutation(E), jnp.int32)
    perm2 = jnp.asarray(rng.permutation(E), jnp.int32)

    y0, _ = moe_mod.moe_layer(params, cfg, x, ident, ragged=True)
    p1 = moe_mod.migrate_expert_weights(params, ident, perm1)
    y1, _ = moe_mod.moe_layer(p1, cfg, x, perm1, ragged=True)
    p2 = moe_mod.migrate_expert_weights(p1, perm1, perm2)
    y2, _ = moe_mod.moe_layer(p2, cfg, x, perm2, ragged=True)
    # round-trip back to identity
    p3 = moe_mod.migrate_expert_weights(p2, perm2, ident)
    y3, _ = moe_mod.moe_layer(p3, cfg, x, ident, ragged=True)
    for ya in (y1, y2, y3):
        np.testing.assert_allclose(np.asarray(y0, np.float32),
                                   np.asarray(ya, np.float32),
                                   rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(np.asarray(p3["w_gate"]),
                                  np.asarray(params["w_gate"]))


def test_ragged_statistics_match_padded():
    """B[e]/A[s, e] collected on the sorted ids must equal the scatter-add
    statistics of the padded path, including under non-identity placement."""
    cfg = _cfg()
    params = moe_mod.init_moe(KEY, cfg)
    B, S = 3, 16
    x = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.bfloat16)
    E = cfg.moe.n_experts
    perm = jnp.asarray(np.random.default_rng(5).permutation(E), jnp.int32)
    src = jnp.asarray([0, 1, 1], jnp.int32)
    _, s_r = moe_mod.moe_layer(params, cfg, x, perm, source_ids=src,
                               n_sources=2, ragged=True)
    _, s_p = moe_mod.moe_layer(params, cfg, x, perm, source_ids=src,
                               n_sources=2, ragged=False)
    np.testing.assert_array_equal(np.asarray(s_r["expert_counts"]),
                                  np.asarray(s_p["expert_counts"]))
    np.testing.assert_array_equal(np.asarray(s_r["source_expert"]),
                                  np.asarray(s_p["source_expert"]))
    assert int(np.asarray(s_r["expert_counts"]).sum()) == \
        B * S * cfg.moe.top_k


def test_ragged_grad_is_finite():
    """The custom-VJP ragged GMM backward (XLA formulation) must produce
    finite grads for params and inputs (train path)."""
    cfg = _cfg()
    params = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 8, cfg.d_model), jnp.bfloat16)
    placement = jnp.arange(cfg.moe.n_experts, dtype=jnp.int32)

    def loss(p, x):
        y, st = moe_mod.moe_layer(p, cfg, x, placement, ragged=True)
        return jnp.sum(y.astype(jnp.float32)) + st["aux_loss"]

    gp, gx = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
    for leaf in jax.tree.leaves((gp, gx)):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())
    # expert weights actually receive gradient signal
    assert float(jnp.abs(gp["w_gate"].astype(jnp.float32)).sum()) > 0


@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_property_gates_sum_to_one(seed, k):
    cfg = _cfg(top_k=k)
    params = moe_mod.init_moe(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (30, cfg.d_model),
                          jnp.bfloat16)
    gates, idx, probs = moe_mod.route(params, cfg, x)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-5)
    assert int(idx.min()) >= 0 and int(idx.max()) < cfg.moe.n_experts
    # top-k ids are distinct per token
    ids = np.asarray(idx)
    for row in ids:
        assert len(set(row.tolist())) == k
