"""Blocked flash attention vs naive softmax oracle; SSM chunk-vs-step laws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.attention import flash_attention, ring_positions
from repro.models.ssm import (_mlstm_chunk, init_mamba, init_mlstm,
                              mamba_block, mlstm_block, mlstm_state_init,
                              mlstm_step)

RNG = np.random.default_rng(0)


def naive_attention(q, k, v, q_pos, k_pos, causal=True, window=0, cap=0.0):
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd).astype(np.float64)
    s = np.einsum("bqkgd,bskd->bkgqs", qg,
                  np.asarray(k, np.float64)) / np.sqrt(hd)
    if cap > 0:
        s = cap * np.tanh(s / cap)
    valid = (k_pos[:, None, None, None, :] >= 0)
    valid = np.broadcast_to(
        valid, (B, Hkv, G, Sq, Skv)).copy()
    if causal:
        valid = valid & (k_pos[:, None, None, None, :]
                         <= q_pos[:, None, None, :, None])
    if window > 0:
        valid = valid & (k_pos[:, None, None, None, :]
                         > (q_pos[:, None, None, :, None] - window))
    s = np.where(valid, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = np.where(valid, p, 0.0)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = np.einsum("bkgqs,bskd->bqkgd", p, np.asarray(v, np.float64))
    return out.reshape(B, Sq, Hq, hd)


@pytest.mark.parametrize("Sq,Skv,window,cap", [
    (16, 16, 0, 0.0), (33, 33, 0, 0.0), (16, 16, 5, 0.0),
    (24, 24, 0, 30.0), (8, 40, 0, 0.0),
])
def test_flash_matches_naive(Sq, Skv, window, cap):
    B, Hq, Hkv, hd = 2, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, Sq, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(Skv - Sq, Skv)[None], (B, Sq))
    k_pos = jnp.broadcast_to(jnp.arange(Skv)[None], (B, Skv))
    out = flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=True,
                          window=window, softcap_val=cap, q_block=8,
                          kv_block=8)
    ref = naive_attention(np.asarray(q), np.asarray(k), np.asarray(v),
                          np.asarray(q_pos), np.asarray(k_pos),
                          window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_flash_invalid_slots_ignored():
    """Slots marked k_pos = -1 must contribute nothing (ring buffers)."""
    B, S, H, hd = 1, 8, 2, 8
    q = jnp.asarray(RNG.normal(size=(B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    q_pos = jnp.full((B, 1), 100, jnp.int32)
    kp_full = jnp.arange(S)[None]
    out_full = flash_attention(q, k, v, q_pos=q_pos, k_pos=kp_full)
    # poison the masked-out half with huge values
    kp_half = jnp.where(kp_full < 4, kp_full, -1)
    k_poison = k.at[:, 4:].set(1e4)
    v_poison = v.at[:, 4:].set(1e4)
    out_half = flash_attention(q, k_poison, v_poison, q_pos=q_pos,
                               k_pos=kp_half)
    ref = naive_attention(np.asarray(q), np.asarray(k[:, :4]),
                          np.asarray(v[:, :4]), np.asarray(q_pos),
                          np.asarray(kp_full[:, :4]))
    np.testing.assert_allclose(np.asarray(out_half), ref, rtol=1e-4,
                               atol=1e-4)
    assert not np.allclose(np.asarray(out_half), np.asarray(out_full))


@given(st.integers(1, 200), st.integers(4, 16))
@settings(max_examples=30, deadline=None)
def test_ring_positions_properties(pos, L):
    wp = jnp.asarray([pos], jnp.int32)
    rp = np.asarray(ring_positions(wp, L))[0]
    # slot of the current position holds it
    assert rp[pos % L] == pos
    # every valid entry p satisfies p % L == slot and p <= pos
    for i, p in enumerate(rp):
        if p >= 0:
            assert p % L == i and p <= pos and p > pos - L
        else:
            assert pos < L  # only unfilled buffers have invalid slots


# ---------------------------------------------------------------- mLSTM
def test_mlstm_chunked_equals_stepwise():
    """The chunkwise-parallel form must equal the sequential recurrence."""
    B, S, H, hd = 2, 24, 2, 8
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    log_i = jnp.asarray(RNG.normal(size=(B, S, H)), jnp.float32)
    log_f = jnp.asarray(-np.abs(RNG.normal(size=(B, S, H))), jnp.float32)

    # stepwise
    st_ = mlstm_state_init(B, H, hd)
    outs = []
    for t in range(S):
        h, st_ = mlstm_step(q[:, t], k[:, t], v[:, t], log_i[:, t],
                            log_f[:, t], st_)
        outs.append(h)
    ref = jnp.stack(outs, axis=1)

    # chunked (chunk 6 divides 24)
    st2 = mlstm_state_init(B, H, hd)
    hs = []
    for c in range(0, S, 6):
        h, st2 = _mlstm_chunk(q[:, c:c+6], k[:, c:c+6], v[:, c:c+6],
                              log_i[:, c:c+6], log_f[:, c:c+6], st2)
        hs.append(h)
    out = jnp.concatenate(hs, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st2["C"]), np.asarray(st_["C"]),
                               rtol=2e-4, atol=2e-4)


def test_mlstm_block_prefill_then_step_consistent():
    d, H = 32, 2
    p = init_mlstm(jax.random.PRNGKey(0), d, H, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(1, 9, d)), jnp.float32)
    y_full, st_full = mlstm_block(p, x, H, chunk=4, return_state=True)
    _, st_pre = mlstm_block(p, x[:, :8], H, chunk=4, return_state=True)
    y_step, st_step = mlstm_block(p, x[:, 8:9], H, state=st_pre,
                                  return_state=True)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full[:, 8:9]),
                               rtol=1e-3, atol=1e-3)


def test_mamba_chunked_state_consistency():
    d = 16
    p = init_mamba(jax.random.PRNGKey(1), d, state_dim=4, conv_width=4,
                   expand=2, dtype=jnp.float32)
    x = jnp.asarray(RNG.normal(size=(1, 12, d)), jnp.float32)
    y_full, st_full = mamba_block(p, x, 4, 4, chunk=4, return_state=True)
    _, st_a = mamba_block(p, x[:, :8], 4, 4, chunk=4, return_state=True)
    y_b, st_b = mamba_block(p, x[:, 8:], 4, 4, state=st_a, chunk=4,
                            return_state=True)
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_full[:, 8:]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st_b["h"]), np.asarray(st_full["h"]),
                               rtol=1e-3, atol=1e-3)
