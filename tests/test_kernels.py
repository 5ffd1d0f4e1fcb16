"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.moe_dispatch import (gmm_blocked_xla, padded_rows,
                                        pick_row_block, ragged_combine,
                                        ragged_dispatch)
from repro.kernels.moe_gmm import (SLAB_BYTES, moe_gmm, moe_gmm_ragged,
                                   weight_block_index, weight_slab)
from repro.kernels.source_expert_count import source_expert_count

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("T,K,E,S", [
    (64, 1, 8, 2), (257, 2, 16, 2), (1000, 4, 32, 4),
    (2048, 8, 128, 16), (13, 8, 128, 2),
])
def test_source_expert_count_sweep(T, K, E, S):
    eidx = jnp.asarray(RNG.integers(0, E, (T, K)), jnp.int32)
    src = jnp.asarray(RNG.integers(0, S, (T,)), jnp.int32)
    b, a = source_expert_count(eidx, src, n_experts=E, n_sources=S,
                               t_block=256, interpret=True)
    b_r, a_r = ref.source_expert_count_ref(eidx, src, n_experts=E,
                                           n_sources=S)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b_r))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_r))
    # invariants: B is A's source-marginal; totals = T*K
    assert int(b.sum()) == T * K
    np.testing.assert_array_equal(np.asarray(a.sum(0)), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [
    (2, 64, 128, 128), (4, 128, 256, 128), (8, 32, 512, 256),
])
def test_moe_gmm_sweep(E, C, D, F, dtype):
    x = jnp.asarray(RNG.normal(size=(E, C, D)), dtype)
    w = jnp.asarray(RNG.normal(size=(E, D, F)), dtype)
    y = moe_gmm(x, w, c_block=32, f_block=128, d_block=128, interpret=True)
    y_r = ref.moe_gmm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("E,C,D,F", [
    (2, 37, 100, 130), (3, 5, 64, 96), (4, 128, 200, 72),
])
def test_moe_gmm_nondivisible_dims(E, C, D, F):
    """Odd shapes auto-pad to the block multiple instead of asserting."""
    x = jnp.asarray(RNG.normal(size=(E, C, D)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(E, D, F)), jnp.float32)
    y = moe_gmm(x, w, c_block=32, f_block=128, d_block=64, interpret=True)
    assert y.shape == (E, C, F)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.moe_gmm_ref(
        x, w)), rtol=1e-5, atol=1e-4)


def _skewed_ids(T, K, E, alpha, rng):
    p = 1.0 / np.arange(1, E + 1) ** alpha
    p /= p.sum()
    g = rng.gumbel(size=(T, E)) + np.log(p)
    return np.argpartition(-g, K, axis=1)[:, :K].astype(np.int32)


_RAGGED_CASES = [
    (64, 1, 8, 64, 128, 0.0),      # tiny, uniform
    (200, 4, 16, 96, 160, 1.0),    # skewed, odd dims
    (256, 8, 64, 128, 96, 1.4),    # heavy skew: many empty experts
    (33, 2, 128, 72, 64, 2.0),     # E >> T*K: most groups empty
    (16, 2, 8, 2048, 768, 1.0),    # published widths (d_model, d_expert)
]


def _case_id(case):
    return "-".join(str(v) for v in case)


# each case under both weight tilings: the expert's whole (D, F) slab per
# grid step, and a budget too small for any slab, which falls to slabs of
# 128 columns with F padded up to a multiple of 128
@pytest.mark.parametrize("T,K,E,D,F,alpha,split", [
    pytest.param(*case, split, id=_case_id(case) + ("-split" if split else ""))
    for case in _RAGGED_CASES for split in (False, True)])
def test_moe_gmm_ragged_sweep(T, K, E, D, F, alpha, split):
    rng = np.random.default_rng(7)
    x2d = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    phys = jnp.asarray(_skewed_ids(T, K, E, alpha, rng))
    nb = pick_row_block(T * K, E)
    disp = jax.jit(
        lambda x, p: ragged_dispatch(x, p, E, row_block=nb))(x2d, phys)
    assert disp.xs.shape[0] == padded_rows(T * K, E, nb)
    # group_sizes is the physical-expert bincount
    np.testing.assert_array_equal(
        np.asarray(disp.group_sizes),
        np.bincount(np.asarray(phys).ravel(), minlength=E))

    w = jnp.asarray(rng.normal(size=(E, D, F)), jnp.float32)
    budget = 1 if split else D * F * 4
    assert weight_slab(D, F, 4, budget) == (128 if split else F)
    y = moe_gmm_ragged(disp.xs, w, disp.tile_expert, disp.group_sizes,
                       disp.padded_offsets, n_block=nb, slab_bytes=budget,
                       interpret=True)
    y_ref = ref.moe_gmm_ragged_ref(disp.xs, w, disp.group_sizes,
                                   disp.padded_offsets)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)
    # XLA fallback agrees on live rows (dead rows are zero-input anyway)
    y_xla = gmm_blocked_xla(disp.xs, w, disp.tile_expert, row_block=nb)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)

    # combine(unsort) reproduces the per-token gated mixture exactly
    gates = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    out = np.asarray(ragged_combine(y, disp.dest, gates))
    xn, pn, gn, wn = (np.asarray(x2d), np.asarray(phys), np.asarray(gates),
                      np.asarray(w))
    expect = np.einsum("tk,tkf->tf", gn,
                       np.einsum("td,tkdf->tkf", xn, wn[pn]))
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("d_in,d_out,whole", [
    (2048, 768, True),      # Qwen3 / Kanana gate and up
    (768, 2048, True),      # their down projection
    (5120, 8192, False),    # Llama 4 Maverick's experts split
])
def test_weight_slab_follows_the_shape(d_in, d_out, whole):
    fb = weight_slab(d_in, d_out, 2)            # bf16
    assert (fb == d_out) == whole
    assert fb % 128 == 0 and d_out % fb == 0
    assert d_in * fb * 2 <= SLAB_BYTES
    # the widest such slab: twice as wide would not fit or not divide
    if not whole:
        assert d_out % (2 * fb) != 0 or d_in * 2 * fb * 2 > SLAB_BYTES


@pytest.mark.parametrize("E,alpha,n_slabs", [(128, 1.2, 1), (64, 2.0, 3)])
def test_weight_slab_fetched_once_per_touched_expert(E, alpha, n_slabs):
    """The weight block index that the kernel's index map gives changes
    only where the row tiles pass to another expert: over the grid, at
    most (touched experts + 1) fetches per slab column, the one extra for
    the dead tail tiles that map to expert E-1."""
    rng = np.random.default_rng(3)
    T, K = 512, 6
    phys = jnp.asarray(_skewed_ids(T, K, E, alpha, rng))
    nb = pick_row_block(T * K, E)
    disp = ragged_dispatch(jnp.zeros((T, 8), jnp.float32), phys, E,
                           row_block=nb)
    te, sz, po = (np.asarray(a) for a in (
        disp.tile_expert, disp.group_sizes, disp.padded_offsets))
    touched = int((sz > 0).sum())
    fetches, last = 0, None
    for f in range(n_slabs):                    # the kernel's grid order
        for n in range(len(te)):
            block = weight_block_index(f, n, te, sz, po)
            fetches += block != last
            last = block
    assert n_slabs * touched <= fetches <= n_slabs * (touched + 1)
    assert len(te) > touched + 1                # tiles share a fetch


def test_ragged_dispatch_dest_is_injective():
    """Every (token, k) slot maps to a distinct live row of the sorted
    buffer, and live rows carry the right token content."""
    rng = np.random.default_rng(11)
    T, K, E, D = 100, 3, 12, 16
    x2d = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    phys = jnp.asarray(rng.integers(0, E, (T, K)), jnp.int32)
    disp = ragged_dispatch(x2d, phys, E, row_block=8)
    dest = np.asarray(disp.dest)
    assert len(set(dest.tolist())) == T * K
    xs = np.asarray(disp.xs)
    for slot in (0, T * K // 2, T * K - 1):
        np.testing.assert_array_equal(xs[dest[slot]],
                                      np.asarray(x2d)[slot // K])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,hd,L", [
    (1, 4, 4, 64, 512), (2, 8, 4, 64, 1024), (2, 8, 2, 128, 2048),
])
def test_flash_decode_sweep(B, Hq, Hkv, hd, L, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Hq, hd)), dtype)
    kc = jnp.asarray(RNG.normal(size=(B, L, Hkv, hd)), dtype)
    vc = jnp.asarray(RNG.normal(size=(B, L, Hkv, hd)), dtype)
    qpos = jnp.asarray(RNG.integers(L // 4, L - 1, (B,)), jnp.int32)
    kpos = jnp.where(jnp.arange(L)[None] <= qpos[:, None],
                     jnp.arange(L)[None], -1).astype(jnp.int32)
    o = flash_decode(q, kc, vc, kpos, qpos, l_block=256, interpret=True)
    o_r = ref.flash_decode_ref(q, kc, vc, kpos, qpos)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)


def test_ops_wrappers_run():
    eidx = jnp.asarray(RNG.integers(0, 16, (128, 2)), jnp.int32)
    src = jnp.asarray(RNG.integers(0, 2, (128,)), jnp.int32)
    b, a = ops.source_expert_count(eidx, src, n_experts=16, n_sources=2)
    assert int(b.sum()) == 256
