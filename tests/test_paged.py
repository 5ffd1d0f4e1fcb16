"""Paged KV serving runtime: kernel parity, allocator invariants, engine
end-to-end (chunked prefill, preemption/resume determinism, rejection)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.paged_decode import (paged_decode_pallas, paged_decode_xla)
from repro.serving import (PagedBlockAllocator, PagedEngineConfig,
                           PagedModelRunner, PagedRealEngine,
                           RealClusterConfig, Request, RequestState,
                           serve_real_cluster)

RNG = np.random.default_rng(7)


def _random_block_setup(B, P, ps, NB, ctx_lens, rng):
    """Random distinct physical pages per request (page 0 stays garbage)."""
    bt = np.zeros((B, NB), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b in range(B):
        for j in range(-(-int(ctx_lens[b]) // ps)):
            bt[b, j] = free.pop()
    return jnp.asarray(bt)


# ------------------------------------------------------------ kernel parity
def _case(B, Hq, Hkv, hd, ps, NB, ctx=None, dtype=jnp.float32, *, id):
    return pytest.param(B, Hq, Hkv, hd, ps, NB, ctx, dtype, id=id)


# ctx None: ragged lengths incl. an empty lane and a page-aligned boundary.
# The kernel walks blocks of pages_per_block(ps, NB) slots (16 at ps 16,
# 8 at ps 32 once NB allows); ctx 256 ends a block exactly.
@pytest.mark.parametrize("B,Hq,Hkv,hd,ps,NB,ctx,dtype", [
    _case(2, 4, 4, 32, 16, 4, id="2-4-4-32-16-4"),         # MHA
    _case(3, 8, 2, 16, 8, 5, id="3-8-2-16-8-5"),           # GQA 4:1
    _case(4, 4, 1, 64, 32, 3, id="4-4-1-64-32-3"),         # MQA, bigger pages
    # GQA 8:1 as in Qwen3; NB 20 is not a multiple of its 16-page blocks
    _case(5, 32, 4, 16, 16, 20, [0, 16, 256, 320, 257], id="gqa8-bf16",
          dtype=jnp.bfloat16),
    _case(5, 32, 4, 16, 16, 20, [255, 0, 320, 17, 256], id="gqa8-f32"),
    # NB 3 is smaller than a block: one block of 3 slots, full table
    _case(4, 32, 4, 16, 16, 3, [48, 0, 16, 47], id="nb-below-block-bf16",
          dtype=jnp.bfloat16),
    # 8-page blocks of 256 tokens, 12 slots; empty lanes between live ones
    _case(6, 8, 2, 16, 32, 12, [0, 384, 0, 0, 256, 33], id="blocks-f32"),
    _case(6, 8, 2, 16, 32, 12, [256, 0, 0, 384, 1, 0], id="blocks-bf16",
          dtype=jnp.bfloat16),
])
def test_paged_decode_parity_sweep(B, Hq, Hkv, hd, ps, NB, ctx, dtype):
    P = B * NB + 4
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, hd)), dtype)
    if ctx is None:
        ctx = np.minimum([0, 1, ps, NB * ps - 3][:B] or [5], NB * ps)
    ctx = jnp.asarray(np.resize(ctx, B), jnp.int32)
    bt = _random_block_setup(B, P, ps, NB, np.asarray(ctx), rng)

    o_ref = ref.paged_decode_ref(q, kp, vp, bt, ctx)
    o_pal = paged_decode_pallas(q, kp, vp, bt, ctx, interpret=True)
    o_xla = paged_decode_xla(q, kp, vp, bt, ctx)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2    # one bf16 output ulp
    for got in (o_pal, o_xla):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   rtol=tol, atol=tol)
    assert not np.asarray(o_pal[np.asarray(ctx) == 0]).any()


@pytest.mark.parametrize("ps,NB", [(16, 20), (32, 12)])
def test_paged_decode_never_reads_dead_pages(ps, NB):
    """Page 0, every page wholly past a lane's context and the rows past
    the context in its last page hold NaN: the kernel must neither fetch
    nor multiply them, so its output is finite and equals the reference
    computed on a pool whose dead rows are finite."""
    B, Hq, Hkv, hd = 5, 8, 2, 16
    P = B * NB + 4
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
    kp = rng.normal(size=(P, ps, Hkv, hd)).astype(np.float32)
    vp = rng.normal(size=(P, ps, Hkv, hd)).astype(np.float32)
    ctx = np.array([NB * ps // 2 + 3, 0, ps, 1, NB * ps - ps - 1], np.int32)
    bt = np.asarray(_random_block_setup(B, P, ps, NB, ctx, rng))
    live = np.zeros((P, ps), bool)
    for b in range(B):
        for t in range(ctx[b]):
            live[bt[b, t // ps], t % ps] = True
    assert not live[0].any()
    o_ref = ref.paged_decode_ref(q, jnp.asarray(kp), jnp.asarray(vp),
                                 jnp.asarray(bt), jnp.asarray(ctx))
    kp[~live], vp[~live] = np.nan, np.nan
    o_pal = paged_decode_pallas(q, jnp.asarray(kp), jnp.asarray(vp),
                                jnp.asarray(bt), jnp.asarray(ctx),
                                interpret=True)
    assert np.isfinite(np.asarray(o_pal)).all()
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_matches_dense_flash_decode():
    """Gathering a request's pages into a dense cache and running the dense
    kernel must agree with the paged kernel on the same state."""
    B, Hq, Hkv, hd, ps, NB = 2, 8, 4, 32, 16, 4
    P = B * NB + 2
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, hd)), jnp.float32)
    ctx = jnp.asarray([37, 64], jnp.int32)
    bt = _random_block_setup(B, P, ps, NB, np.asarray(ctx), rng)

    o_paged = paged_decode_pallas(q, kp, vp, bt, ctx, interpret=True)

    L = NB * ps
    kd = kp[bt].reshape(B, L, Hkv, hd)
    vd = vp[bt].reshape(B, L, Hkv, hd)
    pos = jnp.arange(L, dtype=jnp.int32)[None]
    kpos = jnp.where(pos < ctx[:, None], pos, -1).astype(jnp.int32)
    qpos = (ctx - 1).astype(jnp.int32)
    o_dense = flash_decode(q, kd, vd, kpos, qpos, l_block=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ allocator
def test_paged_allocator_roundtrip_and_tables():
    a = PagedBlockAllocator(8, page_size=16)
    assert a.allocate(1, 40)              # 3 pages
    assert a.allocate(2, 16)              # 1 page
    assert len(a.table_of(1)) == 3 and len(a.table_of(2)) == 1
    assert a.usage == pytest.approx(4 / 8)
    bt = a.block_table_array([2, None, 1], max_blocks=4)
    assert bt.shape == (3, 4)
    assert (bt[1] == 0).all()             # inactive lane -> garbage page
    assert set(bt[0, 1:]) == {0} and bt[0, 0] == a.table_of(2)[0]
    assert not a.allocate(3, 5 * 16)      # 5 pages > 4 free
    a.check_invariants()
    a.free(1)
    assert a.usage == pytest.approx(1 / 8)
    a.check_invariants()


def test_paged_allocator_accounting_matches_blockpool():
    """Random op stream: the physical free-list and the inherited BlockPool
    books never diverge, and no page is ever double-booked."""
    a = PagedBlockAllocator(32, page_size=8)
    held = {}
    rng = np.random.default_rng(1)
    for _ in range(300):
        rid = int(rng.integers(0, 10))
        if rng.random() < 0.25 and rid in held:
            a.free(rid)
            held.pop(rid)
        else:
            tok = held.get(rid, 0) + int(rng.integers(1, 40))
            if a.allocate(rid, tok):
                held[rid] = tok
        a.check_invariants()
        assert 0.0 <= a.usage <= 1.0


# ------------------------------------------------------------ engine fixtures
# (tiny_model comes session-scoped from conftest.py)
def _mk_requests(cfg, n, *, prompt_lens, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(prompt_lens[i % len(prompt_lens)])
        reqs.append(Request(
            req_id=i, prompt_len=plen, max_new_tokens=max_new,
            arrival_time=0.001 * i,     # distinct arrivals: deterministic
                                        # latest-arrival eviction order
            prompt_tokens=rng.integers(0, cfg.vocab_size, plen).tolist()))
    return reqs


def _drive(engine, reqs, max_steps=400):
    for r in reqs:
        engine.enqueue(r, 0.0)
    now = 0.0
    for _ in range(max_steps):
        engine.step(now)
        now += 0.01
        if not engine.has_work:
            break
    return now


# ------------------------------------------------------------ engine behavior
def test_paged_engine_serves_chunked_prefill(tiny_model):
    cfg, params = tiny_model
    ecfg = PagedEngineConfig(page_size=8, n_pages=40, max_blocks_per_req=8,
                             max_batch=4, token_budget=16,
                             chunk_buckets=(8, 16), attn_backend="xla")
    e = PagedRealEngine(0, cfg, params, ecfg, n_sources=1)
    reqs = _mk_requests(cfg, 3, prompt_lens=[21, 9, 30], max_new=4)
    _drive(e, reqs)
    assert all(r.state is RequestState.FINISHED and not r.error
               for r in reqs)
    assert all(len(r.output_tokens) == 4 for r in reqs)
    # 21 and 30-token prompts need >= 2 chunks at budget 16
    assert e.total_prefill_tokens == 21 + 9 + 30
    # routing statistics count REAL tokens only: chunk padding rows and
    # inactive decode lanes are masked out of B/A (truthful coordinator
    # signals), so totals must equal layers * top_k * processed tokens
    B, A = e.window_stats()
    expected = cfg.n_moe_layers * cfg.moe.top_k * (
        e.total_prefill_tokens + e.total_decode_tokens)
    assert int(B.sum()) == expected
    assert int(A.sum()) == expected
    e.pool.check_invariants()
    assert e.pool.usage == 0.0            # everything freed on finish
    t = e.trace(1.0)
    assert t.kv_usage == 0.0 and t.n_running == 0


def test_paged_engine_rejects_overlong_prompt(tiny_model):
    cfg, params = tiny_model
    ecfg = PagedEngineConfig(page_size=8, n_pages=40, max_blocks_per_req=4,
                             attn_backend="xla")     # 32-token capacity
    e = PagedRealEngine(0, cfg, params, ecfg, n_sources=1)
    r = _mk_requests(cfg, 1, prompt_lens=[64])[0]
    e.enqueue(r, 0.0)
    assert r.state is RequestState.FINISHED
    assert r.error == "prompt_exceeds_kv_capacity"
    assert not e.waiting and not e.has_work
    # within block-table reach but prompt+decode cannot fit the pool
    small = PagedRealEngine(1, cfg, params, dataclasses.replace(
        ecfg, n_pages=2), runner=e.runner, n_sources=1)
    r2 = _mk_requests(cfg, 1, prompt_lens=[20])[0]
    small.enqueue(r2, 0.0)
    assert r2.error == "prompt_exceeds_kv_capacity"


def test_real_engine_rejects_overlong_prompt(tiny_model):
    cfg, params = tiny_model
    from repro.serving.real_engine import RealModelEngine
    e = RealModelEngine(0, cfg, params, max_slots=2, max_len=32, n_sources=1)
    r = _mk_requests(cfg, 1, prompt_lens=[40])[0]
    e.enqueue(r, 0.0)
    assert r.state is RequestState.FINISHED
    assert r.error == "prompt_exceeds_max_len"
    assert not e.has_work


def test_preemption_resume_determinism(tiny_model):
    """Identical output tokens with and without KV-pressure eviction: the
    recompute path must reproduce the unpressured run bit-for-bit."""
    cfg, params = tiny_model
    roomy = PagedEngineConfig(page_size=8, n_pages=64, max_blocks_per_req=6,
                              max_batch=4, token_budget=16,
                              chunk_buckets=(8, 16), attn_backend="xla")
    e1 = PagedRealEngine(0, cfg, params, roomy, n_sources=1)
    reqs1 = _mk_requests(cfg, 4, prompt_lens=[17, 23, 11, 19], max_new=6)
    _drive(e1, reqs1)
    assert all(r.state is RequestState.FINISHED for r in reqs1)
    assert sum(r.n_preemptions for r in reqs1) == 0

    # 7 pages = 56 tokens for ~100 tokens of steady-state demand -> eviction
    tight = dataclasses.replace(roomy, n_pages=7)
    e2 = PagedRealEngine(0, cfg, params, tight, runner=e1.runner,
                         n_sources=1)
    reqs2 = _mk_requests(cfg, 4, prompt_lens=[17, 23, 11, 19], max_new=6)
    _drive(e2, reqs2)
    assert all(r.state is RequestState.FINISHED for r in reqs2)
    assert sum(r.n_preemptions for r in reqs2) > 0
    for a, b in zip(reqs1, reqs2):
        assert a.output_tokens == b.output_tokens, \
            f"req {a.req_id} diverged after eviction/recompute"
    e2.pool.check_invariants()
    assert e2.pool.usage == 0.0


def test_preemption_does_not_reclaim_shared_pages(tiny_model):
    """Preemption suite × prefix sharing: evicting a request that shares
    pages must only drop its own references — peers keep decoding on the
    same physical pages (the per-step invariant check would trip on a
    double-free), and the victim's resume re-matches the cache and stays
    deterministic vs the unpressured shared run."""
    cfg, params = tiny_model
    roomy = PagedEngineConfig(page_size=8, n_pages=64, max_blocks_per_req=6,
                              max_batch=4, token_budget=16,
                              chunk_buckets=(8, 16), attn_backend="xla",
                              prefix_sharing=True)
    shared = list(np.random.default_rng(21).integers(0, cfg.vocab_size, 16))

    def mk():
        tails = [[7] * 1, [11] * 7, [13] * 3, [17] * 5]
        return [Request(req_id=i, prompt_len=16 + len(t), max_new_tokens=6,
                        arrival_time=0.001 * i,
                        prompt_tokens=[int(x) for x in shared] + t)
                for i, t in enumerate(tails)]

    def drive(e, reqs):
        for r in reqs:
            e.enqueue(r, 0.0)
        now = 0.0
        for _ in range(400):
            e.step(now)
            e.pool.check_invariants()     # peers' pages never double-freed
            now += 0.01
            if not e.has_work:
                break

    e1 = PagedRealEngine(0, cfg, params, roomy, n_sources=1)
    r1 = mk()
    drive(e1, r1)
    assert all(r.state is RequestState.FINISHED for r in r1)
    assert sum(r.n_preemptions for r in r1) == 0

    tight = dataclasses.replace(roomy, n_pages=7)
    e2 = PagedRealEngine(0, cfg, params, tight, runner=e1.runner,
                         n_sources=1)
    r2 = mk()
    drive(e2, r2)
    assert all(r.state is RequestState.FINISHED for r in r2)
    assert sum(r.n_preemptions for r in r2) > 0
    for a, b in zip(r1, r2):
        assert a.output_tokens == b.output_tokens, \
            f"req {a.req_id} diverged after shared-page eviction"
    e2.pool.check_invariants()
    assert e2.pool.usage == 0.0


def test_dpengine_rejects_trajectory_exceeding_pool():
    """A prompt+decode trajectory larger than the whole pool can never
    complete; it is rejected at enqueue instead of stalling forever."""
    from repro.serving import DPEngine, EngineConfig
    from repro.serving.costmodel import CostModelConfig, EngineCostModel
    e = DPEngine(0, EngineConfig(kv_tokens=64, kv_block=16),
                 EngineCostModel(CostModelConfig()))
    r = Request(req_id=1, prompt_len=32, max_new_tokens=500,
                arrival_time=0.0)
    e.enqueue(r, 0.0)
    assert r.state is RequestState.FINISHED
    assert r.error == "prompt_exceeds_kv_capacity"
    assert not e.has_work


def test_dpengine_stall_surfaces_in_trace():
    """When preemption cannot free KV (nothing else to evict), the decode
    lane stalls and the trace reports it — it must not proceed unbacked."""
    from repro.serving import DPEngine, EngineConfig
    from repro.serving.costmodel import CostModelConfig, EngineCostModel
    e = DPEngine(0, EngineConfig(kv_tokens=1024, kv_block=16,
                                 token_budget=32),
                 EngineCostModel(CostModelConfig()))
    # 60 of 64 blocks reserved outside the engine's own requests (stand-in
    # for pressure the victim search cannot reach)
    assert e.pool.allocate(999, 960)
    r = Request(req_id=1, prompt_len=32, max_new_tokens=500,
                arrival_time=0.0)
    e.enqueue(r, 0.0)
    now, stalled_seen = 0.0, 0
    for _ in range(80):
        dur, _, info = e.step(now)
        stalled_seen += e.trace(now).n_stalled
        now += max(dur, 1e-3)
    # the 4 reachable blocks are exhausted after a few tokens; the lone
    # request can evict nobody -> it stalls instead of corrupting the pool
    assert stalled_seen > 0
    assert r.state is RequestState.RUNNING
    held = e.pool._held[r.req_id]
    assert held + 60 <= e.pool.total_blocks and e.pool.free_blocks >= 0


def test_runner_on_a_device_warms_every_step_shape(tiny_model):
    """A runner given a device commits its params and every pool it makes
    there; warmup compiles the decode step and each (lane, chunk) prefill
    bucket without counting as served traffic."""
    cfg, params = tiny_model
    dev = jax.devices()[-1]
    ecfg = PagedEngineConfig(page_size=8, n_pages=8, max_blocks_per_req=4,
                             max_batch=2, token_budget=16, chunk_buckets=(8,),
                             max_prefill_lanes=2, lane_buckets=(1, 2),
                             attn_backend="xla")
    runner = PagedModelRunner(cfg, params, ecfg, n_sources=2, device=dev)
    for x in jax.tree.leaves((runner.params, runner.init_pages())):
        assert x.committed and x.devices() == {dev}
    runner.warmup()
    assert sorted(runner._prefill_jits) == [(1, 8), (2, 8)]
    assert runner.padded_tokens_total == 0
    bt = f"tensor<{ecfg.max_batch}x{ecfg.max_blocks_per_req}xi32>"
    assert bt in runner.lower_decode().as_text()   # the served decode shape


# ------------------------------------------------------------ cluster e2e
@pytest.mark.slow
def test_live_expert_migration_moves_weights(tiny_model):
    """When the coordinator migrates experts mid-run, the cluster must
    permute the physical weights along with the placement — identical
    degenerate prompts then produce identical outputs across engines and
    across the migration boundary."""
    from repro.core.placement import PlacementConfig
    cfg, params = tiny_model
    ecfg = PagedEngineConfig(page_size=8, n_pages=48, max_blocks_per_req=6,
                             max_batch=4, token_budget=16,
                             chunk_buckets=(8, 16), attn_backend="xla")
    runner = PagedModelRunner(cfg, params, ecfg, n_sources=2)
    engines = [PagedRealEngine(i, cfg, params, ecfg, runner=runner,
                               n_sources=2) for i in range(2)]
    # one repeated token -> maximally skewed routing; uncalibrated greedy
    # rebalances at smoke scale (the calibrated 1e4-token migration cost
    # never pays off inside a 50-token window)
    reqs = [Request(req_id=i, prompt_len=20, max_new_tokens=4,
                    arrival_time=0.02 * i, prompt_tokens=[0] * 20)
            for i in range(8)]
    res = serve_real_cluster(reqs, engines, cluster_cfg=RealClusterConfig(
        window_tokens=50, placement_cfg=PlacementConfig.uncalibrated()))
    assert all(r.state is RequestState.FINISHED and not r.error
               for r in reqs)
    assert res.signals["migrations"] > 0
    assert len({tuple(r.output_tokens) for r in reqs}) == 1, \
        "expert migration changed the served model"


@pytest.mark.slow
def test_two_engine_gimbal_cluster_on_paged_plane(tiny_model):
    cfg, params = tiny_model
    ecfg = PagedEngineConfig(page_size=8, n_pages=32, max_blocks_per_req=6,
                             max_batch=4, token_budget=16,
                             chunk_buckets=(8, 16), attn_backend="xla")
    runner = PagedModelRunner(cfg, params, ecfg, n_sources=2)
    engines = [PagedRealEngine(i, cfg, params, ecfg, runner=runner,
                               n_sources=2) for i in range(2)]
    reqs = _mk_requests(cfg, 8, prompt_lens=[13, 21, 9, 17], max_new=4)
    for i, r in enumerate(reqs):
        r.arrival_time = 0.02 * i
    res = serve_real_cluster(
        reqs, engines, cluster_cfg=RealClusterConfig(window_tokens=200))
    done = [r for r in reqs if r.state is RequestState.FINISHED]
    assert len(done) == len(reqs) and not any(r.error for r in reqs)
    # both engines participated and the scheduler used live traces
    assert all(n > 0 for n in res.signals["per_engine"].values())
    assert sum(res.signals["decisions"].values()) == len(reqs)
    for e in engines:
        e.pool.check_invariants()
    # real (not hardcoded) trace signals were observable during the run
    assert res.mean_ttft > 0 and res.mean_e2e > 0
