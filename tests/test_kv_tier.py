"""Host KV tier: swap round-trips, prefix archiving, truthful books,
swap-aware planning, int8 quantized pages.

Layers:

* **allocator oracle** — a numpy "device" page store backs the tiered
  allocator's save/load callbacks; random interleavings of
  match/allocate/register/free/swap-out/swap-in/drop must keep every
  live request's page contents bit-exact (swapped pages round-trip
  through host memory; archived prefix pages rematerialize on match)
  while the allocator + tier invariants hold after every op;
* **planner properties** — StepPlanner over a tiered pool with
  ``swap_policy`` in {swap, auto} upholds the StepPlan invariant pack
  (including the swap-record checks) on random interleavings;
* **engine differential (sim)** — a tight-pool tiered DPEngine serves
  the same workload as the recompute baseline with strictly fewer
  prefill tokens (victims keep their KV) while still admitting;
* **engine differential (real)** — a tight-pool tiered PagedRealEngine
  under ``swap_policy="swap"`` emits bit-identical outputs to a roomy
  reference with zero re-prefill, and swap-based drain re-attaches
  residents on a tier-sharing engine without recompute;
* **int8 pages** — pack/unpack round-trip error bounds, backend parity,
  dequant-on-read decode parity, and the capacity-ratio claim.
"""
import dataclasses

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp
import numpy as np
import pytest

import test_step_planner as tsp
from repro.core.queue_policy import order_queue
from repro.kernels.kv_pack import (pack_kv_pallas, pack_kv_xla,
                                   unpack_kv_pallas, unpack_kv_xla)
from repro.kernels.paged_decode import paged_decode_pallas, paged_decode_xla
from repro.serving import (DPEngine, EngineConfig, HostKVTier,
                           PagedEngineConfig, PagedRealEngine, PlannerConfig,
                           Request, RequestState, StepPlanner,
                           TieredSharedAllocator, check_plan_invariants)
from repro.serving.step_plan import written_kv_len


# ================================================================ helpers
class _FakeStore:
    """Numpy 'device' pages: one float row per (page, slot). The tier
    callbacks copy whole rows, so tier round-trips must reproduce them
    bit-exactly."""

    def __init__(self, n_pages, ps):
        self.data = np.zeros((n_pages + 1, ps))

    def save(self, ids):
        return self.data[np.asarray(ids, int)].copy()

    def load(self, payload, ids):
        self.data[np.asarray(ids, int)] = payload


def _rows(tokens, ps):
    """Expected page rows for a token sequence: slot j holds the token
    ids it covers (content is a pure function of the tokens, so shared
    prefix pages agree across requests by construction)."""
    out = np.zeros((-(-len(tokens) // ps), ps))
    flat = np.asarray(tokens, float)
    out.reshape(-1)[:len(tokens)] = flat
    return out


def _tiered(n_pages, ps, store, capacity=0, archive=True):
    tier = HostKVTier(capacity_pages=capacity, page_nbytes=ps * 8)
    a = TieredSharedAllocator(n_pages, ps, tier=tier,
                              save_pages=store.save, load_pages=store.load,
                              archive_prefixes=archive)
    return a, tier


def _stamp(a, store, rid, tokens, ps):
    rows = _rows(tokens, ps)
    for j, p in enumerate(a.table_of(rid)):
        store.data[p] = rows[j]


def _verify(a, store, rid, tokens, ps):
    rows = _rows(tokens, ps)
    table = a.table_of(rid)
    assert len(table) == len(rows)
    for j, p in enumerate(table):
        np.testing.assert_array_equal(store.data[p], rows[j])


# ================================================================ allocator
def test_tier_swap_roundtrip_bit_exact_and_truthful_books():
    ps = 4
    store = _FakeStore(16, ps)
    a, tier = _tiered(16, ps, store)
    toks = list(range(100, 100 + 3 * ps))
    assert a.allocate(1, len(toks))
    _stamp(a, store, 1, toks, ps)
    used_before = a.free_blocks

    rec = a.swap_out_request(1, len(toks))
    assert rec is not None and rec.kind == "out" and rec.n_pages == 3
    assert rec.nbytes == 3 * tier.page_nbytes
    # truthful books: swapped pages leave the device accounting entirely
    assert a.usage == 0.0 and not a.table_of(1)
    assert a.free_blocks == used_before + 3
    assert tier.holds_request(1) and a.holds_swapped(1)
    assert a.swapped_tokens == len(toks) == tier.swapped_tokens
    # idempotence: a second swap-out of the same request is refused
    assert a.swap_out_request(1, len(toks)) is None
    a.check_invariants()

    # scribble over the old physical rows: swap-in must not depend on them
    store.data[1:] = -1.0
    rec = a.swap_in_request(1)
    assert rec is not None and rec.kind == "in" and rec.tokens == len(toks)
    _verify(a, store, 1, toks, ps)
    assert not tier.holds_request(1) and a.swapped_tokens == 0
    assert tier.stat_in_pages == tier.stat_out_pages == 3
    a.check_invariants()

    # quarantine path: a dropped swapped entry is gone for good
    assert a.swap_out_request(1, len(toks)) is not None
    assert a.drop_swapped(1) and not tier.holds_request(1)
    assert a.swapped_tokens == 0 and tier.stat_dropped_pages == 3
    assert a.swap_in_request(1) is None
    a.check_invariants()


def test_tier_capacity_full_refuses_swap_out():
    ps = 4
    store = _FakeStore(16, ps)
    a, tier = _tiered(16, ps, store, capacity=2)
    assert a.allocate(1, 3 * ps)          # 3 pages > 2-page tier
    _stamp(a, store, 1, list(range(3 * ps)), ps)
    assert a.swap_out_request(1, 3 * ps) is None     # caller recomputes
    assert a.table_of(1) and not tier.holds_request(1)
    a.check_invariants()


def test_archived_prefix_stays_matchable_and_revives_bit_exact():
    ps = 4
    store = _FakeStore(8, ps)
    a, tier = _tiered(8, ps, store)
    prompt = list(range(100, 100 + 4 * ps))
    assert a.allocate(1, len(prompt))
    _stamp(a, store, 1, prompt, ps)
    a.register_prefix(1, prompt)
    a.free(1)                              # 4 reclaimable cached pages

    # a big allocation archives the cached pages instead of discarding
    assert a.allocate(2, 8 * ps)
    assert a.stat_archived_pages == 4
    assert tier.pages_used == 4
    _stamp(a, store, 2, list(range(500, 500 + 8 * ps)), ps)
    a.check_invariants()
    a.free(2)

    # the archived prefix is still matchable; matching rematerializes it
    store.data[1:] = -7.0                  # device rows are stale
    matched = a.match_prefix(3, prompt)
    assert matched == len(prompt)
    assert a.stat_revived_pages == 4
    assert a.allocate(3, len(prompt))
    _verify(a, store, 3, prompt, ps)       # restored, not recomputed
    assert tier.pages_used == 0
    a.check_invariants()


def test_drop_index_keeps_request_entries():
    ps = 4
    store = _FakeStore(8, ps)
    a, tier = _tiered(8, ps, store)
    prompt = list(range(2 * ps))
    assert a.allocate(1, len(prompt))
    _stamp(a, store, 1, prompt, ps)
    a.register_prefix(1, prompt)
    a.free(1)
    assert a.allocate(2, 7 * ps)           # archives the cached pages
    archived = a.stat_archived_pages
    assert archived > 0
    _stamp(a, store, 2, list(range(300, 300 + 7 * ps)), ps)
    assert a.swap_out_request(2, 7 * ps) is not None

    a.drop_index()                         # crash teardown
    assert tier.holds_request(2)           # host copies survive the crash
    assert tier.pages_used == 7            # ...but parked pages are dropped
    assert tier.stat_dropped_pages == archived


@given(st.integers(0, 10**6), st.integers(10, 28), st.integers(0, 1))
@settings(max_examples=20, deadline=None)
def test_property_tier_oracle_random_interleavings(seed, n_pages, tight_tier):
    """Oracle differential: random allocate/register/free/swap-out/swap-in/
    drop interleavings against a numpy page store. Every live request's
    pages must hold exactly the rows its tokens dictate (bit-exact through
    swap round-trips and archive/revive), ``swapped_tokens`` must equal the
    oracle's swapped set, and the allocator+tier invariants must hold after
    every operation."""
    rng = np.random.default_rng(seed)
    ps = 4
    store = _FakeStore(n_pages, ps)
    a, tier = _tiered(n_pages, ps, store,
                      capacity=int(rng.integers(2, 8)) if tight_tier else 0)
    shared = list(range(1000, 1000 + 8 * ps))   # common-prefix token pool
    live, swapped = {}, {}
    next_id = 0
    for _ in range(120):
        op = rng.random()
        if op < 0.40:                            # admit a new request
            rid, next_id = next_id, next_id + 1
            k = int(rng.integers(0, 4)) * ps     # shared-prefix pages
            n = int(rng.integers(1, 4)) * ps     # unique tail pages
            toks = shared[:k] + (2000 + rid * 100
                                 + np.arange(n)).tolist()
            matched = a.match_prefix(rid, toks)
            assert matched % 1 == 0 and matched <= len(toks)
            if a.allocate(rid, len(toks)):
                _stamp(a, store, rid, toks, ps)
                live[rid] = toks
            else:
                a.release_match(rid)
        elif op < 0.55 and live:                 # finish: register + free
            rid = int(rng.choice(list(live)))
            if rng.random() < 0.7:
                a.register_prefix(rid, live[rid])
            a.free(rid)
            del live[rid]
        elif op < 0.75 and live:                 # preempt by swap-out
            rid = int(rng.choice(list(live)))
            rec = a.swap_out_request(rid, len(live[rid]))
            if rec is not None:
                assert rec.n_pages == len(_rows(live[rid], ps))
                swapped[rid] = live.pop(rid)
        elif op < 0.92 and swapped:              # re-admit by swap-in
            rid = int(rng.choice(list(swapped)))
            rec = a.swap_in_request(rid)
            if rec is not None:
                live[rid] = swapped.pop(rid)
                _verify(a, store, rid, live[rid], ps)
        elif swapped:                            # quarantine/cancel
            rid = int(rng.choice(list(swapped)))
            assert a.drop_swapped(rid)
            del swapped[rid]
        a.check_invariants()
        assert a.swapped_tokens == sum(len(t) for t in swapped.values())
        assert tier.swapped_tokens == a.swapped_tokens
        for rid, toks in live.items():
            _verify(a, store, rid, toks, ps)
    assert tier.stat_out_pages >= tier.stat_in_pages


# ================================================================ planner
@given(st.integers(0, 10**6), st.integers(6, 40), st.integers(0, 1))
@settings(max_examples=20, deadline=None)
def test_property_step_plan_invariants_with_swap(seed, n_pages, auto):
    """The StepPlan invariant pack (budget, lane states, growth atomicity,
    and the swap-record checks) holds across random interleavings when the
    planner preempts by swapping to the tier instead of recomputing."""
    rng = np.random.default_rng(seed)
    ps = 8
    store = _FakeStore(n_pages, ps)
    pool, tier = _tiered(n_pages, ps, store)
    host = tsp._Host(pool)
    cfg = PlannerConfig(
        token_budget=int(rng.integers(8, 48)),
        max_running=int(rng.integers(2, 8)),
        chunk_cap=int(rng.choice([0, 8, 16])),
        lanes_per_dispatch=int(rng.integers(1, 6)),
        sharing=True, prefill_preempt=True,
        swap_policy="auto" if auto else "swap")
    from repro.serving.costmodel import SwapCostModel
    planner = StepPlanner(cfg, pool, host,
                          order_waiting=lambda w, now: order_queue(
                              w, now, host.qcfg),
                          preempt_one=host.preempt_one,
                          swap_cost=SwapCostModel() if auto else None)
    shared = rng.integers(0, 500, 12).tolist()
    next_id = 0
    now = 0.0
    for _ in range(60):
        now += 0.01
        for _ in range(int(rng.integers(0, 3))):
            plen = int(rng.integers(2, 30))
            toks = (shared[:plen] + rng.integers(
                500, 999, max(plen - 12, 0)).tolist())[:plen]
            if plen + 3 > n_pages * ps:
                continue
            r = Request(req_id=next_id, prompt_len=plen,
                        max_new_tokens=int(rng.integers(1, 6)),
                        arrival_time=now, prompt_tokens=toks)
            r.state = RequestState.WAITING
            host.waiting.append(r)
            next_id += 1
        plan = planner.plan(now)
        check_plan_invariants(plan, cfg, pool, host.running)
        for rec in plan.swap_out + plan.swap_in:
            assert rec.tokens > 0 and rec.n_pages > 0
            assert rec.nbytes == rec.n_pages * tier.page_nbytes
        tsp._apply_plan_effects(plan, host, now)
        pool.check_invariants()
    # every swapped-out victim is either restored or still parked
    assert pool.stat_swapped_in_reqs <= pool.stat_swapped_out_reqs


# ================================================================ sim engine
def test_sim_engine_swap_preemption_avoids_recompute():
    """Tight pool forcing decode-growth preemption: the tiered engine swaps
    victims (keeping their prefill) and finishes with exactly the workload's
    prefill tokens; the recompute baseline re-prefills its victims. The
    tiered engine keeps admitting off device-resident usage only."""
    cfg = EngineConfig(token_budget=32, max_running=8, kv_tokens=48,
                       kv_block=8, swap_policy="swap")

    def run(tier):
        eng = DPEngine(0, dataclasses.replace(
            cfg, swap_policy="swap" if tier else "recompute"), tier=tier)
        reqs = [Request(req_id=i, prompt_len=16, max_new_tokens=24,
                        arrival_time=0.001 * i) for i in range(3)]
        for r in reqs:
            eng.enqueue(r, 0.0)
        now, max_swapped = 0.0, 0.0
        for _ in range(400):
            dur, _, _ = eng.step(now)
            now += max(dur, 1e-3)
            tr = eng.trace(now)
            max_swapped = max(max_swapped, tr.swapped_tokens)
            assert 0.0 <= tr.kv_usage <= 1.0     # device-resident only
            if not eng.has_work:
                break
        return eng, reqs, max_swapped

    tiered, reqs, max_swapped = run(HostKVTier())
    base, _, _ = run(None)
    # the tiered engine finishes the whole workload (the recompute baseline
    # thrashes on this pool: victims lose their KV and re-prefill)
    assert all(r.state is RequestState.FINISHED and not r.error
               for r in reqs)
    assert tiered.pool.stat_swapped_out_reqs > 0
    assert tiered.pool.stat_swapped_out_reqs \
        == tiered.pool.stat_swapped_in_reqs        # everyone came back
    assert max_swapped > 0                         # trace signal fired
    assert tiered.total_prefill_tokens == 3 * 16   # zero re-prefill
    assert base.total_prefill_tokens > 3 * 16      # baseline recomputed
    tiered.pool.check_invariants()


# ================================================================ real engine
def _mk_real_requests(cfg, n, plen, max_new, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i, prompt_len=plen, max_new_tokens=max_new,
                    arrival_time=0.001 * i,
                    prompt_tokens=rng.integers(0, cfg.vocab_size,
                                               plen).tolist())
            for i in range(n)]


def _drive_real(engine, reqs, max_steps=300):
    for r in reqs:
        engine.enqueue(r, 0.0)
    now = 0.0
    for _ in range(max_steps):
        engine.step(now)
        now += 0.01
        if not engine.has_work:
            break
    return now


def test_real_engine_swap_bit_exact_no_recompute(tiny_model, shared_runner):
    """A pool too small for the workload, backed by the tier: preemption
    swaps fp pages to host and back, outputs are bit-identical to a roomy
    reference, and no prefill token is ever recomputed."""
    cfg, params = tiny_model
    roomy = dataclasses.replace(shared_runner.ecfg, n_pages=40,
                                prefix_sharing=True)
    tight = dataclasses.replace(roomy, n_pages=12, swap_policy="swap")

    ref = PagedRealEngine(0, cfg, params, roomy, runner=shared_runner)
    reqs_ref = _mk_real_requests(cfg, 4, 16, 10)
    _drive_real(ref, reqs_ref)

    tier = HostKVTier()
    eng = PagedRealEngine(1, cfg, params, tight, runner=shared_runner,
                          tier=tier)
    reqs = _mk_real_requests(cfg, 4, 16, 10)
    _drive_real(eng, reqs)
    eng.pool.check_invariants()

    for a, b in zip(reqs, reqs_ref):
        assert a.state is RequestState.FINISHED and not a.error
        assert a.output_tokens == b.output_tokens       # bit-exact pages
    assert eng.pool.stat_swapped_out_reqs > 0           # pressure was real
    assert eng.total_prefill_tokens == ref.total_prefill_tokens == 4 * 16
    # measured transfer/compute rates fed the cost model
    assert eng.swap_cost.d2h_bw > 0 and eng.swap_cost.h2d_bw > 0


def test_real_engine_drain_reattaches_through_tier(tiny_model, shared_runner):
    """Swap-based drain: residents export with their progress through the
    tier; a tier-sharing engine re-attaches and continues the exact token
    stream with zero re-prefill."""
    cfg, params = tiny_model
    ecfg = dataclasses.replace(shared_runner.ecfg, n_pages=40,
                               prefix_sharing=True)

    ref = PagedRealEngine(0, cfg, params, ecfg, runner=shared_runner)
    reqs_ref = _mk_real_requests(cfg, 2, 16, 8, seed=3)
    _drive_real(ref, reqs_ref)

    tier = HostKVTier()
    e1 = PagedRealEngine(1, cfg, params, ecfg, runner=shared_runner,
                         tier=tier)
    reqs = _mk_real_requests(cfg, 2, 16, 8, seed=3)
    for r in reqs:
        e1.enqueue(r, 0.0)
    for _ in range(4):                     # prefill (2 steps) + some decode
        e1.step(0.0)
    assert all(r.prefill_done == 16 and r.generated > 0 for r in reqs)

    moved = e1.drain(0.1)
    assert {r.req_id for r in moved} == {0, 1}
    for r in moved:
        assert tier.holds_request(r.req_id)
        assert r.prefill_done == 16 and r.n_recoveries == 1
        assert r.state is RequestState.WAITING
    assert not e1.running and e1.pool.usage == 0.0

    e2 = PagedRealEngine(2, cfg, params, ecfg, runner=shared_runner,
                         tier=tier)
    _drive_real(e2, moved)
    assert e2.total_prefill_tokens == 0    # re-attach, not re-prefill
    assert e2.pool.stat_swapped_in_reqs == 2
    for a, b in zip(reqs, reqs_ref):
        assert a.state is RequestState.FINISHED and not a.error
        assert a.output_tokens == b.output_tokens


def test_real_engine_fail_keeps_tier_backed_progress(tiny_model,
                                                     shared_runner):
    """Crash semantics: requests whose pages live in the (surviving) host
    tier keep their progress; device-resident ones fold into resume
    prompts."""
    cfg, params = tiny_model
    ecfg = dataclasses.replace(shared_runner.ecfg, n_pages=40,
                               prefix_sharing=True)
    tier = HostKVTier()
    eng = PagedRealEngine(0, cfg, params, ecfg, runner=shared_runner,
                          tier=tier)
    reqs = _mk_real_requests(cfg, 2, 16, 8, seed=4)
    for r in reqs:
        eng.enqueue(r, 0.0)
    for _ in range(4):
        eng.step(0.0)
    assert all(r.generated > 0 for r in reqs)
    # park request 0 in the tier (what drain/swap preemption would do)
    rec = eng.pool.swap_out_request(0, written_kv_len(reqs[0]))
    assert rec is not None
    eng.running.remove(reqs[0])
    eng.waiting.append(reqs[0])

    exported = eng.fail(0.1)
    assert eng.dead and len(exported) == 2
    assert reqs[0].prefill_done == 16      # tier-backed: progress kept
    assert reqs[0].n_recoveries == 1
    assert reqs[1].prefill_done == 0       # device KV lost: resume prompt
    assert reqs[1].prompt_len > 16         # emitted tokens folded in


# ================================================================ int8 pages
def test_pack_unpack_roundtrip_bounds_and_parity():
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.normal(size=(3, 8, 2, 32)) * 4.0, jnp.float32)
    q_x, s_x = pack_kv_xla(t)
    q_p, s_p = pack_kv_pallas(t, interpret=True)
    np.testing.assert_array_equal(np.asarray(q_x), np.asarray(q_p))
    np.testing.assert_allclose(np.asarray(s_x), np.asarray(s_p), rtol=1e-6)

    back_x = unpack_kv_xla(q_x, s_x)
    back_p = unpack_kv_pallas(q_p, s_p, interpret=True)
    np.testing.assert_allclose(np.asarray(back_x), np.asarray(back_p),
                               rtol=1e-6, atol=1e-6)
    # per-row absolute error is bounded by half a quantization step
    err = np.abs(np.asarray(back_x) - np.asarray(t))
    bound = 0.5 * np.asarray(s_x)[..., None] + 1e-6
    assert (err <= bound).all()
    # zero rows survive exactly (scale clamp, no NaN/garbage)
    z = jnp.zeros((2, 4, 1, 16), jnp.float32)
    qz, sz = pack_kv_xla(z)
    assert not np.isnan(np.asarray(sz)).any()
    np.testing.assert_array_equal(np.asarray(unpack_kv_xla(qz, sz)),
                                  np.asarray(z))


def test_paged_decode_int8_scales_parity():
    """Dequant-on-read: the paged decode kernels fed int8 pages + scales
    must match the fp kernels fed the dequantized pages."""
    B, Hq, Hkv, hd, ps, NB = 2, 4, 2, 32, 8, 4
    P = B * NB + 2
    rng = np.random.default_rng(9)
    kf = jnp.asarray(rng.normal(size=(P, ps, Hkv, hd)) * 3.0, jnp.float32)
    vf = jnp.asarray(rng.normal(size=(P, ps, Hkv, hd)) * 3.0, jnp.float32)
    kq, ks = pack_kv_xla(kf)
    vq, vs = pack_kv_xla(vf)
    kd = unpack_kv_xla(kq, ks)
    vd = unpack_kv_xla(vq, vs)
    q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
    ctx = jnp.asarray([ps + 3, NB * ps], jnp.int32)
    from test_paged import _random_block_setup
    bt = _random_block_setup(B, P, ps, NB, np.asarray(ctx), rng)

    o_fp = paged_decode_xla(q, kd, vd, bt, ctx)
    o_q = paged_decode_xla(q, kq, vq, bt, ctx, k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(o_q), np.asarray(o_fp),
                               rtol=1e-5, atol=1e-5)
    o_qp = paged_decode_pallas(q, kq, vq, bt, ctx, k_scales=ks,
                               v_scales=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_qp), np.asarray(o_fp),
                               rtol=1e-4, atol=1e-4)


def test_int8_page_capacity_ratio():
    """Equal pool bytes hold >= 1.8x the tokens with int8 pages at
    head_dim=64 (ratio 2*hd/(hd+4) for 2-byte fp values + fp32 scales)."""
    from repro.configs import get_smoke_config
    from repro.configs.base import reduced
    from repro.models.transformer import (init_paged_cache,
                                          paged_cache_page_nbytes)
    cfg = reduced(get_smoke_config("qwen3-moe-30b-a3b"), n_layers=2,
                  head_dim=64)
    fp = paged_cache_page_nbytes(init_paged_cache(cfg, 4, 8))
    i8 = paged_cache_page_nbytes(init_paged_cache(cfg, 4, 8,
                                                  kv_dtype="int8"))
    assert fp / i8 >= 1.8                  # tokens per byte ratio
    assert fp / i8 == pytest.approx(2 * 64 / (64 + 4))


def test_real_engine_int8_pages_serve(tiny_model, shared_runner):
    """An int8-paged engine serves the workload end to end (its own runner:
    quantized pools carry scale arrays the fp runner lacks)."""
    cfg, params = tiny_model
    ecfg = dataclasses.replace(shared_runner.ecfg, n_pages=40,
                               kv_dtype="int8")
    eng = PagedRealEngine(0, cfg, params, ecfg, n_sources=2)
    reqs = _mk_real_requests(cfg, 3, 12, 6, seed=6)
    _drive_real(eng, reqs)
    assert all(r.state is RequestState.FINISHED and not r.error
               for r in reqs)
    assert all(len(r.output_tokens) == 6 for r in reqs)
    eng.pool.check_invariants()
