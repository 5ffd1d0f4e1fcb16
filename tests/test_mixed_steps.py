"""Mixed fused steps: decode lanes ride the prefill dispatches.

Four layers, mirroring test_step_planner.py's proof structure:

* **model oracle** — ``mixed_step_paged`` (decode rows + prefill rows in
  one (B, S) dispatch) agrees with the split ``decode_step_paged`` +
  ``prefill_chunk_paged`` calls it replaces: per-lane logits within a
  written tolerance, every owned page and the mask-reduced MoE statistic
  sums exactly, across decode+prefill and all-decode rounds;
* **engine differential** — ``PagedRealEngine`` with ``mixed_steps`` on
  vs off serves identical streams to token-identical outputs, finish
  times and MoE window statistics with strictly fewer total model
  dispatches (decode dispatches drop to zero), plus a sim ``DPEngine``
  twin proving the control-plane telemetry and timing agree;
* **cluster differential (slow)** — a 2-engine Gimbal cluster, mixed on
  vs off: identical outputs, finish order and placement, fewer
  dispatches cluster-wide via the coordinator signals;
* **swap-in telemetry** — a blocked head-of-line swap-in (tiered pool
  that cannot back the record yet) is counted on the plan, the engine
  counter and the engine trace instead of masquerading as an ordinary
  full-pool stall.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import test_step_planner as tsp
from repro.serving import (DPEngine, EngineConfig, HostKVTier,
                           PagedBlockAllocator, PagedRealEngine,
                           PlannerConfig, RealClusterConfig, Request,
                           RequestState, StepPlanner, TieredSharedAllocator,
                           check_plan_invariants, serve_real_cluster)
from repro.core.queue_policy import order_queue
from repro.serving.step_plan import written_kv_len


# ================================================================ model oracle
def test_mixed_step_model_oracle_bit_exact(tiny_model, shared_runner):
    """Two rounds of interleaved serving — (2 decode + 1 prefill) fused,
    then an all-decode fused step — against the split dispatches, on
    independently threaded page trees: lane logits within
    ``tsp.LOGITS_TOL``, owned pages and the MoE statistic sums bit for
    bit. (aux_loss is the one
    deliberate exception: it normalizes over padded shapes, which differ
    between the fused and split dispatches, and nothing in serving
    consumes it.)"""
    cfg, params = tiny_model
    runner = shared_runner
    ps = runner.ecfg.page_size
    NB = 4
    rng = np.random.default_rng(17)
    lens = (11, 13, 9)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    pool = PagedBlockAllocator(32, ps)
    for i, p in enumerate(prompts):
        assert pool.allocate(i, len(p) + 4)       # room for decode writes
    owned = sorted(p for t in pool.tables.values() for p in t)
    from repro.models.transformer import identity_placement
    placement = jnp.asarray(identity_placement(cfg))
    src = lambda B: jnp.zeros((B,), jnp.int32)

    def prefill(pages, rid, start, ln):
        S = runner.bucket_for(ln)
        t = np.zeros((1, S), np.int32)
        t[0, :ln] = prompts[rid][start:start + ln]
        batch = {"tokens": jnp.asarray(t),
                 "chunk_starts": jnp.asarray([start], jnp.int32),
                 "chunk_lens": jnp.asarray([ln], jnp.int32)}
        bt = jnp.asarray(pool.block_table_array([rid], NB))
        return runner.prefill_chunk(batch, pages, bt, placement, src(1))

    def decode(pages, items):
        """items: (rid, token, ctx_len) decode lanes, padded to B=4."""
        B = 4
        toks = np.zeros(B, np.int32)
        lengths = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        rids = [None] * B
        for j, (rid, tok, ctx) in enumerate(items):
            toks[j], lengths[j], active[j], rids[j] = tok, ctx, True, rid
        bt = jnp.asarray(pool.block_table_array(rids, NB))
        return runner.decode(jnp.asarray(toks), pages,
                             jnp.asarray(lengths), bt,
                             jnp.asarray(active), placement, src(B))

    def mixed(pages, dec_items, pre_items):
        """One fused dispatch over decode lanes then prefill lanes."""
        n = len(dec_items) + len(pre_items)
        B = runner.lane_bucket_for(n)
        S = runner.mixed_bucket_for(
            max([1] + [ln for _, _, ln in pre_items]))
        toks = np.zeros((B, S), np.int32)
        starts = np.zeros(B, np.int32)
        lens_arr = np.zeros(B, np.int32)
        dmask = np.zeros(B, bool)
        rids = [None] * B
        for j, (rid, tok, ctx) in enumerate(dec_items):
            toks[j, 0] = tok
            starts[j], lens_arr[j], dmask[j], rids[j] = ctx, 1, True, rid
        for j, (rid, start, ln) in enumerate(pre_items,
                                             start=len(dec_items)):
            toks[j, :ln] = prompts[rid][start:start + ln]
            starts[j], lens_arr[j], rids[j] = start, ln, rid
        batch = {"tokens": jnp.asarray(toks),
                 "chunk_starts": jnp.asarray(starts),
                 "chunk_lens": jnp.asarray(lens_arr),
                 "decode_mask": jnp.asarray(dmask)}
        bt = jnp.asarray(pool.block_table_array(rids, NB))
        return runner.mixed_step(batch, pages, bt, placement, src(B))

    def stat_sums(stats_list):
        return {k: sum(np.asarray(s[k]) for s in stats_list)
                for k in ("expert_counts", "source_expert")}

    # setup (pre-divergence, shared by both branches): prefill r0 and r2
    # fully, r1 half-way — r0/r2 become decoders, r1 keeps prefilling
    pages0 = runner.init_pages()
    lg0, pages0, _ = prefill(pages0, 0, 0, 11)
    _, pages0, _ = prefill(pages0, 1, 0, 6)
    lg2, pages0, _ = prefill(pages0, 2, 0, 9)
    t0 = int(jnp.argmax(lg0[0]))
    t2 = int(jnp.argmax(lg2[0]))
    pa = pb = pages0

    # ---- round A: two decode lanes + one prefill lane, fused vs split
    ld, pa, sd = decode(pa, [(0, t0, 11), (2, t2, 9)])
    lp, pa, sp = prefill(pa, 1, 6, 7)
    lm, pb, sm = mixed(pb, [(0, t0, 11), (2, t2, 9)], [(1, 6, 7)])
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), **tsp.LOGITS_TOL)
    close(ld[0], lm[0])
    close(ld[1], lm[1])
    close(lp[0], lm[2])
    A, B = stat_sums([sd, sp]), stat_sums([sm])
    for k in A:
        np.testing.assert_array_equal(A[k], B[k])

    # ---- round B: every lane decoding — the fused step pads S to 1
    t0b, t2b = int(jnp.argmax(ld[0])), int(jnp.argmax(ld[1]))
    t1 = int(jnp.argmax(lp[0]))
    items = [(0, t0b, 12), (1, t1, 13), (2, t2b, 10)]
    ld2, pa, sd2 = decode(pa, items)
    lm2, pb, sm2 = mixed(pb, items, [])
    assert int(lm2.shape[0]) == 4 and int(np.asarray(lm2).ndim) == 2
    for j in range(3):
        close(ld2[j], lm2[j])
    A, B = stat_sums([sd2]), stat_sums([sm2])
    for k in A:
        np.testing.assert_array_equal(A[k], B[k])

    # both branches wrote identical KV into every owned page
    for pos in pa:
        for arr in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(pa[pos][arr])[:, owned],
                np.asarray(pb[pos][arr])[:, owned])


# ============================================================== engine diff
def test_engine_mixed_vs_split_differential(tiny_model, shared_runner):
    """Mixed fused steps on vs off on one engine: token-identical outputs,
    identical finish times and MoE window statistics, strictly fewer
    total model dispatches (decode dispatches drop to zero — decode
    lanes ride the fused prefill calls)."""
    cfg, params = tiny_model
    # overhead 64 prices a dispatch as worth trading real (B, S) padding
    # for — the grouper fuses decode into the prefill calls
    base = dataclasses.replace(shared_runner.ecfg, n_pages=64,
                               max_batch=4, token_budget=16,
                               dispatch_overhead_tokens=64)

    def serve(mixed):
        ecfg = dataclasses.replace(base, mixed_steps=mixed)
        e = PagedRealEngine(0, cfg, params, ecfg, runner=shared_runner,
                            n_sources=2)
        reqs = tsp._mk_requests(cfg, 6, [17, 9, 23, 12, 5, 14], max_new=6,
                                seed=23)
        waste0 = shared_runner.padding_waste_tokens
        padded0 = shared_runner.padded_tokens_total
        tsp._drive(e, reqs)
        assert all(r.state is RequestState.FINISHED and not r.error
                   for r in reqs)
        e.pool.check_invariants()
        assert e.pool.usage == 0.0
        waste = shared_runner.padding_waste_tokens - waste0
        assert shared_runner.padded_tokens_total > padded0
        return e, reqs, waste

    e_m, r_m, waste_m = serve(True)
    e_s, r_s, waste_s = serve(False)
    for a, b in zip(r_m, r_s):
        assert a.output_tokens == b.output_tokens, \
            f"req {a.req_id} diverged under mixed fusion"
        assert a.finish_time == b.finish_time, \
            f"req {a.req_id} finish time changed under mixed fusion"
    # same token population routed — the window statistics agree exactly
    Bm, Am = e_m.window_stats()
    Bs, As = e_s.window_stats()
    np.testing.assert_array_equal(Bm, Bs)
    np.testing.assert_array_equal(Am, As)
    assert e_m.total_decode_tokens == e_s.total_decode_tokens > 0
    # decode lanes rode the fused dispatches: strictly fewer model calls
    assert e_m.decode_dispatches == 0 and e_s.decode_dispatches > 0
    total_m = e_m.prefill_dispatches + e_m.decode_dispatches
    total_s = e_s.prefill_dispatches + e_s.decode_dispatches
    assert total_m < total_s, (total_m, total_s)
    assert waste_m >= 0 and waste_s >= 0       # counters actually ticked


def test_sim_engine_mixed_telemetry_agrees():
    """The simulator twin: mixed on vs off changes only the dispatch
    telemetry — step timing, finish times and token accounting are
    identical (the cost model prices the planned token population, not
    the dispatch grouping)."""
    base = EngineConfig(token_budget=16, max_running=4, kv_tokens=512,
                        kv_block=8, dispatch_overhead_tokens=64)

    def run(mixed):
        eng = DPEngine(0, dataclasses.replace(base, mixed_steps=mixed))
        reqs = [Request(req_id=i, prompt_len=14, max_new_tokens=5,
                        arrival_time=0.001 * i) for i in range(6)]
        for r in reqs:
            eng.enqueue(r, 0.0)
        now = 0.0
        for _ in range(200):
            dur, _, _ = eng.step(now)
            now += max(dur, 1e-3)
            if not eng.has_work:
                break
        return eng, reqs

    e_m, r_m = run(True)
    e_s, r_s = run(False)
    for a, b in zip(r_m, r_s):
        assert a.state is RequestState.FINISHED
        assert a.finish_time == b.finish_time
    assert e_m.total_decode_tokens == e_s.total_decode_tokens > 0
    assert e_m.decode_dispatches == 0 and e_s.decode_dispatches > 0
    assert (e_m.prefill_dispatches
            < e_s.prefill_dispatches + e_s.decode_dispatches)
    assert e_m.prefill_lanes_total \
        == e_s.prefill_lanes_total + e_s.total_decode_tokens


@pytest.mark.slow
def test_cluster_mixed_differential(tiny_model, shared_runner):
    """2-engine Gimbal cluster, mixed on vs off: token-identical outputs,
    identical finish order and placement, fewer total model dispatches
    cluster-wide (the coordinator's ``decode_dispatches`` signal drops
    to zero under fusion)."""
    cfg, params = tiny_model

    def serve(mixed):
        ecfg = dataclasses.replace(shared_runner.ecfg, n_pages=48,
                                   mixed_steps=mixed,
                                   dispatch_overhead_tokens=64)
        engines = [PagedRealEngine(i, cfg, params, ecfg,
                                   runner=shared_runner, n_sources=2)
                   for i in range(2)]
        reqs = tsp._mk_requests(cfg, 8, [13, 9, 7, 11], max_new=4, seed=5,
                                gap=0.02)
        res = serve_real_cluster(
            reqs, engines, cluster_cfg=RealClusterConfig(window_tokens=200))
        for e in engines:
            e.pool.check_invariants()
        return res, reqs

    res_m, r_m = serve(True)
    res_s, r_s = serve(False)
    for reqs in (r_m, r_s):
        assert all(r.state is RequestState.FINISHED and not r.error
                   for r in reqs)
    for a, b in zip(r_m, r_s):
        assert a.output_tokens == b.output_tokens
        assert a.finish_time == b.finish_time
        assert a.engine_id == b.engine_id     # same dispatch decisions
    assert res_m.signals["decode_dispatches"] == 0
    assert res_s.signals["decode_dispatches"] > 0
    assert (res_m.signals["prefill_dispatches"]
            < res_s.signals["prefill_dispatches"]
            + res_s.signals["decode_dispatches"])


# ========================================================= swap-in telemetry
class _FakeStore:
    def __init__(self, n_pages, ps):
        self.data = np.zeros((n_pages + 1, ps))

    def save(self, ids):
        return self.data[np.asarray(ids, int)].copy()

    def load(self, payload, ids):
        self.data[np.asarray(ids, int)] = payload


def test_planner_counts_blocked_head_of_line_swap_in():
    """A swapped-out victim at the head of the queue over a pool that
    cannot back its pages yet: the planner must still block admission
    (no bypass) but count the blocked swap-in on the plan — it is tier
    pressure, not an ordinary full-pool stall."""
    ps, n_pages = 8, 6
    store = _FakeStore(n_pages, ps)
    tier = HostKVTier(capacity_pages=0, page_nbytes=ps * 8)
    pool = TieredSharedAllocator(n_pages, ps, tier=tier,
                                 save_pages=store.save,
                                 load_pages=store.load)
    host = tsp._Host(pool)
    cfg = PlannerConfig(token_budget=8, max_running=4, sharing=True,
                        prefill_preempt=True, swap_policy="swap")
    planner = StepPlanner(cfg, pool, host,
                          order_waiting=lambda w, now: order_queue(
                              w, now, host.qcfg),
                          preempt_one=host.preempt_one)

    # r2: fully prefilled then swapped out to the tier (3 pages parked)
    r2 = Request(req_id=2, prompt_len=20, max_new_tokens=4,
                 arrival_time=0.0)
    r2.prefill_done, r2.generated, r2.output_tokens = 20, 1, [7]
    assert pool.allocate(2, written_kv_len(r2) + 1)
    assert pool.swap_out_request(2, written_kv_len(r2)) is not None
    r2.n_preemptions, r2.state = 1, RequestState.PREEMPTED
    host.waiting.append(r2)
    # r1: a decoding resident holding 5 of the 6 pages -> 1 free page,
    # r2's 3-page record cannot be backed
    r1 = Request(req_id=1, prompt_len=20, max_new_tokens=20,
                 arrival_time=0.1)
    r1.prefill_done, r1.generated, r1.output_tokens = 20, 1, [5]
    assert pool.allocate(1, 38)
    r1.state = RequestState.RUNNING
    host.running.append(r1)

    plan = planner.plan(1.0)
    check_plan_invariants(plan, cfg, pool, host.running)
    assert plan.swap_in_blocked == 1               # counted, not silent
    assert r2 in host.waiting                      # ... and still parked
    assert plan.decode == [r1]                     # resident kept serving
    assert not plan.swap_in

    # peer frees the pool -> the very next plan swaps the victim back in
    host.running.remove(r1)
    pool.free(1)
    plan = planner.plan(2.0)
    check_plan_invariants(plan, cfg, pool, host.running)
    assert plan.swap_in_blocked == 0
    assert len(plan.swap_in) == 1 and plan.swap_in[0].req_id == 2
    assert r2 in host.running


def test_sim_engine_surfaces_swap_in_blocked():
    """End to end through the sim engine: a blocked swap-in shows up on
    the engine counter and the per-step trace, and clears once the pool
    can back the record again."""
    cfg = EngineConfig(token_budget=8, max_running=4, kv_tokens=48,
                       kv_block=8, swap_policy="swap")
    eng = DPEngine(0, cfg, tier=HostKVTier())
    r = Request(req_id=2, prompt_len=20, max_new_tokens=6,
                arrival_time=0.0)
    eng.enqueue(r, 0.0)
    now = 0.0
    while not (r.remaining_prefill == 0 and r.generated >= 1):
        dur, _, _ = eng.step(now)
        now += max(dur, 1e-3)
    # park r on the tier, then squat on the freed pages so its 3-page
    # record cannot come back
    assert eng.pool.swap_out_request(2, written_kv_len(r)) is not None
    eng.running.remove(r)
    r.n_preemptions += 1
    r.state = RequestState.PREEMPTED
    eng.waiting.append(r)
    assert eng.pool.allocate(99, 36)               # 5 of 6 blocks held
    dur, _, _ = eng.step(now)
    tr = eng.trace(now)
    assert tr.swap_in_blocked == 1.0
    assert eng.swap_in_blocked_total == 1
    # release the squatter: the victim swaps back in and finishes
    eng.pool.free(99)
    for _ in range(100):
        now += max(dur, 1e-3)
        dur, _, _ = eng.step(now)
        if not eng.has_work:
            break
    assert r.state is RequestState.FINISHED and not r.error
    assert eng.pool.stat_swapped_in_reqs == 1
    assert eng.swap_in_blocked_total == 1          # blocked exactly once
    assert eng.trace(now).swap_in_blocked == 0.0
    eng.pool.check_invariants()
