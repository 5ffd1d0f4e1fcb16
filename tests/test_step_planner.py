"""Plan/execute step refactor: batched B>1 chunked prefill proofs.

Four layers:

* **model level** — one fused B>1 ``prefill_chunk_paged`` dispatch
  agrees, lane for lane, with the B=1 sequential calls (logits within a
  written tolerance; written pages and masked MoE statistics exactly);
* **engine differential** — ``PagedRealEngine`` with lane fusion on
  (``max_prefill_lanes=8``) vs off (=1) serves identical streams to
  token-identical outputs and finish order with strictly fewer prefill
  dispatches (plus a slow 2-engine Gimbal cluster variant);
* **planner properties** — random arrival/step interleavings through
  ``StepPlanner`` (sharing on and off, tight pools forcing preemption
  and stalls) uphold the :class:`StepPlan` invariant pack after every
  plan: budget respected, no lane on a preempted/stalled/waiting
  request, growth atomic, grouping bounded;
* **cross-plane agreement** — the simulator ``DPEngine`` and the real
  ``PagedRealEngine``, configured equivalently, make identical packing
  decisions (same lanes, chunks and decode sets, step for step) on the
  same arrival trace.
"""
import dataclasses

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import (DPEngine, EngineConfig, PagedBlockAllocator,
                           PagedRealEngine, PlannerConfig, RealClusterConfig,
                           Request, RequestState, SharedPagedAllocator,
                           StepPlanner, check_plan_invariants,
                           serve_real_cluster)
from repro.serving.costmodel import CostModelConfig, EngineCostModel
from repro.serving.engine_util import select_preemption_victim
from repro.core.queue_policy import QueueConfig, order_queue


# ================================================================ helpers
# Fused and split dispatches may reduce in a different order: XLA promises
# no batch-shape-invariant reductions on the CPU or the chip (observed gap
# 2.4e-7 on the CPU). The model computes in bf16, so a reordered f32 sum
# can also flip one bf16 rounding (2^-8 relative) upstream of the logits;
# a lane that read the wrong pages or positions is off by O(1).
LOGITS_TOL = dict(rtol=1e-2, atol=1e-2)


def _mk_requests(cfg, n, prompt_lens, max_new=4, seed=0, gap=0.001):
    rng = np.random.default_rng(seed)
    return [Request(
        req_id=i, prompt_len=int(prompt_lens[i % len(prompt_lens)]),
        max_new_tokens=max_new, arrival_time=gap * i,
        prompt_tokens=rng.integers(
            0, cfg.vocab_size, int(prompt_lens[i % len(prompt_lens)])
        ).tolist()) for i in range(n)]


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


# ================================================================ model level
def test_model_level_batched_prefill_bit_exact(tiny_model, shared_runner):
    """One fused B-lane dispatch == the B=1 calls: lane logits within
    ``LOGITS_TOL``, every written page and the mask-reduced MoE
    statistics exactly."""
    cfg, params = tiny_model
    runner = shared_runner
    ps = runner.ecfg.page_size
    NB = 4
    rng = np.random.default_rng(3)
    lens = (5, 11, 8)                   # one lane needs a second chunk
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    pool = PagedBlockAllocator(32, ps)
    for i, p in enumerate(prompts):
        assert pool.allocate(i, len(p))
    owned = sorted(p for t in pool.tables.values() for p in t)
    from repro.models.transformer import identity_placement
    placement = jnp.asarray(identity_placement(cfg))

    def phases():
        """Two rounds of chunks: (lane chunks) per phase, chunk cap 8."""
        done = [0] * len(prompts)
        out = []
        while any(done[i] < lens[i] for i in range(len(prompts))):
            phase = []
            for i in range(len(prompts)):
                c = min(lens[i] - done[i], 8)
                if c > 0:
                    phase.append((i, done[i], c))
                    done[i] += c
            out.append(phase)
        return out

    def run(batched):
        pages = runner.init_pages()
        logits_at_end = {}
        stat_sums = []
        for phase in phases():
            groups = [phase] if batched else [[l] for l in phase]
            for g in groups:
                S = runner.bucket_for(max(c for _, _, c in g))
                B = runner.lane_bucket_for(len(g))
                toks = np.zeros((B, S), np.int32)
                starts = np.zeros(B, np.int32)
                lens_arr = np.zeros(B, np.int32)
                rids = [None] * B
                for j, (i, s0, c) in enumerate(g):
                    toks[j, :c] = prompts[i][s0:s0 + c]
                    starts[j], lens_arr[j], rids[j] = s0, c, i
                batch = {"tokens": jnp.asarray(toks),
                         "chunk_starts": jnp.asarray(starts),
                         "chunk_lens": jnp.asarray(lens_arr)}
                bt = jnp.asarray(pool.block_table_array(rids, NB))
                logits, pages, stats = runner.prefill_chunk(
                    batch, pages, bt, placement,
                    jnp.zeros((B,), jnp.int32))
                if stats is not None:
                    stat_sums.append(
                        np.asarray(stats["expert_counts"]).sum())
                for j, (i, s0, c) in enumerate(g):
                    if s0 + c == lens[i]:
                        logits_at_end[i] = np.asarray(logits[j])
        return logits_at_end, pages, sum(stat_sums)

    lg_b, pages_b, stats_b = run(batched=True)
    lg_s, pages_s, stats_s = run(batched=False)
    for i in range(len(prompts)):
        np.testing.assert_allclose(lg_b[i], lg_s[i], **LOGITS_TOL,
                                   err_msg=f"lane {i} logits diverged")
    for pos in pages_b:
        for arr in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(pages_b[pos][arr])[:, owned],
                np.asarray(pages_s[pos][arr])[:, owned])
    # padding lanes / rows are masked out of the statistics, so the
    # fused dispatch routes exactly the same token population
    assert stats_b == stats_s


# ================================================================ engine diff
def test_engine_batched_vs_sequential_differential(tiny_model, shared_runner):
    """Fusion on vs off on one engine: identical outputs and finish order,
    strictly fewer (>= 2x) prefill dispatches for the fused run."""
    cfg, params = tiny_model
    base = dataclasses.replace(shared_runner.ecfg, n_pages=64,
                               max_batch=8, token_budget=64)
    lens = [5, 9, 7, 6, 11, 8, 5, 10]

    def serve(lanes):
        e = PagedRealEngine(0, cfg, params,
                            dataclasses.replace(base,
                                                max_prefill_lanes=lanes),
                            runner=shared_runner, n_sources=2)
        reqs = _mk_requests(cfg, 8, lens, max_new=4, seed=11)
        _drive(e, reqs)
        assert all(r.state is RequestState.FINISHED and not r.error
                   for r in reqs)
        e.pool.check_invariants()
        assert e.pool.usage == 0.0
        return e, reqs

    e_b, r_b = serve(8)
    e_s, r_s = serve(1)
    for a, b in zip(r_b, r_s):
        assert a.output_tokens == b.output_tokens, \
            f"req {a.req_id} diverged under lane fusion"
        assert a.finish_time == b.finish_time, \
            f"req {a.req_id} finish order changed under lane fusion"
    assert e_b.total_prefill_tokens == e_s.total_prefill_tokens == sum(lens)
    assert e_s.prefill_dispatches >= 2 * e_b.prefill_dispatches
    assert e_s.prefill_lanes_total == e_b.prefill_lanes_total
    assert e_b.prefill_lanes_total / e_b.prefill_dispatches > 1.0


@pytest.mark.slow
def test_cluster_batched_prefill_differential(tiny_model, shared_runner):
    """2-engine Gimbal cluster, fusion on vs off: token-identical outputs,
    identical finish order, fewer prefill dispatches cluster-wide."""
    cfg, params = tiny_model

    def serve(lanes):
        ecfg = dataclasses.replace(shared_runner.ecfg, n_pages=48,
                                   max_prefill_lanes=lanes)
        engines = [PagedRealEngine(i, cfg, params, ecfg,
                                   runner=shared_runner, n_sources=2)
                   for i in range(2)]
        reqs = _mk_requests(cfg, 8, [13, 9, 7, 11], max_new=4, seed=5,
                            gap=0.02)
        res = serve_real_cluster(
            reqs, engines, cluster_cfg=RealClusterConfig(window_tokens=200))
        for e in engines:
            e.pool.check_invariants()
        return res, reqs

    res_b, r_b = serve(8)
    res_s, r_s = serve(1)
    for reqs in (r_b, r_s):
        assert all(r.state is RequestState.FINISHED and not r.error
                   for r in reqs)
    for a, b in zip(r_b, r_s):
        assert a.output_tokens == b.output_tokens
        assert a.finish_time == b.finish_time
        assert a.engine_id == b.engine_id     # same dispatch decisions
    assert res_b.signals["prefill_dispatches"] \
        < res_s.signals["prefill_dispatches"]
    assert res_b.signals["prefill_lanes_per_dispatch"] > 1.0
    assert res_s.signals["prefill_lanes_per_dispatch"] == 1.0


# ================================================================ properties
class _Host:
    """Minimal planner host: the queues plus engine-style preemption."""

    def __init__(self, pool):
        self.pool = pool
        self.waiting = []
        self.running = []
        self.qcfg = QueueConfig()

    def preempt_one(self, protect=None):
        victim = select_preemption_victim(self.running, protect)
        if victim is None:
            return False
        self.running.remove(victim)
        self.pool.free(victim.req_id)
        victim.prefill_done = 0
        victim.generated = 0
        victim.output_tokens = []
        victim.n_preemptions += 1
        victim.state = RequestState.PREEMPTED
        self.waiting.append(victim)
        return True


def _apply_plan_effects(plan, host, now):
    """The data-plane contract, without a data plane: advance exactly the
    planned lanes (the engines apply the same effects off real logits)."""
    for lane in plan.prefill_lanes:
        r = lane.req
        assert r.prefill_done == lane.start
        r.prefill_done += lane.chunk
        if r.remaining_prefill == 0:
            r.generated = 1
            r.output_tokens = [7]
            if r.done:
                _finish(r, host)
    for r in plan.decode:
        r.generated += 1
        r.output_tokens = (r.output_tokens or []) + [7]
        if r.done:
            _finish(r, host)


def _finish(r, host):
    r.state = RequestState.FINISHED
    host.running.remove(r)
    if isinstance(host.pool, SharedPagedAllocator) and r.prompt_tokens:
        host.pool.register_prefix(
            r.req_id, (list(r.prompt_tokens) + list(r.output_tokens or []))
            [:r.prefill_done + max(r.generated - 1, 0)])
    host.pool.free(r.req_id)


@given(st.integers(0, 10**6), st.integers(6, 40), st.integers(0, 1),
       st.integers(0, 1), st.integers(0, 1))
@settings(max_examples=25, deadline=None)
def test_property_step_plan_invariants(seed, n_pages, sharing, sim_flavor,
                                       mixed):
    """Random interleavings: every emitted StepPlan satisfies the invariant
    pack — budget respected (decode + prefill <= token_budget), no planned
    lane on a preempted/stalled/waiting request, growth atomic (tables
    cover every planned write), grouping bounded, mixed groups (when on) a
    faithful repartition of the split plan — and the pool books stay
    consistent, across tight pools (preemption + stalls), sharing on/off,
    mixed fused steps on/off and both plane flavors."""
    rng = np.random.default_rng(seed)
    ps = 8
    pool = (SharedPagedAllocator(n_pages, ps) if sharing
            else PagedBlockAllocator(n_pages, ps))
    host = _Host(pool)
    cfg = PlannerConfig(
        token_budget=int(rng.integers(8, 48)),
        max_running=int(rng.integers(2, 8)),
        chunk_cap=int(rng.choice([0, 8, 16])),
        lanes_per_dispatch=int(rng.integers(1, 6)),
        sharing=bool(sharing),
        decode_reserve_extra=int(sim_flavor),
        prefill_preempt=bool(sharing or not sim_flavor),
        mixed_steps=bool(mixed),
        lane_buckets=(1, 2, 4, 8) if rng.integers(0, 2) else (),
        chunk_buckets=(8, 16) if rng.integers(0, 2) else ())
    planner = StepPlanner(cfg, pool, host,
                          order_waiting=lambda w, now: order_queue(
                              w, now, host.qcfg),
                          preempt_one=host.preempt_one)
    shared = rng.integers(0, 500, 12).tolist()
    next_id = 0
    now = 0.0
    for _ in range(60):
        now += 0.01
        for _ in range(int(rng.integers(0, 3))):
            plen = int(rng.integers(2, 30))
            toks = (shared[:plen] + rng.integers(
                500, 999, max(plen - 12, 0)).tolist())[:plen]
            cap = n_pages * ps
            if plen + 3 > cap:      # would stall forever: skip like enqueue
                continue
            host.waiting.append(Request(
                req_id=next_id, prompt_len=plen, max_new_tokens=3,
                arrival_time=now, prompt_tokens=toks,
                state=RequestState.WAITING))
            next_id += 1
        plan = planner.plan(now)
        check_plan_invariants(plan, cfg, pool, host.running)
        _apply_plan_effects(plan, host, now)
        if hasattr(pool, "check_invariants"):
            pool.check_invariants()
    # drain: no new arrivals; the planner must keep planning to quiescence.
    # The anti-thrash admission gate bounds the recompute-mode preemption
    # ping-pong (a victim re-admits only once the FREE pool covers the KV
    # it lost plus its next chunk, so every re-admission round coincides
    # with real peer progress — and an empty pool always passes the gate,
    # so the head of the queue can never starve): preempting configs MUST
    # now fully drain, with drain-phase churn linear in the live set. The
    # legacy sim flavor's never-preempt non-sharing prefill path can still
    # wedge on an exhausted pool (inherited), so only it gets tolerance.
    strict = cfg.prefill_preempt or cfg.sharing
    live = host.running + host.waiting
    preempt_before = sum(r.n_preemptions for r in live)
    n_live = len(live)
    for _ in range(1500):
        now += 0.01
        plan = planner.plan(now)
        check_plan_invariants(plan, cfg, pool, host.running)
        if strict and host.running:
            assert plan.has_work or plan.n_admitted, \
                "planner wedged: queued work but an empty plan"
        _apply_plan_effects(plan, host, now)
        if not host.running and not host.waiting:
            break
    leftovers = host.running + host.waiting
    if strict:
        assert not leftovers, \
            f"preempting planner failed to drain: {len(leftovers)} left"
        assert pool.usage == 0.0
    elif not leftovers:
        assert pool.usage == 0.0
    if leftovers:
        # non-strict wedge tolerance: bounded churn still must hold —
        # unbounded ping-pong during drain is the bug the gate fixes
        churn = sum(r.n_preemptions for r in leftovers) - preempt_before
        assert churn <= 4 * n_live + 4, \
            f"drain-phase thrash unbounded: {churn} preemption rounds"


def _mk_planner(pool, host, **over):
    cfg = PlannerConfig(**{**dict(token_budget=8, max_running=8,
                                  lanes_per_dispatch=4), **over})
    return cfg, StepPlanner(cfg, pool, host,
                            order_waiting=lambda w, now: order_queue(
                                w, now, host.qcfg),
                            preempt_one=host.preempt_one)


def test_decode_lanes_capped_at_token_budget():
    """More decoders than token_budget: the plan defers the tail (stall-
    accounted, no effects) instead of silently over-packing the step, and
    the deferred lanes decode on subsequent steps."""
    ps = 8
    pool = PagedBlockAllocator(40, ps)
    host = _Host(pool)
    cfg, planner = _mk_planner(pool, host, token_budget=3)
    for i in range(5):                     # 5 decoders, budget 3
        r = Request(req_id=i, prompt_len=4, max_new_tokens=6,
                    arrival_time=0.0, prompt_tokens=list(range(4)),
                    state=RequestState.RUNNING, prefill_done=4, generated=1,
                    output_tokens=[7])
        assert pool.allocate(i, 5)
        host.running.append(r)
    plan = planner.plan(0.0)
    check_plan_invariants(plan, cfg, pool, host.running)
    assert len(plan.decode) == 3
    assert plan.n_stalled == 2
    assert len(plan.decode) + plan.prefill_tokens <= cfg.token_budget
    deferred = [r for r in host.running if r not in plan.decode]
    gen_before = {r.req_id: r.generated for r in deferred}
    _apply_plan_effects(plan, host, 0.0)
    for r in deferred:                     # no effects on deferred lanes
        assert r.generated == gen_before[r.req_id]
    # every lane decodes within ceil(5/3) = 2 steps
    plan2 = planner.plan(0.01)
    check_plan_invariants(plan2, cfg, pool, host.running)
    assert {r.req_id for r in plan.decode} | {r.req_id for r in plan2.decode} \
        == {0, 1, 2, 3, 4}


def test_anti_thrash_gate_demands_lost_footprint():
    """A recompute-preempted victim is NOT re-admitted into the hole its
    own eviction opened: re-admission waits until the free pool covers the
    KV it lost plus its next chunk, and an empty pool always passes."""
    ps = 8
    pool = PagedBlockAllocator(6, ps)      # 48 tokens
    host = _Host(pool)
    cfg, planner = _mk_planner(pool, host, token_budget=16, max_running=4)
    # victim: deep into decode (holds 4 pages, written 28), then evicted
    v = Request(req_id=0, prompt_len=24, max_new_tokens=8, arrival_time=0.0,
                prompt_tokens=list(range(24)), state=RequestState.RUNNING,
                prefill_done=24, generated=5, output_tokens=[7] * 5)
    assert pool.allocate(0, 29)
    host.running.append(v)
    # peer holds 2 pages and is mid-prefill
    p = Request(req_id=1, prompt_len=30, max_new_tokens=2, arrival_time=0.1,
                prompt_tokens=list(range(100, 130)),
                state=RequestState.RUNNING, prefill_done=16)
    assert pool.allocate(1, 16)
    host.running.append(p)
    assert planner._preempt(protect=p)     # classic recompute eviction
    assert v.state is RequestState.PREEMPTED
    assert v.preempt_written == 28         # 24 prompt + 4 written decodes
    assert v.n_preemptions == 1

    # peer grows to 4 pages: 2 free. The victim's first chunk (16 tokens =
    # 2 pages) WOULD allocate, but the gate demands its lost footprint —
    # blocks_for(min(28 + 16, 32)) = 4 pages — so it must stay out.
    assert pool.allocate(1, 32)
    plan = planner.plan(1.0)
    check_plan_invariants(plan, cfg, pool, host.running)
    assert v.state is RequestState.PREEMPTED and v not in host.running
    assert plan.n_admitted == 0

    # peer finishes: pool empty, the gate passes, the victim re-admits
    host.running.remove(p)
    pool.free(1)
    plan = planner.plan(2.0)
    check_plan_invariants(plan, cfg, pool, host.running)
    assert plan.n_admitted == 1 and v in host.running


# ================================================================ cross-plane
def test_sim_and_real_planners_agree_on_packing(tiny_model, shared_runner):
    """The simulator DPEngine and the real PagedRealEngine, configured
    equivalently (same budget, caps, lane fusion, pool capacity), make the
    SAME packing decisions step for step on the same arrival trace: same
    prefill lanes with the same chunk spans, same decode lane sets."""
    cfg, params = tiny_model
    ps = shared_runner.ecfg.page_size
    ecfg = dataclasses.replace(shared_runner.ecfg, n_pages=64, max_batch=4,
                               token_budget=16, max_prefill_lanes=4)
    real = PagedRealEngine(0, cfg, params, ecfg, runner=shared_runner,
                           n_sources=2)
    sim = DPEngine(0, EngineConfig(
        token_budget=ecfg.token_budget, max_running=ecfg.max_batch,
        kv_tokens=ecfg.n_pages * ps, kv_block=ps,
        max_chunk=ecfg.chunk_buckets[-1],
        max_prefill_lanes=ecfg.max_prefill_lanes),
        EngineCostModel(CostModelConfig()))

    logs = {"real": [], "sim": []}

    def record(engine, key):
        orig = engine.planner.plan

        def wrapped(now):
            p = orig(now)
            if p.has_work:
                logs[key].append((
                    [(l.req.req_id, l.start, l.chunk)
                     for l in p.prefill_lanes],
                    sorted(r.req_id for r in p.decode)))
            return p
        engine.planner.plan = wrapped

    record(real, "real")
    record(sim, "sim")

    reqs_r = _mk_requests(cfg, 7, [21, 9, 13, 6], max_new=3, seed=2,
                          gap=0.03)
    reqs_s = _mk_requests(cfg, 7, [21, 9, 13, 6], max_new=3, seed=2,
                          gap=0.03)
    for engine, reqs in ((real, reqs_r), (sim, reqs_s)):
        pending = sorted(reqs, key=lambda r: r.arrival_time)
        now = 0.0
        for _ in range(300):
            while pending and pending[0].arrival_time <= now:
                engine.enqueue(pending.pop(0), now)
            engine.step(now)
            now += 0.01
            if not pending and not engine.has_work:
                break
    assert all(r.state is RequestState.FINISHED for r in reqs_r + reqs_s)
    assert logs["real"] == logs["sim"], "sim/real packing diverged"
    assert len(logs["real"]) > 0
    # dispatch telemetry agrees too (same grouping arithmetic)
    assert real.prefill_dispatches == sim.prefill_dispatches
    assert real.prefill_lanes_total == sim.prefill_lanes_total
