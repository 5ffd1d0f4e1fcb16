"""Serving substrate: KV pool, engine continuous batching, end-to-end sim."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving import (BlockPool, DPEngine, EngineConfig, PAPER_SYSTEMS,
                           Request, RequestState, simulate)
from repro.serving.costmodel import CostModelConfig, EngineCostModel
from repro.workloads import DISTRIBUTIONS, generate_trace


# --------------------------------------------------------------- block pool
def test_block_pool_alloc_free_roundtrip():
    p = BlockPool(1600, block_size=16)
    assert p.allocate(1, 100)
    held = p.held_tokens(1)
    assert held >= 100
    assert 0 < p.usage < 1
    p.free(1)
    assert p.usage == 0.0


@given(st.lists(st.tuples(st.integers(1, 50), st.integers(1, 500)),
                min_size=1, max_size=40))
@settings(max_examples=30, deadline=None)
def test_property_pool_never_oversubscribes(ops):
    p = BlockPool(4000, block_size=16)
    held = {}
    for rid, tok in ops:
        if p.allocate(rid, held.get(rid, 0) + tok):
            held[rid] = held.get(rid, 0) + tok
    assert p.free_blocks >= 0
    total_blocks = sum(-(-max(t, 1) // 16) for t in held.values())
    assert total_blocks <= p.total_blocks


# --------------------------------------------------------------- engine
def _mk_engine(**kw):
    return DPEngine(0, EngineConfig(**kw), EngineCostModel(CostModelConfig()))


def test_engine_serves_one_request_to_completion():
    e = _mk_engine()
    r = Request(req_id=1, prompt_len=3000, max_new_tokens=5,
                arrival_time=0.0)
    e.enqueue(r, 0.0)
    now = 0.0
    for _ in range(100):
        dur, _, _ = e.step(now)
        now += max(dur, 1e-4)
        if r.state is RequestState.FINISHED:
            break
    assert r.state is RequestState.FINISHED
    assert r.first_token_time > 0 and r.finish_time >= r.first_token_time
    # chunked prefill: a 3000-token prompt needs >= 2 chunks at budget 2048
    assert r.ttft > 0


def test_engine_preempts_under_kv_pressure():
    e = _mk_engine(kv_tokens=4096, token_budget=512)
    rs = [Request(req_id=i, prompt_len=1500, max_new_tokens=2000,
                  arrival_time=0.0) for i in range(4)]
    for r in rs:
        e.enqueue(r, 0.0)
    now = 0.0
    for _ in range(300):
        dur, _, _ = e.step(now)
        now += max(dur, 1e-4)
    assert sum(r.n_preemptions for r in rs) > 0 or \
        any(r.state is RequestState.FINISHED for r in rs)


def test_trace_reports_token_level_pressure():
    e = _mk_engine()
    e.enqueue(Request(req_id=1, prompt_len=5000, max_new_tokens=4,
                      arrival_time=0.0), 0.0)
    e.enqueue(Request(req_id=2, prompt_len=100, max_new_tokens=4,
                      arrival_time=0.0), 0.0)
    e.step(0.0)
    t = e.trace(0.1)
    assert t.remaining_prefill_tokens + t.waiting_prefill_tokens > 0
    assert 0.0 <= t.kv_usage <= 1.0


# --------------------------------------------------------------- simulator
def test_simulation_completes_all_requests():
    trace = generate_trace("random", 40, rps=4.0, seed=0, mean_output=50)
    res = simulate(trace, PAPER_SYSTEMS["gimbal"])
    done = [r for r in trace if r.state is RequestState.FINISHED]
    assert len(done) == len(trace)
    assert res.mean_ttft > 0 and res.mean_tpot > 0


@pytest.mark.parametrize("dist", DISTRIBUTIONS)
def test_all_distributions_simulate(dist):
    trace = generate_trace(dist, 25, rps=4.0, seed=0, mean_output=30)
    res = simulate(trace, PAPER_SYSTEMS["vllm"])
    assert res.throughput > 0


def test_gimbal_not_worse_than_vllm_at_load():
    """The paper's core claim, at reduced scale: gimbal e2e <= vllm e2e."""
    t1 = generate_trace("random", 120, rps=4.0, seed=3, mean_output=150)
    r_v = simulate(t1, PAPER_SYSTEMS["vllm"], traffic_seed=3)
    t2 = generate_trace("random", 120, rps=4.0, seed=3, mean_output=150)
    r_g = simulate(t2, PAPER_SYSTEMS["gimbal"], traffic_seed=3)
    assert r_g.mean_e2e <= r_v.mean_e2e * 1.02
    assert r_g.mean_ttft <= r_v.mean_ttft * 1.05


def test_workload_lengths_bounded():
    for dist in DISTRIBUTIONS:
        trace = generate_trace(dist, 200, rps=2.0, seed=1)
        lens = np.array([r.prompt_len for r in trace])
        assert lens.min() >= 16 and lens.max() <= 8192
        arr = np.array([r.arrival_time for r in trace])
        assert (np.diff(arr) >= 0).all()
