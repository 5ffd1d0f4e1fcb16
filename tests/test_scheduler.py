"""Gimbal core unit + property tests (Algorithm 1 & 2, placement, MINLP)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (BaselineScheduler, EngineTrace, GimbalScheduler,
                        PlacementConfig, QueueConfig, SchedulerConfig,
                        TraceTable, anneal_layer, assignment_to_permutation,
                        brute_force_layer, calibrate,
                        default_distance_matrix, greedy_layer_placement,
                        layer_objective, order_queue, total_objective)


class Req:
    def __init__(self, arrival, plen):
        self.arrival_time = arrival
        self.prompt_len = plen


# ------------------------------------------------------------- Algorithm 1
def test_fallback_on_incomplete_traces():
    tt = TraceTable([0, 1, 2])
    tt.report(EngineTrace(0), now=0.0)
    s = GimbalScheduler(tt)
    picks = {s.select_engine(100, 0.0) for _ in range(6)}
    assert s.decisions["fallback"] == 6
    assert picks == {0, 1, 2}          # ordered dispatch cycles everyone


def test_kv_protection_path():
    tt = TraceTable([0, 1])
    tt.report(EngineTrace(0, kv_usage=0.95,
                          remaining_prefill_tokens=0), now=0.0)
    tt.report(EngineTrace(1, kv_usage=0.3,
                          remaining_prefill_tokens=1e6), now=0.0)
    s = GimbalScheduler(tt)
    # engine 1 is massively loaded by score, but KV path overrides
    assert s.select_engine(100, 0.0) == 1
    assert s.decisions["kv_path"] == 1


def test_score_path_prefers_light_engine():
    tt = TraceTable([0, 1])
    tt.report(EngineTrace(0, remaining_prefill_tokens=9000,
                          waiting_prefill_tokens=2000), now=0.0)
    tt.report(EngineTrace(1, remaining_prefill_tokens=10), now=0.0)
    s = GimbalScheduler(tt)
    assert s.select_engine(500, 0.0) == 1


def test_compensation_spreads_burst():
    """Without fresh traces, a burst must not all land on one engine."""
    tt = TraceTable([0, 1])
    tt.report(EngineTrace(0, remaining_prefill_tokens=600), now=0.0)
    tt.report(EngineTrace(1, remaining_prefill_tokens=0), now=0.0)
    s = GimbalScheduler(tt)
    picks = [s.select_engine(2000, 0.0) for _ in range(6)]
    assert len(set(picks)) == 2


def test_moe_pressure_feedback_biases_dispatch():
    tt = TraceTable([0, 1])
    tt.report(EngineTrace(0, moe_pressure=5000.0), now=0.0)
    tt.report(EngineTrace(1, moe_pressure=0.0), now=0.0)
    s = GimbalScheduler(tt)
    assert s.select_engine(100, 0.0) == 1


def test_close_guard_round_robins():
    tt = TraceTable([0, 1])
    tt.report(EngineTrace(0, remaining_prefill_tokens=1000), now=0.0)
    tt.report(EngineTrace(1, remaining_prefill_tokens=1001), now=0.0)
    s = GimbalScheduler(tt)
    picks = [s.select_engine(10, 0.0) for _ in range(4)]
    assert s.decisions["close_path"] >= 1


@given(st.lists(st.tuples(st.floats(0, 1e5), st.floats(0, 1e5),
                          st.floats(0, 1), st.floats(0, 1e4)),
                min_size=2, max_size=8))
@settings(max_examples=50, deadline=None)
def test_property_selects_valid_engine(rows):
    tt = TraceTable(range(len(rows)))
    for i, (pre, wait, kv, moe) in enumerate(rows):
        tt.report(EngineTrace(i, remaining_prefill_tokens=pre,
                              waiting_prefill_tokens=wait, kv_usage=kv,
                              moe_pressure=moe), now=0.0)
    s = GimbalScheduler(tt)
    e = s.select_engine(128.0, 0.0)
    assert 0 <= e < len(rows)


# ------------------------------------------------------------- Algorithm 2
def test_sjf_orders_by_prefill_length():
    q = [Req(0, 500), Req(1, 10), Req(2, 100)]
    out = order_queue(q, now=1.0)
    assert [r.prompt_len for r in out] == [10, 100, 500]


def test_aging_promotes_starved_requests():
    q = [Req(0.0, 9000), Req(5.5, 5)]
    out = order_queue(q, now=6.0, cfg=QueueConfig(theta_age_s=5.0))
    assert out[0].prompt_len == 9000   # aged past theta -> high priority


@given(st.lists(st.tuples(st.floats(0, 100), st.integers(1, 10000)),
                min_size=0, max_size=30))
@settings(max_examples=50, deadline=None)
def test_property_queue_is_permutation_and_aged_first(items):
    now = 50.0
    q = [Req(a, p) for a, p in items]
    out = order_queue(q, now=now)
    assert sorted(id(r) for r in out) == sorted(id(r) for r in q)
    aged = [r for r in out if now - r.arrival_time >= 5.0]
    # all aged requests precede all non-aged ones
    if aged:
        last_aged = max(out.index(r) for r in aged)
        first_fresh = min((out.index(r) for r in out if r not in aged),
                          default=len(out))
        assert last_aged < first_fresh


# ------------------------------------------------------------- placement
def _instance(seed, E=8, G=4, S=2):
    rng = np.random.default_rng(seed)
    B = rng.integers(10, 1000, E).astype(np.float64)
    A = rng.integers(0, 300, (S, E)).astype(np.float64)
    D = default_distance_matrix(S, G)
    prev = np.arange(E) // (E // G)
    return B, A, D, prev


def test_greedy_respects_capacity():
    B, A, D, prev = _instance(0, E=16, G=4)
    cfg = PlacementConfig()
    a = greedy_layer_placement(B, A, D, prev, cfg)
    counts = np.bincount(a, minlength=4)
    assert counts.max() <= 4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_anneal_reaches_bruteforce_optimum(seed):
    B, A, D, prev = _instance(seed)
    cfg = PlacementConfig(mig_cost_tokens=100)
    bf = brute_force_layer(B, A, D, prev, cfg)
    an = anneal_layer(B, A, D, prev, cfg, iters=4000, restarts=3, seed=seed)
    assert abs(total_objective(an, B, A, D, prev, cfg)
               - total_objective(bf, B, A, D, prev, cfg)) < 1e-9


def test_zero_migration_cost_when_unchanged():
    B, A, D, prev = _instance(3)
    cfg = PlacementConfig()
    _, _, cmig = layer_objective(prev, B, A, D, prev, cfg)
    assert cmig == 0.0


def test_high_gamma_freezes_placement():
    B, A, D, prev = _instance(4)
    cfg = PlacementConfig(gamma=1e9, mig_cost_tokens=1e9)
    a = greedy_layer_placement(B, A, D, prev, cfg)
    np.testing.assert_array_equal(a, prev)


def test_assignment_to_permutation_is_bijection():
    assign = np.array([0, 0, 1, 1, 2, 2, 3, 3])
    perm = assignment_to_permutation(assign, 4)
    assert sorted(perm.tolist()) == list(range(8))
    # expert e's physical slot lies on its assigned rank
    for e, g in enumerate(assign):
        assert perm[e] // 2 == g


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_property_permutation_valid(seed):
    rng = np.random.default_rng(seed)
    E, G = 16, 4
    assign = rng.integers(0, G, E)
    # repair capacity violations the way the manager guarantees them
    cfg = PlacementConfig()
    B = rng.integers(1, 100, E).astype(np.float64)
    A = rng.integers(0, 50, (2, E)).astype(np.float64)
    D = default_distance_matrix(2, G)
    a = greedy_layer_placement(B, A, D, None, cfg)
    perm = assignment_to_permutation(a, G)
    assert sorted(perm.tolist()) == list(range(E))


def test_calibration_meets_paper_bands():
    """Calibrated greedy: >=80% agreement with the MINLP reference (paper
    band). Comm excess lands ~6% on our synthetic windows vs the paper's
    0.6% on their traces — the online greedy trades residual comm for
    migration stability (recorded in EXPERIMENTS.md §Claims)."""
    rng = np.random.default_rng(7)
    L, E, S, G = 6, 16, 2, 4
    from repro.serving.routing_sim import SourceExpertTraffic
    tr = SourceExpertTraffic(L, E, S, seed=7)
    A = rng.poisson(tr.pref * 2000).astype(np.float64)
    B = A.sum(axis=1)
    D = default_distance_matrix(S, G)
    prev = np.stack([np.arange(E) // (E // G)] * L)
    res = calibrate(B, A, D, prev, ref_cfg=PlacementConfig(
        mig_cost_tokens=200.0))
    assert res.agreement >= 0.8
    assert abs(res.comm_excess) <= 0.08
