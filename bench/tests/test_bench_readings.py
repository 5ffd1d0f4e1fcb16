"""The metric and check arithmetic on a hand-made window: tokens per
second, the counters, the check's sample of finished and in-flight
requests, its gap numbers, and the costs behind rooflines."""
import math

import numpy as np
import pytest
import tiny

from arch import qwen3_moe
from harness import check, costs, readings
from harness.driver import ReqRecord
from harness.readings import Context
from harness.traffic import Arrival
from repro.serving.request import Request


def _rec(i, due, tokens):
    r = ReqRecord(Arrival(i, due, 10, len(tokens) or 4),
                  Request(i, 10, 4, 0.0, prompt_tokens=[0] * 10))
    r.tokens = list(tokens)
    r.released = 100.0 + due
    return r


def _ctx(records):
    return Context(model=tiny.MODEL, peak=costs.peaks("TPU v5 lite"),
                   setup_s=1.5, t0=100.0, t1=110.0, records=records,
                   kv_peak=0.25, decode_lanes=[2, 4],
                   calls=[])


def test_window_counters():
    recs = [_rec(0, 1.0, [99.5, 101.7, 101.9]),     # one token before t0
            _rec(1, 2.0, [102.1]),
            _rec(2, 9.0, [110.5])]                  # one after t1
    ctx = _ctx(recs)
    assert readings.output_tokens_per_s(ctx) == pytest.approx(0.3)
    assert readings.decode_lanes_mean(ctx) == 3.0
    assert readings.kv_peak_share(ctx) == 25.0
    assert readings.step_mfu(ctx) is None          # nothing traced
    assert readings.device_idle_share(ctx) is None


def _served(i, prompt, out, stamps, finished):
    r = ReqRecord(Arrival(i, 0.0, prompt, 64),
                  Request(i, prompt, 64, 0.0, prompt_tokens=[1] * prompt))
    r.req.output_tokens = list(range(out))
    r.tokens = list(stamps)
    r.req.finish_time = 1.0 if finished else -1.0
    return r


def test_check_samples_in_flight_requests_longest_first():
    recs = [_served(0, 100, 10, [101.0] * 10, True),
            _served(1, 400, 30, [100.5] * 30, False),   # in flight, longest
            _served(2, 50, 20, [102.0] * 20, False),
            _served(3, 80, 5, [99.0] * 5, True)]         # before the window
    picked = check.sample(recs, seed=7, max_tokens=10_000, min_served=25,
                          t0=100.0)
    assert picked[0] is recs[1] and len(picked) == 1
    picked = check.sample(recs, seed=7, max_tokens=10_000, min_served=100,
                          t0=100.0)
    assert sorted(r.req.req_id for r in picked) == [0, 1, 2]
    picked = check.sample(recs, seed=7, max_tokens=480, min_served=100,
                          t0=100.0)
    assert [r.req.req_id for r in picked] == [1]     # room for no other


def test_gap_stats():
    g = check.gap_stats(np.array([0.0, 0.0, 0.5, 0.1]))
    assert g == pytest.approx({"logit_gap_max": 0.5, "logit_gap_mean": 0.15,
                               "argmax_miss": 0.5})


def test_paged_decode_cost_counts_live_tokens_only():
    m = {"num_attention_heads": 32, "num_key_value_heads": 4,
         "head_dim": 128}
    ops, nbytes = qwen3_moe.paged_decode_cost([100, 300], m)
    assert ops == 4 * 32 * 128 * 400
    assert nbytes == 400 * 2 * 4 * 128 * 2 + 2 * 2 * 32 * 128 * 2


def test_gmm_cost_and_bound():
    ops, nbytes = costs.gmm_cost(rows=256, touched=100, d_in=2048,
                                 d_out=768)
    assert ops == 2 * 256 * 2048 * 768
    assert nbytes == 100 * 2048 * 768 * 2 + 256 * (2048 * 2 + 768 * 4)
    peak = costs.peaks("TPU v5 lite")
    t = costs.min_time_s(ops, nbytes, peak)
    assert t == pytest.approx(nbytes / 819e9)      # weight-bound


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


def test_model_flops():
    m = tiny.MODEL
    f1 = costs.decode_flops([10], m)
    f2 = costs.decode_flops([20], m)
    L, H, hd = 2, 4, 16
    assert f2 - f1 == pytest.approx(L * 4 * H * hd * 10)
    pf = costs.prefill_flops([(0, 4, True)], m)
    per_tok = qwen3_moe.token_flops(m, 0)
    attn = 4 * H * hd * (4 * 5 / 2)
    head = 2 * m["hidden_size"] * m["vocab_size"]
    assert pf == pytest.approx(L * (4 * per_tok + attn) + head)
    assert costs.prefill_flops([(0, 4, False)], m) == \
        pytest.approx(pf - head)
    assert not math.isnan(costs.gmm_min_time(
        np.ones((2, 8), np.int32), m, costs.peaks("TPU v5 lite")))
