"""The trace reduction on a short trace recorded on a TPU v5e chip
(``bench/data/chat_small.xplane.pb``: two decode steps of the chat mix of
``qwen3-30b-a3b-4l``, written by ``bench/tools/record_trace.py``), against
the reduction's summary kept beside it and against what the metrics need."""
import json
import os

import pytest
import tiny  # noqa: F401  (puts bench/ and src/ on the path)

from harness import trace

DATA = os.path.join(tiny.BENCH, "data")
TRACE = os.path.join(DATA, "chat_small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(TRACE + ".json") as f:
        return trace.reduce(TRACE), json.load(f)


def test_same_summary_as_on_the_chip(recorded):
    got, want = recorded
    assert got.window_s == pytest.approx(want["window_s"])
    assert got.busy_s == pytest.approx(want["busy_s"])
    assert got.call_kinds == want["call_kinds"] == want["calls"]
    assert got.call_device_s == pytest.approx(want["call_device_s"])
    assert got.kernel_s == pytest.approx(want["kernel_s"])


def test_what_the_metrics_read_is_there(recorded):
    got, _ = recorded
    assert 0 < got.busy_s <= got.window_s
    assert "decode" in got.call_kinds
    assert all(s > 0 for s in got.call_device_s)
    assert got.kernel_s["paged_decode"] > 0 and got.kernel_s["moe_gmm"] > 0
    assert sum(got.call_device_s) <= got.window_s
    assert got.kernel_s["paged_decode"] < sum(got.call_device_s)
    assert len(got.device_ops) <= 10 and len(got.idle_gaps) <= 10
    idle = sum(s for _, s in got.idle_gaps)
    assert idle <= got.window_s - got.busy_s + 1e-9


def test_kernel_names():
    gmm = ("%moe_gmm_ragged.24 = f32[1152,768]{1,0} custom-call(s32[144]{0} "
           "%a), custom_call_target=\"tpu_custom_call\"")
    decode = ("%closed_call.11 = bf16[32,32,128]{2,1,0:T(8,128)(2,1)S(1)} "
              "custom-call(s32[32,640]{1,0:T(8,128)S(1)} %copy-done.3, "
              "s32[32]{0} %g), custom_call_target=\"tpu_custom_call\"")
    assert trace.kernel_of(gmm) == "moe_gmm"
    assert trace.kernel_of(decode) == "paged_decode"
    assert trace.kernel_of("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") \
        is None
