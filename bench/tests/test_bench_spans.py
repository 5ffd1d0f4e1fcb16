"""The chip's idle time split by the program's spans
(``bench/harness/spans.py``), on a short trace recorded on a TPU v5e chip
(``bench/data/reasoning_small.xplane.pb``: a few steps of the reasoning
cell of ``qwen3-30b-a3b-4l``, written by ``bench/tools/record_trace.py``
with the program's ``gimbal/`` spans), and on the older
``chat_small.xplane.pb``, recorded before the program wrote spans."""
import json
import os

import pytest
import tiny  # noqa: F401  (puts bench/ and src/ on the path)

from harness import spans, trace
from repro.serving import spans as names

DATA = os.path.join(tiny.BENCH, "data")
TRACE = os.path.join(DATA, "reasoning_small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(TRACE + ".json") as f:
        return trace.reduce(TRACE), spans.read(TRACE), json.load(f)


def test_a_program_without_spans_reads_as_nothing():
    assert spans.read(os.path.join(DATA, "chat_small.xplane.pb")) is None


def test_the_reduction_reads_as_on_the_chip(recorded):
    """What the metrics read is what the chip's run reduced, with the
    program's spans in the trace beside the harness's."""
    got, _, want = recorded
    assert got.window_s == pytest.approx(want["window_s"])
    assert got.busy_s == pytest.approx(want["busy_s"])
    assert got.call_kinds == want["call_kinds"] == want["calls"]
    assert got.call_device_s == pytest.approx(want["call_device_s"])
    assert got.kernel_s == pytest.approx(want["kernel_s"])
    assert [k for k, _ in got.device_ops] == \
        [k for k, _ in want["device_ops"]]
    assert [v for _, v in got.device_ops] == \
        pytest.approx([v for _, v in want["device_ops"]])
    assert got.kernel_s["paged_decode"] > 0 and got.kernel_s["moe_gmm"] > 0


def test_idle_time_adds_up(recorded):
    summary, got, _ = recorded
    assert got.window_s == pytest.approx(summary.window_s)
    assert got.idle_s == pytest.approx(summary.window_s - summary.busy_s)
    assert got.short_s + sum(got.by_span.values()) \
        == pytest.approx(got.idle_s)
    assert got.engine_s + got.control_s <= sum(got.by_span.values()) + 1e-9
    assert set(got.by_span) <= set(names.NAMES) | {""}


def test_idle_is_named_by_the_program(recorded):
    """Long gaps fall under spans; their time under engine steps or whole
    rounds is counted per step or round. A round open when the trace
    started or stopped has no ``gimbal/round`` span: its time outside its
    engine step falls under neither."""
    _, got, _ = recorded
    attributed = sum(got.by_span.values())
    assert got.unspanned_s < 0.05 * attributed
    assert 0 < got.engine_s + got.control_s <= attributed + 1e-12
    assert got.steps > 0 and got.rounds > 0
    assert got.engine_idle_ms > 0 and got.control_idle_ms > 0


def test_spans_and_chip_share_a_clock(recorded):
    _, got, _ = recorded
    assert got.readback_lag_s
    assert all(0 <= lag < 5e-3 for lag in got.readback_lag_s)


def test_step_programs_are_named():
    from jax.profiler import ProfileData
    modules = {e.name.split("(", 1)[0]
               for p in ProfileData.from_file(TRACE).planes
               if p.name.startswith("/device:TPU:")
               for line in p.lines if line.name == "XLA Modules"
               for e in line.events}
    assert "jit_decode_step" in modules and "jit_traced" not in modules


def test_time_is_cut_under_the_innermost_span():
    sp = spans.Span
    got = spans.pieces([sp(names.ROUND, 0, 50, {}),
                        sp(names.ENGINE_STEP, 10, 40, {"engine": 3}),
                        sp(names.PLAN, 12, 18, {}),
                        sp(names.READBACK, 20, 40, {}),
                        sp(names.REPORT, 60, 70, {})])
    assert [tuple(p) for p in got] == [
        (0, 10, names.ROUND, None, True),
        (10, 12, names.ENGINE_STEP, 3, True),
        (12, 18, names.PLAN, 3, True),
        (18, 20, names.ENGINE_STEP, 3, True),
        (20, 40, names.READBACK, 3, True),
        (40, 50, names.ROUND, None, True),
        (60, 70, names.REPORT, None, False)]
