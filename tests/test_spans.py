"""The serving path's profiler spans (``serving/spans.py``), read back from a
profiler trace of a tiny two-engine Gimbal cluster on the CPU, and the
names of the step programs.

One engine runs split steps (a decode call and prefill calls), the other
mixed steps, and the uncalibrated placement makes Algorithm 2 migrate
experts, so every span of the serving path is written at least once.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.placement import PlacementConfig
from repro.serving import PagedEngineConfig, PagedModelRunner, spans
from repro.serving.engine_util import pin_dispatch_mode
from repro.serving.paged_engine import PagedRealEngine
from repro.serving.real_cluster import RealClusterConfig, serve_real_cluster
from repro.serving.request import Request, RequestState

ECFG = PagedEngineConfig(page_size=8, n_pages=48, max_blocks_per_req=6,
                         max_batch=4, token_budget=16, chunk_buckets=(8, 16),
                         attn_backend="xla")

# each span's parent: the innermost span around it on the serving thread
PARENT = {
    spans.DISPATCH: spans.ROUND, spans.ENGINE_STEP: spans.ROUND,
    spans.STATS: spans.ROUND, spans.REBALANCE: spans.ROUND,
    spans.MIGRATE: spans.REBALANCE,
    spans.PLAN: spans.ENGINE_STEP, spans.INPUTS: spans.ENGINE_STEP,
    spans.READBACK: spans.ENGINE_STEP,
    spans.STATS_READBACK: spans.ENGINE_STEP,
    spans.COMMIT: spans.ENGINE_STEP,
    **{spans.LAUNCH + k: spans.ENGINE_STEP for k in spans.LAUNCH_KINDS},
}


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    line: int
    args: dict
    parent: str = None


def _read_host(path):
    """The trace's ``gimbal/`` spans, each with its parent, and the names
    of every other host event."""
    from jax.profiler import ProfileData
    out, names = [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, i,
                                    {k: v for k, v in e.stats}))
                else:
                    names.add(e.name)
    for s in out:
        around = [p for p in out if p is not s and p.line == s.line
                  and p.start <= s.start and s.end <= p.end]
        if around:
            s.parent = min(around, key=lambda p: p.end - p.start).name
    return sorted(out, key=lambda s: s.start), names


@pytest.fixture(scope="module")
def served(tiny_model, tmp_path_factory):
    cfg, params = tiny_model
    runner = PagedModelRunner(cfg, params, ECFG, n_sources=2)
    # (kind, lanes of a decode call, live context tokens) of every call
    received = []
    decode, prefill, mixed = (runner.decode, runner.prefill_chunk,
                              runner.mixed_step)

    def recording_decode(tokens, pages, lengths, block_tables, active,
                         placement, source_ids):
        act = np.asarray(active)
        received.append(("decode", int(act.sum()),
                         int((np.asarray(lengths)[act] + 1).sum())))
        return decode(tokens, pages, lengths, block_tables, active,
                      placement, source_ids)

    def recording(kind, call):
        def run(batch, *args):
            received.append((kind, None, int(
                np.asarray(batch["chunk_starts"]).sum()
                + np.asarray(batch["chunk_lens"]).sum())))
            return call(batch, *args)
        return run

    runner.decode = recording_decode
    runner.prefill_chunk = recording("prefill", prefill)
    runner.mixed_step = recording("mixed", mixed)
    engines = [PagedRealEngine(0, cfg, params, ECFG, runner=runner,
                               n_sources=2),
               PagedRealEngine(1, cfg, params,
                               dataclasses.replace(ECFG, mixed_steps=True),
                               runner=runner, n_sources=2)]
    # one repeated token skews routing, so the uncalibrated greedy
    # placement migrates experts within the run
    reqs = [Request(req_id=i, prompt_len=20, max_new_tokens=4,
                    arrival_time=0.02 * i, prompt_tokens=[0] * 20)
            for i in range(8)]
    tdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # the spans, not every call
    with jax.profiler.trace(tdir, profiler_options=opts):
        res = serve_real_cluster(reqs, engines, cluster_cfg=RealClusterConfig(
            window_tokens=50, placement_cfg=PlacementConfig.uncalibrated()))
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert all(r.state is RequestState.FINISHED and not r.error
               for r in reqs)
    return (reqs, res, *_read_host(path), received)


def test_every_span_is_written(served):
    _, res, got, _, _ = served
    assert res.signals["migrations"] > 0
    assert {s.name for s in got} == set(spans.NAMES)


def test_spans_nest_as_the_layers_call_each_other(served):
    _, res, got, _, _ = served
    for s in got:
        if s.name == spans.ROUND:
            assert s.parent is None
        elif s.name == spans.REPORT:      # engines announce before round 0
            assert s.parent in (spans.ROUND, None)
        else:
            assert s.parent == PARENT[s.name], s
    rounds = [s.args["round"] for s in got if s.name == spans.ROUND]
    assert rounds == list(range(res.signals["rounds"]))


def test_span_arguments_count_the_work(served):
    reqs, res, got, _, received = served
    by = lambda n: [s for s in got if s.name == n]
    lanes = [s.args["lanes"] for s in by(spans.LAUNCH + "decode")]
    assert lanes == [n for k, n, _ in received if k == "decode"]
    assert len(lanes) > 0
    assert sum(s.args["n"] for s in by(spans.DISPATCH)) == len(reqs)
    assert sum(s.args["migrated"] for s in by(spans.REBALANCE)) \
        == res.signals["migrations"]
    steps = by(spans.ENGINE_STEP)
    assert {s.args["engine"] for s in steps} == {0, 1}
    assert sum(s.args["finished"] for s in by(spans.COMMIT)) == len(reqs)
    for kind in spans.LAUNCH_KINDS:
        assert all(s.args["lanes"] >= 1 and s.args["tokens"] >= 1
                   for s in by(spans.LAUNCH + kind))


def test_launch_spans_carry_the_live_context(served):
    """``ctx``: the live context tokens the call attends over, each lane's
    written tokens plus this call's."""
    got, received = served[2], served[4]
    for kind in spans.LAUNCH_KINDS:
        ctx = [s.args["ctx"] for s in got if s.name == spans.LAUNCH + kind]
        assert ctx == [c for k, _, c in received if k == kind]
        assert len(ctx) > 0 and min(ctx) >= 1


def test_step_programs_are_named_in_the_trace(served):
    names = served[3]
    for kind in spans.LAUNCH_KINDS:
        assert f"PjitFunction({kind}_step)" in names


def test_step_programs_have_stable_names(shared_runner):
    assert "module @jit_decode_step" in \
        shared_runner.lower_decode().as_text()


def test_pinned_program_takes_the_given_name():
    f = pin_dispatch_mode(lambda x: x + 1, lambda: True, "prefill_step")
    text = jax.jit(f).lower(np.float32(0)).as_text()
    assert "module @jit_prefill_step" in text
