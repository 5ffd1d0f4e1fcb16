"""What the metric readers read, and the arithmetic they share.

Each file in ``bench/metrics/`` defines ``read(ctx)`` for one metric and
returns a number, or None where the run gives it nothing to read (the
harness then leaves the metric out). ``ctx`` is a :class:`Context`.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional

import numpy as np

from harness import costs
from harness.driver import CallRecord, ReqRecord
from harness.trace import Summary


@dataclasses.dataclass
class Context:
    model: dict                       # the configuration file
    peak: Dict[str, float]            # the chip's published peaks
    setup_s: float
    t0: float                         # window, host clock
    t1: float
    records: List[ReqRecord]
    kv_peak: float                    # largest pool usage after a step
    decode_lanes: List[int]           # active lanes of every decode call
    calls: List[CallRecord]           # model calls in the traced window
    trace: Optional[Summary] = None
    memory_peak_bytes: int = 0
    finished_twice: int = 0
    compiles_in_window: int = 0
    step_ends: List[float] = dataclasses.field(default_factory=list)
    placements_adopted: int = 0
    gc_s: float = 0.0                 # garbage collection in the window

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def output_tokens_per_s(ctx: Context) -> float:
    n = sum(1 for r in ctx.records for t in r.tokens
            if ctx.t0 <= t <= ctx.t1)
    return n / ctx.window_s


def call_times(ctx: Context, kind: str) -> List[float]:
    """Device seconds of each traced model call of ``kind``."""
    if ctx.trace is None:
        return []
    return [s for k, s in zip(ctx.trace.call_kinds, ctx.trace.call_device_s)
            if k == kind and s > 0]


def mean_ms(values) -> Optional[float]:
    return statistics.fmean(values) * 1e3 if values else None


def _traced_calls(ctx: Context) -> bool:
    """The recorded calls are the trace's calls, in the same order."""
    return ctx.trace is not None and ctx.calls and \
        [c.kind for c in ctx.calls] == ctx.trace.call_kinds


def step_mfu(ctx: Context) -> Optional[float]:
    """Model FLOPs the real tokens need over the step programs' device
    time at the chip's bf16 peak, in %."""
    if not _traced_calls(ctx):
        return None
    flops = sum(costs.decode_flops(c.ctx, ctx.model) if c.kind == "decode"
                else costs.prefill_flops(c.lanes or [], ctx.model)
                for c in ctx.calls)
    t = sum(ctx.trace.call_device_s)
    return 100.0 * flops / (t * ctx.peak["flops"]) if t > 0 else None


def paged_decode_roofline(ctx: Context) -> Optional[float]:
    t = ctx.trace.kernel_s.get("paged_decode", 0.0) if ctx.trace else 0.0
    if not _traced_calls(ctx) or t <= 0:
        return None
    layers = ctx.model["num_hidden_layers"]
    ideal = sum(layers * costs.min_time_s(
        *costs.paged_decode_cost(c.ctx, ctx.model), ctx.peak)
        for c in ctx.calls if c.kind == "decode")
    return 100.0 * ideal / t


def moe_gmm_roofline(ctx: Context) -> Optional[float]:
    t = ctx.trace.kernel_s.get("moe_gmm", 0.0) if ctx.trace else 0.0
    if not _traced_calls(ctx) or t <= 0:
        return None
    ideal = sum(costs.gmm_min_time(c.expert_counts, ctx.model, ctx.peak)
                for c in ctx.calls)
    return 100.0 * ideal / t


def device_idle_share(ctx: Context) -> Optional[float]:
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def kv_peak_share(ctx: Context) -> Optional[float]:
    return 100.0 * ctx.kv_peak if ctx.kv_peak > 0 else None


def decode_lanes_mean(ctx: Context) -> Optional[float]:
    return statistics.fmean(ctx.decode_lanes) if ctx.decode_lanes else None
