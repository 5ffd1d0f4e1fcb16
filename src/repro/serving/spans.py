"""The serving path's profiler spans: their names, and how one is opened.

A span is a ``jax.profiler.TraceAnnotation``: a host event in the
profiler's own trace, on the clock the device events share, recorded only
while a profiler session is active (``jax.profiler.start_trace``). With
no session a span costs about a microsecond. Keyword arguments become the
event's stats in the ``.xplane.pb``.

Spans nest on the serving thread, each inside the one that caused it::

    gimbal/round                  one round of serve_real_cluster
      gimbal/dispatch             Algorithm 1: due arrivals and orphans
      gimbal/engine_step          PagedRealEngine.step
        gimbal/plan               StepPlanner.plan
        gimbal/inputs             host arrays, block table, host-to-device
        gimbal/launch/<kind>      the step program's (async) enqueue: its
                                  lanes, tokens and live context tokens
        gimbal/readback           argmax read back: the step's sync point
        gimbal/stats_readback     MoE statistics read back
        gimbal/commit             per-lane append / finish / free
      gimbal/report               the engine's trace into the table
      gimbal/stats                window statistics into the profiler
      gimbal/rebalance            Algorithm 2 and MoE-pressure feedback
        gimbal/migrate            adopting a placement (moving weights)
"""
from __future__ import annotations

import jax

PREFIX = "gimbal/"
ROUND = "gimbal/round"
DISPATCH = "gimbal/dispatch"
REPORT = "gimbal/report"
STATS = "gimbal/stats"
REBALANCE = "gimbal/rebalance"
MIGRATE = "gimbal/migrate"
ENGINE_STEP = "gimbal/engine_step"
PLAN = "gimbal/plan"
INPUTS = "gimbal/inputs"
LAUNCH = "gimbal/launch/"              # + decode | prefill | mixed
READBACK = "gimbal/readback"
STATS_READBACK = "gimbal/stats_readback"
COMMIT = "gimbal/commit"

LAUNCH_KINDS = ("decode", "prefill", "mixed")
NAMES = (ROUND, DISPATCH, REPORT, STATS, REBALANCE, MIGRATE, ENGINE_STEP,
         PLAN, INPUTS, *(LAUNCH + k for k in LAUNCH_KINDS), READBACK,
         STATS_READBACK, COMMIT)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A span named ``name`` whose stats are ``args``; use it with
    ``with``. ``set_metadata`` on it adds stats known only inside."""
    return jax.profiler.TraceAnnotation(name, **args)
