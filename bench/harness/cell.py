"""One run of one cell: set up, measure a window, check, report.

Set-up makes the weights from the seed on the chip in one jitted call,
builds one runner per chip and compiles or loads every step program the
engine can use, warms up the host-side operations the serving path runs
(one short serve of a few requests), and builds fresh engines. Then the
window opens. After it closes the samples are read, the program's state
is freed, and the served tokens are compared with the reference.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, costs, trace, traffic
from harness.driver import Driver, RecordingRunner, engine_class
from harness.readings import Context
from harness.spec import Cell
from harness.weights import is_moe, make_params


class _CompileCount:
    """Backend compiles while ``counting`` is set (there should be none in
    the window: every shape is warmed up in set-up)."""

    def __init__(self):
        self.n, self.counting = 0, False
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, name: str, *_a, **_kw) -> None:
        if self.counting and name.endswith("backend_compile_duration"):
            self.n += 1


_compile_count: Optional[_CompileCount] = None


class _GcTime:
    """Seconds the garbage collector ran while this object was counting."""

    def __init__(self):
        self.seconds, self._t = 0.0, 0.0
        gc.callbacks.append(self)

    def __call__(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t

    def stop(self) -> None:
        gc.callbacks.remove(self)


def program_config(m: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig
    moe = is_moe(m)
    if m.get("mlp_only_layers") or int(m.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("every layer must be alike (all MoE or all dense)")
    return ModelConfig(
        name=m["name"], family="moe" if moe else "dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        d_ff=m["moe_intermediate_size"] if moe else m["intermediate_size"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        moe=MoEConfig(n_experts=m["num_experts"],
                      top_k=m["num_experts_per_tok"],
                      d_expert=m["moe_intermediate_size"], moe_every=1)
        if moe else MoEConfig(),
        qk_norm=True, rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), dtype="bfloat16")


def engine_config(m: dict, mix: dict, interpret: bool):
    """The engine sizes of the configuration, with any the mix sets for
    its traffic (``"engine"``), and a block table that holds the mix's
    longest request."""
    from repro.serving.paged_engine import PagedEngineConfig
    e = dict(m["engine"], **mix.get("engine", {}))
    nb = -(-traffic.max_tokens(mix) // e["page_size"])
    e["chunk_buckets"] = tuple(e["chunk_buckets"])
    e["lane_buckets"] = tuple(e["lane_buckets"])
    return PagedEngineConfig(max_blocks_per_req=nb, interpret=interpret, **e)


def devices_for(chips: int, require_chip: bool):
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def _warm_host_ops(runner, cfg, ecfg) -> None:
    """The engine's own small device operations, at every shape the
    serving path gives them: per-lane argmax reads and lane id vectors."""
    V = cfg.vocab_size
    dev = runner.device
    with jax.default_device(dev):
        for B in tuple(ecfg.lane_buckets) + (ecfg.max_batch,):
            z = jnp.zeros((B, V), jnp.float32)
            np.asarray(jnp.argmax(z, axis=-1))
            for i in range(B):
                int(jnp.argmax(z[i]))
            jnp.full((B,), 0, jnp.int32).block_until_ready()
    if cfg.moe.enabled:
        from repro.models import transformer as tfm
        ident = np.asarray(tfm.identity_placement(cfg))
        jax.block_until_ready(tfm.migrate_params_for_placement(
            runner.params, cfg, ident, ident))


def _warm_serve(runners, cfg, ecfg, n_engines) -> None:
    """A short serve of a few requests through the whole path."""
    rng = np.random.default_rng(0)
    n = max(ecfg.lane_buckets) + 1
    lens = [min(ecfg.chunk_buckets[i % len(ecfg.chunk_buckets)] + 3,
                ecfg.max_len - 8) for i in range(n)]
    arrivals = [traffic.Arrival(i, 0.0, L, 4) for i, L in enumerate(lens)]
    prompts = [rng.integers(0, cfg.vocab_size, L).tolist() for L in lens]
    drv = Driver(arrivals, prompts, seconds=None)
    drv.serve(_engines(drv, runners, cfg, ecfg, n_engines))
    drv.engines = []
    for r in runners:
        r.drv = None
    gc.collect()


def _engines(drv, runners, cfg, ecfg, n_engines):
    cls = engine_class(drv)
    for r in runners:
        r.drv = drv
    return [cls(i, cfg, None, ecfg, runner=runners[i % len(runners)],
                n_sources=n_engines) for i in range(n_engines)]


def _peak_bytes(devices) -> int:
    out = 0
    for d in devices:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


class Setup:
    """The program as set up for one seed: runners with their compiled
    step programs, and what the engines are built from."""

    def __init__(self, cell: Cell, seed: int, require_chip: bool = True):
        global _compile_count
        if _compile_count is None:
            _compile_count = _CompileCount()
        self.compiles = _compile_count
        self.cell, self.seed = cell, seed
        m = cell.config
        self.devices = devices_for(cell.chips, require_chip)
        self.kind = self.devices[0].device_kind
        self.peak = costs.peaks(self.kind) if require_chip else None
        self.cfg = program_config(m)
        self.ecfg = engine_config(m, cell.traffic,
                                  interpret=self.devices[0].platform != "tpu")
        self.n_engines = cell.chips        # one engine on each chip
        params = make_params(m, seed)
        self.runners = []
        for i, d in enumerate(self.devices):
            r = RecordingRunner(self.cfg, params, self.ecfg,
                                n_sources=self.n_engines, device=d)
            r.device_index = i
            self.runners.append(r)
        del params
        for r in self.runners:
            r.warmup()
            _warm_host_ops(r, self.cfg, self.ecfg)
        _warm_serve(self.runners, self.cfg, self.ecfg, self.n_engines)

    def window(self, mix: dict, seconds: float, traced: bool = False,
               t_start: Optional[float] = None,
               keep_trace: Optional[str] = None,
               trace_span: Tuple[float, float] = (0.3, 0.2)) -> Context:
        """Serve ``mix`` for ``seconds`` on fresh engines; return what the
        metrics read. ``t_start`` (host clock) dates set-up's start. A
        traced window traces the share ``trace_span`` (start, length) of
        it; ``keep_trace`` copies the trace file there."""
        arrivals = traffic.schedule(mix, seconds)
        prompts = traffic.prompt_tokens(arrivals, self.cfg.vocab_size,
                                        self.seed)
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        drv = Driver(arrivals, prompts, seconds, trace_dir=tdir,
                     trace_span=trace_span, preroll_s=traffic.preroll_s(mix))
        engines = _engines(drv, self.runners, self.cfg, self.ecfg,
                           self.n_engines)
        # Everything set-up made is kept out of the collector's scans, so a
        # full collection in the window walks only what serving allocates.
        gc.collect()
        gc.freeze()
        gc_time = _GcTime()
        self.compiles.n, self.compiles.counting = 0, True
        try:
            drv.serve(engines)
        finally:
            self.compiles.counting = False
            gc_time.stop()
            gc.unfreeze()
        setup_s = drv.t0 - t_start if t_start else math.nan

        peak_bytes = _peak_bytes(self.devices)
        finished = [r.req_id for e in engines for r in e.finished]
        for c in drv.calls:
            if c.expert_counts is not None:
                c.expert_counts = np.asarray(c.expert_counts)
        summary = None
        if traced:
            files = [os.path.join(dp, f) for dp, _, fs in os.walk(tdir)
                     for f in fs if f.endswith(".xplane.pb")]
            summary = trace.reduce(files[0])
            if keep_trace:
                shutil.copy(files[0], keep_trace)
            shutil.rmtree(tdir, ignore_errors=True)
        drv.engines = []
        for r in self.runners:
            r.drv = None
        ctx = Context(model=self.cell.config, peak=self.peak,
                      setup_s=setup_s, t0=drv.t0, t1=drv.t0 + seconds,
                      records=drv.records, kv_peak=drv.kv_peak,
                      decode_lanes=drv.decode_lanes, calls=drv.calls,
                      trace=summary,
                      memory_peak_bytes=peak_bytes,
                      finished_twice=len(finished) - len(set(finished)),
                      compiles_in_window=self.compiles.n,
                      step_ends=drv.step_ends, gc_s=gc_time.seconds,
                      placements_adopted=drv.placements_adopted)
        del engines, drv
        gc.collect()
        return ctx

    def free(self) -> None:
        """Drop the program's state from the chip."""
        self.runners = []
        gc.collect()


def verify(ctx: Context, seed: int, control: bool = False,
           log=sys.stderr) -> Tuple[bool, List[Tuple[str, float, float]],
                                    Dict[str, float], Optional[bool]]:
    """The correctness check of a window whose program state is freed.
    Returns (correct, [(name, value, limit)], readings, control_correct),
    where readings has every gap number of the program and, with
    ``control``, of the control (``control_<number>``); control_correct
    is the same comparison with the control's numbers in the program's
    place (None without ``control``)."""
    m = ctx.model
    limits = m["check"]
    t = time.perf_counter()
    counts = check.accounting(ctx.records, m["vocab_size"])
    counts["finished_twice"] = ctx.finished_twice
    checks = [(k, float(v), 0.0) for k, v in counts.items()]
    picked = check.sample(ctx.records, seed, limits["max_tokens"],
                          limits["min_served"], ctx.t0)
    readings: Dict[str, float] = {}
    control_ok = None
    if picked:
        gap, ctrl = check.logit_gaps(m, seed, picked, control=control)
        readings.update(check.gap_stats(gap))
        if ctrl is not None:
            readings.update({"control_" + k: v for k, v in
                             check.gap_stats(ctrl).items()})
            control_ok = all(readings["control_" + k] <= float(lim)
                             for k, lim in limits["limits"].items())
        checks += [(k, readings[k], float(lim))
                   for k, lim in limits["limits"].items()]
    else:
        checks.append(("no_served_request", 1.0, 0.0))
    served = sum(len(r.req.output_tokens) for r in picked)
    print(f"check: {len(picked)} requests, {served} served tokens against "
          f"the reference in {time.perf_counter() - t:.1f} s; readings "
          f"{readings}", file=log)
    return all(v <= lim for _, v, lim in checks), checks, readings, \
        control_ok


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_chip: bool = True,
        log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line's object."""
    s = Setup(cell, seed, require_chip)
    ctx = s.window(cell.traffic, seconds, traced, t_start=t_start)
    devices, kind = s.devices, s.kind
    s.free()
    del s

    metrics = {}
    for metric in (cell.per_layer if traced else cell.end_to_end):
        v = metric.read(ctx)
        if v is not None:
            metrics[metric.name] = {"value": float(v), "unit": metric.unit}
    due = [r for r in ctx.records if 0 <= r.arrival.due_s < seconds]
    failed = sum(1 for r in due if r.req.error)
    late = [(r.released - (ctx.t0 + r.arrival.due_s)) * 1e3
            for r in ctx.records if not math.isnan(r.released)]
    print(f"cell {cell.name} seed {seed}: {len(due)} requests due in the "
          f"{seconds} s window, "
          f"{sum(1 for r in due if r.req.finish_time >= 0)} "
          f"finished, {failed} failed; setup {ctx.setup_s:.3f} s; "
          f"compiles while serving {ctx.compiles_in_window}", file=log)
    if late:
        print(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
              f"p99 {np.percentile(late, 99):.3f} max {max(late):.3f}",
              file=log)
    gaps = np.diff(ctx.step_ends) * 1e3
    if len(gaps):
        print(f"steps in window {len(ctx.step_ends)}; step gap ms: p50 "
              f"{np.percentile(gaps, 50):.3f} p99 "
              f"{np.percentile(gaps, 99):.3f} max {gaps.max():.3f}, "
              f"{int((gaps > 150).sum())} over 150 ms; garbage collection "
              f"{ctx.gc_s:.3f} s; placements adopted "
              f"{ctx.placements_adopted}", file=log)
    summary = ctx.trace
    if summary is not None:
        print(f"trace: window {summary.window_s:.3f} s busy "
              f"{summary.busy_s:.3f} s; ops {summary.device_ops}; "
              f"idle {summary.idle_gaps}", file=log)

    correct, checks, _, _ = verify(ctx, seed, log=log)
    for k, v, lim in checks:
        print(f"check {k}: {v!r} limit {lim!r}", file=log)
    result = {
        "correct": correct, "attempted": len(due), "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": ctx.memory_peak_bytes},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, v, lim in checks}
    return result
