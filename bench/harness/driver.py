"""Open-loop serving on the wall clock, through the program's own loop.

``serve_real_cluster`` counts time in rounds (``now += dt``). The driver
leaves it as it is and wraps its engines and runners instead:

- every request enters the loop with ``arrival_time = inf``, so the loop
  holds it in its queue;
- after each engine step, which returns only once the step's tokens are
  read back from the device, the engine releases every request whose
  wall-clock due time has passed, by setting ``arrival_time`` to the
  loop's ``now``; the loop dispatches it in its next round (Algorithm 1);
- it stamps every new output token with ``time.perf_counter()`` then;
- when no engine has work and no released request waits for dispatch, it
  sleeps until the next due time;
- at the end of the window it raises :class:`WindowClosed`, which leaves
  the loop. Counters are read from the engines and runners afterwards.

Requests due before the window (an open loop's pre-roll) are served from
``preroll_s`` before it opens, so the window opens on a loaded system;
samples start when it opens.

Latencies are measured from each request's due time, so a late release
or a stall shows in them. The harness's spans (``bench/...``) mark the
traced window, each engine step, each plan and each model call.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional

import jax
import numpy as np

from repro.serving.paged_engine import PagedModelRunner, PagedRealEngine
from repro.serving.real_cluster import serve_real_cluster
from repro.serving.request import Request

from harness.traffic import Arrival

INF = float("inf")


class WindowClosed(Exception):
    """Raised from an engine step once the measured window has ended."""


@dataclasses.dataclass
class ReqRecord:
    arrival: Arrival
    req: Request
    released: float = math.nan          # host clock
    enqueued: List[float] = dataclasses.field(default_factory=list)
    tokens: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class CallRecord:
    kind: str                           # decode | prefill
    ctx: Optional[np.ndarray] = None    # decode: live tokens per lane
    lanes: Optional[list] = None        # prefill: (start, chunk, completes)
    expert_counts: object = None        # (MoE layers, experts) real rows


class _Span:
    """A profiler span that is opened and closed by hand."""

    def __init__(self, name: str):
        self._ann = jax.profiler.TraceAnnotation(name)
        self._ann.__enter__()

    def close(self) -> None:
        self._ann.__exit__(None, None, None)


class Driver:
    """Releases, stamps and counts one serving window."""

    def __init__(self, arrivals: List[Arrival], prompts: List[List[int]],
                 seconds: Optional[float], trace_dir: Optional[str] = None,
                 trace_span=(0.3, 0.2), preroll_s: float = 0.0):
        self.records = [ReqRecord(a, Request(
            req_id=a.req_id, prompt_len=a.prompt_len,
            max_new_tokens=a.output_len, arrival_time=INF,
            prompt_tokens=p)) for a, p in zip(arrivals, prompts)]
        self.seconds = seconds
        self.preroll_s = preroll_s
        self.trace_dir = trace_dir
        self.trace_span = trace_span    # (start, length) as window shares
        self.engines: List[PagedRealEngine] = []
        self.t0 = self.t_end = math.nan
        self._next = 0
        self._released = self._enqueued = 0
        self.in_window = False
        self.recording = False
        self.calls: List[CallRecord] = []
        self.decode_lanes: List[int] = []
        self.kv_peak = 0.0
        self.step_ends: List[float] = []    # host clock, steps in window
        self.placements_adopted = 0         # placement changes in window
        self._placement = None
        self.pending_lanes: Optional[list] = None
        self._trace_state = 0           # 0 before, 1 tracing, 2 done
        self._window_span: Optional[_Span] = None

    # ---- the window ------------------------------------------------------
    def serve(self, engines: List[PagedRealEngine]) -> None:
        """Serve this driver's requests until the window closes (or, with
        ``seconds=None``, until every request has finished)."""
        self.engines = engines
        for e in engines:
            e.planner.plan = _spanned(e.planner.plan, "bench/plan")
        self.t0 = time.perf_counter() + self.preroll_s
        self.t_end = self.t0 + self.seconds if self.seconds else INF
        if not self.preroll_s:
            self._open()
        try:
            serve_real_cluster([r.req for r in self.records], engines)
            if self.seconds:             # all served early: idle to the end
                time.sleep(max(self.t_end - time.perf_counter(), 0.0))
        except WindowClosed:
            pass
        finally:
            self.in_window = False
            self._stop_trace()

    def _open(self) -> None:
        """The window opens: samples start here."""
        self.in_window = True

    def _tick(self) -> None:
        now = time.perf_counter()
        if now >= self.t_end:
            raise WindowClosed
        if not self.in_window and now >= self.t0:
            self._open()
        if self.trace_dir is None or self._trace_state == 2:
            return
        start = self.t0 + self.trace_span[0] * self.seconds
        stop = start + self.trace_span[1] * self.seconds
        if self._trace_state == 0 and now >= start:
            jax.profiler.start_trace(self.trace_dir)
            self._window_span = _Span("bench/window")
            self._trace_state = 1
            self.recording = True
        elif self._trace_state == 1 and now >= stop:
            self._stop_trace()

    def _stop_trace(self) -> None:
        if self._trace_state == 1:
            self.recording = False
            self._window_span.close()
            jax.profiler.stop_trace()
            self._trace_state = 2

    # ---- release ---------------------------------------------------------
    def _release(self, now_virtual: float) -> None:
        t = time.perf_counter() - self.t0
        while self._next < len(self.records) and \
                self.records[self._next].arrival.due_s <= t:
            rec = self.records[self._next]
            rec.req.arrival_time = now_virtual
            rec.released = time.perf_counter()
            self._released += 1
            self._next += 1

    def _sleep_if_idle(self, now_virtual: float) -> None:
        if any(e.has_work for e in self.engines) \
                or self._released > self._enqueued \
                or self._next >= len(self.records):
            return
        due = self.t0 + self.records[self._next].arrival.due_s
        wait = min(due, self.t_end) - time.perf_counter()
        if wait > 0:
            span = _Span("bench/sleep")
            time.sleep(wait)
            span.close()
        if time.perf_counter() >= self.t_end:
            raise WindowClosed
        self._release(now_virtual)

    # ---- engine hooks ----------------------------------------------------
    def on_enqueue(self, req: Request) -> None:
        self.records[req.req_id].enqueued.append(time.perf_counter())
        self._enqueued += 1

    def before_step(self, e: PagedRealEngine, now: float) -> None:
        self._tick()
        self._sleep_if_idle(now)

    def after_step(self, e: PagedRealEngine, now: float,
                   finished: List[Request]) -> None:
        t = time.perf_counter()
        for r in list(e.running) + list(finished):
            rec = self.records[r.req_id]
            n = len(r.output_tokens or ())
            if n > len(rec.tokens):
                rec.tokens += [t] * (n - len(rec.tokens))
        if self.in_window:
            self.kv_peak = max(self.kv_peak, float(e.pool.usage))
            self.step_ends.append(t)
            if self._placement is not None and \
                    e.placement is not self._placement:
                self.placements_adopted += 1
        self._placement = e.placement
        self._release(now)

    # ---- runner hooks ----------------------------------------------------
    def on_decode(self, lengths, active, stats) -> None:
        if not self.in_window:
            return
        act = np.asarray(active)
        self.decode_lanes.append(int(act.sum()))
        if self.recording:
            ctx = np.asarray(lengths)[act] + 1
            self.calls.append(CallRecord("decode", ctx=ctx,
                                         expert_counts=_counts(stats)))

    def on_prefill(self, stats) -> None:
        lanes, self.pending_lanes = self.pending_lanes, None
        if self.recording:
            self.calls.append(CallRecord("prefill", lanes=lanes,
                                         expert_counts=_counts(stats)))


def _counts(stats):
    if stats is None or "expert_counts" not in stats:
        return None
    return stats["expert_counts"]       # read back after the window


def _spanned(fn, name: str):
    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def engine_class(drv: Driver):
    """A ``PagedRealEngine`` that reports its steps to ``drv``."""

    class WallClockEngine(PagedRealEngine):
        def enqueue(self, req: Request, now: float) -> None:
            drv.on_enqueue(req)
            super().enqueue(req, now)

        def step(self, now: float) -> List[Request]:
            drv.before_step(self, now)
            with jax.profiler.TraceAnnotation("bench/engine_step"):
                finished = super().step(now)
            drv.after_step(self, now, finished)
            return finished

        def _run_prefill_group(self, group, now):
            drv.pending_lanes = [
                (l.start, l.chunk, l.start + l.chunk == l.req.prompt_len)
                for l in group]
            return super()._run_prefill_group(group, now)

    return WallClockEngine


class RecordingRunner(PagedModelRunner):
    """A ``PagedModelRunner`` whose calls are spanned and recorded."""

    drv: Optional[Driver] = None
    device_index: int = 0

    def decode(self, tokens, pages, lengths, block_tables, active,
               placement, source_ids):
        with jax.profiler.TraceAnnotation(
                f"bench/call/decode@{self.device_index}"):
            out = super().decode(tokens, pages, lengths, block_tables,
                                 active, placement, source_ids)
        if self.drv is not None:
            self.drv.on_decode(lengths, active, out[2])
        return out

    def prefill_chunk(self, batch, pages, block_tables, placement,
                      source_ids):
        with jax.profiler.TraceAnnotation(
                f"bench/call/prefill@{self.device_index}"):
            out = super().prefill_chunk(batch, pages, block_tables,
                                        placement, source_ids)
        if self.drv is not None:
            self.drv.on_prefill(out[2])
        return out
