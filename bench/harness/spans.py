"""The chip's idle time in a traced window, split by the program's spans.

The serving path writes its own spans (``gimbal/...``, named in
``repro.serving.spans``) on the profiler's host plane, on the clock of the
device planes. The serving thread's time is cut into pieces, each under
its innermost span. Each idle gap of the first chip inside
``bench/window`` that lasts at least ``SHORT_GAP_NS`` is split over the
pieces it overlaps: what the host was doing, and for how long, while the
chip waited. A gap usually runs from one step program's end to the next
one's start, across several host phases, so it is split rather than
named after one of them. From that:

- idle seconds by innermost span (``""`` where no span is open);
- ``engine_idle_ms``: idle time under ``gimbal/engine_step`` and its
  children, per engine step that starts in the window;
- ``control_idle_ms``: idle time under ``gimbal/round`` but outside every
  engine step (dispatch, traces, statistics, Algorithm 2), per round;
- the clock check: for each ``gimbal/readback`` span, its end minus the end
  of the last program on its engine's chip before it. The readback waits
  for that program, so a small lag says the host's spans and the chip
  share a clock; a large one is a host stall after the program ended.

A trace of a program that writes no ``gimbal/`` spans reads as None.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

from harness.trace import SHORT_GAP_NS, WINDOW_SPAN, _clip, _union

try:
    from repro.serving import spans as names
except ImportError:             # a program that writes no spans
    names = None


class Span(NamedTuple):
    name: str
    start: int                  # ns
    end: int
    args: dict


class Piece(NamedTuple):
    """A stretch of the serving thread's time under one innermost span."""
    start: int
    end: int
    name: str
    engine: Optional[int]       # the engine step it lies in, if any
    in_round: bool


@dataclasses.dataclass
class SpanIdle:
    window_s: float
    idle_s: float                  # the first chip's idle time
    short_s: float                 # of it, in gaps shorter than SHORT_GAP_NS
    by_span: Dict[str, float]      # innermost span -> idle seconds
    engine_s: float                # idle under an engine step
    control_s: float               # idle in a round, outside engine steps
    steps: int                     # engine steps that start in the window
    rounds: int                    # rounds that start in the window
    readback_lag_s: List[float]    # clock check, one per readback
    step_gaps_s: List[float]       # between starts of engine steps
    longest: List[Tuple[float, str]]   # longest gaps, by their main span

    @property
    def unspanned_s(self) -> float:
        return self.by_span.get("", 0.0)

    @property
    def engine_idle_ms(self) -> Optional[float]:
        return 1e3 * self.engine_s / self.steps if self.steps else None

    @property
    def control_idle_ms(self) -> Optional[float]:
        return 1e3 * self.control_s / self.rounds if self.rounds else None

    def lag_ms(self) -> Dict[str, float]:
        return _p50_max_ms(self.readback_lag_s)

    def step_gap_ms(self) -> Dict[str, float]:
        return _p50_max_ms(self.step_gaps_s)


def _p50_max_ms(values: List[float]) -> Dict[str, float]:
    if not values:
        return {}
    return {"p50": 1e3 * statistics.median(values),
            "max": 1e3 * max(values), "n": len(values)}


def pieces(spans: List[Span]) -> List[Piece]:
    """Cut the time the spans cover into pieces, each under its innermost
    span, in order. The spans nest, as spans of one thread do."""
    out: List[Piece] = []
    stack: List[Tuple[Span, Optional[int], bool]] = []
    t = 0

    def cut(until: int) -> None:
        nonlocal t
        if stack and until > t:
            sp, engine, in_round = stack[-1]
            out.append(Piece(t, until, sp.name, engine, in_round))
        t = max(t, until)

    def close(before: float) -> None:
        while stack and stack[-1][0].end <= before:
            cut(stack[-1][0].end)
            stack.pop()

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        close(sp.start)
        cut(sp.start)
        engine, in_round = (stack[-1][1], stack[-1][2]) if stack \
            else (None, False)
        if sp.name == names.ENGINE_STEP:
            engine = int(sp.args.get("engine", 0))
        stack.append((sp, engine, in_round or sp.name == names.ROUND))
    close(float("inf"))
    return out


def read(path: str) -> Optional[SpanIdle]:
    from jax.profiler import ProfileData
    if names is None:
        return None
    pd = ProfileData.from_file(path)
    window, spans, devices = None, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {l.name: l for l in plane.lines}
            devices[int(plane.name.rsplit(":", 1)[1])] = tuple(
                [(e.start_ns, e.start_ns + e.duration_ns)
                 for e in lines[k].events] if k in lines else []
                for k in ("XLA Ops", "XLA Modules"))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(names.PREFIX):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          {k: v for k, v in e.stats}))
    if window is None or not devices:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span or no device plane")
    if not spans:
        return None
    w0, w1 = window
    chips = sorted(devices)
    iv = _union(_clip(devices[chips[0]][0], w0, w1))
    gaps, prev = [], w0
    for s, e in iv + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)

    cut = pieces(spans)
    by_span: Dict[str, float] = defaultdict(float)
    longest = []
    short = engine = control = 0.0
    j = 0
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:
            short += b - a
            continue
        while j < len(cut) and cut[j].end <= a:
            j += 1
        share: Dict[str, float] = defaultdict(float)
        k = j
        while k < len(cut) and cut[k].start < b:
            p = cut[k]
            t = min(b, p.end) - max(a, p.start)
            share[p.name] += t
            if p.engine is not None:
                engine += t
            elif p.in_round:
                control += t
            k += 1
        share[""] += (b - a) - sum(share.values())
        for name, t in share.items():
            if t > 0:
                by_span[name] += t * 1e-9
        longest.append(((b - a) * 1e-9, max(share, key=share.get)))

    # a readback has no children, so it is one piece
    modules = {c: sorted(end for _, end in devices[c][1]) for c in chips}
    lags = []
    for p in cut:
        if p.name == names.READBACK and w0 <= p.start < w1:
            ends = modules[chips[(p.engine or 0) % len(chips)]]
            i = bisect.bisect_right(ends, p.end) - 1
            if i >= 0:
                lags.append((p.end - ends[i]) * 1e-9)

    step_starts = sorted(sp.start for sp in spans
                         if sp.name == names.ENGINE_STEP
                         and w0 <= sp.start < w1)
    return SpanIdle(window_s=(w1 - w0) * 1e-9,
                    idle_s=(w1 - w0 - sum(e - s for s, e in iv)) * 1e-9,
                    short_s=short * 1e-9, by_span=dict(by_span),
                    engine_s=engine * 1e-9, control_s=control * 1e-9,
                    steps=len(step_starts),
                    rounds=sum(1 for sp in spans if sp.name == names.ROUND
                               and w0 <= sp.start < w1),
                    readback_lag_s=lags,
                    step_gaps_s=[(b - a) * 1e-9 for a, b in
                                 zip(step_starts, step_starts[1:])],
                    longest=sorted(longest, reverse=True)[:5])
