"""The profiler's trace of a run, reduced to what the metrics read.

The JAX profiler writes an ``.xplane.pb``. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds every operation the chip
ran, their ``XLA Modules`` line every program. The host plane
``/host:CPU`` holds the harness's own spans (``bench/...``, written with
``jax.profiler.TraceAnnotation``) on the same clock.

Kernels are found under the names the trace shows today: the ragged
grouped matmul as ``%moe_gmm_ragged...`` custom calls, the paged decode
kernel as a ``tpu_custom_call`` whose first operand is the (lanes, slots)
int32 block table and whose result is the (lanes, heads, head_dim)
bfloat16 attention output.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
CALL_SPAN = "bench/call/"
SHORT_GAP_NS = 50_000      # shorter idle gaps are not attributed
SKEW_NS = 2_000_000        # host and device clocks agree to well within this

_PAGED_DECODE = re.compile(
    r"^%\S+ = bf16\[\d+,\d+,\d+\]\{[^}]*\} custom-call\(s32\[\d+,\d+\]")


def kernel_of(op_name: str) -> Optional[str]:
    if op_name.startswith("%moe_gmm_ragged"):
        return "moe_gmm"
    if 'custom_call_target="tpu_custom_call"' in op_name \
            and _PAGED_DECODE.match(op_name):
        return "paged_decode"
    return None


def _short(op_name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``; kernels by their kernel name."""
    k = kernel_of(op_name)
    if k:
        return k
    head = op_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head)


@dataclasses.dataclass
class Summary:
    window_s: float                  # length of the traced window
    busy_s: float                    # device busy time, mean over chips
    call_kinds: List[str]            # host call spans in order
    call_device_s: List[float]       # each call's program, device time
    kernel_s: Dict[str, float]       # kernel -> summed device time
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def _union(intervals: List[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {l.name: _events(l) for l in plane.lines}
            devices[int(plane.name.rsplit(":", 1)[1])] = (
                lines.get("XLA Ops", []), lines.get("XLA Modules", []))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span or no device plane")
    w0, w1 = windows[0][1], windows[0][2]
    busy, kernel_ns, op_ns = [], defaultdict(float), defaultdict(float)
    for ops, _ in devices.values():
        ops = [(n, s, e) for n, s, e in ops if e > w0 and s < w1]
        iv = _union(_clip([(s, e) for _, s, e in ops], w0, w1))
        busy.append(sum(e - s for s, e in iv))
        for n, s, e in ops:
            op_ns[_short(n)] += e - s
            k = kernel_of(n)
            if k:
                kernel_ns[k] += e - s
    # idle gaps of the first chip, named by the innermost harness span
    # that covers each gap's middle
    ops0 = devices[min(devices)][0]
    iv = _union(_clip([(s, e) for _, s, e in ops0], w0, w1))
    gaps, prev = [], w0
    for s, e in iv:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if w1 > prev:
        gaps.append((prev, w1))
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    idle = defaultdict(float)
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            idle["between_ops"] += e - s
            continue
        mid = 0.5 * (s + e)
        cover = [sp for sp in inner if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else "bench/cluster_loop"
        idle[name[len(SPAN_PREFIX):]] += e - s
    # each model call's program: the longest module on its chip that
    # starts between its host span and the next call's, both moved back by
    # the clocks' skew (call spans are ``bench/call/<kind>@<device>``;
    # every call's result is read back before the next call)
    calls = sorted((sp for sp in spans if sp[0].startswith(CALL_SPAN)),
                   key=lambda sp: sp[1])
    call_s = []
    for i, sp in enumerate(calls):
        dev = int(sp[0].rsplit("@", 1)[1])
        lo = sp[1] - SKEW_NS
        hi = calls[i + 1][1] - SKEW_NS if i + 1 < len(calls) else w1
        cands = [e - s for _, s, e in devices[dev][1] if lo <= s < hi]
        call_s.append(max(cands) * 1e-9 if cands else 0.0)
    top = lambda d: sorted(((k, v * 1e-9) for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   call_kinds=[sp[0][len(CALL_SPAN):].rsplit("@", 1)[0]
                               for sp in calls],
                   call_device_s=call_s,
                   kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
                   device_ops=top(op_ns), idle_gaps=top(idle))
