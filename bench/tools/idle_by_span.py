#!/usr/bin/env python3
"""Split a kept trace's device idle time by the program's spans.

    python3 bench/tools/idle_by_span.py <trace.xplane.pb> [...]

Reads traces written by ``bench/tools/record_trace.py`` and prints one
JSON line for each: the window, the first chip's idle seconds by the
innermost ``gimbal/`` span (``""``: no span), idle per engine step
(``engine_idle_ms``) and per round (``control_idle_ms``), the longest
gaps, the clock check (each readback's end after its chip's last program,
in ms) and the gaps between engine steps. Reading needs no chip.
"""
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main() -> None:
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    from harness import spans
    for path in sys.argv[1:]:
        got = spans.read(path)
        if got is None:
            print(json.dumps({"trace": path, "spans": None}))
            continue
        out = dataclasses.asdict(got)
        lags = out.pop("readback_lag_s")
        del out["step_gaps_s"]
        out["by_span"] = dict(sorted(out["by_span"].items(),
                                     key=lambda kv: -kv[1]))
        out.update(trace=path, engine_idle_ms=got.engine_idle_ms,
                   control_idle_ms=got.control_idle_ms,
                   unspanned_s=got.unspanned_s, readback_lag_ms=got.lag_ms(),
                   step_gap_ms=got.step_gap_ms(),
                   first_readback_lag_ms=1e3 * lags[0] if lags else None)
        print(json.dumps(out))


if __name__ == "__main__":
    main()
