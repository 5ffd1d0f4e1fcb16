#!/usr/bin/env python3
"""Record a short traced window of a cell, for the trace reduction's test.

    python3 bench/tools/record_trace.py --workload <cell> --seed <n> \
        --out bench/data/<name>.xplane.pb

Serves the cell's mix for ``--seconds`` and traces a share of it as a run
with ``--trace 1`` does, then writes the trace file and the reduction's
summary (``<out>.json``) beside it.
"""
import argparse
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--span", type=float, nargs=2, default=(0.5, 0.08))
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from harness.jaxcache import configure
    configure(ROOT)
    from harness.cell import Setup
    from harness.spec import load_cell

    cell = load_cell(args.workload, ROOT)
    s = Setup(cell, args.seed)
    ctx = s.window(cell.traffic, args.seconds, traced=True,
                   keep_trace=args.out, trace_span=tuple(args.span))
    summary = dataclasses.asdict(ctx.trace)
    summary["calls"] = [c.kind for c in ctx.calls]
    with open(args.out + ".json", "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
