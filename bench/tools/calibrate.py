#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from.

For each seed: set up the program with that seed's weights, serve the
cell's mix for a window at its own load, free the program, and compare
the sample a run compares with the float32 reference: the gaps of the
served tokens below the reference's best (widest, mean, share not the
best). For the control seeds the float8 reference is read on the same
prompts and tokens too, and judged by the cell's own limits
(``control_correct``, which has to come out false).

    python3 bench/tools/calibrate.py --workload <cell> --seconds 51 \
        --seeds 1 2 3 ... --control-seeds 1 2 3

With ``--out <file>`` the rows are also written there as JSON.
"""
import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args()
    from harness.jaxcache import configure
    configure(ROOT)
    from harness.cell import Setup, verify
    from harness.readings import output_tokens_per_s
    from harness.spec import load_cell

    cell = load_cell(args.workload, ROOT)
    rows = []
    for seed in args.seeds:
        s = Setup(cell, seed)
        ctx = s.window(cell.traffic, args.seconds)
        s.free()
        del s
        gc.collect()
        ok, checks, readings, control_ok = verify(
            ctx, seed, control=seed in args.control_seeds)
        row = {"seed": seed, "correct": ok, "control_correct": control_ok,
               "output_tok_s": output_tokens_per_s(ctx),
               "steps": len(ctx.step_ends),
               "placements_adopted": ctx.placements_adopted,
               "gc_s": ctx.gc_s, **readings, "checks": {k: v for k, v, _ in checks}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
