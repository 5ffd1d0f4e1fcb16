#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cell, its configuration, its traffic
mix and its metrics are found by name from ``BENCHMARK.json``. The run
needs as many TPU chips as the cell asks for, and fails without them.
The last line of standard output is the result as one JSON object; the
numbers the correctness check compared, each with its limit, are the last
lines of standard error and the ``checks`` key of the result.

JAX's compilation cache is ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness.jaxcache import configure
    configure(ROOT)

    from harness import cell as cell_run
    from harness.spec import load_cell
    cell = load_cell(args.workload, ROOT)
    result = cell_run.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
