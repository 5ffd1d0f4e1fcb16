"""A mix and a metric are added as files plus entries in BENCHMARK.json,
and the harness finds them by name with no other change."""
import json
import os

import tiny

from harness.spec import load_cell

THROWAWAY_READER = '''"""Requests finished in the window (a throwaway metric)."""


def read(ctx):
    return sum(1 for r in ctx.records if r.req.finish_time >= 0)
'''


def test_throwaway_mix_and_metric(tmp_path):
    mix = dict(tiny.BACKLOG, n_requests=3, schedule_seed=11)
    metric = {"name": "finished_count", "unit": "count",
              "better": "higher", "source": "program_counter",
              "layer": "engine and StepPlanner", "moves": "output_tok_s",
              "workloads": ["tiny.throwaway"]}
    root = tiny.make_root(str(tmp_path), model=tiny.DENSE,
                          mixes={"throwaway": mix}, extra_metrics=[metric])
    with open(os.path.join(root, "bench", "metrics",
                           "finished_count.py"), "w") as f:
        f.write(THROWAWAY_READER)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["end_to_end"] = [m for m in spec["end_to_end"]
                          if m["name"] in ("output_tok_s", "setup_s")]
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = load_cell("tiny.throwaway", root)
    assert cell.traffic == mix
    assert [m.name for m in cell.end_to_end] == ["output_tok_s", "setup_s"]
    assert "finished_count" in [m.name for m in cell.per_layer]

    ctx_reader = next(m for m in cell.per_layer
                      if m.name == "finished_count").read
    from harness.cell import Setup
    s = Setup(cell, seed=1, require_chip=False)
    ctx = s.window(cell.traffic, 2.0)
    s.free()
    assert ctx_reader(ctx) == 3
    res = tiny.run_tiny(root, cell="tiny.throwaway", seconds=2.0)
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["attempted"] == 3 and res["correct"]
    assert list(res)[-1] == "checks"
