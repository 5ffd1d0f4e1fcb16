"""A throwaway checkout root holding a tiny cell, for the CPU tests.

The tiny configuration has the shape of the benchmark's MoE configuration
(qk-norm GQA, 8 experts top-2) at widths a CPU runs in seconds, with the
Pallas kernels in interpret mode.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {
    "name": "tiny-moe", "source": "test", "model_type": "qwen3_moe",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "reduced": {}, "assumed": {},
    "engine": {"page_size": 8, "n_pages": 256, "max_batch": 4,
               "token_budget": 32, "chunk_buckets": [16, 32],
               "max_prefill_lanes": 2, "lane_buckets": [1, 2],
               "attn_backend": "pallas"},
    "check": {"limits": {"logit_gap_mean": 0.003}, "max_tokens": 2048,
              "min_served": 48},
}

DENSE = dict(MODEL, name="tiny-dense", model_type="qwen3", num_experts=0,
             intermediate_size=96)

CHAT = {"kind": "open_loop", "schedule_seed": 3, "rate_rps": 3.0,
        "arrival_cv": 2.0,
        "prompt_len": {"dist": "lognormal", "mu": 3.5, "sigma": 0.5,
                       "min": 8, "max": 64},
        "output_len": {"dist": "lognormal", "mu": 1.8, "sigma": 0.4,
                       "min": 2, "max": 12}}

BACKLOG = {"kind": "backlog", "schedule_seed": 4, "n_requests": 6,
           "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
           "output_len": {"dist": "uniform", "min": 4, "max": 10}}


def make_root(path: str, model=MODEL, mixes=None, extra_metrics=None):
    """A checkout root at ``path`` with one config, its mixes (one cell
    each, named ``tiny.<mix>``) and the benchmark's metric readers."""
    mixes = mixes or {"chat": CHAT}
    bench = os.path.join(path, "bench")
    os.makedirs(os.path.join(bench, "configs"), exist_ok=True)
    os.makedirs(os.path.join(bench, "traffic"), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"), dirs_exist_ok=True)
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(model, f)
    for name, mix in mixes.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [f"tiny.{n}" for n in mixes]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            m["workloads"] = cells
    spec["per_layer"] += extra_metrics or []
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "tiny"}]
    spec["workloads"] = [{"name": f"tiny.{n}", "config": "tiny",
                          "traffic": n, "chips": 1, "why": "test"}
                         for n in mixes]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return path


def run_tiny(root: str, cell: str = "tiny.chat", seed: int = 5,
             seconds: float = 3.0, traced: bool = False):
    import time
    from harness import cell as cell_run
    from harness.spec import load_cell
    return cell_run.run(load_cell(cell, root), seed, seconds, traced,
                        t_start=time.perf_counter(), require_chip=False)
