"""The float32 reference against the program, at the tiny size.

With the program's weights and pages in float32, its paged serving path
(chunked prefill, the Pallas paged decode kernel in interpret mode, the
router's top-k and the ragged grouped matmul, qk-norm, RoPE, embedding and
head) must serve exactly the reference's best token at every position.
The weight maker's per-layer leaves equal the whole tree's.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

from arch.qwen3_moe import program_config
from harness import weights as W
from reference.qwen3 import served_logits


def test_layer_leaves_equal_the_whole_tree():
    m = tiny.MODEL
    seed = 2**33 + 5
    tree = W.make_params(m, seed)
    pos0 = tree["blocks"]["pos0"]
    for layer in range(m["num_hidden_layers"]):
        for path, v in W.make_layer(m, seed, layer).items():
            leaf = pos0
            for k in path.split("/"):
                leaf = leaf[k]
            np.testing.assert_array_equal(np.asarray(leaf[layer]),
                                          np.asarray(v))
    for path, v in W.make_global(m, seed).items():
        leaf = tree
        for k in path.split("/"):
            leaf = leaf[k]
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(v))


@pytest.mark.parametrize("model", [tiny.MODEL, tiny.DENSE],
                         ids=["moe", "dense"])
def test_paged_path_in_float32_serves_the_reference_best(model):
    from repro.serving.paged_engine import (PagedEngineConfig,
                                            PagedModelRunner,
                                            PagedRealEngine)
    from repro.serving.request import Request
    cfg = dataclasses.replace(program_config(model), dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          W.make_params(model, 9))
    ecfg = PagedEngineConfig(page_size=8, n_pages=64, max_blocks_per_req=8,
                             max_batch=2, token_budget=16,
                             chunk_buckets=(16,), max_prefill_lanes=2,
                             lane_buckets=(1, 2), attn_backend="pallas",
                             interpret=True)
    runner = PagedModelRunner(cfg, params, ecfg, n_sources=1)
    eng = PagedRealEngine(0, cfg, None, ecfg, runner=runner, n_sources=1)
    rng = np.random.default_rng(0)
    reqs = []
    for i, n in enumerate((21, 9)):              # several prefill chunks
        toks = rng.integers(0, model["vocab_size"], n).tolist()
        reqs.append(Request(req_id=i, prompt_len=n, max_new_tokens=6,
                            arrival_time=0.0, prompt_tokens=toks))
        eng.enqueue(reqs[-1], 0.0)
    with jax.default_matmul_precision("highest"):
        while eng.has_work:
            eng.step(0.0)
    ref = served_logits(model, 9, [r.prompt_tokens for r in reqs],
                        [r.output_tokens for r in reqs])
    gap = np.concatenate(ref["best"]) - np.concatenate(ref["served"])
    assert gap.max() < 1e-4
