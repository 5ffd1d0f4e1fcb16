"""A block architecture is added as files: its module in ``bench/arch/``
and a configuration naming its ``model_type``. The harness takes the
program's config, the weight leaves, the reference and the arithmetic of
the costs from that module with no other change. The Qwen3 module gives
the numbers the harness gave before it was split out."""
import hashlib
import json
import os
import re

import jax
import numpy as np
import pytest
import tiny

from harness import check, costs, readings
from harness import weights as W
from harness.driver import CallRecord, ReqRecord
from harness.readings import Context
from harness.spec import load_cell
from harness.trace import Summary
from harness.traffic import Arrival
from repro.serving.request import Request

# A block whose first layer differs from the rest: latent attention in
# every layer, a dense feed-forward in layer 0 and experts after it. Its
# arithmetic is a stand-in with round numbers, so that a test can tell
# which module counted.
LEAD_ARCH = '''"""A throwaway block: a dense first layer, then experts."""
import numpy as np

from harness.weights import BF16, F32, Leaf

CALLS = []


def program_config(m):
    raise NotImplementedError("the program has no leading dense layer")


def leaves(m):
    D, V, H, hd = (m["hidden_size"], m["vocab_size"],
                   m["num_attention_heads"], m["head_dim"])
    r, E, F, I = (m["kv_lora_rank"], m["num_experts"],
                  m["moe_intermediate_size"], m["intermediate_size"])
    first, rest = (0,), tuple(range(1, m["num_hidden_layers"]))
    out = [Leaf("embed/embedding", (V, D), BF16, "normal", 0.02),
           Leaf("embed/lm_head", (D, V), BF16, "trunc", 0.1),
           Leaf("final_norm", (D,), F32, "normal", 0.1)]
    for stack, layers in (("blocks/first", first), ("blocks/pos0", rest)):
        out += [Leaf("attn/wq", (D, H * hd), BF16, "trunc", 0.1, layers,
                     stack),
                Leaf("attn/wkv_a", (D, r), BF16, "trunc", 0.1, layers,
                     stack),
                Leaf("attn/wkv_b", (r, 2 * H * hd), BF16, "trunc", 0.1,
                     layers, stack)]
    return out + [
        Leaf("mlp/w_gate", (D, I), BF16, "trunc", 0.1, first,
             "blocks/first"),
        Leaf("mlp/w_down", (I, D), BF16, "trunc", 0.1, first,
             "blocks/first"),
        Leaf("moe/router", (D, E), F32, "trunc", 0.1, rest, "blocks/pos0"),
        Leaf("moe/w_gate", (E, D, F), BF16, "trunc", 0.1, rest,
             "blocks/pos0")]


def served_logits(m, seed, prompts, outputs, control=False):
    CALLS.append((seed, len(prompts)))
    out = {"best": [np.full(len(o), 2.0) for o in outputs],
           "served": [np.full(len(o), 1.5) for o in outputs]}
    if control:
        out["control"] = [np.zeros(len(o)) for o in outputs]
    return out


def token_flops(m, layer):
    return 1000.0 if layer == 0 else 10.0


def attention_flops(m, layer, ctx):
    return 3.0 * ctx


def paged_decode_cost(ctx_lens, m, itemsize=2):
    n = float(np.sum(ctx_lens))
    return 7.0 * n, 5.0 * n
'''

LEAD = dict(tiny.MODEL, name="tiny-lead", model_type="tiny_lead",
            num_hidden_layers=3, kv_lora_rank=8)


@pytest.fixture(scope="module")
def lead_root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("lead")), model=LEAD)
    with open(os.path.join(root, "bench", "arch", "tiny_lead.py"), "w") as f:
        f.write(LEAD_ARCH)
    return root


@pytest.fixture(scope="module")
def lead_cell(lead_root):
    return load_cell("tiny.chat", lead_root)


def test_module_found_by_model_type(lead_root, lead_cell):
    assert lead_cell.arch.__file__ == os.path.join(
        lead_root, "bench", "arch", "tiny_lead.py")
    from harness.cell import Setup
    with pytest.raises(NotImplementedError, match="leading dense"):
        Setup(lead_cell, seed=1, require_chip=False)


def test_first_layer_stacked_apart(lead_cell):
    m, seed = lead_cell.config, 2**40 + 3
    tree = W.make_params(m, seed)
    blocks = tree["blocks"]
    assert blocks["first"]["mlp"]["w_gate"].shape == (1, 64, 96)
    assert blocks["pos0"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    assert "moe" not in blocks["first"] and "mlp" not in blocks["pos0"]
    layered = [lf for lf in lead_cell.arch.leaves(m) if lf.layers]
    for layer in range(m["num_hidden_layers"]):
        one = W.make_layer(m, seed, layer)
        assert ("mlp/w_gate" in one) == (layer == 0)
        assert ("moe/w_gate" in one) == (layer > 0)
        for lf in layered:
            if layer not in lf.layers:
                continue
            leaf = tree
            for k in (lf.stack + "/" + lf.path).split("/"):
                leaf = leaf[k]
            np.testing.assert_array_equal(
                np.asarray(leaf[lf.layers.index(layer)]),
                np.asarray(one.pop(lf.path)))
        assert not one
    # the two stacks of one leaf path draw different numbers
    assert not np.array_equal(np.asarray(blocks["first"]["attn"]["wq"][0]),
                              np.asarray(blocks["pos0"]["attn"]["wq"][0]))


def test_check_asks_the_modules_reference(lead_cell):
    recs = []
    for i, (prompt, out) in enumerate(((30, 4), (20, 3))):
        r = ReqRecord(Arrival(i, 0.0, prompt, out),
                      Request(i, prompt, out, 0.0,
                              prompt_tokens=[1] * prompt))
        r.req.output_tokens = list(range(out))
        recs.append(r)
    arch = lead_cell.arch
    arch.CALLS.clear()
    gap, ctrl = check.logit_gaps(lead_cell.config, 7, recs, control=True)
    assert arch.CALLS == [(7, 2)]
    np.testing.assert_array_equal(gap, np.full(7, 0.5))
    np.testing.assert_array_equal(ctrl, np.full(7, 2.0))


def test_readings_use_the_modules_arithmetic(lead_cell):
    m = lead_cell.config
    peak = costs.peaks("TPU v5 lite")
    calls = [CallRecord("decode", ctx=np.array([10, 20])),
             CallRecord("prefill", lanes=[(0, 4, True)])]
    summary = Summary(window_s=1.0, busy_s=0.5,
                      call_kinds=["decode", "prefill"],
                      call_device_s=[0.25, 0.5],
                      kernel_s={"paged_decode": 0.125}, device_ops=[],
                      idle_gaps=[])
    ctx = Context(model=m, peak=peak, setup_s=1.0, t0=0.0, t1=1.0,
                  records=[], kv_peak=0.0, decode_lanes=[2], calls=calls,
                  trace=summary)
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    # three layers, the first of 1000 weight FLOPs a token, the others 10;
    # attention 3 a context token
    decode = (1020 + 9 * 10) + (1020 + 9 * 20) + 2 * head
    prefill = 4 * 1020 + 3 * 3 * (1 + 2 + 3 + 4) + head
    assert readings.step_mfu(ctx) == pytest.approx(
        100.0 * (decode + prefill) / (0.75 * peak["flops"]))
    ideal = 3 * costs.min_time_s(7.0 * 30, 5.0 * 30, peak)
    assert readings.paged_decode_roofline(ctx) == pytest.approx(
        100.0 * ideal / 0.125)


def test_unknown_model_type_fails_at_load_naming_the_path(tmp_path):
    root = tiny.make_root(str(tmp_path),
                          model=dict(tiny.MODEL, model_type="no_such_arch"))
    path = os.path.join(root, "bench", "arch", "no_such_arch.py")
    with pytest.raises(FileNotFoundError, match=re.escape(path)):
        load_cell("tiny.chat", root)


# What the harness counted and drew for Qwen3 before its arithmetic and
# leaves moved into ``bench/arch/qwen3_moe.py``: the costs of the reasoning
# cell's configuration, and a digest of each weight leaf of the tiny
# configurations, drawn on the CPU.
CONFIG = os.path.join(tiny.BENCH, "configs", "qwen3-30b-a3b-4l.json")
CTX = [[1], [17, 300], [553, 1024, 2047, 4096],
       list(range(100, 3300, 100))]
LANES = [[(0, 128, True)], [(0, 512, False), (512, 41, True)],
         [(1000, 128, False), (0, 7, True), (300, 512, True),
          (2000, 48, False)]]
PINNED_COSTS = {
    "decode_flops": [1077477376.0, 2175598592.0, 4815585280.0,
                     37937479680.0],
    "prefill_flops": [59413889024.0, 262321537024.0, 351499714560.0],
    "paged_decode_cost": [(16384.0, 18432.0), (5193728.0, 681984.0),
                          (126484480.0, 15876096.0),
                          (865075200.0, 108658688.0)],
}
PINNED_WEIGHTS = {
    "moe": {
        "['blocks']['pos0']['attn']['k_norm']":
            "500ca2d4ec7e3e18",
        "['blocks']['pos0']['attn']['q_norm']":
            "a725b5ff43f88233",
        "['blocks']['pos0']['attn']['wk']":
            "b53bca624829f654",
        "['blocks']['pos0']['attn']['wo']":
            "e7a2eec443b157bb",
        "['blocks']['pos0']['attn']['wq']":
            "348a8ec18fa5d245",
        "['blocks']['pos0']['attn']['wv']":
            "0ef98351c0acc807",
        "['blocks']['pos0']['ln_attn']":
            "bd14c366b9fc2bab",
        "['blocks']['pos0']['ln_ffn']":
            "4a1981b60b33924c",
        "['blocks']['pos0']['moe']['router']":
            "6d1e7dff05726682",
        "['blocks']['pos0']['moe']['w_down']":
            "04ea314c1de554ab",
        "['blocks']['pos0']['moe']['w_gate']":
            "5361fa1a8d4c89e2",
        "['blocks']['pos0']['moe']['w_up']":
            "373cd589b1763b00",
        "['embed']['embedding']":
            "bd96b95a25f3dccb",
        "['embed']['lm_head']":
            "d56a52484f75c2c9",
        "['final_norm']":
            "ed4320248665a997",
    },
    "dense": {
        "['blocks']['pos0']['attn']['k_norm']":
            "500ca2d4ec7e3e18",
        "['blocks']['pos0']['attn']['q_norm']":
            "a725b5ff43f88233",
        "['blocks']['pos0']['attn']['wk']":
            "b53bca624829f654",
        "['blocks']['pos0']['attn']['wo']":
            "e7a2eec443b157bb",
        "['blocks']['pos0']['attn']['wq']":
            "348a8ec18fa5d245",
        "['blocks']['pos0']['attn']['wv']":
            "0ef98351c0acc807",
        "['blocks']['pos0']['ln_attn']":
            "bd14c366b9fc2bab",
        "['blocks']['pos0']['ln_ffn']":
            "4a1981b60b33924c",
        "['blocks']['pos0']['mlp']['w_down']":
            "705479493d97e2ad",
        "['blocks']['pos0']['mlp']['w_gate']":
            "ee804619daef2819",
        "['blocks']['pos0']['mlp']['w_up']":
            "803a9f121e2e23a1",
        "['embed']['embedding']":
            "bd96b95a25f3dccb",
        "['embed']['lm_head']":
            "d56a52484f75c2c9",
        "['final_norm']":
            "ed4320248665a997",
    },
}


@pytest.mark.parametrize("fn", sorted(PINNED_COSTS))
def test_qwen3_costs_as_before(fn):
    with open(CONFIG) as f:
        m = json.load(f)
    args = LANES if fn == "prefill_flops" else CTX
    got = [getattr(costs, fn)(a, m) for a in args]
    assert [tuple(g) if isinstance(g, tuple) else g for g in got] == \
        PINNED_COSTS[fn]


def _digests(tree):
    return {jax.tree_util.keystr(p): hashlib.sha256(
        f"{x.dtype}{x.shape}".encode() + np.asarray(x).tobytes())
        .hexdigest()[:16]
        for p, x in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("name", ["moe", "dense"])
def test_qwen3_weights_as_before(name):
    model = tiny.MODEL if name == "moe" else tiny.DENSE
    assert _digests(W.make_params(model, 9)) == PINNED_WEIGHTS[name]
