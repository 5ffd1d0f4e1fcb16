#!/usr/bin/env python3
"""Chip smoke run: Qwen3-30B-A3B layers at their published widths served
through the paged Gimbal path on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # only the four-chip replica phase

One chip: kernel parity at the model's widths, then 8 seeded requests
served to completion by ``PagedModelRunner`` -> two ``PagedRealEngine``s
sharing it -> ``serve_real_cluster`` (Gimbal scheduler and coordinator;
uncalibrated placement, so experts migrate live at full width).

``--chips 4``: four engines, each with a runner whose params and pages are
committed to its own chip, against the same four engines sharing one
runner on chip 0. Dispatch decisions and tokens must match.

JAX must find a TPU; there is no CPU path. No phase's exception is caught,
so the last line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
is printed only when every phase passed. The lines before it are checks
and counts, not benchmark numbers.
"""
import argparse
import gc
import json
import os
import sys
import time
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
N_REQUESTS = 8
PROMPT_LENS = (128, 1025)         # drawn from [lo, hi)
NEW_TOKENS = 32
ONE_CHIP_PAGES = 4096             # 16-token pages: 0.54 GB over 4 layers
FOUR_ENGINE_PAGES = 1024          # four pools must fit chip 0 beside weights

# Parity tolerances, fixed before any chip run.
# paged decode: outputs are bf16 (one ulp is 2^-8 relative). The kernel's
# online softmax and the XLA path's two-pass softmax round in different
# places, and the kernel may multiply its f32 softmax weights by V in
# single bf16 MXU passes (2^-9 relative per weight).
PAGED_DECODE_TOL = dict(rtol=1e-2, atol=1e-2)
# ragged GMM: both multiply the same bf16 operands exactly and accumulate
# in f32; only the order of the 2048-term sums differs.
GMM_TOL = dict(rtol=1e-3, atol=1e-3)
# source_expert_count: integer counts accumulated in f32 far below 2^24,
# so they must be exact.
# replica logits: bf16 matmul outputs (ulp 2^-8 relative, <= 1.6e-2 at
# |logit| <= 4). Replicas run one program on one chip kind and are
# expected bit-equal; the bound only admits a near-tie token flip.
LOGITS_TOL = dict(rtol=1e-2, atol=3e-2)


def engine_config(n_pages: int):
    """Page size 16; one prefill chunk bucket and two lane buckets, so the
    cold compile is three step programs."""
    from repro.serving import PagedEngineConfig
    return PagedEngineConfig(
        page_size=16, n_pages=n_pages, max_blocks_per_req=72,  # 1152 tokens
        max_batch=8, token_budget=1024, chunk_buckets=(512,),
        max_prefill_lanes=2, lane_buckets=(1, 2), attn_backend="pallas")


def cluster_kw():
    from repro.core.placement import PlacementConfig
    return dict(window_tokens=512,
                placement_cfg=PlacementConfig.uncalibrated())


def make_requests(cfg, seed: int):
    from repro.serving.paged_cluster import random_requests
    return random_requests(cfg.vocab_size, np.random.default_rng(seed),
                           N_REQUESTS, prompt_lens=PROMPT_LENS,
                           new_tokens=(NEW_TOKENS, NEW_TOKENS + 1))


def require(ok, what: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


def device_bytes(device, key: str) -> int:
    return int(device.memory_stats()[key])


def assert_kernels_compiled(runner) -> None:
    """No kernel of the decode step may have been lowered interpreted."""
    text = runner.lower_decode().compile().as_text()
    require("tpu_custom_call" in text, "decode step holds no TPU kernel")


def logit_tap_runner():
    """A ``PagedModelRunner`` that keeps every call's logits on the host,
    per engine (``source_ids`` carries the engine id) in call order."""
    from repro.serving import PagedModelRunner

    class LogitTap(PagedModelRunner):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.logits = defaultdict(list)

        def _keep(self, out, source_ids):
            self.logits[int(source_ids[0])].append(
                np.asarray(out[0], np.float32))
            return out

        def decode(self, tokens, pages, lengths, block_tables, active,
                   placement, source_ids):
            return self._keep(super().decode(
                tokens, pages, lengths, block_tables, active, placement,
                source_ids), source_ids)

        def prefill_chunk(self, batch, pages, block_tables, placement,
                          source_ids):
            return self._keep(super().prefill_chunk(
                batch, pages, block_tables, placement, source_ids),
                source_ids)

    return LogitTap


def check_close(name, got, want, tol) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, f"{name}: {got.shape} vs {want.shape}")
    require(np.isfinite(got).all(), f"{name}: non-finite output")
    np.testing.assert_allclose(got, want, err_msg=name, **tol)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    print(f"parity {name}: max |diff| {err:.3e} (tolerance {tol})")
    return err


def kernel_parity(cfg, ecfg, seed: int) -> None:
    """Each kernel of the serving path against its XLA reference, at the
    model's widths and the engine's shapes, on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.moe_dispatch import (gmm_blocked_xla, pick_row_block,
                                            ragged_dispatch)
    from repro.kernels.paged_decode import (paged_decode_pallas,
                                            paged_decode_xla)
    from repro.models.moe import expert_statistics

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    B, NB, ps = ecfg.max_batch, ecfg.max_blocks_per_req, ecfg.page_size
    P = ecfg.n_pages + 1
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jax.random.normal(next(keys), (B, Hq, hd), jnp.bfloat16)
    kp = jax.random.normal(next(keys), (P, ps, Hkv, hd), jnp.bfloat16)
    vp = jax.random.normal(next(keys), (P, ps, Hkv, hd), jnp.bfloat16)
    bt = jax.random.randint(next(keys), (B, NB), 1, P)
    ctx = jax.random.randint(next(keys), (B,), 1, NB * ps + 1)
    got = jax.jit(lambda *a: paged_decode_pallas(
        *a, interpret=ecfg.interpret))(q, kp, vp, bt, ctx)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_decode_xla)(q, kp, vp, bt, ctx)
    check_close("paged_decode_pallas vs paged_decode_xla", got, want,
                PAGED_DECODE_TOL)

    D, E, K, F = cfg.d_model, cfg.moe.n_experts, cfg.moe.top_k, \
        cfg.moe.d_expert
    w = jax.random.normal(next(keys), (E, D, F), jnp.bfloat16) \
        / float(np.sqrt(D))
    prefill_tokens = ecfg.lane_buckets[-1] * ecfg.chunk_buckets[-1]
    for T in (ecfg.max_batch, prefill_tokens):        # decode, prefill
        x = jax.random.normal(next(keys), (T, D), jnp.bfloat16)
        ids = jax.lax.top_k(jax.random.normal(next(keys), (T, E)), K)[1]
        nb = pick_row_block(T * K, E)
        disp = ragged_dispatch(x, ids, E, row_block=nb)
        got = ops.moe_gmm_ragged(disp.xs, w, disp.tile_expert,
                                 disp.group_sizes, disp.padded_offsets,
                                 n_block=nb)
        want = jax.jit(gmm_blocked_xla, static_argnames="row_block")(
            disp.xs, w, disp.tile_expert, row_block=nb)
        check_close(f"moe_gmm_ragged vs gmm_blocked_xla (T={T}, "
                    f"row block {nb})", got, want, GMM_TOL)

    T = prefill_tokens
    ids = jax.lax.top_k(jax.random.normal(next(keys), (T, E)), K)[1]
    src = jax.random.randint(next(keys), (T,), 0, 2)
    keep = jax.random.bernoulli(next(keys), 0.9, (T,))
    flat = ids.reshape(T * K, 1)
    b, a = ops.source_expert_count(flat, jnp.repeat(src, K), n_experts=E,
                                   n_sources=2)
    ref = expert_statistics(ids, E, src, 2)
    _, a_masked = ops.source_expert_count(
        flat, jnp.where(jnp.repeat(keep, K), jnp.repeat(src, K), -1),
        n_experts=E, n_sources=2)
    ref_masked = expert_statistics(ids, E, src, 2, token_mask=keep)
    exact = dict(rtol=0, atol=0)
    check_close("source_expert_count B vs expert_statistics", b,
                ref["expert_counts"], exact)
    check_close("source_expert_count A vs expert_statistics", a,
                ref["source_expert"], exact)
    check_close("source_expert_count masked A vs expert_statistics",
                a_masked, ref_masked["source_expert"], exact)


def serve_one_chip(cfg, ecfg, seed: int) -> None:
    """Two engines sharing one runner serve the seeded requests."""
    import jax

    from repro.serving.paged_cluster import (init_params, serve_paged,
                                             served_ok)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    runner = logit_tap_runner()(cfg, init_params(cfg, seed), ecfg,
                                n_sources=2)
    runner.warmup()
    assert_kernels_compiled(runner)
    compile_s = time.perf_counter() - t0

    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    res, engines = serve_paged(reqs, [runner, runner], ecfg, **cluster_kw())
    serve_s = time.perf_counter() - t0

    ok = served_ok(reqs)
    require(len(ok) == len(reqs), f"errors: {res.signals['errors']}")
    require(all(len(r.output_tokens) == NEW_TOKENS and
                all(0 <= t < cfg.vocab_size for t in r.output_tokens)
                for r in reqs), "output tokens of the wrong count or range")
    calls = [x for per in runner.logits.values() for x in per]
    require(calls and all(np.isfinite(x).all() for x in calls),
            "non-finite logits")
    require(res.signals["migrations"] >= 1, "no expert migration ran")
    print(f"device: {dev.device_kind}")
    print(f"compile {compile_s:.1f} s (params, warmup of every step "
          f"shape), serve {serve_s:.1f} s")
    print(f"served {len(ok)}/{len(reqs)} requests, "
          f"{sum(r.prompt_len for r in reqs)} prompt tokens, "
          f"{sum(len(r.output_tokens) for r in reqs)} output tokens, "
          f"{len(calls)} model calls")
    print(f"expert migrations: {res.signals['migrations']} "
          f"({res.signals['expert_moves']} expert moves)")
    print(f"dispatch decisions: {res.signals['decisions']}")
    print(f"peak_bytes_in_use: {device_bytes(dev, 'peak_bytes_in_use')}")


def compare_logits(a, b) -> float:
    """Replica logits, call by call per engine, up to and including each
    engine's first call whose argmax rows differ (after a flipped token
    the two streams no longer see the same inputs)."""
    worst = 0.0
    require(sorted(a) == sorted(b), "engines differ between the arms")
    for eid in a:
        for x, y in zip(a[eid], b[eid]):
            np.testing.assert_allclose(x, y, err_msg=f"engine {eid}",
                                       **LOGITS_TOL)
            worst = max(worst, float(np.max(np.abs(x - y))))
            if (x.argmax(-1) != y.argmax(-1)).any():
                break
    return worst


def serve_replicas(cfg, ecfg, seed: int, devices, per_chip: bool):
    """Four engines: each on its own chip's runner, or all sharing one
    runner on the first chip. Returns what the two arms must agree on."""
    import jax

    from repro.serving.paged_cluster import (init_params, serve_paged,
                                             served_ok)
    Tap = logit_tap_runner()
    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    if per_chip:
        runners = [Tap(cfg, params, ecfg, n_sources=len(devices), device=d)
                   for d in devices]
    else:
        runners = [Tap(cfg, params, ecfg, n_sources=len(devices),
                       device=devices[0])] * len(devices)
    del params
    distinct = list({id(r): r for r in runners}.values())
    with ThreadPoolExecutor(len(distinct)) as ex:   # compile per chip
        list(ex.map(lambda r: r.warmup(), distinct))
    compile_s = time.perf_counter() - t0
    in_use = [device_bytes(d, "bytes_in_use") for d in devices]

    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    res, engines = serve_paged(reqs, runners, ecfg, **cluster_kw())
    serve_s = time.perf_counter() - t0
    for e in engines:          # steps ran where each runner's state lives
        where = {d for x in jax.tree.leaves((e.pages, e.runner.params))
                 for d in x.devices()}
        require(where == {e.runner.device},
                f"engine {e.engine_id} holds state on {where}")
    require(len(served_ok(reqs)) == len(reqs),
            f"errors: {res.signals['errors']}")
    logits = {}
    for r in distinct:
        logits.update(r.logits)
    arm = "one runner per chip" if per_chip else "one shared runner"
    print(f"{arm}: compile {compile_s:.1f} s, serve {serve_s:.1f} s, "
          f"migrations {res.signals['migrations']}, "
          f"bytes_in_use per device {in_use}")
    return dict(engine_of={r.req_id: r.engine_id for r in reqs},
                tokens={r.req_id: list(r.output_tokens) for r in reqs},
                decisions=dict(res.signals["decisions"]), logits=logits,
                in_use=in_use, param_bytes=param_bytes)


def replicas_four_chips(cfg, ecfg, seed: int, devices) -> None:
    a = serve_replicas(cfg, ecfg, seed, devices, per_chip=True)
    gc.collect()                     # engines hold cycles: free chip 0
    b = serve_replicas(cfg, ecfg, seed, devices, per_chip=False)
    require(all(u >= a["param_bytes"] for u in a["in_use"]),
            f"weights not resident on every chip: {a['in_use']}")
    require(a["engine_of"] == b["engine_of"], "dispatch decisions differ")
    require(a["decisions"] == b["decisions"],
            f"decision counts differ: {a['decisions']} vs {b['decisions']}")
    worst = compare_logits(a["logits"], b["logits"])
    same = a["tokens"] == b["tokens"]
    print(f"dispatch decisions match: {a['decisions']}")
    print(f"per-request engines: {a['engine_of']}")
    print(f"tokens match: {same}; logits max |diff| {worst:.3e} "
          f"(tolerance {LOGITS_TOL})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the one-replica-per-chip phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{devices[0].platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.qwen3_moe_30b_a3b import one_chip_config
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache(ROOT)}")

    cfg = one_chip_config()
    if args.chips == 4:
        replicas_four_chips(cfg, engine_config(FOUR_ENGINE_PAGES), SEED,
                            devices[:4])
    else:
        ecfg = engine_config(ONE_CHIP_PAGES)
        kernel_parity(cfg, ecfg, SEED)
        serve_one_chip(cfg, ecfg, SEED)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
