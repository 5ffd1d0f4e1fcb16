"""Gimbal over the paged real data plane (the production-shaped runtime).

Two PagedRealEngine DP replicas serve a tiny MoE model end to end:
physical paged KV with block tables, chunked prefill under a per-step token
budget, batched block-table decode, preemption that reclaims pages and
recomputes, and truthful trace signals feeding Algorithm 1. The Gimbal
coordinator consumes REAL router statistics and migrates experts live.

With ``--shared-prefix`` every request carries a common 24-token system
prompt and the engines run the ``SharedPagedAllocator`` (ref-counted pages
+ radix-tree token-granular prefix cache + copy-on-write); the run is
repeated with sharing off to show pages saved, prefill skipped and the
TTFT delta — with bit-identical outputs. Under sharing the engines also
ship radix prefix summaries on their traces, so Algorithm 1's
prefix-affinity credit routes repeated prefixes to the engine already
holding them (the ``affinity`` dispatch count in the report).

With ``--chaos`` the same stream is served twice — fault-free, then under
a deterministic :class:`~repro.ft.faults.FaultPlan` that crashes engine 1
mid-run (KV pool lost) and recovers it later: the health monitor fences
the silent engine, its residents re-dispatch with emitted tokens folded
into resume prompts, and the run proves every request completes bit-exact
vs the fault-free pass.

PYTHONPATH=src python examples/serve_moe_paged.py [--shared-prefix|--chaos]
"""
import dataclasses

import numpy as np

from repro.configs import get_smoke_config
from repro.serving import PagedEngineConfig, PagedModelRunner
from repro.serving.paged_cluster import (init_params, random_requests,
                                         report, serve_paged, served_ok)


def _requests(cfg, rng, system=None):
    return random_requests(cfg.vocab_size, rng, 12, system=system)


def _serve(runner, ecfg, reqs, **cluster_kw):
    return serve_paged(reqs, [runner, runner], ecfg, window_tokens=300,
                       **cluster_kw)


def _chaos(cfg, runner, ecfg):
    """Crash engine 1 mid-run, recover it, prove nothing was lost."""
    from repro.ft import FaultEvent, FaultPlan
    from repro.ft.health import HealthConfig

    mk = lambda: _requests(cfg, np.random.default_rng(0))
    _serve(runner, ecfg, base := mk())
    want = {r.req_id: r.output_tokens for r in base}

    plan = FaultPlan(events=(FaultEvent("crash", 1, 10),
                             FaultEvent("recover", 1, 22)))
    res, engines = _serve(
        runner, ecfg, reqs := mk(), fault_plan=plan,
        health_cfg=HealthConfig(trace_timeout_s=0.3))

    print("== chaos: engine 1 crashes at round 10, recovers at 22 ==")
    report(reqs, engines, res)
    print(f"health events: {res.signals['health_events']}")
    print(f"engine failures: {res.signals['n_failures']}  "
          f"requests recovered: {res.signals['recovered_requests']}  "
          f"recompute tokens: {res.signals['recovery_recompute_tokens']}")
    exact = all(r.full_output_tokens == want[r.req_id] for r in reqs)
    ok = {r.req_id for r in served_ok(reqs)}
    lost = [r.req_id for r in reqs if r.req_id not in ok]
    print(f"bit-exact vs fault-free: {exact}  lost/errored: {lost}")
    assert exact and not lost


def main(shared_prefix: bool = False, chaos: bool = False):
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    params = init_params(cfg, seed=0)

    ecfg = PagedEngineConfig(page_size=8, n_pages=32, max_blocks_per_req=8,
                             max_batch=4, token_budget=16,
                             chunk_buckets=(8, 16))
    runner = PagedModelRunner(cfg, params, ecfg, n_sources=2)

    if chaos:
        _chaos(cfg, runner, ecfg)
        return
    if not shared_prefix:
        reqs = _requests(cfg, np.random.default_rng(0))
        res, engines = _serve(runner, ecfg, reqs)
        report(reqs, engines, res)
        return

    # shared-system-prompt workload, sharing on vs off on the same stream
    system = np.random.default_rng(7).integers(0, cfg.vocab_size, 24)
    mk = lambda: _requests(cfg, np.random.default_rng(0), system=system)
    res_off, eng_off = _serve(runner, ecfg, reqs_off := mk())
    shared_cfg = dataclasses.replace(ecfg, prefix_sharing=True)
    res_on, eng_on = _serve(runner, shared_cfg, reqs_on := mk())

    print("== sharing OFF ==")
    report(reqs_off, eng_off, res_off)
    print("== sharing ON (ref-counted prefix cache + COW) ==")
    report(reqs_on, eng_on, res_on)
    identical = all(a.output_tokens == b.output_tokens
                    for a, b in zip(reqs_off, reqs_on))
    saved = res_off.signals["pages_allocated"] \
        - res_on.signals["pages_allocated"]
    print(f"bit-identical outputs: {identical}")
    print(f"physical pages saved: {saved} "
          f"({res_on.signals['pages_allocated']} vs "
          f"{res_off.signals['pages_allocated']})")
    print(f"prefill tokens skipped via cache: "
          f"{res_on.signals['prefix_hit_tokens']}  "
          f"cow copies: {res_on.signals['cow_copies']}")
    print(f"affinity dispatches (prefix-holding engine picked): "
          f"{res_on.signals['decisions']['affinity_path']}  "
          f"per-engine hits: {res_on.signals['per_engine_prefix_hits']}")
    assert identical and saved > 0


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-system-prompt workload with the "
                         "prefix-sharing allocator, vs a no-sharing run")
    ap.add_argument("--chaos", action="store_true",
                    help="crash engine 1 mid-run and recover it: fence, "
                         "re-dispatch, rejoin — bit-exact vs fault-free")
    _a = ap.parse_args()
    main(shared_prefix=_a.shared_prefix, chaos=_a.chaos)
