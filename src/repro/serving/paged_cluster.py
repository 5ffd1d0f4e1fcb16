"""Setting up a Gimbal cluster of paged engines: seeded weights, seeded
requests, one ``PagedRealEngine`` per runner slot under
``serve_real_cluster``, and the summary a run prints.

Shared by ``examples/serve_moe_paged.py`` (tiny model on the CPU) and
``chip_smoke.py`` (full-width layers on a TPU), so both drive the same
path.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.models import build_model
from repro.serving.paged_engine import (PagedEngineConfig, PagedModelRunner,
                                        PagedRealEngine)
from repro.serving.real_cluster import RealClusterConfig, serve_real_cluster
from repro.serving.request import Request, RequestState


def init_params(cfg, seed: int):
    """Random weights from ``seed``, made in one jitted program so no
    float32 intermediate of a full-width expert stack is ever live."""
    return jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))


def random_requests(vocab_size: int, rng: np.random.Generator, n: int, *,
                    prompt_lens: Tuple[int, int] = (8, 40),
                    new_tokens: Tuple[int, int] = (4, 10),
                    system: Optional[Sequence[int]] = None) -> List[Request]:
    """``n`` requests arriving 50 ms apart, prompts drawn uniformly over
    the vocabulary. Lengths are drawn from ``[lo, hi)``. With ``system``
    each prompt is that shared prefix plus 12 own tokens."""
    reqs = []
    for i in range(n):
        toks = rng.integers(0, vocab_size,
                            int(rng.integers(*prompt_lens))).tolist()
        if system is not None:
            toks = list(system) + toks[:12]
        reqs.append(Request(
            req_id=i, prompt_len=len(toks),
            max_new_tokens=int(rng.integers(*new_tokens)),
            arrival_time=0.05 * i, prompt_tokens=toks))
    return reqs


def serve_paged(reqs: List[Request], runners: Sequence[PagedModelRunner],
                ecfg: PagedEngineConfig, **cluster_kw):
    """Serve ``reqs`` on one engine per entry of ``runners`` (repeat a
    runner to share it), under the Gimbal control plane. Returns
    ``(SimResult, engines)``; every engine's allocator is checked."""
    engines = [PagedRealEngine(i, r.cfg, r.params, ecfg, runner=r,
                               n_sources=r.n_sources)
               for i, r in enumerate(runners)]
    res = serve_real_cluster(reqs, engines,
                             cluster_cfg=RealClusterConfig(**cluster_kw))
    for e in engines:
        e.pool.check_invariants()
    return res, engines


def served_ok(reqs: List[Request]) -> List[Request]:
    """Requests that finished without an error."""
    return [r for r in reqs
            if r.state is RequestState.FINISHED and not r.error]


def report(reqs: List[Request], engines, res) -> None:
    sig = res.signals
    print(f"served {len(served_ok(reqs))}/{len(reqs)} requests on "
          f"{len(engines)} paged engines ({sig['rounds']} cluster rounds)")
    print(f"dispatch decisions: {sig['decisions']}")
    print(f"prefill dispatches: {sig['prefill_dispatches']} "
          f"(avg {sig['prefill_lanes_per_dispatch']:.2f} "
          f"lanes fused per dispatch)")
    print(f"preemptions: {sig['preemptions']}  "
          f"stalls: {sig['stalled']}  "
          f"kv peak: {sig['kv_peak']:.1%}")
    print(f"expert migrations: {sig['migrations']} "
          f"({sig['expert_moves']} expert moves)")
    print(f"requests per engine: {sig['per_engine']}")
    print(f"mean ttft {res.mean_ttft:.2f}s  mean e2e {res.mean_e2e:.2f}s "
          f"(virtual time)")
