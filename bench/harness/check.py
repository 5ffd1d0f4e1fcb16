"""Is what the timed path produced correct?

Two kinds of numbers are compared, each against its limit:

- accounting: every request due in the window was dispatched once; no
  request finished twice; every finished request carries as many tokens
  as it asked for; every served token is a valid token id (limits 0);
- the served tokens against the float32 reference: once the window has
  closed and the program's state is freed, a sample of the requests that
  were served tokens in the window, finished or still in flight, drawn
  from the seed with the longest among them, is run through the
  reference over its prompt and every token served to it by the close. At each served
  token the gap is the reference's best logit minus its logit of the
  served token (greedy decoding serves the program's own best; a correct
  program serves a near-best token). Three numbers are read from the
  gaps: the widest (``logit_gap_max``), the mean (``logit_gap_mean``) and
  the share of tokens that are not the reference's best
  (``argmax_miss``); the configuration's ``check.limits`` names those
  compared and their limits.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness.driver import ReqRecord
from harness.spec import arch_of


def accounting(records: List[ReqRecord], vocab: int) -> Dict[str, int]:
    due = [r for r in records if not np.isnan(r.released)]
    twice = sum(1 for r in due if len(r.enqueued) > 1)
    never = sum(1 for r in records if r.enqueued and np.isnan(r.released))
    done = [r for r in due if r.req.finish_time >= 0 and not r.req.error]
    short = sum(1 for r in done
                if len(r.req.output_tokens or ()) != r.req.max_new_tokens)
    bad = sum(1 for r in due for t in (r.req.output_tokens or ())
              if not 0 <= t < vocab)
    return {"dispatched_twice": twice, "dispatched_before_due": never,
            "finished_short": short, "token_out_of_range": bad}


def sample(records: List[ReqRecord], seed: int, max_tokens: int,
           min_served: int, t0: float) -> List[ReqRecord]:
    """Requests served a token in the window (host clock ``t0`` on),
    finished or in flight: the longest, then others in an order drawn
    from the seed, until ``min_served`` served tokens or ``max_tokens``
    in all. Teacher forcing needs no finished request: an in-flight one
    is compared over the tokens served to it by the close."""
    cands = [r for r in records if not r.req.error and r.req.output_tokens
             and r.tokens and r.tokens[-1] >= t0]
    if not cands:
        return []
    size = lambda r: r.req.prompt_len + len(r.req.output_tokens)
    longest = max(cands, key=size)
    rest = [r for r in cands if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, total = [longest], size(longest)
    served = len(longest.req.output_tokens)
    for i in order:
        if served >= min_served:
            break
        r = rest[i]
        if total + size(r) > max_tokens:
            continue
        out.append(r)
        total += size(r)
        served += len(r.req.output_tokens)
    return out


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean()),
            "argmax_miss": float(np.mean(gaps > 0))}


def logit_gaps(model: dict, seed: int, picked: List[ReqRecord],
               control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Per served token: reference best minus reference logit of the
    served token, and (with ``control``) of the control's token, by the
    architecture's reference."""
    ref = arch_of(model).served_logits(
        model, seed, [r.req.prompt_tokens for r in picked],
        [r.req.output_tokens for r in picked], control=control)
    best = np.concatenate(ref["best"])
    gap = best - np.concatenate(ref["served"])
    ctrl = best - np.concatenate(ref["control"]) if control else None
    return gap, ctrl
