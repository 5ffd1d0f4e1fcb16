"""One generator for every traffic mix, driven by the mix's data file.

A mix file holds parameters only::

    {"kind": "open_loop",            # or "backlog"
     "schedule_seed": 7,             # fixes the sizes and the arrivals
     "rate_rps": 1.0,                # open loop: offered requests/s
     "arrival_cv": 2.0,              # gamma inter-arrivals (1 = Poisson)
     "preroll_s": 30,                # open loop: arrivals start this long
                                     # before the window opens
     "engine": {"max_batch": 16},    # engine sizes set for this traffic
     "n_requests": 64,               # backlog: all due at the start
     "prompt_len": {"dist": "lognormal", "mu": 6.8, "sigma": 1.2,
                    "min": 16, "max": 8192},
     "output_len": {"dist": "uniform", "min": 512, "max": 2048}}

Sizes and arrival times come from ``schedule_seed``, so every run seed
serves the same work and runs differ by the seed's token ids and weights
only. The lognormal lengths and gamma arrivals follow
``src/repro/workloads/burstgpt.py`` (BurstGPT, paper section 7.1):
``rng.lognormal(mu, sigma)`` clipped, and gamma gaps of shape
``1 / cv**2`` at mean ``1 / rate``.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    req_id: int
    due_s: float          # seconds after the window opens (< 0: pre-roll)
    prompt_len: int
    output_len: int


def _length(rng: np.random.Generator, d: dict) -> int:
    if d["dist"] == "lognormal":
        x = rng.lognormal(d["mu"], d["sigma"])
    elif d["dist"] == "uniform":
        x = rng.integers(d["min"], d["max"] + 1)
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return int(np.clip(x, d["min"], d["max"]))


def max_tokens(mix: dict) -> int:
    """Longest prompt plus output the mix can draw."""
    return int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])


def preroll_s(mix: dict) -> float:
    return float(mix.get("preroll_s", 0.0))


def schedule(mix: dict, seconds: float) -> List[Arrival]:
    """Every request due before ``seconds``, in due order. An open loop
    starts ``preroll_s`` before the window, so that the window opens on a
    system under its steady load; those requests are served in set-up and
    not measured. Drawn one request at a time, so a longer window extends
    the same sequence."""
    rng = np.random.default_rng(mix["schedule_seed"])
    out: List[Arrival] = []
    if mix["kind"] == "backlog":
        for i in range(int(mix["n_requests"])):
            out.append(Arrival(i, 0.0, _length(rng, mix["prompt_len"]),
                               _length(rng, mix["output_len"])))
        return out
    if mix["kind"] != "open_loop":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    rate, cv = float(mix["rate_rps"]), float(mix.get("arrival_cv", 1.0))
    t = -preroll_s(mix)
    while True:
        if cv == 1.0:
            t += rng.exponential(1.0 / rate)
        else:
            shape = 1.0 / cv ** 2
            t += rng.gamma(shape, 1.0 / (rate * shape))
        p = _length(rng, mix["prompt_len"])
        o = _length(rng, mix["output_len"])
        if t >= seconds:
            return out
        out.append(Arrival(len(out), t, p, o))


def prompt_tokens(arrivals: List[Arrival], vocab_size: int,
                  seed: int) -> List[List[int]]:
    """Unshared prompt token ids, uniform over the vocabulary, from the
    run seed."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab_size, a.prompt_len).tolist()
            for a in arrivals]
