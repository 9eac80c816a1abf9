"""The one general request generator: a traffic mix is a data file of
parameters (the ``traffic`` object of ``workloads/<cell>.json``), and this
module turns it into requests.

Every seed gets the SAME multiset of prompt lengths, output budgets and
inter-arrival gaps — drawn once from the mix's own ``base_seed`` — in another
order, and other token ids.  A seed therefore changes which request meets
which burst, not how much work the run holds, so runs with different seeds
spread like runs of one seed.

Lengths: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``.
Arrivals: ``{"process": "gamma", "rate_per_s": r, "cv": c}`` — gaps with mean
1/r and coefficient of variation c, so ``cv`` 1 draws exponential gaps and
``cv`` 3 is bursty; the gaps are rescaled so that all of them sum to n/r, and
every seed's last request is due at the same time.  One fixed sample of gaps,
permuted, is therefore replayed by every seed: a Poisson process in shape, not
a fresh draw of one.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Generated:
    prompts: list          # one int32 array per request
    budgets: np.ndarray    # (n,) int, output tokens each request emits
    due_s: np.ndarray      # (n,) float, seconds after the generator's t=0, ascending

    def __len__(self) -> int:
        return len(self.prompts)


def _draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * rng.standard_normal(n))
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)


def _draw_gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["process"] != "gamma":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = float(spec["rate_per_s"])
    cv = float(spec["cv"])
    gaps = rng.gamma(1.0 / cv**2, cv**2 / rate, n)
    return gaps * ((n / rate) / gaps.sum())


def n_requests(traffic: dict, seconds: float) -> int:
    """How many requests fall due in ``seconds`` at the mix's fixed rate."""
    return max(int(round(float(traffic["arrivals"]["rate_per_s"]) * seconds)), 1)


def generate(traffic: dict, *, seed: int, seconds: float, vocab_size: int) -> Generated:
    """Requests due in ``[0, seconds)`` (the kind shifts them onto its clock)."""
    n = n_requests(traffic, seconds)
    base = np.random.default_rng(int(traffic.get("base_seed", 0)))
    prompt_len = _draw_lengths(traffic["prompt_len"], n, base)
    budget = _draw_lengths(traffic["output_len"], n, base)
    gaps = _draw_gaps(traffic["arrivals"], n, base)
    cap = int(traffic["max_total"])
    # prompt + budget within the position table: the budget yields first,
    # down to 1, then the prompt.
    budget = np.maximum(np.minimum(budget, cap - prompt_len), 1)
    prompt_len = np.minimum(prompt_len, cap - budget)

    rng = np.random.default_rng(int(seed))
    order = rng.permutation(n)          # lengths travel as (prompt, budget) pairs
    prompt_len, budget = prompt_len[order], budget[order]
    gaps = gaps[rng.permutation(n)]
    # The first request is due half a gap in, the last before ``seconds``.
    due = np.cumsum(gaps) - gaps[0] / 2.0

    tokens = rng.integers(0, vocab_size, int(prompt_len.sum()), dtype=np.int32)
    cuts = np.cumsum(prompt_len)[:-1]
    prompts = np.split(tokens, cuts)
    return Generated([np.ascontiguousarray(p) for p in prompts], budget, due)
