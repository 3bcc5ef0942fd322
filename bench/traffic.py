"""The one traffic generator: a traffic file's parameters and a seed give
a frozen open-loop schedule of requests with their due times and prompts.

Every seed gets the same schedule of work: the same requests with the
same prompt and output lengths, deadlines, accuracies, channel gains and
due times, in one fixed order; the seed draws only the prompts' tokens
(and the weights).  The order is fixed because the program's admission
turns another order into another amount of work: a cohort shares one
write position, so which rows start together decides how many later
ones fit (see PERF.md, section 4).  The marginals are the paper's (§IV): lengths uniform over the levels, τ and
a uniform over their ranges, Rayleigh channel gains, and exponential
gaps of a Poisson process, here taken at evenly spaced quantiles.  With
bursts the process runs on the time scale of its integrated rate, so a
burst of factor f packs f times the arrivals into its span.

A traffic file (``bench/traffic/<name>.json``) holds::

    {"prompt_tokens": [128, 256, 512], "output_tokens": [128, 256, 512],
     "tau_s": [0.5, 2.0], "accuracy": [0.0, 1.0], "path_loss": 0.001,
     "bursts": null | {"first_s": 3, "period_s": 10, "length_s": 2,
                       "factor": 3}}

and the cell gives the base rate in requests per second.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

ORDER_SEED = 0          # the one order of every schedule


@dataclass
class Due:
    """One request of the schedule: its due time (seconds from the start
    of the window) and the paper's request fields."""
    rid: int
    due_s: float
    s: int
    n: int
    tau: float
    a: float
    h: float
    prompt: np.ndarray = field(repr=False)


def _rate_factor(t: float, bursts: Optional[dict]) -> float:
    if not bursts or t < bursts["first_s"]:
        return 1.0
    phase = (t - bursts["first_s"]) % bursts["period_s"]
    return bursts["factor"] if phase < bursts["length_s"] else 1.0


def _integrated(seconds: float, bursts: Optional[dict], step: float
                ) -> np.ndarray:
    """Λ(t)/rate on a grid of ``step`` seconds over [0, seconds]."""
    n = int(math.ceil(seconds / step))
    grid = np.arange(n + 1) * step
    f = np.array([_rate_factor(t + step / 2, bursts) for t in grid[:-1]])
    return np.concatenate([[0.0], np.cumsum(f * step)])


def schedule(traffic: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> List[Due]:
    """The frozen schedule of one run: every request due inside
    ``[0, seconds)``, sorted by due time."""
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate} and seconds {seconds} must be > 0")
    bursts = traffic.get("bursts")
    step = 1e-3
    lam = _integrated(seconds, bursts, step)          # in units of 1/rate
    n = int(round(rate * lam[-1]))
    if n == 0:
        return []
    rng = np.random.default_rng(ORDER_SEED)
    q = (np.arange(n) + 0.5) / n                      # evenly spaced quantiles

    def levels(key):
        lv = list(traffic[key])
        return np.array([lv[i % len(lv)] for i in range(n)])

    s = levels("prompt_tokens")[rng.permutation(n)]
    out = levels("output_tokens")[rng.permutation(n)]
    lo, hi = traffic["tau_s"]
    tau = (lo + (hi - lo) * q)[rng.permutation(n)]
    alo, ahi = traffic["accuracy"]
    acc = (alo + (ahi - alo) * q)[rng.permutation(n)]
    sigma = math.sqrt(traffic["path_loss"] / 2.0)
    h = (sigma * np.sqrt(-2.0 * np.log1p(-q)))[rng.permutation(n)]
    gaps = -np.log1p(-q)[rng.permutation(n)]
    # scale the unit-rate gaps so the last arrival falls inside the window
    u = np.cumsum(gaps) * (rate * lam[-1]) / (gaps.sum() + gaps.mean())
    due = np.interp(u / rate, lam, np.arange(lam.size) * step)
    reqs = []
    for i in range(n):
        prng = np.random.default_rng([seed, i])
        reqs.append(Due(rid=i, due_s=float(due[i]), s=int(s[i]),
                        n=int(out[i]), tau=float(tau[i]), a=float(acc[i]),
                        h=float(h[i]),
                        prompt=prng.integers(1, vocab, size=int(s[i]),
                                             dtype=np.int32)))
    return reqs
