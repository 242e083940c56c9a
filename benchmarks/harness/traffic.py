"""The benchmark's own traffic generator.

Copied in spirit from ``dlbb_tpu/serve/traffic.py`` (``generate_trace``:
clipped-lognormal lengths between bounds, exponential gaps) so that a
later PR may change the program's generator but not the yardstick's.
One thing differs, on purpose: every seed gets the SAME lengths and the
SAME gaps in the SAME order.  Lengths and gaps are the quantiles of
their distributions at ``(i + 0.5) / n``, shuffled once by the mix's own
``order_seed``; ``--seed`` draws each request's embeddings (and, in the
runner, the weights).  So the work of a run does not depend on the seed
and runs with different seeds compare.  The order had to be fixed too:
with the same set of lengths in a per-seed order, a backlog's output
tokens per second differed by 4.8% between seeds and by under 1.4%
between two runs of one seed (my chip runs, PR 24), because which
requests come last decides how long the batch drains half empty.

A traffic mix is a data file ``traffic/<name>.json``; this is the one
general generator that reads it.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any

import numpy as np


def lognormal_lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` integer lengths in ``[lo, hi]``: the quantiles of the
    program's clipped lognormal (median at the geometric middle, sigma a
    quarter of the log range), ascending."""
    if lo < 1 or lo > hi:
        raise ValueError(f"length bounds must satisfy 1 <= lo <= hi, "
                         f"got [{lo}, {hi}]")
    mu = 0.5 * (math.log(lo) + math.log(hi))
    sigma = (math.log(hi) - math.log(lo)) / 4.0
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(mu + sigma * z)
    return np.clip(np.round(raw).astype(np.int64), lo, hi)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate`` per
    second: the quantiles of the exponential distribution, ascending."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0 req/s, got {rate}")
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def generate(traffic: dict[str, Any], seed: int, n: int,
             output_range: "tuple[int, int] | None" = None
             ) -> list[dict[str, Any]]:
    """``n`` requests of the mix ``traffic`` as plain records
    (``rid``, ``arrival_s``, ``prompt_len``, ``output_len``, ``seed``),
    in arrival order.  ``kind: backlog`` makes every request due at 0;
    ``kind: paced`` spaces them by the shuffled exponential gaps."""
    if n <= 0:
        raise ValueError(f"a trace needs at least one request, got {n}")
    order = np.random.default_rng(int(traffic["order_seed"]))
    prompts = order.permutation(
        lognormal_lengths(n, *traffic["prompt_range"]))
    outputs = order.permutation(lognormal_lengths(
        n, *(output_range or traffic["output_range"])))
    if traffic["kind"] == "paced":
        arrivals = np.cumsum(order.permutation(
            exponential_gaps(n, float(traffic["rate_rps"]))))
    else:
        arrivals = np.zeros(n)
    seeds = np.random.default_rng(seed).integers(0, 2**31 - 1, size=n)
    return [
        {"rid": i, "arrival_s": float(arrivals[i]),
         "prompt_len": int(prompts[i]), "output_len": int(outputs[i]),
         "seed": int(seeds[i])}
        for i in range(n)
    ]


def request_count(traffic: dict[str, Any], seconds: float) -> int:
    """How many requests a window of ``seconds`` offers: the mix's fixed
    rate times the window (``backlog_rps`` is the rate at which the
    parent drains a backlog, found once on the chip)."""
    rate = traffic["rate_rps" if traffic["kind"] == "paced"
                   else "backlog_rps"]
    return max(1, round(float(rate) * seconds))
