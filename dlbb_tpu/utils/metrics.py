"""Metrics summaries and timing.

Parity with reference ``utils.py:17-87``, collapsed to what the harnesses
actually consume: ``summarize`` (the reference ``MetricsCollector.summary``'s
mean/std/min/max/median/p95/p99 math, applied by every harness to its timing
series) and a ``Timer`` context manager — timing here is
``time.perf_counter`` with an optional ``jax.block_until_ready`` sync,
because under XLA's async dispatch a wall-clock timer without a device sync
measures dispatch latency, not execution (SURVEY §7 "hard parts").

The reference's stateful named-series ``MetricsCollector`` object is
deliberately NOT reproduced: in this design each harness owns its timing
list and calls ``summarize`` once, so a collector would be a write-then-
read-back indirection (the reference itself leaves half its ``utils.py``
helpers unused — ``run_experiment``, ``gather_metrics_from_all_ranks``,
``utils.py:172-244`` — a known quirk SURVEY §7 says not to replicate).
"""

from __future__ import annotations

import time
from typing import Any, Optional

import numpy as np


# the full summary schema, empty series included: every caller can rely
# on these keys existing (serving-path metrics key on p99.9 tail latency,
# hence p999)
SUMMARY_KEYS = ("mean", "std", "min", "max", "median", "p95", "p99",
                "p999", "count")


def summarize(values: list[float]) -> dict[str, float]:
    """Summary statistics over a timing series (seconds), matching the
    reference's metric names (``utils.py:43-66``) plus ``p999`` (the
    p99.9 tail the serving-path metrics need).

    An EMPTY series (every sample quarantined, a preempted run) returns
    explicit NaN-valued keys with ``count == 0`` — never a bare ``{}``
    that would KeyError the stats pipeline downstream; NaN is visibly
    not-a-number in every artifact it reaches."""
    arr = np.asarray(values, dtype=np.float64)
    if arr.size == 0:
        out = {k: float("nan") for k in SUMMARY_KEYS}
        out["count"] = 0
        return out
    return {
        "mean": float(arr.mean()),
        "std": float(arr.std()),
        "min": float(arr.min()),
        "max": float(arr.max()),
        "median": float(np.median(arr)),
        "p95": float(np.percentile(arr, 95)),
        "p99": float(np.percentile(arr, 99)),
        "p999": float(np.percentile(arr, 99.9)),
        "count": int(arr.size),
    }


class Timer:
    """Context-manager wall timer (reference ``utils.py:73-87``), with an
    optional result to synchronise on before stopping the clock."""

    def __init__(self, sync: Optional[Any] = None) -> None:
        self._sync = sync
        self.elapsed: float = float("nan")

    def __enter__(self) -> "Timer":
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._sync is not None:
            import jax

            jax.block_until_ready(self._sync)
        self.elapsed = time.perf_counter() - self._start
