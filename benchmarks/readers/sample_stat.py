"""A statistic of one of the run's sample lists: ``mean``, ``sum`` or a
percentile ``p<q>``; divided by the scalar ``over`` if given; times
``scale``."""

from __future__ import annotations

from typing import Optional

import numpy as np


def read(run, samples: str, stat: str, scale: float = 1.0,
         over: Optional[str] = None) -> Optional[float]:
    values = run.samples.get(samples)
    if not values:
        return None
    if stat == "mean":
        value = float(np.mean(values))
    elif stat == "sum":
        value = float(np.sum(values))
    elif stat.startswith("p"):
        value = float(np.percentile(values, float(stat[1:])))
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    if over is not None:
        if not run.scalars.get(over):
            return None
        value /= run.scalars[over]
    return value * scale
