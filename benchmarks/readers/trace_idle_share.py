"""The share of the traced window in which no op ran on the device: one
minus the union of the device-op intervals over the window."""

from __future__ import annotations

from typing import Optional


def read(run) -> Optional[float]:
    if not run.profile.get("window_s"):
        return None
    return 100.0 * (1.0 - run.profile["busy_s"] / run.profile["window_s"])
