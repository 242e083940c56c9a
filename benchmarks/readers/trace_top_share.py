"""The largest single device op's share of the device's busy time in the
traced window (self time, nested events taken out)."""

from __future__ import annotations

from typing import Optional


def read(run) -> Optional[float]:
    ops = run.profile.get("op_seconds")
    if not ops or not run.profile.get("busy_s"):
        return None
    return 100.0 * max(ops.values()) / run.profile["busy_s"]
