"""The share of one device's traced window spent in ops whose name
matches ``match`` (mean over the devices).  Whether that time is hidden
behind compute or exposed, the trace reduction does not say yet."""

from __future__ import annotations

import re
from typing import Optional


def read(run, match: str) -> Optional[float]:
    ops = run.profile.get("op_seconds")
    if not ops or not run.profile.get("window_s"):
        return None
    seconds = sum(s for name, s in ops.items() if re.search(match, name))
    return 100.0 * seconds / run.profile["window_s"]
