"""The share of the traced window in which the device is idle and the
innermost host span open at the time has a name matching ``match``
(``run.profile["idle_gaps"]``, which ``trace_reduce.attribute_gaps``
fills: seconds of idle gap by span name).  It is the program's own spans
that name the gaps of a serving cell, so this is a program-span metric:
where the host's time went while the device waited for it.

None when no gap carries such a name: a program without the span reads
nothing, not zero."""

from __future__ import annotations

import re
from typing import Optional


def read(run, match: str) -> Optional[float]:
    gaps = run.profile.get("idle_gaps")
    if not gaps or not run.profile.get("window_s"):
        return None
    named = [secs for name, secs in gaps.items() if re.search(match, name)]
    if not named:
        return None
    return 100.0 * sum(named) / run.profile["window_s"]
