"""Device time of the traced window under a name the program gave it
(``readers/named_ops.py``): ops whose ``by`` matches ``match``, as self
time (``trace_reduce.self_times``), mean over the devices.

``by``: ``"scope"`` tests the op's ``jax.named_scope`` path followed by
its own name (``jit(train_step)/transpose(jvp(attn_core))/flash_bwd_dq/
flash_bwd_dq/pallas_call flash_bwd_dq.1``), so a phase is found in the
forward, the recompute and the backward alike and a kernel by either of
its two names; ``"program"`` tests the name of the jitted program the op
ran in (``jit_serve_decode_k16``).

``over``: ``"busy"`` gives the share of device busy time in percent;
``"step"`` gives milliseconds per traced step.

None when the profile carries no such name: a program without the
scopes or program names reads nothing, not zero.
"""

from __future__ import annotations

import re
from typing import Optional

from benchmarks.readers import named_ops


def read(run, match: str, by: str = "scope",
         over: str = "busy") -> Optional[float]:
    if by not in ("scope", "program") or over not in ("busy", "step"):
        raise ValueError(f"by={by!r}, over={over!r}")
    if not run.profile.get("busy_s"):
        return None
    loaded = named_ops.load(run)
    if loaded is None:
        return None
    pattern = re.compile(match)

    def label(op: named_ops.NamedOp) -> str:
        text = f"{op[3]} {op[0]}" if by == "scope" else op[4]
        return "in" if pattern.search(text) else "out"

    seconds = named_ops.group_seconds(loaded, label).get("in", 0.0)
    if seconds <= 0.0:
        return None
    if over == "step":
        return 1e3 * seconds / int(run.cell.traffic["trace_steps"])
    return 100.0 * seconds / run.profile["busy_s"]
