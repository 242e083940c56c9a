"""Model FLOP/s utilization: the operations the model requires per token
(``harness/flops.py``; recomputation not counted) times the tokens per
second of the run's undisturbed stretch, over chips times the published
peak.  An end-to-end utilization: it says nothing of idle time or of any
one kernel."""

from __future__ import annotations

from typing import Optional

from benchmarks.harness import flops
from benchmarks.harness.peaks import peaks_for


def read(run) -> Optional[float]:
    rate = run.scalars.get("tokens_per_s")
    if not rate:
        return None
    model = run.cell.config["program"]["model"]
    seq = run.cell.traffic["sequence_length"]
    per_token = (flops.train_flops_per_token(model, seq)
                 if run.cell.config["mode"] == "train"
                 else flops.forward_flops_per_token(model, seq))
    peak = peaks_for(run.device["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * rate / (run.cell.chips * peak)
