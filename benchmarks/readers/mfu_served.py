"""Model FLOP/s utilization of a serving run: the operations the family
requires for the prompt and output tokens of the requests the run
completed (``harness/<flops>.py::request_flops``; recomputation, padding
and the heads of non-final chunks not counted) over the serving wall
times chips times the published peak.  A share of the whole step's
peak: it bounds what any one kernel's gain can show end to end."""

from __future__ import annotations

import importlib
from typing import Optional

from benchmarks.harness.peaks import peaks_for


def read(run, flops: str) -> Optional[float]:
    prompts = run.samples.get("served_prompt_len")
    outputs = run.samples.get("served_output_len")
    wall = run.scalars.get("wall_s")
    if not prompts or not outputs or not wall:
        return None
    counts = importlib.import_module(f"benchmarks.harness.{flops}")
    model = run.cell.config["program"]["model"]
    required = sum(counts.request_flops(model, int(p), int(o))
                   for p, o in zip(prompts, outputs))
    peak = peaks_for(run.device["kind"])["bf16_flops_per_s"]
    return 100.0 * required / (wall * run.cell.chips * peak)
