"""The flash-attention kernels' share of their roofline in the traced
steps: the least time one chip could take for its heads (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from shapes,
``harness/flops.py``) over the device time of the trace's events whose
name matches ``match``.  At head size 128 and 512 positions the kernels
are compute bound: about 100 operations a byte against the chip's 240."""

from __future__ import annotations

import re
from typing import Optional

from benchmarks.harness import flops
from benchmarks.harness.peaks import peaks_for


def read(run, match: str) -> Optional[float]:
    ops = run.profile.get("op_seconds")
    if not ops:
        return None
    kernel_s = sum(s for name, s in ops.items() if re.search(match, name))
    if kernel_s <= 0:
        return None
    program = run.cell.config["program"]
    model, par = program["model"], program.get("parallelism", {})
    batch = run.cell.traffic["batch_size"] // par.get("data_parallel", 1)
    heads = model["num_heads"] // par.get("world_size", 1)
    seq = run.cell.traffic["sequence_length"]
    backward = run.cell.config["mode"] == "train"
    peaks = peaks_for(run.device["kind"])
    least = max(
        flops.flash_flops(model, batch, seq, heads, backward)
        / peaks["bf16_flops_per_s"],
        flops.flash_bytes(model, batch, seq, heads, backward)
        / peaks["hbm_bytes_per_s"],
    )
    steps = int(run.cell.traffic["trace_steps"])
    return 100.0 * least / (kernel_s / steps)
