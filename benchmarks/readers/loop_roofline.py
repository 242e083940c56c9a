"""The share of its roofline of one of the two things a decode step of a
LOOPED stack (Ouro) is made of, in the traced slice (``kernel``):

- ``weight_pass``: the stack's projections and MLPs.  Least time, per
  decode unit, the larger of (steps x ``total_ut_steps`` x the stack's
  weights: shared weights are read once a PASS) over the published bytes
  per second and of the stepping slots' products over the published
  FLOP/s; over the traced device time under the scopes ``attn_qkv``,
  ``attn_out``, ``mlp_up``, ``mlp_act``, ``mlp_down`` inside the
  ``serve_decode_*`` programs.
- ``kv_attend``: decode attention over the (pass, layer) planes.  Least
  time, per decode unit, the larger of (K and V of every token under the
  stepping slots' lengths, once a pass and layer) over bytes per second
  and of the scores and values over FLOP/s; over the traced device time
  of the kernel ``kv_attend_decode`` (its own scope inside ``kv_attend``:
  the projections' fusions take the query's transpose in and would count
  under the phase) in the ``serve_decode_*`` programs.

Both count the work the FUNCTION needs (``harness/flops_ouro.py``): steps
and live tokens are what the program counted (the ``serve-decode`` spans'
``steps``, the report's samples ``unit_slot_steps`` and
``unit_live_tokens``, which the runner hands on), not what a kernel chose
to fetch.  The decode units of the TRACED SLICE are the ``serve-decode``
spans opened inside the window, placed on the profile's clock by the
``bench-sync`` mark (``readers/lin_roofline.py::spans_in_window``); a
span's ``unit`` is the sample's index.

None where the program has no such scope, span argument or sample.
"""

from __future__ import annotations

import re
from typing import Any, Optional

from benchmarks.harness import flops_ouro as counts
from benchmarks.harness.peaks import peaks_for
from benchmarks.readers import named_ops
from benchmarks.readers.lin_roofline import spans_in_window

SCOPE = {"weight_pass": "attn_qkv|attn_out|mlp_up|mlp_act|mlp_down",
         "kv_attend": "kv_attend_decode"}


def traced_units(run, loaded: dict[str, Any]) -> list[tuple[int, int]]:
    """``(index, steps)`` of every decode unit dispatched inside the
    traced window."""
    return [(ev["args"]["unit"], ev["args"]["steps"])
            for ev in spans_in_window(run, loaded)
            if ev["name"] == "serve-decode"
            and {"unit", "steps"} <= set(ev.get("args", {}))]


def least_seconds(model: dict[str, Any], samples: dict[str, list],
                  units: list[tuple[int, int]], kernel: str,
                  peaks: dict[str, float]) -> Optional[float]:
    bw, fl = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    key = "unit_slot_steps" if kernel == "weight_pass" \
        else "unit_live_tokens"
    values = samples.get(key)
    if not values or any(i >= len(values) for i, _ in units):
        return None
    if kernel == "weight_pass":
        return sum(max(counts.weight_pass_bytes(model, steps) / bw,
                       counts.weight_pass_flops(model, values[i]) / fl)
                   for i, steps in units)
    return sum(max(counts.kv_live_bytes(model, values[i]) / bw,
                   counts.kv_attend_flops(model, values[i]) / fl)
               for i, _ in units)


def read(run, kernel: str) -> Optional[float]:
    if kernel not in SCOPE:
        raise ValueError(f"kernel={kernel!r}")
    if not run.profile.get("busy_s"):
        return None
    loaded = named_ops.load(run)
    if loaded is None or not loaded.get("modules"):
        return None
    pattern = re.compile(rf"(^|[/(])({SCOPE[kernel]})[/)]")
    traced = named_ops.group_seconds(
        loaded, lambda op: "in" if op[4].startswith("jit_serve_decode")
        and pattern.search(f"{op[3]} {op[0]}") else "out").get("in", 0.0)
    if traced <= 0.0:
        return None
    least = least_seconds(run.cell.config["program"]["model"], run.samples,
                          traced_units(run, loaded), kernel,
                          peaks_for(run.device["kind"]))
    if not least:
        return None
    return 100.0 * least / traced
