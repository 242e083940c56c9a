"""The share of its roofline, in the traced slice, of one of the three
things only a model with state-space layers and narrow grouped K/V
heads asks of the chip (``kernel``):

- ``ssm_decode``: the recurrent step.  Least time: per decode unit of
  the slice, its (slot, step) pairs x the state-space layers x the state
  and convolution inputs read and written, over the published bytes per
  second; over the traced device time under the scope ``state_update``.
- ``ssm_prefill``: the chunked scan.  Least time: per prompt chunk of
  the slice, the larger of the recurrence's operations for its REAL
  tokens over the published FLOP/s and of its x, B, C, y and state bytes
  over the published bytes per second; over the traced device time
  under ``state_scan``.
- ``kv_attend``: decode attention over planes of whole rows.  Least
  time: per decode unit, K and V of every cached token under its
  stepping slots' lengths, in every attention layer, over bytes per
  second; over the traced device time of the kernel ``kv_attend_decode``
  in the ``serve_decode_*`` programs.

The work is what the FUNCTION needs (``harness/flops_granite4h.py``),
whatever implements it, and is what the program counted, not what a
kernel chose to fetch: the report's per-unit samples ``unit_slot_steps``
and ``unit_live_tokens`` (index: a ``serve-decode`` span's ``unit``) and
per-chunk sample ``chunk_real_tokens`` (index: a ``serve-prefill-chunk``
span's ``seq``), which the runner hands on.  The units and chunks of the
TRACED SLICE are the spans opened inside the window, placed on the
profile's clock by the ``bench-sync`` mark
(``readers/lin_roofline.py::spans_in_window``).

None where the program has no such scope, span argument or sample.
"""

from __future__ import annotations

import re
from typing import Any, Optional

from benchmarks.harness import flops_granite4h as counts
from benchmarks.harness.peaks import peaks_for
from benchmarks.readers import named_ops
from benchmarks.readers.lin_roofline import spans_in_window

SCOPE = {"ssm_decode": "state_update", "ssm_prefill": "state_scan",
         "kv_attend": "kv_attend_decode"}
# the span whose argument indexes the samples, and the samples
INDEXED = {"ssm_decode": ("serve-decode", "unit", ("unit_slot_steps",)),
           "ssm_prefill": ("serve-prefill-chunk", "seq",
                           ("chunk_real_tokens",)),
           "kv_attend": ("serve-decode", "unit", ("unit_live_tokens",))}


def traced_indices(events: list[dict[str, Any]], kernel: str) -> list[int]:
    """The sample index of every decode unit (or prompt chunk) opened
    inside the traced window."""
    span, key, _ = INDEXED[kernel]
    return [ev["args"][key] for ev in events
            if ev["name"] == span and key in ev.get("args", {})]


def least_seconds(model: dict[str, Any], samples: dict[str, list],
                  indices: list[int], kernel: str,
                  peaks: dict[str, float]) -> Optional[float]:
    bw, fl = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    values = samples.get(INDEXED[kernel][2][0])
    if not values or not indices or any(i >= len(values) for i in indices):
        return None
    if kernel == "ssm_decode":
        return sum(counts.decode_state_bytes(model, values[i])
                   for i in indices) / bw
    if kernel == "kv_attend":
        return sum(counts.kv_live_bytes(model, values[i])
                   for i in indices) / bw
    return sum(max(counts.recurrence_flops(model, values[i]) / fl,
                   counts.prefill_scan_bytes(model, values[i], 1) / bw)
               for i in indices)


def read(run, kernel: str) -> Optional[float]:
    if kernel not in SCOPE:
        raise ValueError(f"kernel={kernel!r}")
    if not run.profile.get("busy_s"):
        return None
    loaded = named_ops.load(run)
    if loaded is None or not loaded.get("modules"):
        return None
    pattern = re.compile(rf"(^|[/(])({SCOPE[kernel]})[/)]")
    in_decode = kernel != "ssm_prefill"
    traced = named_ops.group_seconds(
        loaded, lambda op: "in"
        if op[4].startswith("jit_serve_decode") == in_decode
        and pattern.search(f"{op[3]} {op[0]}") else "out").get("in", 0.0)
    if traced <= 0.0:
        return None
    least = least_seconds(
        run.cell.config["program"]["model"], run.samples,
        traced_indices(spans_in_window(run, loaded), kernel), kernel,
        peaks_for(run.device["kind"]))
    if not least:
        return None
    return 100.0 * least / traced
