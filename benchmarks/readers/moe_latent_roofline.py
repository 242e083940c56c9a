"""The share of its roofline of one of the two kernels kanana-2's block
adds, in the traced slice (``kernel``):

- ``experts``: the grouped products of the expert layers, in the decode
  programs and the prompt chunks alike.  Least time, per decode unit and
  per chunk, the larger of (the weights of the experts that got a token,
  once each, and every assignment's input and output row) over the
  published bytes per second and of the assignments' operations over the
  published FLOP/s; over the traced device time under the scope
  ``moe_experts``.
- ``latent_decode``: decode attention over the latent plane.  Least
  time, per decode unit, the larger of (cached rows under the stepping
  slots' lengths, once a layer for all heads) over bytes per second and
  of the absorbed scores and values over FLOP/s; over the traced device
  time under ``latent_attend`` inside the ``serve_decode_*`` programs.

Both count the work the FUNCTION needs, whatever implements it
(``harness/flops_kanana2.py``): experts touched, assignments and live
tokens are what the program counted (the report's samples
``moe_unit_*``, ``moe_chunk_*``, ``unit_live_tokens``, which the runner
hands on), not what a kernel chose to fetch.  The decode units and
chunks of the TRACED SLICE are the ``serve-decode`` and
``serve-prefill-chunk`` spans opened inside the window, placed on the
profile's clock by the ``bench-sync`` mark (``readers/lin_roofline.py::
spans_in_window``); a span's ``unit`` / ``seq`` is the sample's index.

None where the program has no such scope, span argument or sample.
"""

from __future__ import annotations

import re
from typing import Any, Optional

from benchmarks.harness import flops_kanana2 as counts
from benchmarks.harness.peaks import peaks_for
from benchmarks.readers import named_ops
from benchmarks.readers.lin_roofline import spans_in_window

SCOPE = {"experts": "moe_experts", "latent_decode": "latent_attend"}


def traced_indices(run, loaded: dict[str, Any]) -> tuple[list[int],
                                                        list[int]]:
    """``(decode units, prompt chunks)`` dispatched inside the traced
    window, each by its index into the report's per-unit and per-chunk
    samples."""
    events = spans_in_window(run, loaded)
    units = [ev["args"]["unit"] for ev in events
             if ev["name"] == "serve-decode" and "unit" in ev.get("args", {})]
    chunks = [ev["args"]["seq"] for ev in events
              if ev["name"] == "serve-prefill-chunk"
              and "seq" in ev.get("args", {})]
    return units, chunks


def _at(samples: dict[str, list], key: str, indices: list[int]
        ) -> Optional[list[float]]:
    values = samples.get(key)
    if not values or any(i >= len(values) for i in indices):
        return None
    return [values[i] for i in indices]


def least_seconds(model: dict[str, Any], samples: dict[str, list],
                  units: list[int], chunks: list[int], kernel: str,
                  peaks: dict[str, float]) -> Optional[float]:
    bw, fl = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    if kernel == "latent_decode":
        live = _at(samples, "unit_live_tokens", units)
        if live is None:
            return None
        return sum(max(counts.latent_decode_bytes(model, t) / bw,
                       counts.latent_decode_flops(model, t) / fl)
                   for t in live)
    total = 0.0
    for kind, indices in (("unit", units), ("chunk", chunks)):
        touched = _at(samples, f"moe_{kind}_touched", indices)
        assigned = _at(samples, f"moe_{kind}_assignments", indices)
        if touched is None or assigned is None:
            return None
        total += sum(max(counts.expert_products_bytes(model, t, a) / bw,
                         counts.expert_products_flops(model, a) / fl)
                     for t, a in zip(touched, assigned))
    return total


def read(run, kernel: str) -> Optional[float]:
    if kernel not in SCOPE:
        raise ValueError(f"kernel={kernel!r}")
    if not run.profile.get("busy_s"):
        return None
    loaded = named_ops.load(run)
    if loaded is None or not loaded.get("modules"):
        return None
    pattern = re.compile(rf"(^|[/(]){SCOPE[kernel]}[/)]")

    def label(op: named_ops.NamedOp) -> str:
        if kernel == "latent_decode" \
                and not op[4].startswith("jit_serve_decode"):
            return "out"
        return "in" if pattern.search(f"{op[3]} {op[0]}") else "out"

    traced = named_ops.group_seconds(loaded, label).get("in", 0.0)
    if traced <= 0.0:
        return None
    units, chunks = traced_indices(run, loaded)
    least = least_seconds(run.cell.config["program"]["model"], run.samples,
                          units, chunks, kernel,
                          peaks_for(run.device["kind"]))
    if not least:
        return None
    return 100.0 * least / traced
