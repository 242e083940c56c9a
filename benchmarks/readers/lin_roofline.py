"""The gated delta rule's share of its roofline in the traced slice, for
one of its two forms (``phase``):

- ``decode``: the recurrent step.  Least time: the state and convolution
  inputs of the slots active in a step, read and written in every linear
  layer, over the published bytes per second; over the traced device
  time under the scope ``state_update``.
- ``prefill``: the chunked scan.  Least time: the larger of its required
  operations over the published FLOP/s and of its q, k, v, o and state
  bytes over the published bytes per second; over the traced device time
  under ``state_scan``.

The work is that of the TRACED SLICE, read from what ran there and from
shapes (``harness/flops_olmo_hybrid.py``), whatever implements it, XLA
fusions or a kernel: decode steps are the executions of the
``serve_decode_*`` programs inside the window (``K`` steps for a
``_k<K>``) times the mean of the ``active`` argument of the
``serve-decode`` spans opened in it; prompt tokens are the real
(unpadded) tokens of the ``serve-prefill-chunk`` spans opened in it.
The spans are placed on the profile's clock by the ``bench-sync`` mark,
as ``harness/trace_reduce.py`` places them.

None where the program has no such scope or span.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Any, Optional

from benchmarks.harness import flops_olmo_hybrid as counts
from benchmarks.harness import trace_reduce, traffic as traffic_gen
from benchmarks.harness.peaks import peaks_for
from benchmarks.readers import named_ops

SCOPE = {"decode": "state_update", "prefill": "state_scan"}
_FUSED = re.compile(r"^jit_serve_decode_k(\d+)$")


def spans_in_window(run, loaded: dict[str, Any]) -> list[dict[str, Any]]:
    """The ``B`` events of the run's span file that fall inside the
    traced window, or [] without a span file or a ``bench-sync`` mark."""
    # <scratch>/plugins/profile/<session>/<host>.xplane.pb
    path = Path(named_ops.profile_path(run)).parents[3] / "spans.json"
    if not path.exists():
        return []
    with open(path) as f:
        data = json.load(f)
    events = data["traceEvents"] if isinstance(data, dict) else data
    marks = [s for n, s, _e in loaded["host"] if n == trace_reduce.SYNC_SPAN]
    own = [ev["ts"] * 1e-6 for ev in events
           if ev.get("name") == trace_reduce.SYNC_SPAN]
    if not marks or not own:
        return []
    shift = marks[-1] - own[-1]
    t0, t1 = loaded["window"]
    return [ev for ev in events if ev.get("ph") == "B"
            and t0 <= ev["ts"] * 1e-6 + shift < t1]


def traced_work(run, loaded: dict[str, Any]) -> dict[str, float]:
    """``decode_steps``, ``active_slots`` (mean a step), ``chunks`` and
    real ``prompt_tokens`` of the traced window."""
    t0, t1 = loaded["window"]
    steps = chunks = 0
    for program, start, _dur in next(iter(loaded["modules"].values()), ()):
        if not t0 <= start < t1:
            continue
        fused = _FUSED.match(program)
        if fused:
            steps += int(fused.group(1))
        elif program == "jit_serve_decode_step":
            steps += 1
        elif program.startswith("jit_serve_prefill_chunk_o"):
            chunks += 1
    events = spans_in_window(run, loaded)
    units = [ev["args"] for ev in events if ev["name"] == "serve-decode"]
    weight = sum(a["steps"] for a in units)
    active = (sum(a["active"] * a["steps"] for a in units) / weight
              if weight else 0.0)
    t = run.cell.traffic
    chunk = int(run.cell.config["program"]["serving"]["prefill_chunk"])
    lengths = {r["rid"]: r["prompt_len"] for r in traffic_gen.generate(
        t, 0, traffic_gen.request_count(t, run.seconds))}
    tokens = sum(
        max(0, min(chunk, lengths.get(ev["args"]["rid"], 0)
                   - ev["args"]["chunk"] * chunk))
        for ev in events if ev["name"] == "serve-prefill-chunk")
    return {"decode_steps": steps, "active_slots": active,
            "chunks": chunks, "prompt_tokens": tokens}


def least_seconds(model: dict[str, Any], work: dict[str, float],
                  phase: str, peaks: dict[str, float]) -> float:
    if phase == "decode":
        return (work["decode_steps"] * counts.decode_step_state_bytes(
            model, work["active_slots"]) / peaks["hbm_bytes_per_s"])
    return max(
        counts.delta_rule_flops(model, work["prompt_tokens"])
        / peaks["bf16_flops_per_s"],
        counts.prefill_scan_bytes(model, work["prompt_tokens"],
                                  work["chunks"]) / peaks["hbm_bytes_per_s"])


def read(run, phase: str) -> Optional[float]:
    if phase not in SCOPE:
        raise ValueError(f"phase={phase!r}")
    if not run.profile.get("busy_s"):
        return None
    loaded = named_ops.load(run)
    if loaded is None or not loaded.get("modules"):
        return None
    pattern = re.compile(rf"(^|[/(]){SCOPE[phase]}[/)]")
    traced = named_ops.group_seconds(
        loaded, lambda op: "in" if pattern.search(f"{op[3]} {op[0]}")
        else "out").get("in", 0.0)
    if traced <= 0.0:
        return None
    least = least_seconds(run.cell.config["program"]["model"],
                          traced_work(run, loaded), phase,
                          peaks_for(run.device["kind"]))
    if least <= 0.0:
        return None
    return 100.0 * least / traced
