"""``kind: backlog_latent``: what ``kind: backlog`` does
(``harness/serving.py``: every request due at t=0, the benchmark's own
feed and clock), and ``correct`` also holds the model's OUTPUT and its
ROUTING to a plain float32 reference, at the widths and sizes that were
timed.  The runner of a model with latent attention and routed experts
(kanana-2-30b-a3b), beside ``kind_backlog_checked.py``, whose
``check_outputs`` speaks the Olmo hybrid's reference and compares a
recurrent state.

For the two requests the traffic file names (``check_rids``: one
admitted into a slot nobody had used, one into a recycled slot) the
engine keeps, on the device, what its timed programs produced
(``ServingEngine.probe``): the logits of the last prompt position (the
chunked prefill, expanded attention) and of every decode step (absorbed
attention over the latent cache), and at each of those positions the
experts chosen in every expert layer with the gates they got.  The same
compiled programs run whether or not a request is checked, and nothing
is synced or fetched inside the window.  After the window they are
fetched and compared with the reference's whole forward pass
(``benchmarks/reference/<name>.py``: no cache, no chunks, no absorbed
form, every expert applied to every token) over the prompt and the
tokens the engine committed.  Teacher-forced, so a flipped ``argmax``
cannot cascade; and so is the ROUTING at the compared positions: the
reference's expert layers take there the experts the system took
(weighted by the reference's own scores of them) and report what they
would have chosen.  With seeded random weights the sixth and seventh of
128 sigmoid scores lie 0.004 apart on average, bf16 inputs move a score
by about as much, and an expert swapped in one layer sends the stream
through other weights in every layer behind it: unforced, one position
in seven reads 0.3-0.8 from the reference with nothing wrong (my chip
run, PR 31, the first session's third call: decode steps median
0.014-0.015, mean 0.10-0.12, largest 0.74-0.82, the sets agreeing at
0.84-0.87 of positions and layers).  The reference also judges the
weights it is handed (``weight_faults``), because both sides read the
same tree.

Three measures.  LOGITS, per position: ``|system - reference|_2 /
|reference|_2`` over the vocabulary; per request the last prompt
position's and the MEAN over its decode steps; the largest of each over
the checked requests is held to a limit, and so is the largest single
step.  ROUTING, per position and expert layer: where the system's chosen
set differs from the reference's, the experts swapped must be a near-tie
in the REFERENCE's own selection scores ``s + b``: the largest score the
system passed over less the smallest it took instead, held to a limit
at every position and layer (the forcing gives each layer the system's
own input up to rounding, so each disagreement is judged on its own).
The share of positions and layers whose sets agree is printed and
recorded (``routing_agreement``).  GATES: in the FIRST expert layer,
whose input has gone through one dense layer only, the mean over
positions and chosen experts of ``|g - g_ref| / g_ref`` where the sets
agree: what the chosen experts are weighted by, which the logits blur.
"""

from __future__ import annotations

import importlib
import sys
import time
from typing import Any

import numpy as np

from benchmarks.harness import serving, traffic as traffic_gen
from benchmarks.harness.cells import Cell
from benchmarks.harness.device import CompileCounter
from benchmarks.harness.kind_backlog_checked import relative_errors
from benchmarks.harness.result import Run

# The limits, each between sound readings and named controls through this
# runner on the chip (my chip runs, PR 31, TPU v5e, the published widths,
# the chip tool's calls 37 and 38; PERF.md section 6 has every reading;
# the controls are scripts/kanana_controls.py's).  The system computes in
# bfloat16 (weights, activations, cached rows) with a float32 router,
# softmax, norms and combine, the reference in float32 throughout.
#
# LOGITS.  Sound: every projection's output is rounded to bfloat16, about
# 0.35% a sub-layer, and 16 sub-layers add up like a random walk to 1.5%.
# Over 34 requests of 17 seeds (132-450 decode steps each): prefill
# 0.0130-0.0173, decode mean 0.0136-0.0164, largest step 0.0151-0.0193,
# flat over the steps.  Controls: rotary left off the cached key reads
# prefill 0.070, decode mean 0.071, largest step 0.082; the routed
# scaling factor left out 0.56 / 0.57 / 0.63; the top-k normalisation
# left out 1.12 / 1.15 / 1.21; one shared expert of two 0.70 / 0.70 /
# 0.78; the latent cached and used WITHOUT its norm 0.125 / 0.124 / 0.139
# (the norm's scale is seeded in 0.5 to 1.5: with a scale of ones it read
# 0.0221 / 0.0215 / 0.0245, because a seeded latent's root mean square is
# 1 to within 4% before its norm).  The three limits are 1.7 to 1.8 x
# over the largest sound reading and 2.3 x under the nearest control,
# rotary left off.
#
# ROUTING.  Sound: the largest margin of a disagreement 0.0035-0.0120 (the
# sets agree at 0.928-0.956 of positions and layers); rotary left off
# 0.037, the latent without its norm 0.080, the three gate and expert
# faults 0.52-0.89.  The limit is 1.9 x over the largest sound reading
# and 1.6 x under the nearest control.
#
# GATES, first expert layer.  Sound 0.00031-0.00034.  Two controls that NO
# other limit sees: the nearest precision below the configuration's,
# everything it states as float32 rounded to bfloat16 (by
# ``reduce_precision``: a convert to bfloat16 and back is dropped by XLA
# on the TPU), 0.00112, and the router alone so, 0.00114 (logits 0.0209 /
# 0.0204 / 0.0229 and 0.0191 / 0.0183 / 0.0208, margins 0.0083 and
# 0.0091); and the selection bias used as a weight, 0.0056 (logits 0.0162
# / 0.0157 / 0.0183, margin 0.0092).  Scaling left out 0.59,
# normalisation left out 4.3, rotary left off 0.0026.  The limit is the
# geometric middle of 0.00034 and 0.00112.
#
# NOT seen by any limit: the norms, the combine and a chunk's softmax in
# bfloat16 with the router left in float32 (gates 0.00039, logits 0.0186
# / 0.0177 / 0.0209, margin 0.0064): PERF.md, Open question 14.
PREFILL_REL_L2_MAX = 0.03
DECODE_MEAN_REL_L2_MAX = 0.03
DECODE_STEP_REL_L2_MAX = 0.035
ROUTING_TIE_MAX = 0.023
GATE_MEAN_REL_MAX = 0.0006
LIMITS = {"prefill": PREFILL_REL_L2_MAX, "decode": DECODE_MEAN_REL_L2_MAX,
          "decode_step": DECODE_STEP_REL_L2_MAX,
          "routing_tie": ROUTING_TIE_MAX, "gate": GATE_MEAN_REL_MAX}
# ONE shape for every checked request: the reference is computed over the
# longest one's tokens padded to a whole number of this many (it is
# causal, so what follows a position cannot move it), at as many
# positions as the longest answer has (a shorter one's last position
# over again).  The reference's time on the chip is its compilation, 36
# to 41 s a shape on a machine's first run, and 2 to 3 s after (my chip
# runs, PR 31, call 37)
PAD_TO = 512


def routing_errors(experts: np.ndarray, gates: np.ndarray,
                   select: np.ndarray, chosen: np.ndarray,
                   ref_gates: np.ndarray
                   ) -> tuple[float, float, float]:
    """The system's ``experts`` and ``gates`` ``[positions, layers, k]``
    against the reference's selection scores ``select`` ``[positions,
    layers, E]``, ``chosen`` ``[positions, layers, k]`` and dense
    ``ref_gates`` ``[positions, layers, E]``: ``(share of (position,
    layer) whose chosen sets agree, largest margin of a disagreement in
    the reference's own scores, mean relative gate error in the first
    layer)``.  ``select`` and ``chosen`` are the reference's with the
    system's ``experts`` forced upstream; ``ref_gates`` weight those."""
    took = np.zeros(select.shape, bool)
    np.put_along_axis(took, experts, True, axis=-1)
    want = np.zeros(select.shape, bool)
    np.put_along_axis(want, chosen, True, axis=-1)
    same = (took == want).all(axis=-1)
    # the best the system passed over, the worst it took instead
    passed = np.where(want & ~took, select, -np.inf).max(axis=-1)
    instead = np.where(took & ~want, select, np.inf).min(axis=-1)
    margin = np.where(same, 0.0, passed - instead)
    g_ref = np.take_along_axis(ref_gates[:, 0], experts[:, 0], axis=-1)
    gate = np.abs(gates[:, 0] - g_ref) / np.maximum(g_ref, 1e-12)
    return float(same.mean()), float(margin.max()), float(gate.mean())


def check_outputs(engine: Any, cell: Cell
                  ) -> tuple[list[str], dict[str, float]]:
    """The weights and the probed requests against the reference:
    ``(faults, {"prefill", "decode", "decode_step": the logits' errors
    as ``kind_backlog_checked`` reckons them; "routing_tie": the largest
    margin of a routing disagreement; "gate": the first expert layer's
    mean relative gate error; "routing_agreement": the share of
    positions and layers whose sets agree (recorded, no limit)})``."""
    model = cell.config["program"]["model"]
    reference = importlib.import_module(
        f"benchmarks.reference.{cell.traffic['reference']}")
    faults = list(reference.weight_faults(engine.params, model))
    results = engine.probe_results()
    faults += [f"request {rid} was not probed"
               for rid in cell.traffic["check_rids"] if rid not in results]
    kinds = {rec["recycled"] for rec in results.values()}
    if results and kinds != {False, True}:
        faults.append("check_rids must name one request admitted into an "
                      "unused slot and one into a recycled slot; got "
                      f"recycled={sorted(kinds)}")
    worst = dict.fromkeys(LIMITS, 0.0)
    agreed, judged = 0.0, 0
    checked = {}
    for rid, rec in sorted(results.items()):
        if rec["experts"] is None:
            faults.append(f"request {rid}: the programs returned no routing")
        elif len(rec["logits"]) != len(rec["tokens"]):
            faults.append(f"request {rid} did not finish")
        else:
            checked[rid] = rec
    longest = max((len(rec["prompt_ids"]) + len(rec["tokens"]) - 1
                   for rec in checked.values()), default=0)
    longest += -longest % PAD_TO
    steps_most = max((len(rec["tokens"]) for rec in checked.values()),
                     default=0)
    for rid, rec in checked.items():
        prompt = [int(t) for t in rec["prompt_ids"]]
        tokens = rec["tokens"]
        ids = prompt + tokens[:-1]
        padded = ids + [0] * (longest - len(ids))
        at = list(range(len(prompt) - 1, len(ids)))
        again = steps_most - len(at)
        experts = np.stack(rec["experts"])
        began = time.perf_counter()
        want, select, chosen, ref_gates = (
            np.asarray(a)[:len(at)] for a in reference.forward_logits(
                engine.params, padded, model, positions=at + at[-1:] * again,
                with_routing=True, forced_experts=np.concatenate(
                    [experts, np.repeat(experts[-1:], again, axis=0)])))
        took_s = time.perf_counter() - began
        errors = relative_errors(np.stack(rec["logits"]), want)
        share, tie, gate = routing_errors(
            experts, np.stack(rec["gates"]), select, chosen, ref_gates)
        if not (np.all(np.isfinite(errors)) and np.isfinite(tie)
                and np.isfinite(gate)):
            faults.append(f"request {rid}: logits or routing not finite")
            continue
        steps = errors[1:] if len(errors) > 1 else np.zeros(1)
        worst["prefill"] = max(worst["prefill"], float(errors[0]))
        worst["decode"] = max(worst["decode"], float(steps.mean()))
        worst["decode_step"] = max(worst["decode_step"], float(steps.max()))
        worst["routing_tie"] = max(worst["routing_tie"], tie)
        worst["gate"] = max(worst["gate"], gate)
        agreed += share * len(errors)
        judged += len(errors)
        print(f"[benchmark] request {rid} slot {rec['slot']} "
              f"recycled={rec['recycled']} prompt {len(prompt)} "
              f"tokens {len(tokens)} ({len(set(tokens))} distinct): "
              f"prefill {errors[0]:.5f}, "
              f"decode steps mean {steps.mean():.5f} median "
              f"{np.median(steps):.5f} max {steps.max():.5f}; routing "
              f"agrees at {share:.4f} of {select.shape[0]} positions x "
              f"{select.shape[1]} layers, largest margin of a disagreement "
              f"{tie:.5f}; first expert layer's gates {gate:.5f} (the "
              f"reference over {len(padded)} positions took {took_s:.1f} s)",
              file=sys.stderr)
    print("[benchmark] against the float32 reference: "
          + ", ".join(f"{name} {worst[name]:.5f} (limit {limit})"
                      for name, limit in LIMITS.items()), file=sys.stderr)
    faults += [f"{name} {worst[name]:.5f} from the reference, "
               f"limit {limit}" for name, limit in LIMITS.items()
               if worst[name] > limit]
    worst["routing_agreement"] = agreed / judged if judged else 0.0
    return faults, worst


# the report's samples the per-layer readers price the traced slice by
# (``readers/moe_latent_roofline.py``), and its shares
REPORT_SAMPLES = ("unit_slot_steps", "unit_live_tokens",
                  "moe_unit_assignments", "moe_unit_touched",
                  "moe_unit_load_max", "moe_chunk_assignments",
                  "moe_chunk_touched", "moe_chunk_load_max")
REPORT_SHARES = ("experts_touched_share", "expert_load_max_over_mean",
                 "latent_live_share")


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: CompileCounter, scratch: str) -> Run:
    # ``serving.run`` builds its engine through the module's
    # ``build_engine`` and keeps it and the report to itself; this
    # runner needs the engine before the warm-up (to name the probed
    # requests) and after the window (to fetch what it kept), and the
    # measured trace's report (the program's own counts), so it wraps
    # that one name for the duration of the call and measures with
    # ``serving.run`` itself
    held: dict[str, Any] = {}
    build = serving.build_engine

    def build_and_probe(cell_: Cell, seed_: int) -> Any:
        held["engine"] = engine = build(cell_, seed_)
        engine.probe(cell.traffic["check_rids"])
        run_trace = engine.run_trace

        def keep_report(*args: Any, **kwargs: Any) -> Any:
            held["report"] = report = run_trace(*args, **kwargs)
            return report

        engine.run_trace = keep_report
        return engine

    serving.build_engine = build_and_probe
    try:
        result = serving.run(cell, seed, seconds, trace, compiles, scratch)
    finally:
        serving.build_engine = build

    report = held.get("report", {})
    raw = report.get("raw_samples", {})
    result.samples.update({key: raw[key] for key in REPORT_SAMPLES
                           if raw.get(key)})
    result.scalars.update({key: report[key] for key in REPORT_SHARES
                           if key in report})
    if not result.failed:
        # what the run served, for the readers that price it
        records = traffic_gen.generate(cell.traffic, seed, result.attempted)
        result.samples["served_prompt_len"] = [r["prompt_len"]
                                               for r in records]
        result.samples["served_output_len"] = [r["output_len"]
                                               for r in records]
    faults, worst = check_outputs(held["engine"], cell)
    # the logits' three under the names ``kind_backlog_checked`` gives
    # them, the routing's under their own
    result.scalars.update({
        f"{name}_rel_l2" if name in ("prefill", "decode", "decode_step")
        else name: value for name, value in worst.items()})
    result.faults.extend(faults)
    result.correct = not result.faults
    return result
