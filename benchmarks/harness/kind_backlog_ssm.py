"""``kind: backlog_ssm``: what ``kind: backlog`` does
(``harness/serving.py``: every request due at t=0, the benchmark's own
feed and clock), and ``correct`` also holds the model's OUTPUT and the
first state-space layer's recurrent STATE to a plain float32 reference,
at the widths and sizes that were timed.  The runner of a model with
state-space (``mamba``) layers (Granite 4.0-H), beside
``kind_backlog_checked.py`` (Olmo-Hybrid's delta rule: another
reference, another state, another signature), whose pattern it follows.

For the two requests the traffic file names (``check_rids``: one
admitted into a slot nobody had used, one into a recycled slot, that one
with a prompt of at least two chunks) the engine keeps, on the device,
what its timed programs produced (``ServingEngine.probe``): the logits
of the last prompt position (the chunked prefill) and of every decode
step, and a copy of the slot's recurrent state after the prompt and
after the last decode step.  The same compiled programs run whether or
not a request is checked, and nothing is synced or fetched inside the
window.  After the window they are fetched and compared with the
reference's whole forward pass (``benchmarks/reference/<name>.py``: no
cache, no chunks, token-by-token recurrence) over the prompt and the
tokens the engine committed.  Teacher-forced, so a flipped ``argmax``
cannot cascade.  The reference also judges the weights it is handed
(``weight_faults``), because both sides read the same tree.

Two measures.  LOGITS, per position: ``|system - reference|_2 /
|reference|_2`` over the vocabulary; per request the last prompt
position's (the prefill) and the MEAN over its decode steps; the largest
of each over the checked requests is held to a limit, and so is the
largest single step.  STATE: the FIRST state-space layer's (layer 0),
whose input is the scaled embedding itself, so that nothing upstream has
rounded it; per head ``|S - S_ref|_F / |S_ref|_F``, the largest over the
heads, the two moments and the checked requests.
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
# runner on the chip (my chip runs, PR 37, TPU v5e, the published widths,
# 64 slots; benchmarks/granite4h.md and PERF.md section 6 have every
# reading; the controls are scripts/granite4h_controls.py's).  The system
# computes in bfloat16 (weights, what a sub-layer is fed and gives, the
# residual stream, cached K/V, the convolution's carried inputs) with a
# float32 recurrent state, scan, norms, softmax and logits, the reference
# in float32 throughout.
#
# LOGITS.  Sound: 80 sub-layers each round their output to bfloat16 and
# join a bfloat16 stream: prefill 0.0207-0.0239, a request's mean decode
# step 0.0195-0.0220, its largest 0.0214-0.0253, flat over 91 to 384
# steps, over the 32 requests of the 16 sound runs of calls 3 to 6, each
# another seed.  Controls that change the
# function: the decay skipped (exp(dt A) = 1) reads 0.978-1.010 / 1.036-
# 1.038 / 1.044, ``residual_multiplier`` 1 reads 1.164-1.231 / 1.199-
# 1.238 / 1.241-1.253.  The limits are 3.3 to 4 x the largest sound
# reading and 10 to 12 x under the nearest control.  They do NOT hold
# the state's precision: with the state planes in bfloat16 the logits
# read 0.0224 / 0.0228-0.0388 / 0.0276-0.0623.
#
# STATE, the first state-space layer's, worst of 64 heads.  Sound: x, B
# and the step are one projection of exact inputs rounded once to
# bfloat16, and the step's rounding moves the decay's exponent, so the
# state is 0.0065-0.0107 from the reference's after a prompt and
# 0.0053-0.0100 after 91 to 384 decode steps (64 readings of 32
# requests).  Control, the nearest precision below the configuration's:
# the same programs with the state planes (and the state a chunk hands
# on) in bfloat16, so that every decode step rounds every head's state:
# 0.148 after 102 steps and 0.175 after 230 (after the prompt 0.0067-
# 0.0075, as sound: a chunk rounds once where it hands the state on; it
# is the decode steps that tell), ``correct: false`` by this limit
# alone.  With the decay skipped the state reads 78-207.  The limit is
# 2.8 x the largest sound reading and 4.9 x under the control.  It holds
# the state's precision for all 36 layers because they share one plane
# and one step function; the later layers' own states cannot carry a
# limit, since their inputs have been rounded by the layers before them.
# ``residual_multiplier`` 1 leaves it sound (0.0062-0.0078): layer 0 is
# fed the scaled embedding whatever the layers add to the stream.
PREFILL_REL_L2_MAX = 0.08
DECODE_MEAN_REL_L2_MAX = 0.08
DECODE_STEP_REL_L2_MAX = 0.1
STATE_REL_L2_MAX = 0.03
LIMITS = {"prefill": PREFILL_REL_L2_MAX, "decode": DECODE_MEAN_REL_L2_MAX,
          "decode_step": DECODE_STEP_REL_L2_MAX, "state": STATE_REL_L2_MAX}
# ONE shape for every checked request: the reference is computed over the
# longest one's tokens padded to a whole number of this many (it is
# causal, so what follows a position cannot move it)
PAD_TO = 128


def check_outputs(engine: Any, cell: Cell
                  ) -> tuple[list[str], dict[str, float]]:
    """The weights and the probed requests against the reference:
    ``(faults, {"prefill": largest error at a last prompt position,
    "decode": largest mean over a request's decode steps, "decode_step":
    largest single step, "state": largest error of a head of the first
    state-space layer's recurrent state, after a prompt or after a last
    decode step})``."""
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
    checked = {}
    for rid, rec in sorted(results.items()):
        if rec["end_state"] is None \
                or len(rec["logits"]) != len(rec["tokens"]):
            faults.append(f"request {rid} did not finish")
        else:
            checked[rid] = rec
    longest = max((len(rec["prompt_ids"]) + len(rec["tokens"]) - 1
                   for rec in checked.values()), default=0)
    longest += -longest % PAD_TO
    for rid, rec in checked.items():
        prompt = [int(t) for t in rec["prompt_ids"]]
        tokens = rec["tokens"]
        ids = prompt + tokens[:-1]
        began = time.perf_counter()
        want, states = reference.forward_logits(
            engine.params, ids + [0] * (longest - len(ids)), model,
            positions=list(range(len(prompt) - 1, len(ids))),
            state_at=[len(prompt) - 1, len(ids) - 1])
        want = np.asarray(want)
        took_s = time.perf_counter() - began
        errors = relative_errors(np.stack(rec["logits"]), want)
        # [after the prompt, after the last step] of the first
        # state-space layer, head by head: the largest over the heads
        kept = np.stack([rec["prompt_state"], rec["end_state"]])[:, 0]
        heads = kept.shape[:2] + (-1,)
        state = relative_errors(
            kept.astype(np.float32).reshape(heads),
            np.asarray(states)[:, 0].reshape(heads)).max(axis=-1)
        if not (np.all(np.isfinite(errors)) and np.all(np.isfinite(state))):
            faults.append(f"request {rid}: logits or state not finite")
            continue
        steps = errors[1:] if len(errors) > 1 else np.zeros(1)
        worst["prefill"] = max(worst["prefill"], float(errors[0]))
        worst["decode"] = max(worst["decode"], float(steps.mean()))
        worst["decode_step"] = max(worst["decode_step"], float(steps.max()))
        worst["state"] = max(worst["state"], float(state.max()))
        print(f"[benchmark] request {rid} slot {rec['slot']} "
              f"recycled={rec['recycled']} prompt {len(prompt)} "
              f"tokens {len(tokens)} ({len(set(tokens))} distinct): "
              f"prefill {errors[0]:.5f}, "
              f"decode steps mean {steps.mean():.5f} median "
              f"{np.median(steps):.5f} max {steps.max():.5f}; first "
              f"state-space layer's state after the prompt {state[0]:.5f}, "
              f"after the last step {state[1]:.5f} (the reference over "
              f"{longest} positions took {took_s:.1f} s)", file=sys.stderr)
    print("[benchmark] against the float32 reference: "
          + ", ".join(f"{name} {worst[name]:.5f} (limit {limit})"
                      for name, limit in LIMITS.items()), file=sys.stderr)
    faults += [f"{name} {worst[name]:.5f} from the reference, "
               f"limit {limit}" for name, limit in LIMITS.items()
               if worst[name] > limit]
    return faults, worst


# the report's samples the per-layer readers price the traced slice by
# (``readers/ssm_roofline.py``), and its shares
REPORT_SAMPLES = ("unit_slot_steps", "unit_live_tokens",
                  "chunk_real_tokens", "chunk_rows")
REPORT_SHARES = ("chunk_real_token_share", "kv_live_share")


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: CompileCounter, scratch: str) -> Run:
    # ``serving.run`` builds its engine through the module's
    # ``build_engine`` and keeps it and the report to itself; this
    # runner needs the engine before the warm-up (to name the probed
    # requests) and after the window (to fetch what it kept), and the
    # measured trace's report (the program's own counts), so it wraps
    # that one name for the duration of the call and measures with
    # ``serving.run`` itself, as ``kind_backlog_looped`` does
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
    result.scalars.update({
        f"{name}_rel_l2": value for name, value in worst.items()})
    result.faults.extend(faults)
    result.correct = not result.faults
    return result
