"""``kind: backlog_checked``: what ``kind: backlog`` does
(``harness/serving.py``: every request due at t=0, the benchmark's own
feed and clock), and ``correct`` also holds the model's OUTPUT and its
recurrent STATE to a plain float32 reference, at the widths and sizes
that were timed.

For the two requests the traffic file names (``check_rids``: one
admitted into a slot nobody had used, one into a recycled slot) the
engine keeps, on the device, what its timed programs produced
(``ServingEngine.probe``): the logits of the last prompt position (the
chunked prefill) and of every decode step, and a copy of the slot's
recurrent state after the prompt and after the last decode step.  The
same compiled programs run whether or not a request is checked, and
nothing is synced or fetched inside the window.  After the window they
are fetched and compared with the reference's whole forward pass
(``benchmarks/reference/<name>.py``: no cache, no chunks, token-by-token
recurrence) over the prompt and the tokens the engine committed.
Teacher-forced, so a flipped ``argmax`` cannot cascade: with seeded
random weights the largest logit changes on rounding, the logits do not.
The reference also judges the weights it is handed (``weight_faults``),
because both sides read the same tree.

Two measures.  LOGITS, per position: ``|system - reference|_2 /
|reference|_2`` over the vocabulary; per request the last prompt
position's (the prefill) and the MEAN over its decode steps; the largest
of each over the checked requests is held to a limit, and so is the
largest single step (the mean sees a fault that moves every step a
little, the single step one that hits few positions hard).  STATE: the
first linear-attention layer's, whose inputs are the embedded tokens
themselves, so that nothing upstream has rounded them; per head ``|S -
S_ref|_F / |S_ref|_F``, the largest over the heads, the two moments and
the checked requests.
"""

from __future__ import annotations

import importlib
import sys
from typing import Any

import numpy as np

from benchmarks.harness import serving, traffic as traffic_gen
from benchmarks.harness.cells import Cell
from benchmarks.harness.device import CompileCounter
from benchmarks.harness.result import Run

# The limits, each between two readings on the chip (my chip runs, PR 27,
# TPU v5e, the published widths, requests of 512 and 378 tokens; PERF.md
# section 6 has every reading).  The system computes in bfloat16
# (weights, activations, K/V) with a float32 recurrent state, the
# reference in float32 throughout.
#
# LOGITS.  Sound: every projection's output is rounded to bfloat16 (2^-9
# relative), about 0.45% a sub-layer, and 32 sub-layers add up like a
# random walk to 2.5%.  Over 26 requests of 13 seeds: prefill
# 0.0231-0.0317, decode mean 0.0241-0.0252, largest step 0.030-0.049,
# flat over 512 steps.  Control: a fault that changes the function.  With
# the decay skipped (alpha = 1) the logits read 1.20-1.26 at every
# position, prefill and decode; a state not cleared and a missing decay
# are ``correct: false`` at toy widths in the tier-1 tests.  The limits
# are 3-4 x the largest sound reading and 6-12 x under the control.
# They do NOT see a recurrent state kept in bfloat16, the nearest
# precision below the configuration's: it reads prefill 0.024-0.026 and
# decode mean 0.028-0.031.
#
# STATE.  Sound: the first linear layer's q, k, v are projections of
# exact inputs rounded once, so its state is 0.0031-0.0037 from the
# reference's after a prompt and 0.0027-0.0033 after hundreds of decode
# steps (worst head, 26 requests).  Control: the same programs with the
# state planes in bfloat16, so that every decode step rounds the state:
# heads that forget slowly pile the roundings up to 0.0089-0.0146 after
# 378-512 steps (6 requests of 3 seeds through this runner, all
# ``correct: false`` by this limit alone; 12 more of 148-512 steps read
# 0.0101-0.0119).  The chunked prefill rounds only where a chunk hands
# the state on, so after the prompt the control reads 0.0035-0.0040
# beside the sound readings: it is the decode steps that tell.  The limit
# is the geometric middle of the largest sound and the smallest control
# reading (1.6 x over the one, 1.5 x under the other).  It holds the
# state's precision for all twelve layers because they share one plane
# and one step function; the later layers' own states cannot carry a
# limit, since their inputs have been rounded by the layers before them
# (0.9-5% from the reference, sound).
PREFILL_REL_L2_MAX = 0.1
DECODE_MEAN_REL_L2_MAX = 0.1
DECODE_STEP_REL_L2_MAX = 0.2
STATE_REL_L2_MAX = 0.006


def relative_errors(system: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """Per position, ``|system - reference|_2 / |reference|_2``."""
    return (np.linalg.norm(system - reference, axis=-1)
            / np.linalg.norm(reference, axis=-1))


def check_outputs(engine: Any, cell: Cell
                  ) -> tuple[list[str], dict[str, float]]:
    """The weights and the probed requests against the reference:
    ``(faults, {"prefill": largest error at a last prompt position,
    "decode": largest mean over a request's decode steps, "decode_step":
    largest single step, "state": largest error of a head of the first
    linear-attention layer's recurrent state, after a prompt or after a
    last decode step})``."""
    model = cell.config["program"]["model"]
    max_seq = int(cell.config["program"]["serving"]["max_seq"])
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
    worst = {"prefill": 0.0, "decode": 0.0, "decode_step": 0.0, "state": 0.0}
    for rid, rec in sorted(results.items()):
        if rec["end_state"] is None:
            faults.append(f"request {rid} did not finish")
            continue
        prompt = [int(t) for t in rec["prompt_ids"]]
        ids = prompt + rec["tokens"][:-1]
        # one shape for every request: the reference is causal, so what
        # follows a position cannot move it
        padded = ids + [0] * (max_seq - len(ids))
        want, states = reference.forward_logits(
            engine.params, padded, model["layer_types"],
            linear_key_head_dim=model["linear_key_head_dim"],
            linear_allow_neg_eigval=model["linear_allow_neg_eigval"],
            rms_norm_eps=model["rms_norm_eps"],
            positions=list(range(len(prompt) - 1, len(ids))),
            state_at=[len(prompt) - 1, len(ids) - 1])
        errors = relative_errors(np.stack(rec["logits"]), np.asarray(want))
        # [after the prompt, after the last step] of the first linear
        # layer, head by head: the largest over the heads
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
        tokens = rec["tokens"]
        print(f"[benchmark] request {rid} slot {rec['slot']} "
              f"recycled={rec['recycled']} prompt {len(prompt)} "
              f"tokens {len(tokens)} ({len(set(tokens))} distinct): "
              f"prefill {errors[0]:.5f}, "
              f"decode steps mean {steps.mean():.5f} median "
              f"{np.median(steps):.5f} max {steps.max():.5f}; first linear "
              f"layer's state after the prompt {state[0]:.5f}, after the "
              f"last step {state[1]:.5f}", file=sys.stderr)
    limits = {"prefill": PREFILL_REL_L2_MAX,
              "decode": DECODE_MEAN_REL_L2_MAX,
              "decode_step": DECODE_STEP_REL_L2_MAX,
              "state": STATE_REL_L2_MAX}
    print("[benchmark] against the float32 reference: "
          + ", ".join(f"{name} {worst[name]:.5f} (limit {limit})"
                      for name, limit in limits.items()), file=sys.stderr)
    faults += [f"{name} {worst[name]:.5f} from the reference, "
               f"limit {limit}" for name, limit in limits.items()
               if worst[name] > limit]
    return faults, worst


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: CompileCounter, scratch: str) -> Run:
    # ``serving.run`` builds its engine through the module's
    # ``build_engine`` and keeps it to itself; this runner needs the
    # engine before the warm-up (to name the probed requests) and after
    # the window (to fetch what it kept), so it wraps that one name for
    # the duration of the call and measures with ``serving.run`` itself
    held: dict[str, Any] = {}
    build = serving.build_engine

    def build_and_probe(cell_: Cell, seed_: int) -> Any:
        held["engine"] = engine = build(cell_, seed_)
        engine.probe(cell.traffic["check_rids"])
        return engine

    serving.build_engine = build_and_probe
    try:
        result = serving.run(cell, seed, seconds, trace, compiles, scratch)
    finally:
        serving.build_engine = build

    if not result.failed:
        # what the run served, for the readers that price it
        records = traffic_gen.generate(cell.traffic, seed, result.attempted)
        result.samples["served_prompt_len"] = [r["prompt_len"]
                                               for r in records]
        result.samples["served_output_len"] = [r["output_len"]
                                               for r in records]
    faults, worst = check_outputs(held["engine"], cell)
    result.scalars.update({f"{name}_rel_l2": value
                           for name, value in worst.items()})
    result.faults.extend(faults)
    result.correct = not result.faults
    return result
