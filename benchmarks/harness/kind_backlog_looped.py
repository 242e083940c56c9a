"""``kind: backlog_looped``: what ``kind: backlog`` does
(``harness/serving.py``: every request due at t=0, the benchmark's own
feed and clock), and ``correct`` also holds the model's OUTPUT and the
EXIT GATE of every pass to a plain float32 reference, at the widths and
sizes that were timed.  The runner of a LOOPED stack (Ouro: the same
layers run ``total_ut_steps`` times a token), beside
``kind_backlog_checked.py``, whose comparison holds a recurrent state
this model has not, and ``kind_backlog_latent.py``, whose holds a
routing.

For the two requests the traffic file names (``check_rids``: one
admitted into a slot nobody had used, with a prompt of two chunks, one
into a recycled slot) the engine keeps, on the device, what its timed
programs produced (``ServingEngine.probe``): the logits of the last
prompt position (the chunked prefill) and of every decode step (the
decode kernel over the (pass, layer) planes), and at each of those
positions the exit gate of every pass.  The same compiled programs run
whether or not a request is checked, and nothing is synced or fetched
inside the window.  After the window they are fetched and compared with
the reference's whole forward pass (``benchmarks/reference/<name>.py``:
no cache, no chunks, ``total_ut_steps`` full passes over the whole
sequence) over the prompt and the tokens the engine committed.
Teacher-forced, so a flipped ``argmax`` cannot cascade.  The reference
also judges the weights it is handed (``weight_faults``), because both
sides read the same tree.

Three measures.  LOGITS, per position: ``|system - reference|_2 /
|reference|_2`` over the vocabulary; per request the last prompt
position's and the MEAN over its decode steps; the largest of each over
the checked requests is held to a limit, and so is the largest single
step.  GATES, per position and pass: ``|lambda - lambda_ref|``; per
request and pass the mean over its positions; held to a limit are pass
ONE's (48 layers deep where the logits are 192) and the largest of the
four.  KEYS: what the FIRST layer wrote into its cache plane in the
FIRST pass, of every token the request fed (the engine hands a copy of
the slot's rows of that plane beside the logits): per token ``|k -
k_ref|_2 / |k_ref|_2`` over the heads, the largest over the tokens and
the requests, and the same over a request's tokens taken whole.  A looped stack with seeded weights grows every rounding
from pass to pass, so that logits and gates sit a tenth from float32
with nothing wrong and cannot tell float32 norms from bfloat16 ones;
the keys have one norm, one projection and one rotation behind them and
nothing upstream to grow, and so are what reads the precision the
configuration states.
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
# runner on the chip (my chip runs, PR 33, TPU v5e, the published widths,
# the chip tool's calls 2 to 4; PERF.md section 6 has every reading; the
# controls are scripts/ouro_controls.py's).  The system computes in
# bfloat16 (weights, what a sub-layer is fed and gives, cached K/V) with
# a float32 residual stream, norms, softmax, rotary, SwiGLU activation,
# exit gate and logits, the reference in float32 throughout.
#
# LOGITS.  Sound: 0.101-0.165 at the last prompt position, 0.099-0.149 a
# request's mean decode step, 0.103-0.153 its largest, over 22 requests of
# 11 seeds and the untouched halves of 6 control runs: a looped stack with
# seeded weights GROWS a rounding from pass to pass (the gates below read
# it pass by pass), so its logits sit a tenth from float32 with nothing
# wrong, where the Olmo cell's 32 sub-layers read 0.025.  (With a
# bfloat16 residual stream, as first written and as the control
# ``stream_bfloat16`` runs it: 0.195-0.253, 0.173-0.227, 0.194-0.251 over
# 12 requests.)  Controls: rotary on adjacent pairs 0.966 / 0.983 /
# 0.992, rotary left off 0.936 / 0.935 / 0.965, three passes for four
# 1.163 / 1.170 / 1.189, the layers' two output norms left out 1.295 /
# 1.289 / 1.300, the final norm left out between passes 1.393 / 1.375 /
# 1.400; a decode step's pass t attending to pass t - 1's planes - /
# 1.264 / 1.320 and a prompt leaving the last pass's planes as they were
# - / 1.346 / 1.429 (both sound at the last prompt position: the chunk
# path is not theirs).  The limits are 2.4 to 2.9 x over the largest
# sound reading and 2.1 to 2.3 x under the nearest control.
#
# GATES.  Sound: pass one 0.0011-0.0029 (48 layers deep), the largest of
# the four 0.0096-0.053, and 0.077 in a run whose norms alone were
# rounded (pass four; heavy-tailed: it is where the growth ends up).
# Controls: pass one reads rotary on adjacent pairs 0.074, rotary off
# 0.115, the output norms left out 0.120; the largest of the four reads
# the stale last pass 0.257, three passes 0.293, the previous pass's
# planes 0.323, the norm between passes 0.328.  5 x and 5 x of room for
# pass one, 2.1 x and 1.6 x for the largest.
#
# KEYS, the first layer's in the first pass.  Sound: a whole request's
# 0.00255-0.00277, a token's at most 0.00299-0.00351, over 34 requests
# (the controls that leave the first layer alone among them).  The
# nearest precision below the configuration's, everything it states as
# float32 that has a seam (norms, rotary, a chunk's scores and softmax,
# the exit gate) rounded to bfloat16 by ``reduce_precision``, which NO
# other limit sees (logits 0.140-0.170, pass one's gate 0.0023): a whole
# request's 0.00392 / 0.00403, a token's 0.00548 / 0.00565; the norms
# alone so 0.00289 / 0.00332 and 0.00494 / 0.00503.  Rotary on adjacent
# pairs 1.12 and 1.26.  Each limit lies near the geometric middle of
# its nearest two readings: 1.16-1.2 x over the largest sound one,
# 1.18-1.22 x under the nearest control, on distributions a few per
# cent wide.
#
# NOT seen by any limit: the decode kernel's softmax and the SwiGLU's
# activation in bfloat16 (no seam for a control), and of the float32
# parts behind the first layer's keys only what reaches a limit above
# (PERF.md, Open questions).
PREFILL_REL_L2_MAX = 0.4
DECODE_MEAN_REL_L2_MAX = 0.4
DECODE_STEP_REL_L2_MAX = 0.45
GATE_FIRST_PASS_MAX = 0.015
GATE_ANY_PASS_MAX = 0.16
KEY_REL_L2_MAX = 0.0032
KEY_TOKEN_REL_L2_MAX = 0.0042
LIMITS = {"prefill": PREFILL_REL_L2_MAX, "decode": DECODE_MEAN_REL_L2_MAX,
          "decode_step": DECODE_STEP_REL_L2_MAX,
          "gate_first": GATE_FIRST_PASS_MAX, "gate_any": GATE_ANY_PASS_MAX,
          "key": KEY_REL_L2_MAX, "key_token": KEY_TOKEN_REL_L2_MAX}
# ONE shape for every checked request: the reference is computed over the
# longest one's tokens padded to a whole number of this many (it is
# causal, so what follows a position cannot move it)
PAD_TO = 128


def check_outputs(engine: Any, cell: Cell
                  ) -> tuple[list[str], dict[str, float]]:
    """The weights and the probed requests against the reference:
    ``(faults, {"prefill", "decode", "decode_step": the logits' errors
    as ``kind_backlog_checked`` reckons them; "gate_first": pass one's
    mean absolute gate error; "gate_any": the largest of the passes';
    "key": the largest error of a request's first-layer keys taken
    whole, "key_token": of one token's; "gate_pass<t>": each pass's
    (recorded, no limit)})``."""
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
    per_pass = np.zeros(int(model["total_ut_steps"]))
    checked = {}
    for rid, rec in sorted(results.items()):
        if rec.get("exit_gates") is None:
            faults.append(f"request {rid}: the programs returned no gates")
        elif len(rec["logits"]) != len(rec["tokens"]):
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
        at = list(range(len(prompt) - 1, len(ids)))
        began = time.perf_counter()
        want, want_gates = (np.asarray(a) for a in reference.forward_logits(
            engine.params, ids + [0] * (longest - len(ids)), model,
            positions=at, with_gates=True))
        took_s = time.perf_counter() - began
        errors = relative_errors(np.stack(rec["logits"]), want)
        gates = np.abs(np.stack(rec["exit_gates"]) - want_gates).mean(axis=0)
        # the slot's rows of the first K plane as the last decode step
        # left them, [blocks, block, kvh, d]: one row a token that was fed
        want_keys = np.asarray(reference.first_layer_keys(
            engine.params, ids, model))
        held = np.asarray(rec["end_state"], np.float32)
        held = held.reshape((-1,) + held.shape[2:])[
            :len(ids), :want_keys.shape[1]]
        keys = relative_errors(held.reshape(len(ids), -1),
                               want_keys.reshape(len(ids), -1))
        key_whole = float(np.linalg.norm(held - want_keys)
                          / np.linalg.norm(want_keys))
        if not (np.all(np.isfinite(errors)) and np.all(np.isfinite(gates))
                and np.all(np.isfinite(keys))):
            faults.append(f"request {rid}: logits, gates or keys not finite")
            continue
        steps = errors[1:] if len(errors) > 1 else np.zeros(1)
        worst["prefill"] = max(worst["prefill"], float(errors[0]))
        worst["decode"] = max(worst["decode"], float(steps.mean()))
        worst["decode_step"] = max(worst["decode_step"], float(steps.max()))
        worst["gate_first"] = max(worst["gate_first"], float(gates[0]))
        worst["gate_any"] = max(worst["gate_any"], float(gates.max()))
        worst["key"] = max(worst["key"], key_whole)
        worst["key_token"] = max(worst["key_token"], float(keys.max()))
        per_pass = np.maximum(per_pass, gates)
        print(f"[benchmark] request {rid} slot {rec['slot']} "
              f"recycled={rec['recycled']} prompt {len(prompt)} "
              f"tokens {len(tokens)} ({len(set(tokens))} distinct): "
              f"prefill {errors[0]:.5f}, "
              f"decode steps mean {steps.mean():.5f} median "
              f"{np.median(steps):.5f} max {steps.max():.5f}; exit gates "
              "by pass " + " ".join(f"{g:.5f}" for g in gates)
              + f"; first-layer keys {key_whole:.5f}, a token's at most "
              f"{keys.max():.5f} (the reference's gates lie in {want_gates.min():.3f} to "
              f"{want_gates.max():.3f}; over {longest} positions it took "
              f"{took_s:.1f} s)", file=sys.stderr)
    print("[benchmark] against the float32 reference: "
          + ", ".join(f"{name} {worst[name]:.5f} (limit {limit})"
                      for name, limit in LIMITS.items()), file=sys.stderr)
    faults += [f"{name} {worst[name]:.5f} from the reference, "
               f"limit {limit}" for name, limit in LIMITS.items()
               if worst[name] > limit]
    worst.update({f"gate_pass{t + 1}": float(g)
                  for t, g in enumerate(per_pass)})
    return faults, worst


# the report's samples the per-layer readers price the traced slice by
# (``readers/loop_roofline.py``), and its shares
REPORT_SAMPLES = ("unit_slot_steps", "unit_live_tokens")
REPORT_SHARES = ("exit_pass_mean", "kv_live_share")


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: CompileCounter, scratch: str) -> Run:
    # ``serving.run`` builds its engine through the module's
    # ``build_engine`` and keeps it and the report to itself; this
    # runner needs the engine before the warm-up (to name the probed
    # requests) and after the window (to fetch what it kept), and the
    # measured trace's report (the program's own counts), so it wraps
    # that one name for the duration of the call and measures with
    # ``serving.run`` itself, as ``kind_backlog_latent`` does
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
        f"{name}_rel_l2" if name in ("prefill", "decode", "decode_step")
        else name: value for name, value in worst.items()})
    result.faults.extend(faults)
    result.correct = not result.faults
    return result
