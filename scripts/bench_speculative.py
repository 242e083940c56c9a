#!/usr/bin/env python
"""Speculative-decoding evidence: draft-and-verify vs the fused scan.

Measures the serving engine's speculative decode (docs/serving.md,
"Speculative decoding") through the engine's own trace replay and writes
``BENCH_spec.json`` at the repo root:

- **equivalence gate first** — every token-feedback setting (greedy,
  ngram, draft-model) replays the bench trace with token capture on and
  must produce completed-token sequences IDENTICAL to the per-step
  greedy oracle engine's; a mismatch aborts the bench before any number
  is published.  The ``off`` rows are the LEGACY continuous-feedback
  engine — their sequences differ from the token-quantised modes by
  design (the equivalence-gate weakening the tentpole documents), so
  they are throughput baselines, not identity subjects.
- **throughput grid** — {off, ngram γ in {2,4,8,16}, draft-model γ4}
  x {per-step, fused K16} over the SAME repeating-structure seeded
  trace (``prompt_period`` motif prompts + greedy-feedback cycles give
  the n-gram drafter real lookup structure).  Per-output-token
  throughput with TTFT/TPOT; speculation rows also record acceptance
  rate, mean accepted length, and draft overhead.  The acceptance bar
  — ngram γ16 at >= 1.2x the non-speculative fused-K16 engine — is
  recorded as a checked claim, not prose.

Methodology follows ``scripts/bench_serving.py``: one warmup replay per
engine absorbs compiles, settings are INTERLEAVED within each timed
repetition so host drift cancels, and medians of per-rep throughput are
reported with min/max spread.

On this image the mesh is CPU-simulated, which UNDERSELLS speculation:
each verify unit pays a host sync (commits must land before host
bookkeeping) that the fused scan amortises over K trips, and the
(γ+1)-wide verify forward is priced at its real FLOPs rather than the
weights-bound cost a real chip would give it.  The sim rows are honest
about that regime.  On the chip: not measured.

Usage: python scripts/bench_speculative.py [--requests N] [--reps R]
       [--chip]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from dlbb_tpu.utils.config import atomic_write_text  # noqa: E402

CHIP = "--chip" in sys.argv[1:]
if not CHIP:
    from dlbb_tpu.utils.simulate import force_cpu_simulation  # noqa: E402

    force_cpu_simulation(8)

import jax  # noqa: E402

from dlbb_tpu.comm.mesh import build_parallelism_mesh  # noqa: E402
from dlbb_tpu.models.configs import ModelConfig  # noqa: E402
from dlbb_tpu.serve.config import ServingConfig  # noqa: E402
from dlbb_tpu.serve.engine import ServingEngine  # noqa: E402
from dlbb_tpu.serve.traffic import generate_trace  # noqa: E402
from dlbb_tpu.stats.serving_report import (  # noqa: E402
    write_speculative_report,
)
from dlbb_tpu.utils.simulate import topology_record  # noqa: E402

SERVE = dict(max_batch=8, block_size=8, max_seq=160, queue_capacity=64)

# The bench model: the 2-layer tiny transformer on a dp2 x tp4 mesh —
# the SAME collective geometry the verify-step audit targets pin.
# Greedy argmax feedback through the fixed token table falls into short
# cycles within a few dozen tokens; with 96-128-token outputs the
# n-gram drafter's cyclic extension locks onto them, which is exactly
# the repeating-structure regime prompt-lookup drafting targets.
BENCH_MODEL = dict(hidden_size=64, num_layers=2, num_heads=4,
                   ffn_intermediate=128, dtype="float32",
                   attention="full")

FUSED = dict(decode_horizon=16)

# name -> ServingConfig kwargs.  "off" is the legacy continuous-feedback
# engine (the pre-speculation fast path); "greedy" is token feedback
# without drafting — the per-step greedy row IS the token-identity
# oracle every speculative setting is gated against.
SETTINGS = {
    "off_per_step": dict(speculation="off"),
    "off_fused16": dict(speculation="off", **FUSED),
    "greedy_per_step": dict(speculation="greedy"),
    "greedy_fused16": dict(speculation="greedy", **FUSED),
    "ngram_g2_per_step": dict(speculation="ngram", spec_gamma=2),
    "ngram_g2_fused16": dict(speculation="ngram", spec_gamma=2, **FUSED),
    "ngram_g4_per_step": dict(speculation="ngram", spec_gamma=4),
    "ngram_g4_fused16": dict(speculation="ngram", spec_gamma=4, **FUSED),
    "ngram_g8_per_step": dict(speculation="ngram", spec_gamma=8),
    "ngram_g8_fused16": dict(speculation="ngram", spec_gamma=8, **FUSED),
    "ngram_g16_fused16": dict(speculation="ngram", spec_gamma=16, **FUSED),
    "draft_g4_per_step": dict(speculation="draft-model", spec_gamma=4,
                              spec_draft_layers=1),
    "draft_g4_fused16": dict(speculation="draft-model", spec_gamma=4,
                             spec_draft_layers=1, **FUSED),
}
ORACLE = "greedy_per_step"
BASELINE = "off_fused16"
ACCEPTANCE = {"setting": "ngram_g16_fused16", "baseline": BASELINE,
              "min_speedup": 1.2}


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2]


def _bench_trace(num_requests: int):
    """The replayed repeating-structure trace: burst-ish poisson so the
    batch fills in one wave, motif prompts (period 4), long outputs so
    the greedy-feedback cycles dominate the drafted region."""
    return generate_trace(
        "poisson", num_requests, seed=7, rate=500.0,
        prompt_range=(8, 16), output_range=(96, 128), prompt_period=4)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16,
                    help="requests in the replayed trace (default 16 = "
                         "two admission waves)")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved repetitions per setting (default 3)")
    ap.add_argument("--chip", action="store_true",
                    help="run on the real TPU chip instead of the "
                         "simulated mesh")
    ap.add_argument("--output", default=str(REPO / "BENCH_spec.json"))
    args = ap.parse_args()

    model_cfg = ModelConfig.from_dict(BENCH_MODEL)
    mesh = build_parallelism_mesh(data_parallel=2, tensor_parallel=4)
    trace = _bench_trace(args.requests)

    # equivalence gate FIRST, on the published trace, with dedicated
    # capture engines (token capture syncs every unit, so the timed
    # engines below run with it off): every token-feedback setting must
    # match the per-step greedy oracle's completed sequences
    def _captured_tokens(extra):
        eng = ServingEngine(
            model_cfg, ServingConfig(**SERVE, **extra), mesh,
            verbose=False, capture_tokens=True)
        return eng.run_trace(trace)["completed_tokens"]

    oracle_tokens = _captured_tokens(SETTINGS[ORACLE])
    identity = {}
    for name, extra in SETTINGS.items():
        if extra.get("speculation", "off") == "off" or name == ORACLE:
            continue
        identity[name] = _captured_tokens(extra) == oracle_tokens
    if not all(identity.values()):
        bad = sorted(n for n, ok in identity.items() if not ok)
        raise SystemExit(
            "equivalence gate FAILED: speculative decode produced "
            f"different completed-token sequences than the per-step "
            f"greedy oracle for {bad} — refusing to publish throughput "
            "for a wrong result"
        )
    n_tok = sum(len(v) for v in oracle_tokens.values())
    print(f"[equivalence] {len(identity)} settings == {ORACLE} over "
          f"{n_tok} tokens: OK")

    # timed engines: capture off, one untimed warmup replay each to
    # absorb compiles, then interleaved timed repetitions
    engines = {
        name: ServingEngine(
            model_cfg, ServingConfig(**SERVE, **extra), mesh,
            verbose=False)
        for name, extra in SETTINGS.items()
    }
    for eng in engines.values():
        eng.run_trace(trace)

    per_rep: dict[str, list[dict]] = {name: [] for name in SETTINGS}
    for _ in range(args.reps):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            report = eng.run_trace(trace)
            wall = time.perf_counter() - t0
            spec = report.get("speculation", {})
            per_rep[name].append({
                "tok_s": report["completed_output_tokens"] / wall,
                "ttft_p50_s": report["ttft"]["median"],
                "per_token_p50_s": report["per_token_latency"]["median"],
                "decode_units": report["decode_units"],
                "verify_units": spec.get("verify_units", 0),
                "fallback_units": spec.get("fallback_units", 0),
                "acceptance_rate": spec.get("acceptance_rate"),
                "mean_accepted_len": spec.get("mean_accepted_len"),
                "draft_overhead_s": spec.get("draft_overhead_s"),
            })

    settings_out = {}
    for name, extra in SETTINGS.items():
        reps = per_rep[name]
        tok = [r["tok_s"] for r in reps]
        acc = [r["acceptance_rate"] for r in reps
               if r["acceptance_rate"] is not None]
        mal = [r["mean_accepted_len"] for r in reps
               if r["mean_accepted_len"] is not None]
        draft = [r["draft_overhead_s"] for r in reps
                 if r["draft_overhead_s"] is not None]
        settings_out[name] = {
            "speculation": extra.get("speculation", "off"),
            "spec_gamma": extra.get("spec_gamma"),
            "decode_horizon": extra.get("decode_horizon", 1),
            "output_tokens_per_s": {
                "median": _median(tok), "min": min(tok), "max": max(tok),
                "reps": tok,
            },
            "ttft_p50_ms": round(
                _median([r["ttft_p50_s"] for r in reps]) * 1e3, 3),
            "per_token_p50_ms": round(
                _median([r["per_token_p50_s"] for r in reps]) * 1e3, 3),
            "decode_units": _median([r["decode_units"] for r in reps]),
            "verify_units": _median([r["verify_units"] for r in reps]),
            "fallback_units": _median(
                [r["fallback_units"] for r in reps]),
            "acceptance_rate": (round(_median(acc), 4) if acc else None),
            "mean_accepted_len": (round(_median(mal), 3) if mal else None),
            "draft_overhead_s": (round(_median(draft), 4)
                                 if draft else None),
            "token_identical": identity.get(name),
        }
    # speedups are regime-matched: per-step rows price against the
    # legacy per-step engine, fused rows against the non-speculative
    # fused K16 engine — "what does drafting buy on top of the engine
    # you already run"
    for name, extra in SETTINGS.items():
        base_name = ("off_fused16" if extra.get("decode_horizon")
                     else "off_per_step")
        base_med = settings_out[base_name]["output_tokens_per_s"]["median"]
        med = settings_out[name]["output_tokens_per_s"]["median"]
        settings_out[name]["baseline"] = base_name
        settings_out[name]["speedup_vs_baseline"] = round(
            med / base_med, 3)
    acc_row = settings_out[ACCEPTANCE["setting"]]
    acceptance = {
        **ACCEPTANCE,
        "measured_speedup": acc_row["speedup_vs_baseline"],
        "passed": (acc_row["speedup_vs_baseline"]
                   >= ACCEPTANCE["min_speedup"]),
    }

    backend = jax.default_backend()
    payload = {
        "harness": "scripts/bench_speculative.py",
        "schema": "dlbb_bench_spec_v1",
        "model": dict(BENCH_MODEL),
        "serving": dict(SERVE),
        "mesh": {"dp": 2, "tp": 4},
        "trace": {"kind": trace.kind, "requests": len(trace),
                  "seed": trace.seed, "params": dict(trace.params)},
        "repetitions": args.reps,
        "baseline": BASELINE,
        "oracle": ORACLE,
        "methodology": (
            "identical repeating-structure seeded trace replayed "
            "through every engine; settings interleaved within each "
            "repetition; medians of per-rep completed-output-token "
            "throughput with min/max spread; greedy token-identity "
            "gate (every token-feedback setting == the per-step greedy "
            "oracle) run on the published trace before any timing"
        ),
        "backend": backend,
        "topology": topology_record(),
        "jax_version": jax.__version__,
        "host_cpu_count": os.cpu_count(),
        "timestamp": time.time(),
        "equivalence": {
            "checked": True,
            "oracle": ORACLE,
            "identical": dict(sorted(identity.items())),
            "tokens": n_tok,
            "note": ("off rows are the legacy continuous-feedback "
                     "engine: different sequences by design (the "
                     "documented equivalence-gate weakening), so they "
                     "are baselines, not identity subjects"),
        },
        "settings": settings_out,
        "acceptance": acceptance,
        "claim": (
            "CPU-simulated mesh: every verify unit pays a host sync "
            "(host bookkeeping needs the commit counts) that the fused "
            "scan amortises over K trips, and the (γ+1)-wide verify "
            "forward is priced at real FLOPs, not the weights-bound "
            "cost a chip gives it — so these rows UNDERSELL "
            "speculation; acceptance-rate and accepted-length columns "
            "are regime-independent signal."
            if backend == "cpu" else
            "chip run: walls are device-honest; verify forwards price "
            "weights-bound, the regime speculative decoding targets."
        ),
    }
    atomic_write_text(json.dumps(payload, indent=1) + "\n",
                      Path(args.output))
    write_speculative_report(Path(args.output), REPO / "stats" / "serving")
    for name in SETTINGS:
        s = settings_out[name]
        tps = s["output_tokens_per_s"]
        acc_s = ("-" if s["acceptance_rate"] is None
                 else f"{s['acceptance_rate']:.2f}")
        print(f"[{name:20s}] {tps['median']:8.1f} tok/s "
              f"({tps['min']:.1f}..{tps['max']:.1f})  "
              f"x{s['speedup_vs_baseline']:.2f} vs {s['baseline']}, "
              f"acc={acc_s}")
    print(f"[acceptance] {ACCEPTANCE['setting']} >= "
          f"{ACCEPTANCE['min_speedup']}x vs {BASELINE}: "
          f"{'PASS' if acceptance['passed'] else 'FAIL'} "
          f"({acceptance['measured_speedup']:.2f}x)")
    print(f"BENCH_spec.json -> {args.output}")
    return 0 if acceptance["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
