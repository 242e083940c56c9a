#!/usr/bin/env python
"""Decode fast-path evidence: per-step vs fused-K.

Measures the serving engine's decode fast path (docs/serving.md) through
the engine's own trace replay and writes ``BENCH_serve.json`` at the
repo root:

- **throughput grid** — the SAME seeded poisson trace (decode-bound: a
  burst arrival so the batch stays full) replayed through the per-step
  PR-9 engine and the fused-scan engine at K in {4, 16, 64}, plus a
  tp-only pair on a staggered trace.  The acceptance bar —
  fused K=16 at >= 1.5x the per-step engine's per-output-token
  throughput on the simulated 8-rank mesh — is recorded as a checked
  claim, not prose.
- **equivalence gate** — before any timing, per-step and fused-K
  engines replay a smoke trace with token capture on and must produce
  IDENTICAL completed-token sequences (the argmax-token contract the
  ``serve_fastpath_smoke`` CI stage also pins); a mismatch aborts the
  bench.

Methodology follows ``scripts/bench_compression.py``: settings are
INTERLEAVED within each repetition so host drift cancels across modes,
and medians of per-rep throughput are reported with min/max spread.

On this image the mesh is CPU-simulated — which is exactly the regime
the fast path targets: host dispatch dominates µs-scale decode steps
(the committed cm1 calibration under-predicts ~289x geomean for this
reason), so collapsing K dispatches into one on-device ``lax.scan`` is
measurable signal, not fabric noise.  On the chip: not measured.

Usage: python scripts/bench_serving.py [--requests N] [--reps R] [--chip]
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
from dlbb_tpu.stats.serving_report import write_fastpath_report  # noqa: E402
from dlbb_tpu.utils.simulate import topology_record  # noqa: E402

SERVE = dict(max_batch=8, block_size=16, max_seq=256, queue_capacity=64)

# The bench model: 2-layer MHA at h128 on a dp=8 batch-parallel mesh —
# the DISPATCH-OVERHEAD regime the fast path targets.  On the dp-only
# mesh the decode step lowers to ZERO collectives (audited:
# plan_expected_kinds(dp=8, decode=True) == {}), so the per-step wall
# is device work + per-dispatch host/runtime overhead — exactly the
# cost a fused scan amortises.  The tp4 rows below keep the
# collective-heavy geometry in the grid for honesty: on THIS cpu-sim
# runtime the per-trip collective sync dominates there and fusing
# barely pays (the chip rows re-price that regime on real fabric).
BENCH_MODEL = dict(hidden_size=128, num_layers=2, num_heads=8,
                   num_kv_heads=8, ffn_intermediate=256,
                   dtype="float32", attention="full")

# name -> (mesh key, trace key, ServingConfig fast-path kwargs).  K=1
# IS the per-step PR-9 engine.  The main grid replays the decode-bound
# trace (one aligned admission wave, uniform long outputs — the
# regime the acceptance bar describes); the tp4 rows replay the
# STAGGERED trace (lognormal outputs, so occupancy decays through the
# drain) on identical tp-only topology.
SETTINGS = {
    "per_step": ("dp8", "uniform", {}),
    "fused_k4": ("dp8", "uniform",
                 dict(decode_horizon=4, inflight_window=2)),
    "fused_k16": ("dp8", "uniform",
                  dict(decode_horizon=16, inflight_window=2)),
    "fused_k64": ("dp8", "uniform",
                  dict(decode_horizon=64, inflight_window=2)),
    "tp4_per_step": ("tp4", "staggered", {}),
    "tp4_fused_k16": ("tp4", "staggered",
                      dict(decode_horizon=16, inflight_window=2)),
}
BASELINE = "per_step"
ACCEPTANCE = {"setting": "fused_k16", "min_speedup": 1.5}


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2]


def _build_meshes():
    devs = jax.devices()
    return {
        "dp8": build_parallelism_mesh(data_parallel=8),
        "tp4": build_parallelism_mesh(tensor_parallel=4,
                                      devices=devs[:4]),
    }


def _traces(num_requests: int) -> dict:
    """The two replayed traces (identical per setting, seeded).

    ``uniform``: a burst arrival filling every slot in ONE admission
    wave, uniform long outputs — pure decode-bound replay where the
    event horizon equals the drain, so fused scans reach full K.
    ``staggered``: lognormal outputs, so slots complete at different
    times and occupancy decays through the drain — the regime where
    overshoot masking is exercised.
    """
    return {
        "uniform": generate_trace(
            "poisson", num_requests, seed=11, rate=1e5,
            prompt_range=(8, 16), output_range=(240, 240)),
        "staggered": generate_trace(
            "poisson", num_requests, seed=12, rate=1e5,
            prompt_range=(8, 16), output_range=(32, 240)),
    }


def _equivalence_gate(model_cfg, meshes) -> dict:
    """Per-step vs fused-K token sequences must be identical on a smoke
    trace before any number is published."""
    trace = generate_trace("poisson", 16, seed=3, rate=2000.0,
                           prompt_range=(8, 32), output_range=(8, 24))
    tokens = {}
    for name in ("per_step", "fused_k16"):
        mesh_key, _trace_key, extra = SETTINGS[name]
        engine = ServingEngine(
            model_cfg, ServingConfig(**SERVE, **extra), meshes[mesh_key],
            verbose=False, capture_tokens=True,
        )
        tokens[name] = engine.run_trace(trace)["completed_tokens"]
    identical = tokens["per_step"] == tokens["fused_k16"]
    if not identical:
        raise SystemExit(
            "equivalence gate FAILED: fused-K decode produced different "
            "completed-token sequences than the per-step engine — "
            "refusing to publish throughput for a wrong result"
        )
    return {
        "checked": True,
        "identical": True,
        "requests": len(tokens["per_step"]),
        "tokens": sum(len(v) for v in tokens["per_step"].values()),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8,
                    help="requests in the replayed trace (default 8 = "
                         "one full admission wave)")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved repetitions per setting (default 3)")
    ap.add_argument("--chip", action="store_true",
                    help="run on the real TPU chip instead of the "
                         "simulated mesh")
    ap.add_argument("--output", default=str(REPO / "BENCH_serve.json"))
    args = ap.parse_args()

    model_cfg = ModelConfig.from_dict(BENCH_MODEL)
    meshes = _build_meshes()
    equivalence = _equivalence_gate(model_cfg, meshes)
    print(f"[equivalence] per-step == fused_k16 over "
          f"{equivalence['tokens']} tokens: OK")

    traces = _traces(args.requests)
    engines = {}
    for name, (mesh_key, _trace_key, extra) in SETTINGS.items():
        engines[name] = ServingEngine(
            model_cfg, ServingConfig(**SERVE, **extra), meshes[mesh_key],
            verbose=False,
        )
    # absorb compiles + first-dispatch costs outside the timed reps
    for name, (_m, trace_key, _e) in SETTINGS.items():
        engines[name].run_trace(traces[trace_key])

    per_rep: dict[str, list[dict]] = {name: [] for name in SETTINGS}
    for _ in range(args.reps):
        for name, (_m, trace_key, _e) in SETTINGS.items():
            report = engines[name].run_trace(traces[trace_key])
            per_rep[name].append({
                "tok_s": report["goodput_tokens_per_s"],
                "per_token_p50_s":
                    report["per_token_latency"]["median"],
                "decode_units": report["decode_units"],
                "decode_steps": report["decode_steps"],
                "fused_steps": report["fast_path"]["fused_steps"],
            })

    settings_out = {}
    for name, (mesh_key, trace_key, extra) in SETTINGS.items():
        reps = per_rep[name]
        tok = [r["tok_s"] for r in reps]
        settings_out[name] = {
            "mesh": mesh_key,
            "trace": trace_key,
            "decode_horizon": extra.get("decode_horizon", 1),
            "inflight_window": extra.get("inflight_window", 1),
            "output_tokens_per_s": {
                "median": _median(tok), "min": min(tok), "max": max(tok),
                "reps": tok,
            },
            "per_token_p50_ms": round(
                _median([r["per_token_p50_s"] for r in reps]) * 1e3, 3),
            "decode_units": _median([r["decode_units"] for r in reps]),
            "decode_steps": _median([r["decode_steps"] for r in reps]),
            "fused_steps": _median([r["fused_steps"] for r in reps]),
        }
    # speedups are within-mesh, within-trace: the dp8 grid prices
    # against per_step, the tp4 rows against tp4_per_step
    for name, (mesh_key, _t, _e) in SETTINGS.items():
        base_name = "tp4_per_step" if mesh_key == "tp4" else BASELINE
        base_med = settings_out[base_name]["output_tokens_per_s"]["median"]
        med = settings_out[name]["output_tokens_per_s"]["median"]
        settings_out[name]["baseline"] = base_name
        settings_out[name]["speedup_vs_per_step"] = round(
            med / base_med, 3)
    acc = settings_out[ACCEPTANCE["setting"]]["speedup_vs_per_step"]
    acceptance = {
        **ACCEPTANCE,
        "measured_speedup": acc,
        "passed": acc >= ACCEPTANCE["min_speedup"],
    }

    backend = jax.default_backend()
    payload = {
        "harness": "scripts/bench_serving.py",
        "schema": "dlbb_bench_serve_v1",
        "model": dict(BENCH_MODEL),
        "serving": dict(SERVE),
        "traces": {
            key: {"kind": t.kind, "requests": len(t), "seed": t.seed,
                  "params": dict(t.params)}
            for key, t in traces.items()
        },
        "repetitions": args.reps,
        "baseline": BASELINE,
        "methodology": (
            "identical seeded trace replayed through every engine; "
            "settings interleaved within each repetition; medians of "
            "per-rep goodput with min/max spread; equivalence gate "
            "(identical argmax-token sequences) run before any timing"
        ),
        "backend": backend,
        "topology": topology_record(),
        "jax_version": jax.__version__,
        "host_cpu_count": os.cpu_count(),
        "timestamp": time.time(),
        "equivalence": equivalence,
        "settings": settings_out,
        "acceptance": acceptance,
        "claim": (
            "CPU-simulated mesh: per-decode-step wall is dominated by "
            "host dispatch (the committed cm1 calibration under-"
            "predicts ~289x geomean for exactly this reason), which is "
            "the overhead the fused scan removes — K dispatches become "
            "one lax.scan.  Fabric-sensitive deltas re-price on chip."
            if backend == "cpu" else
            "chip run: walls are device-honest; the fused rows price "
            "real dispatch amortisation on hardware."
        ),
    }
    atomic_write_text(json.dumps(payload, indent=1) + "\n",
                      Path(args.output))
    write_fastpath_report(Path(args.output), REPO / "stats" / "serving")
    for name in SETTINGS:
        s = settings_out[name]
        tps = s["output_tokens_per_s"]
        print(f"[{name:22s}] {tps['median']:8.1f} tok/s "
              f"({tps['min']:.1f}..{tps['max']:.1f})  "
              f"x{s['speedup_vs_per_step']:.2f} vs per-step, "
              f"{s['decode_units']} dispatches")
    print(f"[acceptance] {ACCEPTANCE['setting']} >= "
          f"{ACCEPTANCE['min_speedup']}x: "
          f"{'PASS' if acceptance['passed'] else 'FAIL'} "
          f"({acc:.2f}x)")
    print(f"BENCH_serve.json -> {args.output}")
    return 0 if acceptance["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
