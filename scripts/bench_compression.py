#!/usr/bin/env python
"""Compressed-vs-uncompressed evidence for the quantised collectives.

Measures the compression axis (docs/compression.md) through the
framework's own timed regions and writes ``BENCH_compress.json`` at the
repo root:

- **micro** — ``allreduce_q`` / ``reducescatter_q`` under the
  ``compress_int8`` / ``compress_fp8`` / ``compress_int8_bf16acc``
  variants vs their uncompressed counterparts, swept through the PR-3
  engine (work-unit dedup, payload avals, measurement gate), with the
  ANALYTIC bytes-on-wire of each row (scale side channel included) from
  ``analysis/expectations.op_wire_bytes`` — the same model the comm-lint
  byte ceiling audits against the compiled HLO;
- **train** — loss-curve divergence of the int8/fp8 error-feedback runs
  vs the uncompressed DDP run over a short horizon.  Divergence beyond
  tolerance or a NaN blowup raises ``CorruptStats`` (the chaos harness's
  taxonomy) and lands as a quarantined row, never a silent pass.

Methodology follows ``scripts/bench_overlap.py``: settings are
INTERLEAVED within each repetition so host drift cancels across modes,
and medians-of-medians are reported with min/max spread.

On this image the mesh is CPU-simulated: a ppermute is a memcpy, so wall
clocks carry no fabric signal — the committed claim is **correctness +
wire volume** (equivalence pinned by tests/test_compression.py, the byte
ceiling by the comm-lint audit).  On the chip: not measured.

Usage: python scripts/bench_compression.py [--iters N] [--reps R]
       [--steps S] [--chip]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
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

from dlbb_tpu.analysis.expectations import op_wire_bytes  # noqa: E402
from dlbb_tpu.bench.runner import Sweep1D, run_sweep  # noqa: E402
from dlbb_tpu.resilience.errors import CorruptStats  # noqa: E402
from dlbb_tpu.train.loop import run_train  # noqa: E402
from dlbb_tpu.utils.simulate import topology_record  # noqa: E402

# measurement settings, interleaved per repetition: the uncompressed
# baseline ops under the default variant, the quantised ops under each
# compress_* variant
SETTINGS = (
    ("baseline_bf16", "default", ("allreduce", "reducescatter")),
    ("int8", "compress_int8", ("allreduce_q", "reducescatter_q")),
    ("fp8", "compress_fp8", ("allreduce_q", "reducescatter_q")),
    ("int8_bf16acc", "compress_int8_bf16acc",
     ("allreduce_q", "reducescatter_q")),
)
# compressed op -> the uncompressed op its step-time delta is against
BASELINE_OF = {"allreduce_q": "allreduce", "reducescatter_q": "reducescatter"}

SIZE_LABEL, SIZE_ELEMS = "64KB", 16384
RANKS = 8

# loss-divergence tolerances (max per-step relative difference vs the
# uncompressed run) — beyond these the row is QUARANTINED via CorruptStats
TRAIN_TOL = {"int8": 0.05, "fp8": 0.10}


def _micro_run(variant: str, operations, work: Path, iters: int) -> dict:
    out = work / f"micro_{variant}_{time.monotonic_ns()}"
    sweep = Sweep1D(
        implementation="bench_compress",
        variant=variant,
        operations=operations,
        data_sizes=((SIZE_LABEL, SIZE_ELEMS),),
        rank_counts=(RANKS,),
        dtype="bfloat16",
        warmup_iterations=2,
        measurement_iterations=iters,
        output_dir=str(out),
        compile_cache="off",
    )
    files = run_sweep(sweep, verbose=False)
    medians = {}
    for f in files:
        d = json.loads(Path(f).read_text())
        flat = sorted(t for row in d["timings"] for t in row)
        medians[d["operation"]] = flat[len(flat) // 2]
    return medians


def _train_run(compression: str, steps: int) -> list[float]:
    config = {
        "experiment": {"name": f"compress_{compression}"},
        "model": {"hidden_size": 64, "num_layers": 2, "num_heads": 4,
                  "ffn_intermediate": 128, "attention": "full",
                  "dtype": "float32"},
        "parallelism": {"world_size": 1, "data_parallel": 8},
        "input": {"batch_size": 8, "sequence_length": 32, "seed": 42},
        "execution": {"warmup_iterations": 1,
                      "benchmark_iterations": steps},
        "training": {"learning_rate": 1e-2,
                     **({"grad_compression": compression}
                        if compression != "none" else {})},
    }
    return [float(v) for v in run_train(config, verbose=False)["losses"]]


def _check_divergence(name: str, losses, ref, tol: float) -> float:
    """Max per-step relative divergence; CorruptStats on NaN/blowup —
    the same refusal taxonomy the sweep engine uses for poisoned stats."""
    import math

    if not all(math.isfinite(v) for v in losses):
        raise CorruptStats(
            f"{name}: non-finite loss in {losses} — refusing to publish"
        )
    div = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(ref, losses))
    if div > tol:
        raise CorruptStats(
            f"{name}: loss divergence {div:.4f} exceeds tolerance {tol} "
            f"vs the uncompressed run"
        )
    return div


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2]


def _spread(vals):
    return {
        "median_s": _median(vals),
        "min_s": min(vals),
        "max_s": max(vals),
        "repetitions": len(vals),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20,
                    help="measured iterations per config (default 20)")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved repetitions per setting (default 3)")
    ap.add_argument("--steps", type=int, default=10,
                    help="train steps for the loss-divergence run")
    ap.add_argument("--chip", action="store_true",
                    help="run on the real TPU chip instead of the "
                         "simulated mesh")
    ap.add_argument("--output", default=str(REPO / "BENCH_compress.json"))
    args = ap.parse_args()

    work = Path(tempfile.mkdtemp(prefix="bench_compress_"))
    micro: dict[str, list[dict]] = {name: [] for name, _, _ in SETTINGS}
    try:
        # absorb process one-time costs (imports, first dispatch)
        _micro_run("default", ("allreduce",), work, 3)
        for _ in range(args.reps):
            for name, variant, operations in SETTINGS:
                micro[name].append(
                    _micro_run(variant, operations, work, args.iters))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    micro_out = {}
    for name, variant, operations in SETTINGS:
        compression = None if name == "baseline_bf16" else \
            ("fp8" if name == "fp8" else "int8")
        per_op = {}
        for op in operations:
            per_op[op] = _spread([rep[op] for rep in micro[name]])
            per_op[op]["bytes_on_wire"] = op_wire_bytes(
                op, SIZE_ELEMS, RANKS, 2, compression=compression)
        micro_out[name] = per_op
    # step-time delta + wire ratio of each compressed row vs its baseline
    for name in ("int8", "fp8", "int8_bf16acc"):
        for op, base_op in BASELINE_OF.items():
            row = micro_out[name][op]
            base = micro_out["baseline_bf16"][base_op]
            row["vs_uncompressed"] = {
                "baseline_op": base_op,
                "step_time_ratio": row["median_s"] / base["median_s"],
                "wire_bytes_ratio": (
                    row["bytes_on_wire"] / base["bytes_on_wire"]),
            }

    # ---- train-side loss divergence ------------------------------------
    ref = _train_run("none", args.steps)
    train_out = {"uncompressed_losses": ref,
                 "steps": args.steps, "tolerances": TRAIN_TOL}
    for comp in ("int8", "fp8"):
        try:
            losses = _train_run(comp, args.steps)
            div = _check_divergence(comp, losses, ref, TRAIN_TOL[comp])
            train_out[comp] = {
                "losses": losses,
                "max_relative_divergence": div,
                "within_tolerance": True,
            }
        except CorruptStats as e:
            # the refusal path: a blowup is published as a quarantined
            # row with the reason, never as a green number
            train_out[comp] = {"quarantined": True, "error": str(e)}

    backend = jax.default_backend()
    host_claim = (
        "CPU-simulated mesh: a ppermute is a memcpy, so walls carry no "
        "fabric signal.  The committed claim is correctness + wire "
        "volume: compressed == uncompressed within wire-dtype tolerance "
        "(tests/test_compression.py), the int8 wire <= 0.55x the bf16 "
        "baseline with scales included (comm-lint wire-volume ceiling, "
        "compressed targets in the default registry), and the train "
        "loss curves above within tolerance."
    )
    payload = {
        "harness": "scripts/bench_compression.py",
        "schema": "dlbb_bench_compress_v1",
        "grid": {
            "micro": f"allreduce(_q) + reducescatter(_q), {SIZE_LABEL} "
                     f"({SIZE_ELEMS} elems) x {RANKS} ranks, bf16 payload",
            "train": "h64 L2 full-attention DDP, dp=8, b8 x s32, "
                     f"{args.steps} steps",
        },
        "iterations_per_config": args.iters,
        "repetitions": args.reps,
        "methodology": (
            "settings interleaved within each repetition; medians of "
            "per-rep medians with min/max spread (PR-3 convention); "
            "bytes_on_wire is analytic (analysis/expectations."
            "op_wire_bytes, scale side channel included) — the same "
            "model comm-lint audits against the compiled HLO"
        ),
        "backend": backend,
        "topology": topology_record(),
        "jax_version": jax.__version__,
        "host_cpu_count": os.cpu_count(),
        "timestamp": time.time(),
        "micro_seconds_per_iteration": micro_out,
        "train_loss_divergence": train_out,
        "claim": host_claim if backend == "cpu" else (
            "chip run: walls are device-honest; compression shows as "
            "the _q rows beating their uncompressed baselines at equal "
            "logical payload"
        ),
    }
    atomic_write_text(json.dumps(payload, indent=1) + "\n",
                      Path(args.output))
    for name, _, operations in SETTINGS:
        row = micro_out[name]
        parts = [f"{op} {row[op]['median_s'] * 1e3:8.3f} ms"
                 for op in operations]
        print(f"[{name:13s}] " + " | ".join(parts))
    for comp in ("int8", "fp8"):
        r = train_out[comp]
        if r.get("quarantined"):
            print(f"[train/{comp}] QUARANTINED: {r['error']}")
        else:
            print(f"[train/{comp}] max divergence "
                  f"{r['max_relative_divergence']:.5f} "
                  f"(tol {TRAIN_TOL[comp]})")
    print(f"BENCH_compress.json -> {args.output}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
