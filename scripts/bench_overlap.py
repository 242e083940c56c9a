#!/usr/bin/env python
"""Fused-vs-decomposed evidence for the overlapped collective matmul.

Measures the three TP schedules (``off``/fused, ``ring``, ``bidir`` —
docs/overlap.md) through the framework's own timed regions and writes
``BENCH_overlap.json`` at the repo root:

- **micro** — the two collective-matmul ops (``ag_matmul`` /
  ``matmul_rs``) swept through the PR-3 engine (work-unit dedup, payload
  avals, measurement gate) under the ``default`` / ``overlap_ring`` /
  ``overlap_bidir`` variants;
- **e2e** — the TP transformer forward (``bench/e2e.py``) under
  ``model.tp_overlap`` off/ring/bidir.

Methodology follows ``scripts/bench_sweep_engine.py``: settings are
INTERLEAVED within each repetition so host drift cancels across modes,
and medians-of-medians are reported with min/max spread.

On this image the mesh is CPU-simulated: every device is a host thread
and a ppermute is a memcpy, so wall clocks say nothing about ICI overlap
— the committed artifact's claim is **correctness + schedule shape**
(equivalence is pinned by tests/test_collective_matmul.py, the permute
chain by the comm-lint HLO audit).  The chip's numbers are
``scripts/overlap_candidates.py``'s (docs/overlap.md, "Chip measurement
status").

Usage: python scripts/bench_overlap.py [--iters N] [--reps R] [--chip]
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

from dlbb_tpu.bench.e2e import run_e2e  # noqa: E402
from dlbb_tpu.bench.runner import Sweep3D, run_sweep  # noqa: E402

SCHEDULES = ("off", "ring", "bidir")
# micro-op variant per schedule (the fused baseline is the default variant)
VARIANT_OF = {"off": "default", "ring": "overlap_ring",
              "bidir": "overlap_bidir"}

# LLM-shaped micro grid: S and H divide the 8-rank ring; small enough
# that the simulated mesh measures in seconds, big enough that the
# matmul dominates trace overhead
MICRO_GRID = dict(batch_sizes=(2,), seq_lengths=(256,), hidden_dims=(256,))

E2E_MODEL = {
    "hidden_size": 256,
    "num_layers": 2,
    "num_heads": 8,
    "ffn_intermediate": 1024,
    "attention": "full",
    "dtype": "float32",
}


def _micro_run(schedule: str, work: Path, iters: int) -> dict:
    out = work / f"micro_{schedule}_{time.monotonic_ns()}"
    sweep = Sweep3D(
        implementation="bench_overlap",
        variant=VARIANT_OF[schedule],
        operations=("ag_matmul", "matmul_rs"),
        rank_counts=(8,),
        dtype="float32",
        warmup_iterations=2,
        measurement_iterations=iters,
        output_dir=str(out),
        compile_cache="off",
        **MICRO_GRID,
    )
    files = run_sweep(sweep, verbose=False)
    medians = {}
    for f in files:
        d = json.loads(Path(f).read_text())
        flat = sorted(t for row in d["timings"] for t in row)
        medians[d["operation"]] = flat[len(flat) // 2]
    return medians


def _e2e_run(schedule: str, iters: int) -> float:
    config = {
        "experiment": {"name": f"overlap_{schedule}"},
        "model": dict(E2E_MODEL, tp_overlap=schedule),
        "parallelism": {"world_size": 8, "data_parallel": 1},
        "input": {"batch_size": 2, "sequence_length": 256, "seed": 42},
        "execution": {"warmup_iterations": 2,
                      "benchmark_iterations": iters},
    }
    result = run_e2e(config, verbose=False)
    return float(result["forward_time"]["median"])


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
                    help="interleaved repetitions per schedule (default 3)")
    ap.add_argument("--chip", action="store_true",
                    help="run on the real TPU chip instead of the "
                         "simulated mesh")
    ap.add_argument("--output", default=str(REPO / "BENCH_overlap.json"))
    args = ap.parse_args()

    work = Path(tempfile.mkdtemp(prefix="bench_overlap_"))
    micro: dict[str, list[dict]] = {s: [] for s in SCHEDULES}
    e2e: dict[str, list[float]] = {s: [] for s in SCHEDULES}
    try:
        # absorb process one-time costs so the first measured schedule
        # isn't billed for imports/first-dispatch
        _micro_run("off", work, 3)
        for _ in range(args.reps):
            # interleave schedules within each repetition (host-drift
            # cancellation, same convention as bench_sweep_engine.py)
            for s in SCHEDULES:
                micro[s].append(_micro_run(s, work, args.iters))
            for s in SCHEDULES:
                e2e[s].append(_e2e_run(s, args.iters))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    backend = jax.default_backend()
    micro_out = {
        s: {
            op: _spread([rep[op] for rep in micro[s]])
            for op in ("ag_matmul", "matmul_rs")
        }
        for s in SCHEDULES
    }
    e2e_out = {s: _spread(e2e[s]) for s in SCHEDULES}

    host_claim = (
        "CPU-simulated mesh: devices are host threads and ppermute is a "
        "memcpy, so these walls carry no ICI-overlap signal.  The "
        "committed claim is correctness + schedule shape: ring/bidir == "
        "fused numerically (tests/test_collective_matmul.py) and the "
        "compiled programs are pure collective-permute chains with no "
        "surviving fused collective (comm-lint HLO audit, overlap "
        "targets in the default registry)."
    )
    payload = {
        "harness": "scripts/bench_overlap.py",
        "schema": "dlbb_bench_overlap_v1",
        "grid": {
            "micro": "ag_matmul + matmul_rs, B2 x S256 x H256, 8 ranks",
            "e2e": "h256 L2 full-attention forward, tp=8, B2 x S256",
        },
        "iterations_per_config": args.iters,
        "repetitions": args.reps,
        "methodology": (
            "schedules interleaved within each repetition; medians of "
            "per-rep medians with min/max spread (PR-3 convention, "
            "scripts/bench_sweep_engine.py)"
        ),
        "backend": backend,
        "jax_version": jax.__version__,
        "host_cpu_count": os.cpu_count(),
        "timestamp": time.time(),
        "micro_seconds_per_iteration": micro_out,
        "e2e_forward_seconds": e2e_out,
        "claim": host_claim if backend == "cpu" else (
            "chip run: walls are device-honest; overlap shows as "
            "ring/bidir e2e forward beating off"
        ),
    }
    atomic_write_text(json.dumps(payload, indent=1) + "\n",
                      Path(args.output))
    for s in SCHEDULES:
        print(f"[{s:5s}] e2e fwd median {e2e_out[s]['median_s']*1e3:8.2f} ms"
              f" | ag_matmul {micro_out[s]['ag_matmul']['median_s']*1e3:7.3f}"
              f" ms | matmul_rs"
              f" {micro_out[s]['matmul_rs']['median_s']*1e3:7.3f} ms")
    print(f"BENCH_overlap.json -> {args.output}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
