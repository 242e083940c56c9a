#!/usr/bin/env python
"""Publish the real-TPU-chip E2E artifact set under ``results/e2e/``.

The CPU-simulated corpus (``scripts/publish_baselines.py``) covers the
collective sweeps; this script covers the part only the real chip can
measure — the E2E TP-forward benchmark (reference ``run_mpi.py`` semantics)
on the headline model configs.  Run WITHOUT ``--simulate`` on the TPU image:
the artifacts record the one v5e chip (world_size=1; multi-chip TP numbers
require a pod and are covered by the dryrun + simulated corpus instead).

Configs mirror ``bench.py``'s headline + extras set so the committed
artifacts substantiate the BENCH_r*.json lines:

- 1B  x {simplified, full, flash, dense}  @ S=512
- 7B  x {simplified, full, dense}         @ S=512
- 1B  x {full, dense}  @ S=1024  (flash auto-route pair)
- 1B  x flash @ {2048, 4096, 8192} + the dense@8192 infeasibility
  boundary artifact (long-context ladder, SURVEY §5.7)

Usage: python scripts/publish_tpu_e2e.py [--iters N]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))  # _publish_common

from dlbb_tpu.utils.config import atomic_write_text  # noqa: E402

CONFIGS = (
    ("1B", "simplified", 512),
    ("1B", "full", 512),
    ("1B", "flash", 512),
    ("1B", "dense", 512),   # pinned dense kernel: the un-routed baseline
    ("7B", "simplified", 512),
    ("7B", "full", 512),
    ("7B", "dense", 512),
    ("1B", "full", 1024),
    ("1B", "dense", 1024),
    # long-context ladder (SURVEY §5.7): O(S) flash memory vs the dense
    # path's [B,N,S,S] score tensor — dense is expected to RESOURCE_EXHAUST
    # by S=8192 (16 GiB scores); its failure is recorded, not hidden
    ("1B", "flash", 2048),
    ("1B", "flash", 4096),
    ("1B", "flash", 8192),
    ("1B", "dense", 8192),   # expected infeasible — see EXPECTED_FAIL_OK
)

# Configs whose MEMORY failure is itself the measurement (capability
# boundary): when the worker subprocess dies with a memory/compile-planning
# error signature, a *_infeasible.json boundary artifact is written and the
# run continues; any OTHER failure there still counts as a real failure.
EXPECTED_FAIL_OK = {("1B", "dense", 8192)}


BATCH_SIZE = 8  # every config in this script runs at B=8 (see _run_one)


def _experiment_name(size: str, attention: str, seq: int) -> str:
    return f"{size.lower()}_{attention}_s{seq}_world1"


def _artifact_name(size: str, attention: str, seq: int) -> str:
    """The ONE producer of the artifact basename — must match what
    ``run_e2e`` writes (``dlbb_tpu/bench/e2e.py``: ``xla_tpu_<name>.json``
    from the experiment name this script passes in)."""
    return f"xla_tpu_{_experiment_name(size, attention, seq)}"


def _boundary_reason(size: str, attention: str, seq: int) -> str:
    """Deterministic boundary reason computed from the config's own
    parameters (not hardcoded text): the dense path's [B, N, S, S] fp32
    score tensor vs the 16 GiB v5e HBM."""
    from dlbb_tpu.models.configs import MODEL_CONFIGS

    # the score-tensor arithmetic below is dense-path physics; a new
    # EXPECTED_FAIL_OK entry with another attention mode needs its own
    # reason rather than a factually wrong interpolation of this one
    assert attention == "dense", attention
    n_heads = MODEL_CONFIGS[size].num_heads
    score_gib = BATCH_SIZE * n_heads * seq * seq * 4 / 2**30
    return (
        f"{attention} attention materialises the [B, N, S, S] score "
        f"tensor ({score_gib:.0f} GiB fp32 at B={BATCH_SIZE}, "
        f"N={n_heads}, S={seq}) against the 16 GiB v5e HBM; the flash "
        f"artifact at the same shape is the measured alternative"
    )


def write_boundary_artifact(size: str, attention: str, seq: int,
                            output: str, exit_code: int,
                            observed_error: str) -> Path:
    """The deterministic boundary-artifact writer — the ONLY producer of
    ``*_infeasible.json`` files, so the committed corpus is reproducible
    from this script.  ``observed_error`` is the final error line from the
    worker's stderr (what actually happened), kept separate from the
    deterministic ``reason`` (why the boundary exists)."""
    boundary = {
        "experiment": {
            "name": _experiment_name(size, attention, seq),
        },
        "status": "infeasible",
        "reason": _boundary_reason(size, attention, seq),
        "observed_error": observed_error,
        "exit_code": exit_code,
    }
    out = Path(output)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{_artifact_name(size, attention, seq)}_infeasible.json"
    atomic_write_text(json.dumps(boundary, indent=2) + "\n", path)
    return path


def _run_one(size: str, attention: str, seq: int, iters: int,
             output: str) -> None:
    from _publish_common import require_tpu

    require_tpu()

    from dlbb_tpu.bench.e2e import run_e2e

    config = {
        "experiment": {
            "name": _experiment_name(size, attention, seq),
        },
        "model": {"size": size, "attention": attention},
        "parallelism": {"world_size": 1, "data_parallel": 1},
        "input": {"batch_size": BATCH_SIZE, "sequence_length": seq,
                  "seed": 42},
        "execution": {"warmup_iterations": 3,
                      "benchmark_iterations": iters},
    }
    run_e2e(config, output_dir=output)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--output", default=str(REPO / "results" / "e2e"))
    ap.add_argument("--only", default=None, metavar="SIZE,ATTENTION,SEQ",
                    help="run a single config in THIS process (the "
                         "per-config worker mode)")
    args = ap.parse_args()

    if args.only:
        size, attention, seq = args.only.split(",")
        _run_one(size, attention, int(seq), args.iters, args.output)
        return 0

    # One subprocess per config: a fresh process means a fresh HBM arena —
    # running the whole set in-process accumulates enough leftover
    # allocations that the 7B configs hit RESOURCE_EXHAUSTED on the 16 GB
    # chip after the three 1B models have run.
    from _publish_common import run_worker_matrix

    return run_worker_matrix(
        __file__,
        list(CONFIGS),
        only_str=lambda c: f"{c[0]},{c[1]},{c[2]}",
        artifact_name=lambda c: _artifact_name(*c),
        expected_fail_ok=EXPECTED_FAIL_OK,
        write_boundary=lambda c, out, rc, obs: write_boundary_artifact(
            *c, out, rc, obs),
        output=args.output,
        iters=args.iters,
        label=lambda c: f"{c[0]}/{c[1]}/s{c[2]}",
    )


if __name__ == "__main__":
    sys.exit(main())
