"""End-to-end tensor-parallel forward-pass benchmark.

Replacement for the reference's E2E harness (``run_mpi.py``): YAML config in,
TP transformer + fixed synthetic batch, warmup + timed forward passes,
metrics JSON out.  Differences by design:

- ``mpirun``-spawned ranks → a ``(dp, tp)`` device mesh; the reference's
  ``world_size`` is the TP degree (its only model parallelism — SURVEY §2.2);
- per-iteration ``comm.Barrier()`` pairs (``run_mpi.py:177,183``) →
  ``block_until_ready`` on the jitted step;
- the warmup loop (``run_mpi.py:154-166``) absorbs XLA compilation, which is
  timed separately (first-call cost is compile, not page-faulting —
  SURVEY §7);
- cross-rank variance/CV of forward means (``run_mpi.py:199-212``) becomes
  cross-*host* variance; on a single process it is zero and recorded as such.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from dlbb_tpu.data.synthetic import create_dataset_from_config
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.parallel.plan import ParallelismPlan
from dlbb_tpu.models.sharding import batch_spec
from dlbb_tpu.models.transformer import (
    forward,
    forward_flops,
    init_params_sharded,
    named,
    num_parameters,
)
from dlbb_tpu.ops import mosaic_call_count
from dlbb_tpu.utils.config import load_config, save_json
from dlbb_tpu.utils.metrics import Timer, summarize
from dlbb_tpu.utils.profiling import annotate
from dlbb_tpu.utils.sysinfo import collect_system_info, device_spread
from dlbb_tpu.utils.timing import (
    force_completion,
    resolve_timing_mode,
    time_fn_chained,
    time_fn_per_iter,
)


def build_forward_step(model_cfg: ModelConfig, mesh,
                       num_microbatches: Optional[int] = None):
    """The jitted forward pass ``step(params, x) -> y``, batch-sharded
    output; its program name in a device trace is ``forward``."""
    @named("forward")
    def fwd(p, x):
        return forward(p, x, model_cfg, mesh=mesh,
                       num_microbatches=num_microbatches)

    return jax.jit(fwd,
                   out_shardings=NamedSharding(mesh, batch_spec(mesh)))


def run_e2e(
    config: dict[str, Any],
    devices: Optional[Sequence] = None,
    output_dir: Optional[str] = None,
    verbose: bool = True,
) -> dict[str, Any]:
    """Run the benchmark described by ``config`` (schema:
    ``configs/baseline_config.yaml``; parity with ``run_mpi.py:main``)."""
    with Timer() as t_init:
        model_cfg = ModelConfig.from_dict(config["model"])
        plan = ParallelismPlan.from_config(config, model_cfg, devices)
        mesh, num_microbatches = plan.mesh, plan.num_microbatches
        dtype = jnp.bfloat16 if model_cfg.dtype == "bfloat16" else jnp.float32

        params = init_params_sharded(
            model_cfg, jax.random.key(config["input"].get("seed", 42)), mesh
        )
        # hidden size comes from the resolved ModelConfig, not the raw YAML —
        # a `size: "7B"` config need not spell out hidden_size
        dataset = create_dataset_from_config(
            config, mesh=mesh, spec=batch_spec(mesh), dtype=dtype,
            hidden_size=model_cfg.hidden_size,
        )
        batch = dataset.get_batch()
    init_time = t_init.elapsed

    step = build_forward_step(model_cfg, mesh, num_microbatches)

    execution = config.get("execution", {})
    warmup = execution.get("warmup_iterations", 5)
    iters = execution.get("benchmark_iterations", 10)
    # variant-tuned XLA compilation, same contract as run_train
    comp_opts = {
        str(k): str(v)
        for k, v in (execution.get("compiler_options") or {}).items()
    }

    # The model maps [B,S,H] -> [B,S,H], so chained timing feeds the output
    # straight back as the next input.
    mode = resolve_timing_mode("auto")

    with annotate("compile+warmup"):
        with Timer() as t_compile:
            mosaic_calls = mosaic_call_count(step, params, batch)
            if comp_opts and mode == "per_iter":
                step = step.lower(params, batch).compile(
                    compiler_options=comp_opts
                )
            out = step(params, batch)
            force_completion(out)
        compile_time = t_compile.elapsed
        # what came out, read once here — never inside the timed loop
        out32 = out.astype(jnp.float32)
        output_check = {
            "shape": list(out.shape),
            "finite": bool(jnp.isfinite(out32).all()),
            "mean_abs": float(jnp.abs(out32).mean()),
            "devices": device_spread(out),
        }
        del out, out32

    with annotate("measure"):
        if mode == "per_iter":
            forward_times, _, _ = time_fn_per_iter(
                step, params, batch, warmup=max(0, warmup - 1),
                iterations=iters
            )
            timing_meta = {
                "timing_mode": "per_iter",
                "timing_method": "time.perf_counter() + jax.block_until_ready()",
            }
        else:
            # batch is donated to the timing loop; it is not used again
            forward_times, timing_meta, _ = time_fn_chained(
                step, batch, warmup=1, iterations=iters,
                chunk_size=min(5, iters), op_args=(params,),
                compiler_options=comp_opts or None,
            )

    # cross-host spread of mean forward time (run_mpi.py:199-212 analogue)
    local_mean = float(np.mean(forward_times))
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        host_means = np.asarray(
            multihost_utils.process_allgather(np.float64(local_mean))
        ).ravel()
    else:
        host_means = np.asarray([local_mean])
    variance = float(host_means.var())
    cv = float(host_means.std() / host_means.mean()) if host_means.mean() > 0 else 0.0

    tokens = (config["input"]["batch_size"] * config["input"]["sequence_length"])
    flops = forward_flops(
        model_cfg, config["input"]["batch_size"],
        config["input"]["sequence_length"],
    )
    result = {
        "experiment": config.get("experiment", {}),
        "backend": "xla_tpu",
        "config": config,
        "model": {
            "num_parameters": num_parameters(model_cfg),
            "attention": model_cfg.attention,
            "dtype": model_cfg.dtype,
            # the route the TP projections TOOK (off = GSPMD fused;
            # ring/bidir = overlapped decomposition, docs/overlap.md),
            # not the configuration's word, which may be "auto"
            "tp_overlap": plan.tp_overlap,
        },
        "mesh": plan.mesh_dict(),
        "init_time_s": init_time,
        "compiler_options": comp_opts or None,
        "compile_time_s": compile_time,
        "mosaic_calls": mosaic_calls,
        "output_check": output_check,
        "forward_time": summarize(forward_times),
        **timing_meta,
        "per_host_means_s": host_means.tolist(),
        "cross_host_variance": variance,
        "cross_host_cv": cv,
        "tokens_per_second": tokens / local_mean,
        "model_flops_per_forward": flops,
        "achieved_tflops_per_second": flops / local_mean / 1e12,
        "timings": [forward_times],
        "system_info": collect_system_info(),
        "timestamp": time.time(),
    }

    if verbose:
        ft = result["forward_time"]
        print(
            f"[e2e] {config.get('experiment', {}).get('name', 'experiment')}: "
            f"forward mean {ft['mean'] * 1e3:.2f} ms "
            f"(p95 {ft['p95'] * 1e3:.2f} ms), compile {compile_time:.1f} s, "
            f"{result['tokens_per_second']:.0f} tok/s"
        )

    if output_dir is not None:
        name = config.get("experiment", {}).get("name", "experiment")
        save_json(result, Path(output_dir) / f"xla_tpu_{name}.json")
    return result


def run_e2e_from_config(
    config_path: str,
    output_dir: Optional[str] = None,
    devices: Optional[Sequence] = None,
    tp_overlap: Optional[str] = None,
) -> dict[str, Any]:
    """``tp_overlap`` overrides the config's ``model.tp_overlap`` (the
    ``--tp-overlap`` CLI flag): one YAML can be swept fused-vs-ring-vs-
    bidir without editing it."""
    config = load_config(config_path)
    if tp_overlap is not None:
        config.setdefault("model", {})["tp_overlap"] = tp_overlap
    out = output_dir or config.get("experiment", {}).get("output_dir")
    return run_e2e(config, devices=devices, output_dir=out)
