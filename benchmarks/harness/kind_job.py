"""Runner of ``kind: job`` traffic: one jitted step of the configuration
(a training step or a forward pass, as the configuration's ``mode``
says) on one fixed seeded batch, back to back until the window ends.

The step is built by the program's own builders, the ones ``run_train``
and ``run_e2e`` call.  Two steps are kept in flight, so the host's
dispatch never idles the device, and a step's time is the distance
between two completions.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable

from benchmarks.harness import trace_reduce
from benchmarks.harness.cells import Cell, program_seed
from benchmarks.harness.device import CompileCounter, device_record
from benchmarks.harness.result import Run


def _build(cell: Cell, seed: int) -> tuple[Callable[[], Any], int, str]:
    """Returns ``(step, tokens, mode)``; ``step()`` dispatches one step
    and returns the array to wait on."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from dlbb_tpu.data.synthetic import create_dataset_from_config
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.models.sharding import batch_spec
    from dlbb_tpu.models.transformer import forward, init_params_sharded
    from dlbb_tpu.parallel.plan import ParallelismPlan

    program = dict(cell.config["program"])
    program["input"] = {"batch_size": cell.traffic["batch_size"],
                        "sequence_length": cell.traffic["sequence_length"],
                        "seed": program_seed(seed)}
    model_cfg = ModelConfig.from_dict(program["model"])
    plan = ParallelismPlan.from_config(program, model_cfg)
    mesh = plan.mesh
    dtype = jnp.bfloat16 if model_cfg.dtype == "bfloat16" else jnp.float32

    def dataset(offset: int):
        return create_dataset_from_config(
            program, mesh=mesh, spec=batch_spec(mesh), dtype=dtype,
            hidden_size=model_cfg.hidden_size, seed_offset=offset,
        ).get_batch()

    params = init_params_sharded(
        model_cfg, jax.random.key(program_seed(seed)), mesh)
    batch = dataset(0)
    tokens = (cell.traffic["batch_size"] * cell.traffic["sequence_length"])
    mode = cell.config["mode"]
    if mode == "train":
        from dlbb_tpu.train.loop import make_train_step
        from dlbb_tpu.train.optim import build_optimizer, moments_dtype

        train_cfg = program["training"]
        jit_step, state = make_train_step(
            model_cfg, mesh, build_optimizer(train_cfg), params,
            zero_stage=int(train_cfg.get("zero_stage", 0)),
            num_microbatches=plan.num_microbatches,
            residual_dtype=moments_dtype(train_cfg),
        )
        del params
        targets = dataset(1)
        holder = [state]

        def step():
            holder[0], loss = jit_step(holder[0], batch, targets)
            return loss
    elif mode == "forward":
        fwd = jax.jit(
            lambda p, x: forward(p, x, model_cfg, mesh=mesh,
                                 num_microbatches=plan.num_microbatches),
            out_shardings=NamedSharding(mesh, batch_spec(mesh)),
        )

        def step():
            return fwd(params, batch)
    else:
        raise ValueError(f"a job configuration's mode is 'train' or "
                         f"'forward', got {mode!r}")
    return step, tokens, mode


def _finite_mean(out: Any) -> float:
    """Mean of |out| as a float: NaN or inf anywhere makes it so."""
    import jax.numpy as jnp

    return float(jnp.mean(jnp.abs(out.astype(jnp.float32))))


def _back_to_back(step: Callable[[], Any], stop: Callable[[int], bool]
                  ) -> tuple[float, list[float], Any]:
    """Dispatch steps with two in flight until ``stop(dispatched)``, then
    drain.  Returns the start, every step's completion time and the last
    output."""
    import jax

    start = time.perf_counter()
    done_at: list[float] = []
    pending = None
    while True:
        out = step()
        if pending is not None:
            jax.block_until_ready(pending)
            done_at.append(time.perf_counter())
        pending = out
        if stop(len(done_at) + 1):
            break
    jax.block_until_ready(pending)
    done_at.append(time.perf_counter())
    return start, done_at, pending


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: CompileCounter, scratch: str) -> Run:
    step, tokens, mode = _build(cell, seed)
    phases = {"built": time.perf_counter()}
    first = None
    for _ in range(int(cell.traffic["warmup_steps"])):
        value = _finite_mean(step())       # waits; in training, the loss
        first = value if first is None else first

    profile: dict[str, Any] = {}
    extra_steps = 0
    compiled_before = compiles.count
    t0 = time.perf_counter()
    deadline = t0 + seconds
    if trace:
        # a slice of the window, traced: a few plain steps, a few under
        # the profiler, then on to the deadline as in any run
        _s, head, _o = _back_to_back(step, lambda n: n >= 3)
        profile = _traced_steps(step, int(cell.traffic["trace_steps"]),
                                scratch)
        extra_steps = len(head) + int(cell.traffic["trace_steps"])
    start, done_at, last = _back_to_back(
        step, lambda n: time.perf_counter() >= deadline)
    compiled = compiles.count - compiled_before
    n_steps = extra_steps + len(done_at)
    # the rate is over all the work and all the time of the window; a
    # traced run also keeps the rate of its undisturbed stretch, for the
    # readers (its end-to-end numbers are never reported)
    rate = n_steps * tokens / (done_at[-1] - t0)
    clean_rate = len(done_at) * tokens / (done_at[-1] - start)

    after = _finite_mean(last)
    faults = []
    if compiled:
        faults.append(f"{compiled} program(s) compiled inside the window")
    if not math.isfinite(after):
        faults.append("the output is not finite")
    if mode == "train" and not after < first:
        faults.append(f"the fixed batch's loss did not fall: "
                      f"{first} -> {after}")
    return Run(
        cell=cell, seconds=seconds, started_at=t0, correct=not faults,
        attempted=n_steps, failed=0,
        values={"tokens_per_s": rate},
        samples={"step_s": [b - a for a, b in zip(done_at, done_at[1:])]},
        scalars={"tokens_per_s": clean_rate, "steps": n_steps,
                 "first_value": first, "last_value": after},
        profile=profile, device=device_record(), faults=faults,
        phases=phases,
    )


def _traced_steps(step: Callable[[], Any], n: int,
                  scratch: str) -> dict[str, Any]:
    """``n`` steps under the profiler, dispatch and wait each in a host
    annotation so that an idle gap can be named."""
    import jax

    pending = None
    with trace_reduce.profiling(scratch):
        for _ in range(n):
            with jax.profiler.TraceAnnotation("step-dispatch"):
                out = step()
            if pending is not None:
                with jax.profiler.TraceAnnotation("step-wait"):
                    jax.block_until_ready(pending)
            pending = out
        with jax.profiler.TraceAnnotation("step-wait"):
            jax.block_until_ready(pending)
    return trace_reduce.reduce_profile(scratch)
