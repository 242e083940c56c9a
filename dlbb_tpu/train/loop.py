"""DDP / ZeRO-{1,2,3} training loop.

The reference's training capability is a DeepSpeed smoke: ZeRO-2 engine init,
MSE loss, ``backward()`` (gradient all-reduce / reduce-scatter) and
``step()`` (``test/ccl.py:59-117``), plus ZeRO-0 + Adam (``test/ds_mpi_test.py``).
TPU-native re-design — every stage is a *sharding declaration*, not a
hand-written collective schedule:

- **DDP (stage 0)**: batch sharded over the ``dp`` mesh axis, params
  replicated over ``dp`` (and TP-sharded over ``tp``); the gradient
  all-reduce the reference delegates to DeepSpeed/oneCCL is inserted by XLA
  GSPMD because the loss mean contracts a dp-sharded batch against
  dp-replicated params.
- **ZeRO-1**: optimizer state (Adam mu/nu) sharded over ``dp`` on top of the
  TP layout.  Declaring sharded out-shardings for the optimizer state makes
  XLA lower the grad all-reduce into reduce-scatter + sharded update +
  all-gather of the new params — the ZeRO-1 dataflow of
  BASELINE.json config 5 — without hand-written collectives.
- **ZeRO-2**: additionally pins the *gradients* to the dp-sharded layout with
  a sharding constraint, so the backward's grad buffers are reduce-scattered
  as they are produced (sharded grad memory — DeepSpeed stage-2 semantics,
  the config at reference ``test/ccl.py:86-89``).
- **ZeRO-3 / FSDP**: the parameters themselves live dp-sharded; XLA inserts
  the per-layer all-gathers on use in forward/backward and frees the
  gathered copies after — DeepSpeed stage-3 dataflow, declared in one spec
  tree.
- Adam via optax; MSE loss vs a fixed target batch (parity with
  ``test/ccl.py:110``).
"""

from __future__ import annotations

import os
import signal
import time
import warnings
from pathlib import Path
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.data.synthetic import create_dataset_from_config
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.parallel.plan import ParallelismPlan
from dlbb_tpu.models.sharding import batch_spec, param_specs, specs_for_mesh
from dlbb_tpu.models.transformer import (
    TRAIN_PHASES,
    forward,
    forward_flops,
    init_params_sharded,
    named,
    tp_overlap_route,
)
from dlbb_tpu.obs import spans
from dlbb_tpu.ops import mosaic_call_count
from dlbb_tpu.utils.config import load_config, save_json
from dlbb_tpu.utils.metrics import Timer, summarize
from dlbb_tpu.utils.sysinfo import collect_system_info, device_spread
from dlbb_tpu.utils.timing import resolve_timing_mode, time_fn_chained


LOSS, GRAD_REDUCE, OPTIMIZER = TRAIN_PHASES


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def _is_spec(x) -> bool:
    return isinstance(x, P)


def _dp_shard_spec(spec: P, shape: tuple[int, ...], dp_size: int,
                   dp_axis: str = "dp") -> P:
    """Add a ``dp`` sharding to ``spec`` on the largest unsharded,
    dp-divisible axis (ZeRO optimizer-state / gradient / FSDP-param
    partitioning).  No-op when ``spec`` already uses ``dp`` or no axis
    divides evenly."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    if any(dp_axis in (ax if isinstance(ax, tuple) else (ax,))
           for ax in parts if ax is not None):
        return spec
    candidates = sorted(
        (i for i in range(len(shape))
         if parts[i] is None and shape[i] % dp_size == 0 and shape[i] > 1),
        key=lambda i: -shape[i],
    )
    if not candidates:
        return spec
    parts[candidates[0]] = dp_axis
    return P(*parts)


def dp_sharded_param_specs(params: Any, dp_size: int,
                           dp_axis: str = "dp",
                           base_specs: Any = None) -> Any:
    """The TP (or TP+PP) spec tree with a ``dp`` sharding added per leaf —
    the FSDP / ZeRO-3 parameter layout, also the ZeRO-{1,2}
    optimizer-state/grad layout."""
    if base_specs is None:
        base_specs = param_specs()
    return jax.tree.map(
        lambda s, p: _dp_shard_spec(s, p.shape, dp_size, dp_axis),
        base_specs, params, is_leaf=_is_spec,
    )


def opt_state_specs(params: Any, opt_state: Any, zero1: bool,
                    dp_size: int, base_specs: Any = None) -> Any:
    """Partition specs for the optimizer-state pytree.

    Optax state subtrees that mirror the param pytree (Adam mu/nu) are
    detected *structurally* — any subtree with the params' treedef AND
    leafwise-matching shapes gets the params' spec tree (treedef matching
    alone would misfire on adafactor's v_row/v_col/v subtrees, which mirror
    the params' structure with factored lower-rank statistics; pure shape
    matching would collide when two params share a shape with different TP
    layouts, e.g. ffn_intermediate == hidden_size).  Everything else —
    step counts, empty states, factored adafactor statistics (sublinear in
    parameter count, so ZeRO sharding is moot for them) — stays replicated.
    """
    p_def = jax.tree.structure(params)
    p_shapes = [getattr(p, "shape", None) for p in jax.tree.leaves(params)]
    if base_specs is None:
        base_specs = param_specs()
    spec_for_params = (
        dp_sharded_param_specs(params, dp_size, base_specs=base_specs)
        if zero1 else base_specs
    )

    def recur(node):
        try:
            if jax.tree.structure(node) == p_def and all(
                getattr(leaf, "shape", None) == shape
                for leaf, shape in zip(jax.tree.leaves(node), p_shapes)
            ):
                return spec_for_params
        except Exception:  # noqa: BLE001 — unhashable/exotic nodes
            pass
        if isinstance(node, tuple):  # incl. optax NamedTuple states
            children = [recur(c) for c in node]
            if hasattr(node, "_fields"):  # NamedTuple: positional ctor
                return type(node)(*children)
            return tuple(children)
        if isinstance(node, list):
            return [recur(c) for c in node]
        if isinstance(node, dict):
            return {k: recur(v) for k, v in node.items()}
        return P()  # scalar leaves (adam count) and unknown leaves: replicated

    return recur(opt_state)


def mse_loss(params, batch, targets, config: ModelConfig,
             mesh: Optional[Mesh] = None,
             num_microbatches: Optional[int] = None,
             moe_aux_weight: float = 0.0) -> jax.Array:
    """MSE vs the target batch (parity with ``test/ccl.py:110``), plus the
    weighted MoE load-balancing loss when requested
    (``training.moe_aux_loss_weight``)."""
    if moe_aux_weight > 0.0:
        pred, aux = forward(params, batch, config, mesh=mesh,
                            num_microbatches=num_microbatches,
                            with_aux=True)
    else:
        pred = forward(params, batch, config, mesh=mesh,
                       num_microbatches=num_microbatches)
        aux = 0.0
    with jax.named_scope(LOSS):
        mse = jnp.mean(
            (pred.astype(jnp.float32) - targets.astype(jnp.float32)) ** 2
        )
        return mse + moe_aux_weight * aux


def resolve_zero_stage(zero1: bool = False,
                       zero_stage: Optional[int] = None) -> int:
    """Collapse the legacy ``zero1`` flag and the explicit ``zero_stage``
    into one stage number 0-3."""
    if zero_stage is not None:
        if zero_stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_stage must be 0-3, got {zero_stage}")
        return zero_stage
    return 1 if zero1 else 0


MODE_NAMES = {0: "ddp", 1: "zero1", 2: "zero2", 3: "zero3"}

# Approximate per-parameter update FLOPs for the utilisation accounting
# (elementwise moment updates + bias correction + apply; small vs the 3x
# forward term for any real model).
OPTIMIZER_FLOPS_PER_PARAM = {"adam": 18, "adamw": 22, "sgd": 6,
                             "adafactor": 14}


def make_train_step(
    config: ModelConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    params: Any,
    zero1: bool = False,
    zero_stage: Optional[int] = None,
    num_microbatches: Optional[int] = None,
    moe_aux_weight: float = 0.0,
    grad_accum: int = 1,
    pipeline_schedule: str = "gpipe",
    grad_compression: str = "none",
    compression_accum: str = "float32",
    residual_dtype: Any = None,
):
    """Build (jitted step fn, initial sharded TrainState) for the given
    ZeRO stage (0=DDP, 1=opt-state sharding, 2=+grad sharding, 3=FSDP).
    A mesh with a >1-sized ``pp`` axis makes the inner forward pipelined
    (``num_microbatches`` microbatches, default one per stage);
    ``pipeline_schedule`` picks the training schedule there — "gpipe"
    (autodiff through the forward pipeline) or "1f1b" (interleaved
    backward, activation live-range O(pp) — ``parallel/pipeline.py``);
    ``moe_aux_weight`` adds the MoE load-balancing loss; ``grad_accum``
    splits the batch into that many sequential micro-steps whose mean
    gradient feeds one optimizer update (same numerics as the full batch
    for mean losses, 1/grad_accum the activation memory).

    ``grad_compression`` ("int8"/"fp8", docs/compression.md) swaps the
    dp gradient reduction for the quantised ring of
    ``comm/compression.py``: local grads are computed inside a
    full-manual shard_map (no GSPMD all-reduce exists to begin with),
    the error-feedback residual is added, and the compressed
    ``psum_compressed`` reduces on an int8/fp8 wire.  The residual lives
    as an extra optimizer-state leaf
    (``train/optim.py::GradCompressionState`` — dp-sharded, checkpointed,
    stored in ``residual_dtype``); ``compression_accum`` picks the ring's
    accumulation precision.  Supported envelope: pure-dp meshes (every
    other axis size 1), ZeRO stages 0/2, dense attention, no grad
    accumulation / MoE aux loss — violations raise here, at build time.

    The returned step donates its state argument, and the ``device_put``
    here may alias the caller's ``params`` buffers — treat the input
    ``params`` pytree as consumed once the first step has run."""
    if config.is_hybrid:
        raise ValueError(
            "the train step is not implemented for layer_types models: "
            "the chunked gated delta rule has no backward pass here and "
            "the loss is over hidden states, not a vocabulary "
            "(ROADMAP.md, Queue 2)")
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    if pipeline_schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"unknown pipeline_schedule {pipeline_schedule!r} "
            "(expected 'gpipe' or '1f1b')"
        )
    pp_size = mesh.shape.get("pp", 1)
    if pipeline_schedule == "1f1b" and pp_size <= 1:
        raise ValueError(
            "pipeline_schedule='1f1b' requires parallelism.pipeline_parallel"
            " > 1 (it is a pipeline training schedule)"
        )
    stage = resolve_zero_stage(zero1, zero_stage)
    dp_size = mesh.shape.get("dp", 1)
    from dlbb_tpu.train.optim import (
        GRAD_COMPRESSIONS,
        GradCompressionState,
        init_error_feedback,
    )

    if grad_compression not in GRAD_COMPRESSIONS:
        raise ValueError(
            f"unknown grad_compression {grad_compression!r}; known: "
            f"{GRAD_COMPRESSIONS}"
        )
    compression_on = grad_compression != "none"
    if compression_on:
        # the compressed path computes LOCAL grads inside a full-manual
        # shard_map and owns the reduction; every capability outside that
        # envelope is rejected at build time, not at trace time
        other = [a for a in mesh.axis_names
                 if a != "dp" and mesh.shape[a] > 1]
        if other:
            raise ValueError(
                "training.grad_compression requires a pure data-parallel "
                f"mesh; axes {other} have size > 1 (compose compression "
                "with tp/sp/pp is future work — docs/compression.md)"
            )
        if dp_size <= 1:
            raise ValueError(
                "training.grad_compression with data_parallel=1 has no "
                "gradient reduction to compress: the ring is an identity, "
                "so the error-feedback residual would subtract a "
                "quantisation error that was never incurred — run "
                "uncompressed, or use a dp>1 mesh"
            )
        if stage not in (0, 2):
            raise ValueError(
                "training.grad_compression supports ZeRO stages 0 (DDP) "
                f"and 2 (grad sharding), not stage {stage}: stages 1/3 "
                "shard the optimizer update itself, which the compressed "
                "replicated-update path does not compose with"
            )
        # NOTE stage 2 + compression trades ZeRO-2's grad-MEMORY saving
        # for the wire saving: the ring's gather phase transiently
        # materialises the replicated flat gradient on every rank (DDP
        # peak) before the layout pin slices it back to shards — a
        # sharded-update path on reduce_scatter_compressed alone is the
        # future-work alternative (docs/compression.md)
        if grad_accum != 1:
            raise ValueError(
                "training.grad_compression does not compose with "
                "gradient_accumulation yet (accumulate locally before "
                "one compressed reduction is future work)"
            )
        if moe_aux_weight != 0.0:
            raise ValueError(
                "training.grad_compression does not support the MoE aux "
                "loss (expert-parallel compression is future work)"
            )
        if config.attention not in ("full", "simplified", "dense"):
            raise ValueError(
                f"training.grad_compression requires a dense attention "
                f"mode (full/simplified/dense), got "
                f"{config.attention!r}: shard_map attention modes nest "
                "their own manual meshes"
            )
    base_specs = specs_for_mesh(mesh, moe=config.is_moe)
    dp_specs = dp_sharded_param_specs(params, dp_size, base_specs=base_specs)
    p_spec_tree = dp_specs if stage >= 3 else base_specs
    p_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), p_spec_tree, is_leaf=_is_spec
    )
    params = jax.device_put(params, p_shardings)
    opt_state = optimizer.init(params)
    s_specs = opt_state_specs(params, opt_state, stage >= 1, dp_size,
                              base_specs=base_specs)
    s_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), s_specs, is_leaf=_is_spec
    )
    opt_state = jax.device_put(opt_state, s_shardings)
    if compression_on:
        # error-feedback residual rides as an optimizer-state leaf: one
        # [1, total_params] row per dp rank (P("dp") — per-device memory
        # is 1x the flat grads, never replicated), checkpointed with the
        # rest of the state, stored in residual_dtype (= moments_dtype
        # under the memory-reduced-Adam convention)
        res_dtype = jnp.dtype(residual_dtype) if residual_dtype is not None \
            else jnp.float32
        comp_shardings = GradCompressionState(
            residual=NamedSharding(mesh, P("dp"))
        )
        comp = init_error_feedback(params, dp_size, res_dtype,
                                   sharding=comp_shardings.residual)
        opt_state = (opt_state, comp)
        s_shardings = (s_shardings, comp_shardings)
    state = TrainState(params, opt_state, jnp.zeros((), jnp.int32))

    state_shardings = TrainState(
        p_shardings, s_shardings, NamedSharding(mesh, P())
    )
    grad_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), dp_specs, is_leaf=_is_spec
    )

    if pipeline_schedule == "1f1b":
        from dlbb_tpu.parallel.pipeline import pipeline_1f1b_grads

        def value_and_grads(params, batch, targets):
            return pipeline_1f1b_grads(
                params, batch, targets, config, mesh,
                num_microbatches=num_microbatches,
                moe_aux_weight=moe_aux_weight,
            )
    else:
        def value_and_grads(params, batch, targets):
            return jax.value_and_grad(mse_loss)(
                params, batch, targets, config, mesh, num_microbatches,
                moe_aux_weight,
            )

    def loss_and_grads(params, batch, targets):
        if grad_accum == 1:
            return value_and_grads(params, batch, targets)
        b = batch.shape[0]
        if b % grad_accum != 0:
            raise ValueError(
                f"batch_size={b} not divisible by grad_accum={grad_accum}"
            )
        if (b // grad_accum) % dp_size != 0:
            if config.attention in ("full", "simplified"):
                # dense attention: numerics stay exact — GSPMD reshards
                # each micro-batch onto the dp axis — but the layout churn
                # costs collectives, so surface it without rejecting
                warnings.warn(
                    f"micro-batch size {b // grad_accum} (batch_size={b} / "
                    f"grad_accum={grad_accum}) not divisible by "
                    f"dp={dp_size}; each micro-step reshards the batch "
                    "instead of keeping the dp layout (correct but "
                    "slower — measured pair: results/parallelism/"
                    "train_ddp_ga2_{divisible_b16,reshard_b20}.json, "
                    "per-token throughput in "
                    "stats/parallelism/PARALLELISM.md)",
                    stacklevel=2,
                )
            else:
                # flash/ring/ulysses shard_map the batch dim over dp
                # explicitly and cannot reshard — reject with a clear error
                # instead of letting shard_map fail cryptically at trace
                raise ValueError(
                    f"micro-batch size {b // grad_accum} (batch_size={b} / "
                    f"grad_accum={grad_accum}) not divisible by "
                    f"dp={dp_size}: attention={config.attention!r} "
                    "partitions the batch over dp inside shard_map and "
                    "cannot reshard a smaller micro-batch"
                )
        mb = batch.reshape(grad_accum, b // grad_accum, *batch.shape[1:])
        mt = targets.reshape(grad_accum, b // grad_accum, *targets.shape[1:])

        def acc(carry, xs):
            loss_sum, g_sum = carry
            x, t = xs
            loss, g = value_and_grads(params, x, t)
            if stage >= 2:
                # keep every micro-step's grads (and thus the carry) in
                # the dp-sharded layout, so accumulation never materialises
                # a replicated full-size gradient pytree under ZeRO-2/3
                g = jax.lax.with_sharding_constraint(g, grad_shardings)
            # accumulate in fp32 regardless of params dtype — bf16 sums
            # would round each micro-step and break full-batch equivalence
            g_sum = jax.tree.map(
                lambda s, gi: s + gi.astype(jnp.float32), g_sum, g
            )
            return (loss_sum + loss, g_sum), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        if stage >= 2:
            zeros = jax.lax.with_sharding_constraint(zeros, grad_shardings)
        (loss_sum, g_sum), _ = jax.lax.scan(
            acc, (jnp.zeros((), jnp.float32), zeros), (mb, mt)
        )
        inv = 1.0 / grad_accum
        grads = jax.tree.map(
            lambda g, p: (g * inv).astype(p.dtype), g_sum, params
        )
        return loss_sum * inv, grads

    if compression_on:
        from jax.flatten_util import ravel_pytree

        from dlbb_tpu.comm.compression import (
            psum_compressed,
            quantization_error,
        )
        from dlbb_tpu.compat import shard_map

        accum = (jnp.bfloat16 if compression_accum == "bfloat16"
                 else jnp.float32)
        bspec = batch_spec(mesh)
        # params enter the shard_map replicated (full value per device:
        # every non-dp axis is size 1 and params are dp-replicated)
        local_p_specs = jax.tree.map(lambda _: P(), params)

        def _compressed_body(p, b, t, res):
            # local loss/grads: the batch shard never crosses dp here, so
            # no GSPMD gradient all-reduce exists to begin with — the
            # ONLY gradient reduction is the quantised ring below
            loss, g = jax.value_and_grad(mse_loss)(
                p, b, t, config, None, None, 0.0
            )
            flat_g, unravel = ravel_pytree(g)
            with jax.named_scope(GRAD_REDUCE):
                c = (flat_g.astype(jnp.float32)
                     + res[0].astype(jnp.float32))
                reduced = psum_compressed(
                    c, "dp", compression=grad_compression,
                    accum_dtype=accum
                ) / dp_size
                # Seide-style error feedback: carry the LOCAL quantiser's
                # error into the next step (docs/compression.md)
                new_res = quantization_error(c, grad_compression)
                loss = jax.lax.psum(loss, "dp") / dp_size
            return (loss, unravel(reduced.astype(flat_g.dtype)),
                    new_res.astype(res.dtype)[None])

        compressed_loss_and_grads = shard_map(
            _compressed_body, mesh=mesh,
            in_specs=(local_p_specs, bspec, bspec, P("dp")),
            out_specs=(P(), local_p_specs, P("dp")),
            # the ppermute ring defeats static replication inference for
            # the replicated outputs; correctness is pinned by
            # tests/test_compression.py (psum_compressed == psum)
            check_vma=False,
        )

        @named("train_step")
        def step(state: TrainState, batch, targets):
            inner_state, comp = state.opt_state
            loss, grads, new_res = compressed_loss_and_grads(
                state.params, batch, targets, comp.residual
            )
            if stage >= 2:
                # the reduction wire is already compressed; the ZeRO-2
                # layout pin keeps grad memory dp-sharded downstream
                # (replicated -> sharded is a local slice, no collective)
                grads = jax.lax.with_sharding_constraint(
                    grads, grad_shardings)
            with jax.named_scope(OPTIMIZER):
                updates, new_inner = optimizer.update(
                    grads, inner_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
            return TrainState(
                new_params,
                (new_inner, GradCompressionState(residual=new_res)),
                state.step + 1,
            ), loss
    else:
        @named("train_step")
        def step(state: TrainState, batch, targets):
            loss, grads = loss_and_grads(state.params, batch, targets)
            if stage >= 2:
                # pin grads to the dp-sharded layout: the dp all-reduce
                # lowers to reduce-scatter and grad memory stays sharded
                # (ZeRO-2)
                grads = jax.lax.with_sharding_constraint(
                    grads, grad_shardings)
            with jax.named_scope(OPTIMIZER):
                updates, new_opt = optimizer.update(
                    grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
            return TrainState(new_params, new_opt, state.step + 1), loss

    jit_step = jax.jit(
        step,
        in_shardings=(state_shardings, NamedSharding(mesh, batch_spec(mesh)),
                      NamedSharding(mesh, batch_spec(mesh))),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    return jit_step, state


def run_train(
    config: dict[str, Any],
    zero1: bool = False,
    zero_stage: Optional[int] = None,
    devices: Optional[Sequence] = None,
    output_dir: Optional[str] = None,
    verbose: bool = True,
) -> dict[str, Any]:
    """Config-driven training benchmark (the train-side analogue of the E2E
    forward harness; reference flow ``test/ccl.py:59-117``)."""
    # explicit caller args (zero_stage or legacy zero1) win over the config
    if zero_stage is None and not zero1 \
            and "zero_stage" in config.get("training", {}):
        zero_stage = config["training"]["zero_stage"]
    stage = resolve_zero_stage(zero1, zero_stage)

    model_cfg = ModelConfig.from_dict(config["model"])
    plan = ParallelismPlan.from_config(config, model_cfg, devices)
    mesh, num_microbatches = plan.mesh, plan.num_microbatches
    inp = config["input"]
    dtype = jnp.bfloat16 if model_cfg.dtype == "bfloat16" else jnp.float32
    data = create_dataset_from_config(
        config, mesh=mesh, spec=batch_spec(mesh), dtype=dtype,
        hidden_size=model_cfg.hidden_size,
    )
    targets = create_dataset_from_config(
        config, mesh=mesh, spec=batch_spec(mesh), dtype=dtype,
        hidden_size=model_cfg.hidden_size, seed_offset=1,
    )

    train_cfg = config.get("training", {})
    lr = train_cfg.get("learning_rate", 1e-3)
    moe_aux_weight = float(train_cfg.get("moe_aux_loss_weight", 0.0))
    if moe_aux_weight > 0.0 and not model_cfg.is_moe:
        raise ValueError(
            "training.moe_aux_loss_weight requires a MoE model "
            "(model.num_experts > 0)"
        )
    grad_accum = int(train_cfg.get("gradient_accumulation", 1))
    if grad_accum > 1:
        bs = inp["batch_size"]
        if bs % grad_accum != 0:
            raise ValueError(
                f"batch_size={bs} not divisible by "
                f"gradient_accumulation={grad_accum}"
            )
        if plan.pp > 1:
            # training feeds batch/grad_accum rows to each pipelined
            # micro-step, so the microbatch schedule must also divide the
            # accumulation micro-batch — a training-only constraint, checked
            # here (not in the shared plan) so forward-only harnesses that
            # reuse a training config are unaffected
            from dlbb_tpu.parallel.pipeline import validate_pipeline

            validate_pipeline(model_cfg, plan.pp, bs // grad_accum,
                              plan.num_microbatches)
    from dlbb_tpu.train.optim import (
        build_optimizer,
        compression_accum_dtype,
        moments_dtype,
        resolve_grad_compression,
        resolve_names,
    )

    optimizer = build_optimizer(train_cfg)
    opt_name, sched_name = resolve_names(train_cfg)
    grad_compression = resolve_grad_compression(train_cfg)
    comp_accum = compression_accum_dtype(train_cfg)

    pipeline_schedule = str(train_cfg.get("pipeline_schedule", "gpipe"))
    params = init_params_sharded(
        model_cfg, jax.random.key(inp.get("seed", 42)), mesh
    )
    jit_step, state = make_train_step(
        model_cfg, mesh, optimizer, params, zero_stage=stage,
        num_microbatches=num_microbatches, moe_aux_weight=moe_aux_weight,
        grad_accum=grad_accum, pipeline_schedule=pipeline_schedule,
        grad_compression=grad_compression, compression_accum=comp_accum,
        # the residual follows the moments-storage convention: bf16/fp16
        # moments => bf16/fp16 residual (memory-reduced Adam)
        residual_dtype=moments_dtype(train_cfg),
    )
    # make_train_step may have resharded params into fresh buffers (ZeRO-3);
    # at 13B scale the caller's copy is tens of GB of dead weight on the
    # host simulating the mesh — drop the reference before the step runs
    del params

    # Checkpoint / resume (no reference analogue — SURVEY §5.4 "none"; see
    # dlbb_tpu/train/checkpoint.py).  Resume happens before warmup so the
    # restored step counter carries through the run.
    ckpt = None
    resumed_from = None
    if "checkpoint" in train_cfg \
            and train_cfg["checkpoint"].get("enabled", True):
        from dlbb_tpu.train.checkpoint import CheckpointConfig, Checkpointer

        ckpt = Checkpointer(CheckpointConfig.from_dict(train_cfg["checkpoint"]))
        resumed_from = ckpt.latest_step()
        state = ckpt.restore_or(state)

    execution = config.get("execution", {})
    warmup = execution.get("warmup_iterations", 2)
    iters = execution.get("benchmark_iterations", 10)
    mode = resolve_timing_mode("auto")

    batch, tgt = data.get_batch(), targets.get_batch()
    # variant-tuned XLA compilation (e.g. the "nofuse" combiner-passes-off
    # variant, dlbb_tpu/comm/variants.py) — per-computation compiler options
    # need no process relaunch, unlike XLA_FLAGS
    comp_opts = {
        str(k): str(v)
        for k, v in (execution.get("compiler_options") or {}).items()
    }
    with spans.span("compile+warmup", cat="train"):
        t0 = time.perf_counter()
        mosaic_calls = mosaic_call_count(jit_step, state, batch, tgt)
        if comp_opts and mode == "per_iter":
            # AOT-compile with the options; in chained mode the options are
            # instead applied to the outer timing loop (an AOT executable
            # cannot be traced inside it)
            jit_step = jit_step.lower(state, batch, tgt).compile(
                compiler_options=comp_opts
            )
        state, loss = jit_step(state, batch, tgt)
        float(loss)  # forces completion on any backend
        compile_time = time.perf_counter() - t0
        for _ in range(max(0, warmup - 1)):
            state, loss = jit_step(state, batch, tgt)
            float(loss)  # forces completion on any backend

    # Graceful preemption (docs/resilience.md): SIGTERM between steps
    # breaks the loop and falls through to the forced final checkpoint
    # save below — the TPU-fleet preemption notice becomes a clean
    # resume point instead of a mid-step kill.  The `preempt` fault site
    # (dlbb_tpu.resilience.inject) drives the same path in the chaos gate.
    from dlbb_tpu.resilience import PreemptionGuard, inject

    losses = []
    preempted_at: Optional[int] = None
    with PreemptionGuard() as guard:
        if mode == "per_iter":
            step_times = []
            for i in range(iters):
                if inject.fire("preempt"):
                    os.kill(os.getpid(), signal.SIGTERM)
                if guard.requested:
                    preempted_at = int(jax.device_get(state.step))
                    break
                # the span (and the profiler annotation it opens)
                # wraps the Timer from the OUTSIDE — nothing
                # profiler-shaped inside the timed region (the
                # profiler-in-timed-region lint contract)
                with spans.span("train_step", cat="train", step=i):
                    with Timer() as t:
                        state, loss = jit_step(state, batch, tgt)
                        jax.block_until_ready(loss)
                    step_times.append(t.elapsed)
                losses.append(float(loss))
                if ckpt is not None:
                    ckpt.maybe_save(state)
            timing_meta = {
                "timing_mode": "per_iter",
                "timing_method":
                    "time.perf_counter() + jax.block_until_ready()",
            }
        else:
            # optimisation trajectory first (each float(loss) forces
            # completion, so losses are real), then honest chained step
            # timing
            for _ in range(iters):
                if inject.fire("preempt"):
                    os.kill(os.getpid(), signal.SIGTERM)
                if guard.requested:
                    preempted_at = int(jax.device_get(state.step))
                    break
                state, loss = jit_step(state, batch, tgt)
                losses.append(float(loss))
                if ckpt is not None:
                    ckpt.maybe_save(state)

            if preempted_at is None:
                def timed_step(b, t, st):
                    new_state, _ = jit_step(st, b, t)
                    return new_state

                with spans.span("measure", cat="train"):
                    # state is donated to the timing loop (halves resident
                    # TrainState HBM — decisive for Adam at 1B on the
                    # 16 GiB chip); the returned carry IS the post-timing
                    # state and everything below (final ckpt save,
                    # final_step) uses it
                    step_times, timing_meta, state = time_fn_chained(
                        timed_step, state, warmup=1, iterations=iters,
                        chunk_size=min(5, iters), op_args=(batch, tgt),
                        compiler_options=comp_opts or None,
                    )
            else:
                step_times, timing_meta = [], {
                    "timing_mode": "chained",
                    "timing_method": "preempted before measurement",
                }

    if ckpt is not None:
        # forced final save — ON the preemption path this is the "final
        # save + flush" the SIGTERM contract promises (the restore after
        # preemption starts from the last finished step)
        ckpt.maybe_save(state, force=True)
        ckpt.close()

    if preempted_at is not None and not step_times:
        # preempted before any timed sample: there is nothing honest to
        # publish — save happened above; report the resume point instead
        # of a fabricated benchmark artifact
        result = {
            "preempted": True,
            "preempted_at_step": preempted_at,
            "mode": MODE_NAMES[stage],
            "zero_stage": stage,
            "resumed_from_step": resumed_from,
            "final_step": int(jax.device_get(state.step)),
            "checkpoint_saved": ckpt is not None,
            "losses": losses,
            "timestamp": time.time(),
        }
        if verbose:
            print(f"[train/{result['mode']}] preempted at step "
                  f"{preempted_at}; checkpoint "
                  f"{'saved' if ckpt is not None else 'DISABLED'} — "
                  "no benchmark artifact written")
        return result

    # Utilisation accounting (the train-side analogue of the E2E harness's
    # achieved-TFLOP/s; parity depth with reference ``run_mpi.py:217-225``):
    # backward ≈ 2x forward (grads w.r.t. weights + activations), plus the
    # per-param optimizer update.  Token count per optimizer step is the
    # full batch regardless of grad_accum/pipeline microbatching.
    tokens = inp["batch_size"] * inp["sequence_length"]
    n_params = int(sum(x.size for x in jax.tree.leaves(state.params)))
    fwd_flops = forward_flops(model_cfg, inp["batch_size"],
                              inp["sequence_length"])
    step_flops = 3 * fwd_flops + OPTIMIZER_FLOPS_PER_PARAM.get(
        opt_name, 18) * n_params
    # Device-work accounting under remat: full-policy remat re-runs each
    # block's forward during backward (+1 forward of matmul FLOPs); the
    # "dots" policy saves matmul outputs, so its recompute is elementwise
    # only — zero extra FLOPs under this matmul-only analytic count.
    # ``model_flops_per_step``/``achieved_tflops_per_second`` stay MODEL
    # flops (useful work per second, comparable across remat policies);
    # ``*_incl_recompute`` is the device-work rate.
    recompute_flops = (
        fwd_flops if (model_cfg.remat and model_cfg.remat_policy == "full")
        else 0
    )
    mean_step = float(np.mean(step_times))

    result = {
        "experiment": config.get("experiment", {}),
        "backend": "xla_tpu",
        "config": config,
        "mode": MODE_NAMES[stage],
        "zero_stage": stage,
        "resumed_from_step": resumed_from,
        # quantised gradient reduction (docs/compression.md): "none" =
        # the GSPMD all-reduce path; int8/fp8 = the error-feedback ring
        "grad_compression": grad_compression,
        "compression_accum_dtype": (
            comp_accum if grad_compression != "none" else None
        ),
        # graceful-preemption marker: True when SIGTERM cut the loop short
        # after >=1 timed sample (stats below cover the completed steps)
        "preempted": preempted_at is not None,
        "preempted_at_step": preempted_at,
        "mesh": plan.mesh_dict(),
        "learning_rate": lr,
        "optimizer": opt_name,
        "moments_dtype": moments_dtype(train_cfg),
        "schedule": sched_name,
        "gradient_accumulation": grad_accum,
        "pipeline_schedule": pipeline_schedule if plan.pp > 1 else None,
        "remat": model_cfg.remat,
        "remat_policy": model_cfg.remat_policy if model_cfg.remat else None,
        # the route the TP projections TOOK for a micro-batch (off = GSPMD
        # fused; ring/bidir = overlapped decomposition, docs/overlap.md),
        # not the configuration's word, which may be "auto"
        "tp_overlap": tp_overlap_route(
            model_cfg, plan.mesh,
            (config["input"]["batch_size"] // grad_accum,
             config["input"]["sequence_length"], model_cfg.hidden_size)),
        "compiler_options": comp_opts or None,
        "compile_time_s": compile_time,
        "mosaic_calls": mosaic_calls,
        # devices the parameters ended up spread over
        "param_devices": device_spread(state.params),
        "step_time": summarize(step_times),
        "num_params": n_params,
        "tokens_per_second": tokens / mean_step,
        "model_flops_per_step": step_flops,
        "forward_flops": fwd_flops,
        "recompute_flops_per_step": recompute_flops,
        "recompute_note": (
            "achieved_tflops_per_second counts MODEL flops; with "
            "remat_policy=full the device additionally re-runs ~1 forward "
            "of matmuls per step (see *_incl_recompute)"
            if recompute_flops else None
        ),
        "achieved_tflops_per_second": step_flops / mean_step / 1e12,
        "achieved_tflops_per_second_incl_recompute": (
            (step_flops + recompute_flops) / mean_step / 1e12),
        **timing_meta,
        "losses": losses,
        "final_step": int(state.step),
        "system_info": collect_system_info(),
        "timestamp": time.time(),
    }
    if verbose:
        st = result["step_time"]
        print(
            f"[train/{result['mode']}] step mean {st['mean'] * 1e3:.2f} ms, "
            f"{result['tokens_per_second']:.0f} tok/s, "
            f"{result['achieved_tflops_per_second']:.2f} TFLOP/s, "
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"
        )
    if output_dir is not None:
        name = config.get("experiment", {}).get("name", "experiment")
        save_json(result, Path(output_dir) / f"train_{result['mode']}_{name}.json")
    return result


def run_train_from_config(
    config_path: str,
    zero1: bool = False,
    zero_stage: Optional[int] = None,
    output_dir: Optional[str] = None,
    devices: Optional[Sequence] = None,
    tp_overlap: Optional[str] = None,
    grad_compression: Optional[str] = None,
) -> dict[str, Any]:
    """``tp_overlap`` overrides the config's ``model.tp_overlap`` (the
    ``--tp-overlap`` CLI flag), mirroring ``run_e2e_from_config``;
    ``grad_compression`` overrides ``training.grad_compression`` the same
    way (the ``--grad-compression`` flag)."""
    config = load_config(config_path)
    if tp_overlap is not None:
        config.setdefault("model", {})["tp_overlap"] = tp_overlap
    if grad_compression is not None:
        config.setdefault("training", {})["grad_compression"] = \
            grad_compression
    out = output_dir or config.get("experiment", {}).get("output_dir")
    return run_train(config, zero1=zero1, zero_stage=zero_stage,
                     devices=devices, output_dir=out)
