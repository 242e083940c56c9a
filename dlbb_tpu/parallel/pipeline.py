"""Pipeline parallelism: microbatched pipelines over a ``pp`` mesh axis —
a GPipe forward engine (differentiable, used by forward benchmarks AND as
the default training schedule via autodiff) and a 1F1B training engine
(``pipeline_1f1b_grads``) that interleaves each microbatch's backward into
the steady state so the per-stage activation live-range is bounded by the
stage count, not the microbatch count.

The reference has no pipeline parallelism (SURVEY §2.2: "PP — NO"); this is
a capability extension, designed TPU-first rather than as a port of any
torch pipeline engine:

- the transformer's stacked-layer parameter axis is *sharded* over ``pp`` —
  each stage owns a contiguous block of ``num_layers / pp`` layers
  (``models/sharding.py::param_specs(pp_axis=...)``);
- inside one ``shard_map`` (manual over ``pp`` only — ``dp``/``tp`` stay
  under GSPMD via ``axis_names={pp}``), microbatches flow through the
  stages with a ``lax.ppermute`` ring shift per tick: the classic
  scan-over-ticks pipeline, one traced stage body regardless of depth;
- tick ``t`` injects microbatch ``t`` at stage 0 and collects finished
  microbatch ``t - (pp-1)`` at the last stage; after ``M + pp - 1`` ticks a
  ``lax.psum`` masked to the last stage broadcasts the outputs;
- bubble fraction is the GPipe ``(pp-1)/(M + pp - 1)``; raise
  ``num_microbatches`` to amortise it.

Forward and reverse differentiable (``ppermute``/``scan`` have exact
transpose rules), so the same code path serves the E2E forward benchmark
and the DDP/ZeRO training step.

**1F1B** (``training.pipeline_schedule: "1f1b"``): GPipe autodiff keeps
every microbatch's stage inputs alive from its forward tick until the
backward sweep — O(num_microbatches) activations per stage.  The 1F1B
engine instead interleaves a backward wavefront into the forward
wavefront: scan over ``m + 2(pp-1)`` tick *pairs*; in pair ``u`` stage
``i`` forwards microbatch ``u - i`` and backwards microbatch
``u - 2(pp-1) + i`` (each masked outside ``[0, m)`` — bubble ticks
compute on garbage and are masked out, exactly like the GPipe engine's
bubbles), recomputing the stage forward inside the backward's ``jax.vjp``
from the stored stage INPUT.  Each stage therefore alternates
1-forward/1-backward in steady state and holds at most ``2·pp - 1``
in-flight stage inputs — live-range O(pp), independent of the microbatch
count (GPipe-autodiff holds O(m)).  Numerics equal GPipe-autodiff up to
fp summation order (same per-microbatch math; gradients accumulate in
schedule order).

Design constraint that shapes the engine: under SPMD, every device must
issue an IDENTICAL sequence of collectives — and with ``tp``/``ep`` as
GSPMD auto axes, the stage computation itself contains collectives
(Megatron row-parallel psums).  A per-stage ``lax.switch`` between fwd
and bwd bodies (the classic 1F1B formulation) puts those collectives
inside branches that different stages take differently at the same tick,
which deadlocks the mesh (observed on the CPU in-process runtime; equally
illegal over ICI).  The wavefront formulation keeps every tick-pair's op
sequence identical on every device — fwd body, bwd body, activation hop,
cotangent hop — so collective uniformity holds for any dp x pp x tp x ep
composition.  Total real work equals GPipe (one valid F and one valid B
per microbatch per stage); the bubble overhead is ``2(pp-1)`` pairs vs
GPipe's ``pp-1`` ticks per phase.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dlbb_tpu.compat import pcast, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.models.sharding import PP_AXIS

def schedule_1f1b(n_stages: int, m: int):
    """Closed-form 1F1B wavefront schedule.

    Returns ``(pairs, fwd_mb, bwd_mb)``: the tick-pair count
    ``m + 2(n_stages-1)`` and two ``[pairs, n_stages]`` int32 tables — at
    pair ``u`` stage ``i`` forwards ``fwd_mb[u, i] = u - i`` and backwards
    ``bwd_mb[u, i] = u - 2(n_stages-1) + i``; entries outside ``[0, m)``
    are bubble slots (executed on garbage, masked out).  Invariants (see
    the module docstring and tests): activations/cotangents hop exactly
    one pair between producer and consumer; per-stage in-flight
    microbatches (forwarded, not yet backwarded) never exceed
    ``2·n_stages - 1``.
    """
    pairs = m + 2 * (n_stages - 1)
    u = np.arange(pairs)[:, None]
    i = np.arange(n_stages)[None, :]
    fwd_mb = (u - i).astype(np.int32)
    bwd_mb = (u - 2 * (n_stages - 1) + i).astype(np.int32)
    return pairs, fwd_mb, bwd_mb


def validate_pipeline(config: ModelConfig, n_stages: int, batch_size: int,
                      num_microbatches: Optional[int]) -> int:
    """Check divisibility and attention-mode constraints; returns the
    resolved microbatch count (default: one per stage)."""
    if config.is_hybrid:
        raise ValueError(
            f"pipeline_parallel={n_stages} is not implemented for "
            "layer_types models: the pipeline engine stages one "
            "homogeneous stacked layer, not a period of mixed layers")
    m = num_microbatches if num_microbatches is not None else n_stages
    if m < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {m}")
    if config.num_layers % n_stages != 0:
        raise ValueError(
            f"num_layers={config.num_layers} not divisible by "
            f"pipeline_parallel={n_stages}"
        )
    if batch_size % m != 0:
        raise ValueError(
            f"batch_size={batch_size} not divisible by "
            f"num_microbatches={m}"
        )
    if config.attention not in ("full", "dense", "simplified"):
        raise ValueError(
            f"attention={config.attention!r} cannot run under pipeline "
            "parallelism (ring/ulysses/flash need their own shard_map; "
            "use attention='full'/'dense'/'simplified' with "
            "pipeline_parallel > 1)"
        )
    return m


def pipeline_1f1b_grads(
    params,
    x: jax.Array,
    targets: jax.Array,
    config: ModelConfig,
    mesh: Mesh,
    pp_axis: str = PP_AXIS,
    num_microbatches: Optional[int] = None,
    moe_aux_weight: float = 0.0,
):
    """One full 1F1B training pass: returns ``(loss, grads)`` with ``grads``
    matching the ``params`` pytree (stage-sharded layer blocks + ln_f).

    Loss is the unpipelined ``mse_loss`` semantics: mean squared error over
    the full batch (mean of equal-sized per-microbatch means) plus
    ``moe_aux_weight`` times the layer x microbatch mean MoE aux.

    Every stage's backward step runs ONE shared ``jax.vjp`` of a stage
    function that computes (stage output, per-microbatch loss through
    ln_f + MSE, local aux): mid stages inject the received cotangent on
    the stage output and 0 on the loss; the last stage injects 1/m on the
    loss and 0 on the output — so ln_f gradients flow only where the loss
    is real, with no per-stage code divergence.  Forward recompute inside
    the vjp bounds stored state to the ``2·pp``-deep stage-input ring
    buffer (the 1F1B memory contract; see the module docstring for the
    wavefront schedule and the collective-uniformity rationale).
    """
    from dlbb_tpu.models.transformer import _block, _layernorm

    n_stages = mesh.shape[pp_axis]
    m = validate_pipeline(config, n_stages, x.shape[0], num_microbatches)
    if config.attention == "full":
        # same einsum-pinning rationale as pipeline_forward
        config = config.with_(attention="dense")
    pairs, fwd_tbl, bwd_tbl = schedule_1f1b(n_stages, m)
    depth = 2 * n_stages  # stage-input ring buffer (in-flight <= 2*pp - 1)
    layer_specs = jax.tree.map(lambda _: P(pp_axis), params["layers"])
    aux_cot = moe_aux_weight / (config.num_layers * m)

    def stage_local(sid, layers_local, lnf, x, tgt):
        # the stage index arrives as a pp-sharded [1] array rather than
        # lax.axis_index: under a partial-auto shard_map the latter lowers
        # to a PartitionId instruction the SPMD partitioner rejects
        pp = sid[0]
        is_last = pp == n_stages - 1
        lnf = jax.tree.map(
            lambda t: pcast(t, (pp_axis,), to="varying"), lnf
        )
        mb = x.reshape(m, x.shape[0] // m, *x.shape[1:])
        tgt_mb = tgt.reshape(m, tgt.shape[0] // m, *tgt.shape[1:])
        fwd_mbs = jnp.asarray(fwd_tbl)[:, pp]   # [pairs] this stage's F mb
        bwd_mbs = jnp.asarray(bwd_tbl)[:, pp]   # [pairs] this stage's B mb

        def stage_fn(p, lnf_p, h):
            def body(carry, layer):
                new_h, aux = _block(carry, layer, config)
                return new_h, aux

            if config.remat:
                body = jax.checkpoint(body, prevent_cse=False)
            y, auxs = lax.scan(body, h, p)
            z = _layernorm(y, lnf_p["scale"], lnf_p["bias"])
            return y, z, auxs.sum()

        def stage_fn_with_tgt(p, l, h, t_b):
            y, z, aux = stage_fn(p, l, h)
            loss = jnp.mean(
                (z.astype(jnp.float32) - t_b.astype(jnp.float32)) ** 2
            )
            return y, loss, aux

        def var(t):  # carry entries must be pp-varying
            return pcast(t, (pp_axis,), to="varying")

        mb_shape = mb[0].shape
        grads0 = jax.tree.map(
            lambda p: var(jnp.zeros(p.shape, jnp.float32)), layers_local
        )
        lnf0 = jax.tree.map(
            lambda p: var(jnp.zeros(p.shape, jnp.float32)), lnf
        )
        carry0 = dict(
            acts=var(jnp.zeros((depth, *mb_shape), x.dtype)),
            recv_f=var(jnp.zeros(mb_shape, x.dtype)),
            recv_b=var(jnp.zeros(mb_shape, jnp.float32)),
            grads=grads0,
            dlnf=lnf0,
            loss=var(jnp.zeros((), jnp.float32)),
            aux=var(jnp.zeros((), jnp.float32)),
        )

        def pair(c, u):
            # --- forward wave: stage pp forwards microbatch u - pp ---
            f = fwd_mbs[u]
            valid_f = jnp.logical_and(f >= 0, f < m)
            inject = lax.dynamic_index_in_dim(
                mb, jnp.clip(f, 0, m - 1), 0, keepdims=False
            )
            h_in = jnp.where(pp == 0, inject, c["recv_f"])
            slot = jnp.clip(f, 0, m - 1) % depth
            acts = lax.dynamic_update_index_in_dim(
                c["acts"], h_in.astype(c["acts"].dtype), slot, 0
            )
            acts = jnp.where(valid_f, acts, c["acts"])
            y, _, _ = stage_fn(layers_local, lnf, h_in)

            # --- backward wave: stage pp backwards u - 2(pp-1) + pp ---
            b = bwd_mbs[u]
            valid_b = jnp.logical_and(b >= 0, b < m)
            h_b = lax.dynamic_index_in_dim(
                acts, jnp.clip(b, 0, m - 1) % depth, 0, keepdims=False
            )
            t_b = lax.dynamic_index_in_dim(
                tgt_mb, jnp.clip(b, 0, m - 1), 0, keepdims=False
            )
            (_, loss_b, aux_val), vjp = jax.vjp(
                lambda p, l, h: stage_fn_with_tgt(p, l, h, t_b),
                layers_local, lnf, h_b,
            )
            dy = c["recv_b"].astype(y.dtype)
            cot_y = jnp.where(is_last, jnp.zeros_like(dy), dy)
            cot_loss = jnp.where(is_last, 1.0 / m, 0.0)
            # derive the aux cotangent from the primal so it carries the
            # same shard_map varying-axes type (MoE aux is pp-varying;
            # the dense FFN's constant-zero aux is not)
            cot_aux = aux_val * 0.0 + jnp.float32(aux_cot)
            dp, dl, dh = vjp((cot_y, cot_loss.astype(jnp.float32),
                              cot_aux))
            vb32 = valid_b.astype(jnp.float32)
            grads = jax.tree.map(
                lambda g, a: g + vb32 * a.astype(jnp.float32),
                c["grads"], dp,
            )
            dlnf = jax.tree.map(
                lambda g, a: g + vb32 * a.astype(jnp.float32),
                c["dlnf"], dl,
            )
            loss = c["loss"] + jnp.where(
                jnp.logical_and(is_last, valid_b), loss_b / m, 0.0
            )
            aux = c["aux"] + jnp.where(
                valid_b, aux_val / (config.num_layers * m), 0.0
            )

            # --- hops: activations forward, cotangents backward.  The two
            # permutes MUST execute in one fixed order on every device:
            # XLA's runtimes require a uniform collective order (and at
            # pp=2 the two rings are the same permutation and even share a
            # channel id).  An optimization_barrier is not enough — loop
            # rotation rewires permutes to read the scan carry directly —
            # so the ordering edge is a real data dependency: 0 * fwd_next
            # is not folded by XLA (NaN-honoring semantics), making the
            # cotangent hop consume the activation hop's result.
            send_f = jnp.where(valid_f, y, jnp.zeros_like(y))
            send_b = jnp.where(valid_b, dh.astype(jnp.float32),
                               jnp.zeros(mb_shape, jnp.float32))
            fwd_next = lax.ppermute(
                send_f, pp_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            tie = jnp.zeros_like(send_b) * fwd_next.astype(jnp.float32)
            bwd_next = lax.ppermute(
                send_b + tie, pp_axis,
                [(i, (i - 1) % n_stages) for i in range(n_stages)],
            )
            return dict(
                acts=acts, recv_f=fwd_next, recv_b=bwd_next,
                grads=grads, dlnf=dlnf, loss=loss, aux=aux,
            ), None

        final, _ = lax.scan(pair, carry0, jnp.arange(pairs))
        loss = lax.psum(final["loss"], pp_axis)   # only last stage nonzero
        aux = lax.psum(final["aux"], pp_axis)
        dlnf = lax.psum(final["dlnf"], pp_axis)   # real only where loss was
        return final["grads"], dlnf, loss, aux

    stage_ids = jnp.arange(n_stages, dtype=jnp.int32)
    grads_layers, dlnf, loss, aux = shard_map(
        stage_local,
        mesh=mesh,
        in_specs=(P(pp_axis), layer_specs, P(), P(), P()),
        out_specs=(layer_specs, P(), P(), P()),
        axis_names={pp_axis},
    )(stage_ids, params["layers"], params["ln_f"], x, targets)
    total_loss = loss + moe_aux_weight * aux
    grads = {
        "layers": jax.tree.map(
            lambda g, p: g.astype(p.dtype), grads_layers, params["layers"]
        ),
        "ln_f": jax.tree.map(
            lambda g, p: g.astype(p.dtype), dlnf, params["ln_f"]
        ),
    }
    return total_loss, grads


def pipeline_forward(
    params,
    x: jax.Array,
    config: ModelConfig,
    mesh: Mesh,
    pp_axis: str = PP_AXIS,
    num_microbatches: Optional[int] = None,
    with_aux: bool = False,
):
    """Full-model forward with the layer stack pipelined over ``pp_axis``.

    ``params`` must hold the stacked-layer pytree of
    ``models/transformer.py::init_params`` with the leading layer axis
    sharded over ``pp_axis``; the final layernorm runs outside the
    pipeline (replicated, applied after the shard_map).

    ``with_aux=True`` additionally returns the MoE load-balancing loss,
    averaged over layers AND microbatches: each stage accumulates its
    local layers' aux for the microbatch it validly processes at each tick
    (bubble ticks masked out), and a ``psum`` over ``pp_axis`` totals the
    stages.  Mean-over-microbatches is the same approximation gradient
    accumulation makes (``moe_aux_loss`` is nonlinear in the batch, so it
    is not bit-identical to the unpipelined full-batch aux — the standard
    microbatching semantics).
    """
    from dlbb_tpu.models.transformer import _block, _layernorm

    n_stages = mesh.shape[pp_axis]
    m = validate_pipeline(config, n_stages, x.shape[0], num_microbatches)
    if config.attention == "full":
        # pin the einsum kernel inside the stage body: the TPU flash
        # auto-route would drop an opaque pallas_call under the shard_map's
        # auto dp/tp axes — the exact GSPMD pathology validate_pipeline
        # rejects attention='flash' for.  Same math either way.
        config = config.with_(attention="dense")

    layer_specs = jax.tree.map(lambda _: P(pp_axis), params["layers"])

    def stage_local(sid, layers_local, x):
        # layers_local: this stage's [L/pp, ...] block; x: full [B, S, H];
        # sid: pp-sharded [1] stage index (lax.axis_index would lower to a
        # PartitionId the SPMD partitioner rejects under partial-auto)
        pp = sid[0]
        mb = x.reshape(m, x.shape[0] // m, *x.shape[1:])
        state = pcast(jnp.zeros_like(mb[0]), (pp_axis,), to="varying")
        outputs = pcast(jnp.zeros_like(mb), (pp_axis,), to="varying")
        aux0 = pcast(jnp.zeros((), jnp.float32), (pp_axis,),
                         to="varying")

        def local_fwd(h):
            def body(carry, layer):
                new_h, aux = _block(carry, layer, config)
                return new_h, aux

            if config.remat:
                body = jax.checkpoint(body, prevent_cse=False)
            h, auxs = lax.scan(body, h, layers_local)
            return h, auxs.sum()  # sum over this stage's local layers

        def tick(carry, t):
            state, outputs, aux_sum = carry
            inject = lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, m - 1), 0, keepdims=False
            )
            y, aux = local_fwd(jnp.where(pp == 0, inject, state))
            # stage p processes microbatch t - p at tick t; outside
            # [0, m) it is running on bubble garbage — mask its aux out
            mb_idx = t - pp
            valid = jnp.logical_and(mb_idx >= 0, mb_idx < m)
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
            out_t = t - (n_stages - 1)
            write = jnp.logical_and(
                pp == n_stages - 1,
                jnp.logical_and(out_t >= 0, out_t < m),
            )
            updated = lax.dynamic_update_index_in_dim(
                outputs, y, jnp.clip(out_t, 0, m - 1), 0
            )
            outputs = jnp.where(write, updated, outputs)
            state = lax.ppermute(
                y, pp_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (state, outputs, aux_sum), None

        (_, outputs, aux_sum), _ = lax.scan(
            tick, (state, outputs, aux0), jnp.arange(m + n_stages - 1)
        )
        # only the last stage holds real outputs; the masked psum is the
        # SPMD broadcast back to every stage
        outputs = lax.psum(
            jnp.where(pp == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            pp_axis,
        )
        # stages hold disjoint layer blocks: psum totals all layers x mbs
        aux_total = lax.psum(aux_sum, pp_axis)
        return outputs.reshape(x.shape), aux_total

    stage_ids = jnp.arange(n_stages, dtype=jnp.int32)
    y, aux_total = shard_map(
        stage_local,
        mesh=mesh,
        in_specs=(P(pp_axis), layer_specs, P()),
        out_specs=(P(), P()),
        axis_names={pp_axis},
    )(stage_ids, params["layers"], x)
    out = _layernorm(y, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if with_aux:
        return out, aux_total / (config.num_layers * m)
    return out
