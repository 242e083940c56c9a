"""Pure-JAX tensor-parallel decoder.

Forward semantics match reference ``models.py:107-245`` (pre-LN block:
ln1 → QKV col-parallel → attention → out-proj row-parallel → residual;
ln2 → FFN-up col-parallel → gelu → FFN-down row-parallel → residual; final
LN), re-designed for XLA:

- layers are stacked on a leading axis and executed with ``lax.scan`` —
  one traced layer body regardless of depth (compile time O(1) in layers,
  unlike a Python loop over 40 blocks);
- parallelism comes from partition specs (see ``sharding.py``), not
  hand-written collectives;
- layernorm statistics are computed in fp32 and cast back (bf16-safe);
- ``attention="simplified"`` replicates the reference's shortcut of taking
  the query projection for the attention output (``models.py:162-167``);
  ``attention="full"`` is causal MHA with fp32 softmax.

No code is shared with the reference; citations are for parity auditing.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.models.sharding import PP_AXIS, specs_for_mesh

Params = dict[str, Any]

# The phase names every transformer block carries as ``jax.named_scope``s,
# in the order a block runs them.  A scope is HLO metadata (``op_name``):
# it costs nothing at run time and survives a recompile, so a device
# trace can be grouped by phase where fusion numbers cannot
# (docs/observability.md, "Names").  ``_block`` here and the serving
# twin ``serve/engine.py::_serve_block`` both unpack THIS tuple.
BLOCK_PHASES = ("ln1", "attn_qkv", "attn_core", "attn_out",
                "ln2", "mlp_up", "mlp_act", "mlp_down")
(LN1, ATTN_QKV, ATTN_CORE, ATTN_OUT,
 LN2, MLP_UP, MLP_ACT, MLP_DOWN) = BLOCK_PHASES
# the serving programs' cache phases (inside ``attn_core``) and the
# train step's phases outside the blocks
SERVE_PHASES = ("kv_update", "kv_attend")
TRAIN_PHASES = ("loss", "grad_reduce", "optimizer")


def named(name: str):
    """Decorator: give a function about to be jitted the program name
    the profile's "XLA Modules" line prints (``jit_<name>``)."""
    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return rename


def _dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Initialise the stacked-layer parameter pytree.

    Scaled-normal kernels (1/sqrt(fan_in)), zero biases, unit LN scales —
    standard init; the reference's randn-based init is at ``models.py:33-38``.

    The columns of the fused ``qkv`` kernel ``[L, H, qkv_width]`` and bias
    ``[L, qkv_width]`` are ordered by kv-head group, each head ``head_dim``
    wide: for group ``j`` of ``kv_heads``, its ``num_heads / kv_heads``
    query heads (heads ``j * g .. j * g + g - 1``), then key head ``j``,
    then value head ``j`` (``split_qkv`` is the one reader).  A contiguous
    ``tp`` shard of the columns therefore holds whole groups whenever
    ``tp`` divides ``kv_heads``, and the layout does not depend on ``tp``.
    """
    if config.is_hybrid:
        from dlbb_tpu.models import hybrid

        return hybrid.init_params(config, key)
    h, f, L = config.hidden_size, config.ffn_intermediate, config.num_layers
    dtype = _dtype_of(config.dtype)

    def kernel(key, shape, fan_in):
        # sample directly in the target dtype — avoids a transient fp32 copy
        # of each kernel (full-model memory is addressed by
        # init_params_sharded, which materialises shards in place)
        return jax.random.normal(key, shape, dtype=dtype) / math.sqrt(fan_in)

    ks = jax.random.split(key, 5)
    if config.is_moe:
        E = config.num_experts
        ffn = {
            # router logits in the params dtype; gating math runs in fp32
            "router": {"kernel": kernel(ks[4], (L, h, E), h)},
            "ffn_up": {
                "kernel": kernel(ks[2], (L, E, h, f), h),
                "bias": jnp.zeros((L, E, f), dtype),
            },
            "ffn_down": {
                "kernel": kernel(ks[3], (L, E, f, h), f),
                "bias": jnp.zeros((L, E, h), dtype),
            },
        }
    else:
        ffn = {
            "ffn_up": {
                "kernel": kernel(ks[2], (L, h, f), h),
                "bias": jnp.zeros((L, f), dtype),
            },
            "ffn_down": {
                "kernel": kernel(ks[3], (L, f, h), f),
                "bias": jnp.zeros((L, h), dtype),
            },
        }
    layers = {
        "ln1": {"scale": jnp.ones((L, h), dtype), "bias": jnp.zeros((L, h), dtype)},
        "qkv": {
            # qkv_width = H + 2 * kv_heads * head_dim (GQA shrinks the
            # K/V share; == 3H for full MHA); columns by kv-head group
            "kernel": kernel(ks[0], (L, h, config.qkv_width), h),
            "bias": jnp.zeros((L, config.qkv_width), dtype),
        },
        "out": {
            "kernel": kernel(ks[1], (L, h, h), h),
            "bias": jnp.zeros((L, h), dtype),
        },
        "ln2": {"scale": jnp.ones((L, h), dtype), "bias": jnp.zeros((L, h), dtype)},
        **ffn,
    }
    return {
        "layers": layers,
        "ln_f": {"scale": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)},
    }


def _layernorm(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + 1e-5)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def split_qkv(qkv, config: ModelConfig):
    """The fused projection's output by head, the heads in front of the
    tokens as the attention kernels take them: ``[..., S, qkv_width]``
    -> q ``[..., num_heads, S, d]``, k and v ``[..., kv_heads, S, d]``.

    The one reader of the column order ``init_params`` documents.  The
    group axis of the reshape inherits a ``tp`` sharding of the columns,
    so every shard takes its own heads' q, k and v from what it computed
    and nothing is realigned between chips.

    How it is written is for XLA:TPU (``PERF.md`` §6, PR 32).  The
    barrier keeps the split out of the projection: without it the
    reshape is folded into the matmul, whose kernel is then re-laid out
    to suit, a kernel-sized copy a layer in every serving program
    (``tests/test_serve_fastpath.py`` compiles them for the v5e and
    holds that no such copy is there).  And the activation is moved
    once, a kv-head group at a time, before the heads come off its last
    axis at multiples of ``d``: split first and moved after, the 1B
    training step is 0.7% slower than with q, k and v in thirds, and
    indexed on a ``[..., kv_heads, g + 2, d]`` view 3.3%."""
    n, d, kvh = config.num_heads, config.head_dim, config.kv_heads
    g = n // kvh
    *lead, s, _ = qkv.shape
    qkv = jax.lax.optimization_barrier(qkv)
    grouped = jnp.swapaxes(
        qkv.reshape(*lead, s, kvh, (g + 2) * d), -2, -3)
    q = jnp.swapaxes(
        grouped[..., :g * d].reshape(*lead, kvh, s, g, d), -2, -3)
    return (q.reshape(*lead, n, s, d), grouped[..., g * d:(g + 1) * d],
            grouped[..., (g + 1) * d:])


def _attention(qkv, config: ModelConfig, mesh=None, sp_axis: str = "sp"):
    """qkv: [B, S, qkv_width] -> [B, S, H]."""
    b, s, _ = qkv.shape
    n, d, kvh = config.num_heads, config.head_dim, config.kv_heads
    q, k, v = split_qkv(qkv, config)  # [B, heads, S, d]
    if config.attention == "simplified":
        # reference's benchmarking shortcut: the query projection IS the
        # attention output (``models.py:162-167``)
        return q.transpose(0, 2, 1, 3).reshape(b, s, n * d)

    # Grouped K/V flow at kv_heads width end-to-end through every kernel
    # (dense einsum broadcasting; grouped flash blocks; grouped ring/
    # Ulysses).  The only broadcasts left are sharding fallbacks where a
    # mesh axis cannot divide kv_heads — marked below.

    if config.attention in ("ring", "ulysses"):
        # sequence/context-parallel attention over the mesh's sp axis
        if mesh is None or sp_axis not in mesh.axis_names:
            raise ValueError(
                f"attention={config.attention!r} needs a mesh with a "
                f"{sp_axis!r} axis passed to forward()"
            )
        from dlbb_tpu.parallel import ring_attention, ulysses_attention

        if config.attention == "ring":
            o = ring_attention(q, k, v, mesh, sp_axis=sp_axis,
                               causal=config.causal)
        else:
            if kvh != n and kvh % mesh.shape[sp_axis] != 0:
                # Ulysses all-to-alls the head dim over sp; kv_heads not
                # divisible by sp cannot stay grouped — broadcast fallback
                # (ring attention keeps grouped K/V for any kv_heads)
                k = jnp.repeat(k, n // kvh, axis=1)
                v = jnp.repeat(v, n // kvh, axis=1)
            o = ulysses_attention(q, k, v, mesh, sp_axis=sp_axis,
                                  causal=config.causal)
    elif config.attention == "flash":
        o = _flash_dispatch(q, k, v, config, mesh, sp_axis)
    else:  # "full" (auto-routed exact) | "dense" (forced dense kernel)
        from dlbb_tpu.models.attention import dense_attention

        sp_sharded = (mesh is not None and sp_axis in mesh.axis_names
                      and mesh.shape[sp_axis] > 1)
        if (config.attention == "full" and not sp_sharded
                and _flash_profitable(q.shape)):
            # exact numerics either way; the blocked kernel avoids the
            # [B, N, S, S] score materialisation that throttles (and at
            # S=8192 OOMs) the dense path
            o = _flash_dispatch(q, k, v, config, mesh, sp_axis)
        else:
            o = dense_attention(q, k, v, causal=config.causal)
    return o.transpose(0, 2, 1, 3).reshape(b, s, n * d)


# Route "full" attention through the pallas kernel on real TPUs at
# sequence lengths where it measurably wins; the simulated/CPU dev mesh
# keeps the dense einsum (interpret-mode pallas would be pure overhead).
# Gate calibration (v5e chip, bf16, committed e2e artifacts
# results/e2e/xla_tpu_{1b,7b}_{dense,flash}_s512_world1.json — "dense"
# pins the un-routed kernel, so these pairs stay a real comparison across
# publisher re-runs): at S=512 in-model flash beats dense 1.10x on 1B
# (63.5k vs 57.5k tok/s) and 1.03x on 7B (12.45k vs 12.11k), and the gap
# widens with S (1.31x at S=1024, dense OOMs by 8192).  Standalone
# (outside the model) dense still wins small shapes (B8/N16/D128 S=512:
# 0.29 ms vs 0.41 ms) — in-model numbers govern the route, standalone
# callers pick their own kernel.
FLASH_ROUTE_MIN_SEQ = 512


def _flash_profitable(q_shape) -> bool:
    import jax as _jax

    # lane-aligned sequence required: _fit_block falls back to the largest
    # divisor, and an unfriendly S (e.g. prime) would degrade the grid to
    # tiny blocks — far slower than the dense einsum being replaced
    return (_jax.default_backend() == "tpu"
            and q_shape[2] >= FLASH_ROUTE_MIN_SEQ
            and q_shape[2] % 128 == 0)


def _flash_dispatch(q, k, v, config: ModelConfig, mesh, sp_axis: str):
    """Run the pallas flash kernel under the sharding the mesh dictates.

    pallas_call is opaque to GSPMD — without an explicit shard_map, jit
    would all-gather the batch-(dp) and head-(tp) sharded qkv and run the
    kernel replicated on every device.  Batch entries and heads are
    independent, so map the kernel over whichever of (dp, tp) is actually
    sharded; each device computes only its own slice.
    """
    from dlbb_tpu.ops import flash_attention

    n, kvh = q.shape[1], k.shape[1]
    if mesh is not None and sp_axis in mesh.axis_names and mesh.shape[sp_axis] > 1:
        raise ValueError(
            "attention='flash' does not partition the sequence; use "
            "attention='ring' or 'ulysses' when sequence_parallel > 1"
        )
    dp = (
        "dp" if mesh is not None and "dp" in mesh.axis_names
        and mesh.shape["dp"] > 1 else None
    )
    tp = (
        "tp" if mesh is not None and "tp" in mesh.axis_names
        and mesh.shape["tp"] > 1 else None
    )
    if dp is not None or tp is not None:
        from jax.sharding import PartitionSpec as P

        from dlbb_tpu.compat import shard_map

        if kvh != n and tp is not None and kvh % mesh.shape[tp] != 0:
            # the head axis is tp-sharded; kv_heads not divisible by
            # tp cannot stay grouped — broadcast fallback
            k = jnp.repeat(k, n // kvh, axis=1)
            v = jnp.repeat(v, n // kvh, axis=1)
        spec = P(dp, tp, None, None)
        return shard_map(
            lambda q, k, v: flash_attention(
                q, k, v, causal=config.causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,  # pallas_call declares no vma
        )(q, k, v)
    return flash_attention(q, k, v, causal=config.causal)


def router_probs_gates(logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Full fp32 softmax router distribution and the sparse top-k routing
    weights (k largest probabilities renormalised to sum 1 — Mixtral-style
    gating).  Returns ``(probs, gates)``, both [..., E]; gates have exactly
    k nonzeros."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    mask = jax.nn.one_hot(top_idx, logits.shape[-1],
                          dtype=probs.dtype).sum(axis=-2)
    gated = probs * mask
    return probs, gated / gated.sum(axis=-1, keepdims=True)


def top_k_gates(logits: jax.Array, k: int) -> jax.Array:
    """Sparse top-k routing weights; see ``router_probs_gates``."""
    return router_probs_gates(logits, k)[1]


def moe_aux_loss(probs: jax.Array, gates: jax.Array, k: int) -> jax.Array:
    """Switch-Transformer load-balancing loss, generalised to top-k:
    ``E * sum_e f_e * P_e`` with ``f_e`` the fraction of routing slots sent
    to expert e and ``P_e`` its mean router probability.  Equals 1.0 at
    perfect balance, grows as routing collapses onto few experts."""
    num_experts = probs.shape[-1]
    f = (gates > 0).astype(jnp.float32).mean(axis=(0, 1)) / k
    p = probs.mean(axis=(0, 1))
    return num_experts * jnp.sum(f * p)


def _moe_ffn_dense(y, gates32, layer: Params, config: ModelConfig):
    """Top-k gated mixture-of-experts FFN: [B, S, H] -> [B, S, H].

    Dense-dispatch design: every expert runs on every token and the gate
    weights (zero outside the top-k) select the combination.  Static
    shapes, no token dropping, exact under any sharding; with the expert
    dim sharded over ``ep`` each device computes only its local experts
    and the final gate contraction becomes the psum over ``ep`` (GSPMD).
    """
    gates = gates32.astype(y.dtype)
    up = jnp.einsum("bsh,ehf->bsef", y, layer["ffn_up"]["kernel"])
    up = up + layer["ffn_up"]["bias"][None, None, :, :]
    act = jax.nn.gelu(up)
    per_expert = jnp.einsum("bsef,efh->bseh", act,
                            layer["ffn_down"]["kernel"])
    per_expert = per_expert + layer["ffn_down"]["bias"][None, None, :, :]
    return jnp.einsum("bseh,bse->bsh", per_expert, gates)


def moe_capacity(config: ModelConfig, seq_len: int) -> int:
    """Per-expert capacity slots per sequence (GShard formula:
    capacity_factor * tokens * k / E, floored at 1 and capped at seq_len —
    an expert can never receive more than the group's tokens)."""
    c = math.ceil(
        config.moe_capacity_factor * seq_len * config.moe_top_k
        / config.num_experts
    )
    return max(1, min(c, seq_len))


def _moe_ffn_capacity(y, gates, layer: Params, config: ModelConfig):
    """GShard-style capacity-bounded einsum dispatch: [B, S, H] -> [B, S, H].

    Each sequence is a dispatch group; every expert gets a fixed buffer of
    ``moe_capacity(config, S)`` slots per group, and (token, expert)
    routing slots claim buffer slots in sequence order via a per-expert
    cumulative count.  Over-capacity *routing slots* are dropped
    individually: with top-k > 1 a token can lose one expert's
    contribution while keeping another's (at its un-renormalised gate
    weight); a token dropped by every selected expert flows through the
    block's residual only.  All static shapes; per-device expert FLOPs are
    capacity-bounded rather than all-tokens x all-experts; the combine
    contraction over the expert dim lowers to the ``ep`` psum under GSPMD,
    exactly like dense dispatch.
    """
    b, s, _ = y.shape
    cap = moe_capacity(config, s)
    mask = gates > 0
    # slot index each token would take in each expert's queue (per group)
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1     # [B, S, E]
    keep = jnp.logical_and(mask, pos < cap)
    dispatch = (
        jax.nn.one_hot(pos, cap, dtype=y.dtype)
        * keep[..., None].astype(y.dtype)
    )                                                        # [B, S, E, C]
    expert_in = jnp.einsum("bsec,bsh->bech", dispatch, y)    # [B, E, C, H]
    up = jnp.einsum("bech,ehf->becf", expert_in,
                    layer["ffn_up"]["kernel"])
    up = up + layer["ffn_up"]["bias"][None, :, None, :]
    act = jax.nn.gelu(up)
    out = jnp.einsum("becf,efh->bech", act, layer["ffn_down"]["kernel"])
    out = out + layer["ffn_down"]["bias"][None, :, None, :]
    combine = dispatch * gates[..., None].astype(y.dtype)    # [B, S, E, C]
    return jnp.einsum("bsec,bech->bsh", combine, out)


def _moe_ffn(y, layer: Params, config: ModelConfig):
    """Route + dispatch: returns ``(out, aux)`` — the FFN output and the
    layer's load-balancing loss (``moe_aux_loss``).  Routing is shared;
    only the dispatch strategy differs between dense and capacity."""
    logits = y @ layer["router"]["kernel"]                  # [B, S, E]
    probs, gates = router_probs_gates(logits, config.moe_top_k)  # fp32
    if config.moe_dispatch == "capacity":
        out = _moe_ffn_capacity(y, gates, layer, config)
    else:
        out = _moe_ffn_dense(y, gates, layer, config)
    return out, moe_aux_loss(probs, gates, config.moe_top_k)


def tp_overlap_route(config: ModelConfig, mesh, x_shape,
                     dtype=None) -> str:
    """The route the four TP projections of a block take for a residual
    stream of global shape ``x_shape`` on ``mesh``: "off" (the fused
    GSPMD matmuls and their two all-reduces), "ring" or "bidir"
    (``parallel/collective_matmul.py``, the stream sequence-sharded over
    tp between blocks).

    Without a >1 tp axis there is nothing to overlap, whatever the knob
    says, so single-device runs and non-TP meshes keep the GSPMD lowering
    bit for bit.  A forced ``config.tp_overlap`` is taken at its word
    (``validate_tp_overlap`` has refused what cannot run).  Under "auto"
    the shapes decide (``collective_matmul.auto_schedule``), and a program
    no ring can take (experts, a pipeline, a sequence tp does not divide,
    hops too small to hide) is the fused one: nothing raises."""
    if (mesh is None or "tp" not in getattr(mesh, "axis_names", ())
            or mesh.shape["tp"] <= 1):
        return "off"
    if config.tp_overlap != "auto":
        return config.tp_overlap
    if config.is_moe or (PP_AXIS in mesh.axis_names
                         and mesh.shape[PP_AXIS] > 1):
        return "off"
    from dlbb_tpu.parallel.collective_matmul import auto_schedule

    itemsize = jnp.dtype(dtype or _dtype_of(config.dtype)).itemsize
    return auto_schedule(
        mesh, x_shape, itemsize,
        # column-parallel outputs, then row-parallel contractions
        (config.qkv_width, config.ffn_intermediate,
         config.hidden_size, config.ffn_intermediate)) or "off"


def _block(x, layer: Params, config: ModelConfig, mesh=None,
           sp_axis: str = "sp"):
    """One transformer block (reference ``TransformerBlock.forward``
    ``models.py:147-190``); the FFN is the gated-expert mixture when
    ``config.num_experts > 0``.

    On an overlapped route (``tp_overlap_route``) the four TP projections
    run as ring-decomposed collective matmuls: the residual stream x
    enters sequence-sharded over tp, each column-parallel projection
    gathers it behind partial matmuls (``allgather_matmul``) and each
    row-parallel projection returns it to the sequence-sharded layout
    behind the same ring
    (``matmul_reducescatter``) — no exposed TP all-reduce remains.

    Returns ``(x, aux)`` — aux is the layer's MoE load-balancing loss
    (0.0 for the dense FFN)."""
    sched = tp_overlap_route(config, mesh, x.shape, x.dtype)
    if sched != "off":
        from dlbb_tpu.parallel.collective_matmul import (
            allgather_matmul,
            matmul_reducescatter,
        )

        def col(y, kernel, bias):
            return allgather_matmul(y, kernel, mesh, schedule=sched) + bias

        def row(y, kernel, bias):
            return matmul_reducescatter(y, kernel, mesh,
                                        schedule=sched) + bias
    else:
        def col(y, kernel, bias):
            return y @ kernel + bias

        def row(y, kernel, bias):
            return y @ kernel + bias

    residual = x
    with jax.named_scope(LN1):
        y = _layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
    with jax.named_scope(ATTN_QKV):
        qkv = col(y, layer["qkv"]["kernel"], layer["qkv"]["bias"])
    with jax.named_scope(ATTN_CORE):
        attn = _attention(qkv, config, mesh, sp_axis)
    with jax.named_scope(ATTN_OUT):
        x = row(attn, layer["out"]["kernel"],
                layer["out"]["bias"]) + residual

    residual = x
    with jax.named_scope(LN2):
        y = _layernorm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
    if config.is_moe:
        ffn_out, aux = _moe_ffn(y, layer, config)
        x = ffn_out + residual
    else:
        with jax.named_scope(MLP_UP):
            y = col(y, layer["ffn_up"]["kernel"], layer["ffn_up"]["bias"])
        with jax.named_scope(MLP_ACT):
            y = jax.nn.gelu(y)
        with jax.named_scope(MLP_DOWN):
            x = row(y, layer["ffn_down"]["kernel"],
                    layer["ffn_down"]["bias"]) + residual
        aux = jnp.zeros((), jnp.float32)
    return x, aux


def forward(params: Params, x: jax.Array, config: ModelConfig,
            mesh=None, sp_axis: str = "sp", pp_axis: str = PP_AXIS,
            num_microbatches=None, with_aux: bool = False):
    """Full forward pass: scan over stacked layers + final LN
    (reference ``LLM.forward`` ``models.py:224-237``).

    ``mesh`` is required only for sequence-parallel attention modes
    ("ring"/"ulysses") and pipeline parallelism, whose shard_maps need the
    concrete mesh.  A mesh with a >1-sized ``pp_axis`` dispatches to the
    microbatched pipeline engine (``dlbb_tpu/parallel/pipeline.py``).

    ``with_aux=True`` additionally returns the layer-mean MoE
    load-balancing loss (``moe_aux_loss``); under pipeline parallelism it
    is additionally averaged over microbatches (per-stage masked
    accumulation + psum — see ``pipeline_forward``).

    A ``layer_types`` model (``models/hybrid.py``) takes token ids
    ``[B, S]`` for ``x`` and returns float32 logits ``[B, S, vocab]``.
    """
    if config.is_hybrid:
        from dlbb_tpu.models import hybrid

        if with_aux:
            raise ValueError("with_aux is for MoE models")
        return hybrid.forward(params, x, config, mesh)
    if (mesh is not None and pp_axis in mesh.axis_names
            and mesh.shape[pp_axis] > 1):
        from dlbb_tpu.parallel.pipeline import pipeline_forward

        return pipeline_forward(
            params, x, config, mesh, pp_axis=pp_axis,
            num_microbatches=num_microbatches, with_aux=with_aux,
        )

    if tp_overlap_route(config, mesh, x.shape, x.dtype) != "off":
        # pin the residual stream to the sequence-sharded-over-tp layout
        # BEFORE the scan: the carry's sharding must be stable across
        # iterations (every block returns this layout), and constraining
        # the entry point keeps GSPMD from resharding per iteration
        from dlbb_tpu.parallel.collective_matmul import activation_spec

        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, activation_spec(mesh))
        )

    def body(carry, layer):
        return _block(carry, layer, config, mesh, sp_axis)

    if config.remat:
        # prevent_cse=False: safe and faster under lax.scan, whose loop
        # structure already rules out the CSE the default barriers guard.
        # Policy selects WHAT each block saves (configs.ModelConfig
        # remat_policy): "full" saves nothing, "dots" saves matmul outputs
        # and recomputes only elementwise ops.
        policy = (jax.checkpoint_policies.dots_saveable
                  if config.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    x, auxs = jax.lax.scan(body, x, params["layers"])
    y = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if with_aux:
        return y, auxs.mean()
    return y


def num_parameters(config: ModelConfig) -> int:
    """Total parameter count (reference ``get_num_parameters``
    ``models.py:239-241``; MoE counts every expert + router)."""
    if config.is_hybrid:
        from dlbb_tpu.models import hybrid

        return hybrid.num_parameters(config)
    h, f, L = config.hidden_size, config.ffn_intermediate, config.num_layers
    if config.is_moe:
        E = config.num_experts
        ffn = h * E + E * (h * f + f) + E * (f * h + h)  # router + experts
    else:
        ffn = (h * f + f) + (f * h + h)
    qkvw = config.qkv_width
    per_layer = (
        2 * h            # ln1
        + h * qkvw + qkvw  # fused qkv (GQA-aware width)
        + h * h + h      # out
        + 2 * h          # ln2
        + ffn
    )
    return L * per_layer + 2 * h  # + final LN


def forward_flops(config: ModelConfig, batch_size: int, seq_len: int) -> int:
    """Analytic forward-pass FLOPs for a [B, S, H] batch (matmul and
    dispatch einsum multiply-adds counted as 2 FLOPs; layernorms, gelu,
    softmax, and gating omitted — sub-percent).  Used for
    achieved-TFLOP/s reporting in the harnesses."""
    h, f, L = config.hidden_size, config.ffn_intermediate, config.num_layers
    tokens = batch_size * seq_len
    qkv = 2 * tokens * h * config.qkv_width
    out = 2 * tokens * h * h
    if config.attention == "simplified":
        attn = 0  # the reference's shortcut has no attention matmuls
    else:
        attn = 4 * batch_size * seq_len * seq_len * h  # QK^T + AV
    if config.is_moe:
        E = config.num_experts
        router = 2 * tokens * h * E
        if config.moe_dispatch == "capacity":
            cap = moe_capacity(config, seq_len)
            slots = batch_size * E * cap
            # the one-hot dispatch and combine einsums
            # ('bsec,bsh->bech' / 'bsec,bech->bsh') are dense over
            # [B, S, E, C] x H and dominate for long sequences
            dispatch = 2 * (2 * tokens * E * cap * h)
        else:
            slots = tokens * E
            dispatch = 2 * tokens * E * h  # gate combine 'bseh,bse->bsh'
        ffn = router + dispatch + 2 * slots * h * f * 2
    else:
        ffn = 2 * tokens * h * f * 2
    return L * (qkv + attn + out + ffn)


def shard_params(params: Params, mesh: Mesh, tp_axis: str = "tp") -> Params:
    """Place a parameter pytree onto the mesh with the Megatron TP layout
    (plus layer-stack pp / expert ep sharding when the mesh has those
    axes; MoE is detected from the pytree structure)."""
    specs = specs_for_mesh(mesh, tp_axis, moe="router" in params["layers"])
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs
    )


def init_params_sharded(
    config: ModelConfig, key: jax.Array, mesh: Mesh, tp_axis: str = "tp"
) -> Params:
    """Initialise parameters *directly sharded* onto the mesh.

    jit with sharded out-shardings makes XLA generate each device's shard in
    place (partitionable threefry), so no device ever holds the full
    replicated pytree — required for 7B/13B on 16 GB-HBM chips, where
    ``init_params`` + ``shard_params`` would materialise the whole model on
    the default device first.
    """
    if config.is_hybrid:
        from dlbb_tpu.models import hybrid

        return hybrid.init_params_sharded(config, key, mesh)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs_for_mesh(mesh, tp_axis, moe=config.is_moe),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    return jax.jit(
        lambda k: init_params(config, k), out_shardings=shardings
    )(key)
