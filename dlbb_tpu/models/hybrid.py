"""The ``layer_types`` block family: Olmo-Hybrid, (PR 31) the
``deepseek_v3`` block of kanana-2-30b-a3b, (PR 33) the looped stack
of Ouro, and (PR 37) the ``granitemoehybrid`` block of Granite 4.0-H.

A stack that repeats one PERIOD of layers, each ``linear_attention``
(the gated delta rule of ``ops/gated_delta.py`` behind a short causal
convolution), ``full_attention`` (causal multi-head attention with
QK-norm, with rotary positions on half-split pairs where ``rope_theta``
is given, or with both) or ``latent_attention`` (MLA: per token
one normed low-rank latent and one rotary key shared by all heads, from
which each head's keys and values are expanded, or into which its
queries are absorbed) or ``mamba`` (the Mamba-2 recurrence of
``ops/ssd.py`` behind one fused projection that also yields the gate and
the step, held as two column blocks, and a convolution over x, B and C
together; a gated RMSNorm over the whole inner width in front of its
out-projection), every one followed by an MLP: a SwiGLU, or after
the ``first_k_dense_replace`` leading layers the routed and shared
experts of ``ops/routed_experts.py``.  The RMSNorms sit where
``norm_placement`` says: on a sub-layer's OUTPUT (OLMo 2/3: ``h = x +
norm(mixer(x))``, ``out = h + norm(mlp(h))``), on its INPUT (``h = x +
mixer(norm(x))``, ``out = h + mlp(norm(h))``) or on both (``sandwich``:
``h = x + norm(mixer(norm(x)))``, four scales a layer).  No biases
but the state-space layers' convolution's.  The full-attention layers'
K and V may have fewer heads than the queries (``num_kv_heads``).
Token ids in, logits over ``vocab_size`` out: an embedding table, a
final RMSNorm and a head, untied or (``tie_word_embeddings``) the
embedding table itself.  Granite's four multipliers
(``models/configs.py``) are applied where they differ from 1.

A LOOPED stack (``total_ut_steps`` > 1, :func:`run_stack`) runs the same
layers and the same final norm that many times a token, ``h_t =
norm_f(stack(h_{t-1}))``, with an exit gate ``sigmoid(w . h_t + b)``
after every pass; the K and V a layer computes in pass ``t`` lie in
planes of their own (``pass x L_full + l``) and a later token's pass
``t`` attends to them alone.  The head reads the last pass's ``h``.

ONE definition of the block (:func:`hybrid_block`) and of the period
(:func:`scan_periods`), used by :func:`forward` here (a whole sequence,
no cache) and by every serving program (``serve/hybrid.py``).  What
differs between them is the *mixer*: an object with ``attention(q, k,
v, l, state)`` and ``linear(qkv, log_alpha, beta, conv_w, l, state)``
(and ``latent(q, c, k_rope, wkv_b, l, state)``, ``ssm(xbc, dt, layer,
l, state)``) that owns everything
that touches a cache, and says where its tokens lie (``positions()``,
for the full-attention layers' rotary).  ``state`` is opaque to the
block.  A mixer also says which tokens are real (``valid()``: the
others take no expert's time) and is told what an expert layer chose
(``routed``); ``collect()`` hands both kinds of a period's outputs on.

Parameters are stacked over periods (``lax.scan`` runs one period a
trip), one sub-tree per position of the period: ``params["periods"]``,
and ``params["lead"]`` for the leading dense layers where there are
any (:func:`scan_stack` runs the one scan after the other).  Projections keep the
head as an axis of its own (``[h, heads, d]``) so that tensor
parallelism shards whole heads and nothing is realigned after a split;
the linear layers' q, k and v of one head share one fused projection
and one convolution (``[h, heads, 2 d_k + d_v]``).
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.models.attention import dense_attention
from dlbb_tpu.models.configs import (
    FULL_ATTENTION,
    LATENT_ATTENTION,
    LINEAR_ATTENTION,
    MAMBA,
    ModelConfig,
)
from dlbb_tpu.models.transformer import (
    ATTN_CORE,
    ATTN_OUT,
    ATTN_QKV,
    LN1,
    LN2,
    MLP_ACT,
    MLP_DOWN,
    MLP_UP,
    _dtype_of,
)
from dlbb_tpu.ops.gated_delta import (
    causal_conv,
    gated_delta_chunked,
    l2_normalise,
)
from dlbb_tpu.ops.routed_experts import expert_layer
from dlbb_tpu.ops.ssd import ssd_chunked

Params = dict[str, Any]

# The scopes this family adds to ``transformer.BLOCK_PHASES`` (which it
# keeps where they mean the same: ``attn_*`` in the full-attention
# layers, ``mlp_*``, ``ln1``/``ln2`` for the two output norms).
# ``lin_core`` holds ``state_update`` (decode) or ``state_scan``
# (prefill, forward).  docs/observability.md, "Names".
HYBRID_PHASES = ("embed", "lm_head", "lin_proj", "lin_conv", "lin_core",
                 "lin_out")
EMBED, LM_HEAD, LIN_PROJ, LIN_CONV, LIN_CORE, LIN_OUT = HYBRID_PHASES
# ... and the latent-attention layers' (they keep ``attn_out``): the
# rotation, the query projection, the down-projection to latent and
# rotary key with the latent's norm, and the products with ``W_kv_b``
# (the expansion to per-head keys and values in a chunk, the two
# absorbed products in decode).  The expert layer's ``moe_*`` are
# ``ops/routed_experts.py::MOE_PHASES``.
MLA_PHASES = ("rope", "mla_q", "mla_kv_a", "mla_kv_b")
ROPE, MLA_Q, MLA_KV_A, MLA_KV_B = MLA_PHASES
# ... and the state-space layers': the fused in-projection (gate, x, B,
# C and the step, with the step's softplus), the convolution with its
# bias and SiLU, the recurrence (``ssm_core`` holds ``state_update`` or
# ``state_scan`` as ``lin_core`` does), and the gate, the gated norm and
# the out-projection
SSM_PHASES = ("ssm_proj", "ssm_conv", "ssm_core", "ssm_out")
SSM_PROJ, SSM_CONV, SSM_CORE, SSM_OUT = SSM_PHASES
# ... and the looped stack's, once a PASS (not a layer): the final norm
# between passes and the one-output exit gate
LOOP_PHASES = ("loop_norm", "exit_gate")
LOOP_NORM, EXIT_GATE = LOOP_PHASES

# the recurrent state's precision, wherever it is kept or carried
STATE_DTYPE = jnp.float32


# -- parameters ----------------------------------------------------------------


# float32 whatever the model's dtype: ``A_log`` and ``dt_bias`` feed an
# exponential of an exponential, ``router_bias`` decides near-ties
_FLOAT32 = ("A_log", "dt_bias", "router_bias", "ssm_D")
_SCALES = ("ln1", "ln2", "ln1_out", "ln2_out", "q_norm", "k_norm",
           "o_norm", "kv_norm")
# scales drawn uniform in (0.5, 1.5): what they scale is of unit size
# before its norm, so ones would hide whether the scale is applied
_DRAWN_SCALES = ("kv_norm", "ssm_norm")


def _layer_shapes(config: ModelConfig, kind: str,
                  experts: bool = False) -> dict[str, tuple]:
    """Shapes (without the leading period axis) of one layer of ``kind``
    whose MLP is dense, or the ``experts`` layer."""
    h, f = config.hidden_size, config.ffn_intermediate
    shapes: dict[str, tuple] = {"ln1": (h,), "ln2": (h,)}
    if config.norm_placement == "sandwich":
        # ``ln1``, ``ln2`` on the two sub-layers' inputs, these on their
        # outputs
        shapes.update(ln1_out=(h,), ln2_out=(h,))
    if experts:
        e, fe = config.n_routed_experts, config.moe_intermediate_size
        shapes.update(router=(h, e), router_bias=(e,),
                      exp_gate=(e, h, fe), exp_up=(e, h, fe),
                      exp_down=(e, fe, h))
        if config.n_shared_experts:
            fs = config.n_shared_experts * fe
            shapes.update(shared_gate=(h, fs), shared_up=(h, fs),
                          shared_down=(fs, h))
    else:
        shapes.update(mlp_gate=(h, f), mlp_up=(h, f), mlp_down=(f, h))
    if kind == FULL_ATTENTION:
        n, d, kvh = config.num_heads, config.head_dim, config.kv_heads
        shapes.update(wq=(h, n, d), wk=(h, kvh, d), wv=(h, kvh, d),
                      wo=(n, d, h))
        if config.qk_norm:
            shapes.update(q_norm=(n, d), k_norm=(n, d))
    elif kind == LATENT_ATTENTION:
        n, r = config.num_heads, config.kv_lora_rank
        dn, dr = config.qk_nope_head_dim, config.qk_rope_head_dim
        dv = config.v_head_dim
        shapes.update(wq=(h, n, dn + dr), wkv_a=(h, r + dr), kv_norm=(r,),
                      wkv_b=(r, n, dn + dv), wo=(n, dv, h))
    elif kind == MAMBA:
        nh, inner = config.mamba_n_heads, config.mamba_inner
        channels = config.mamba_conv_channels
        # the fused in-projection [z (gate) | x, B, C | dt], held as two
        # column blocks: ``ssm_in`` the first two parts (whole lanes at
        # the published widths, 8448 = 66 x 128) and ``ssm_dt`` the
        # step's.  Held whole, 8512 columns are no whole number of 128
        # lanes, the TPU lays such a kernel out transposed, and the v5e
        # compiler re-laid all 36 back inside every fused decode scan
        # (1.19 GiB of temporaries, compiled for the chip, PR 37)
        shapes.update(
            ssm_in=(h, inner + channels), ssm_dt=(h, nh),
            ssm_conv=(config.mamba_d_conv, channels),
            A_log=(nh,), dt_bias=(nh,), ssm_D=(nh,),
            ssm_norm=(inner,), ssm_out=(inner, h))
        if config.mamba_conv_bias:
            shapes.update(ssm_conv_b=(channels,))
    else:
        nh = config.linear_num_value_heads
        dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
        shapes.update(
            lin_qkv=(h, nh, 2 * dk + dv),
            lin_conv=(config.linear_conv_kernel_dim, nh, 2 * dk + dv),
            lin_a=(h, nh), lin_b=(h, nh), lin_gate=(h, nh, dv),
            lin_out=(nh, dv, h), o_norm=(dv,),
            A_log=(nh,), dt_bias=(nh,))
    return shapes


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Seeded parameters: scaled-normal kernels (1/sqrt(fan_in)), unit
    norm scales (the latent's own, ``kv_norm``, uniform in (0.5, 1.5): a
    latent is of unit size before its norm, so ones would hide whether
    the norm is applied), a unit-normal embedding (of deviation ``1 /
    embedding_multiplier``, so that what enters the stack is of unit
    size whatever the multiplier); the decay's ``A`` uniform in
    (1, 16) and the step's bias the inverse softplus of a step
    log-uniform in (0.001, 0.1), as Gated DeltaNet and Mamba-2 initialise
    them, so that random weights give decays spread over (0, 1); a
    state-space layer's skip ``D`` ones, its convolution's bias uniform
    in +/-0.1 (small, not zero: a dropped bias shows), its gated norm's
    scale uniform in (0.5, 1.5) as ``kv_norm``; no ``lm_head`` where the
    head is tied to the embedding; the router's
    selection bias uniform in +/-0.01 (a trained model's is a learned
    buffer of that order); a looped stack's exit gate a scaled-normal
    vector and a float32 bias uniform in +/-1 (unit-size ``h`` gives
    ``w . h`` a deviation of 1, so the four gates spread over (0, 1))."""
    dtype = _dtype_of(config.dtype)
    lead = config.first_k_dense_replace // len(config.layer_types)
    periods = config.num_layers // len(config.layer_types) - lead
    h, vocab = config.hidden_size, config.vocab_size

    def normal(key, shape, fan_in):
        # drawn in float32 and rounded once: a draw made in bfloat16
        # comes out with a mean of -0.012 deviations
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    def layer(key, kind, periods, experts):
        out = {}
        shapes = _layer_shapes(config, kind, experts)
        for name, k in zip(sorted(shapes),
                           jax.random.split(key, len(shapes))):
            shape = shapes[name]
            if name == "router_bias":
                out[name] = jax.random.uniform(
                    k, (periods,) + shape, jnp.float32, -0.01, 0.01)
            elif name == "A_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, (periods,) + shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, (periods,) + shape, jnp.float32,
                    math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name == "ssm_D":
                out[name] = jnp.ones((periods,) + shape, jnp.float32)
            elif name == "ssm_conv_b":
                out[name] = jax.random.uniform(
                    k, (periods,) + shape, jnp.float32, -0.1, 0.1
                ).astype(dtype)
            elif name in _DRAWN_SCALES:
                out[name] = jax.random.uniform(
                    k, (periods,) + shape, jnp.float32, 0.5, 1.5
                ).astype(dtype)
            elif name in _SCALES:
                out[name] = jnp.ones((periods,) + shape, dtype)
            else:
                fan_in = (math.prod(shape[:2]) if name in ("wo", "lin_out")
                          else shape[1] if name.startswith("exp_")
                          else shape[0])
                out[name] = normal(k, (periods,) + shape, fan_in)
        return out

    k_embed, k_head, *k_layers = jax.random.split(
        key, 2 + len(config.layer_types))
    params = {
        # of unit size AFTER its multiplier: a unit table times Granite's
        # 12 would drown what the layers add to the stream
        "embed": normal(k_embed, (vocab, h),
                        config.embedding_multiplier ** 2),
        "periods": tuple(layer(k, kind, periods, config.has_routed_experts)
                         for k, kind in zip(k_layers, config.layer_types)),
        "ln_f": jnp.ones((h,), dtype),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = normal(k_head, (h, vocab), h)
    if lead:
        k_lead = jax.random.split(jax.random.fold_in(key, 1),
                                  len(config.layer_types))
        params["lead"] = tuple(layer(k, kind, lead, False) for k, kind
                               in zip(k_lead, config.layer_types))
    if config.total_ut_steps > 1:
        k_w, k_b = jax.random.split(jax.random.fold_in(key, 2))
        params["exit_gate_w"] = normal(k_w, (h,), h)
        params["exit_gate_b"] = jax.random.uniform(k_b, (), jnp.float32,
                                                   -1.0, 1.0)
    return params


def param_specs(config: ModelConfig, mesh: Optional[Mesh],
                tp_axis: str = "tp") -> Params:
    """PartitionSpecs matching :func:`init_params`: heads, the MLP's
    inner width and the vocabulary over ``tp`` (column-parallel in,
    row-parallel out, as ``models/sharding.py`` does for the GPT block);
    norms, gates' small vectors and the embedding's hidden axis whole."""
    axes = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    t = tp_axis if tp_axis in axes and mesh.shape[tp_axis] > 1 else None
    by_name = {
        "ln1": P(None, None), "ln2": P(None, None),
        "ln1_out": P(None, None), "ln2_out": P(None, None),
        "mlp_gate": P(None, None, t), "mlp_up": P(None, None, t),
        "mlp_down": P(None, t, None),
        "wq": P(None, None, t, None), "wk": P(None, None, t, None),
        "wv": P(None, None, t, None), "wo": P(None, t, None, None),
        "q_norm": P(None, t, None), "k_norm": P(None, t, None),
        "lin_qkv": P(None, None, t, None), "lin_conv": P(None, None, t, None),
        "lin_a": P(None, None, t), "lin_b": P(None, None, t),
        "lin_gate": P(None, None, t, None), "lin_out": P(None, t, None, None),
        "o_norm": P(None, None), "A_log": P(None, t), "dt_bias": P(None, t),
        # the latent layers' heads over tp as the full layers'; the
        # latent itself and the routed experts whole (serving refuses tp
        # for both: ``validate_serving``)
        "wkv_a": P(None, None, None), "kv_norm": P(None, None),
        "wkv_b": P(None, None, t, None),
        "router": P(None, None, None), "router_bias": P(None, None),
        "exp_gate": P(None, None, None, None),
        "exp_up": P(None, None, None, None),
        "exp_down": P(None, None, None, None),
        "shared_gate": P(None, None, t), "shared_up": P(None, None, t),
        "shared_down": P(None, t, None),
        # the state-space layers whole (serving refuses tp for them)
        "ssm_in": P(None, None, None), "ssm_dt": P(None, None, None),
        "ssm_conv": P(None, None, None),
        "ssm_conv_b": P(None, None), "ssm_D": P(None, None),
        "ssm_norm": P(None, None), "ssm_out": P(None, None, None),
    }

    def stack(experts):
        return tuple({name: by_name[name]
                      for name in _layer_shapes(config, kind, experts)}
                     for kind in config.layer_types)

    specs = {
        "embed": P(None, None),
        "periods": stack(config.has_routed_experts),
        "ln_f": P(None),
    }
    if not config.tie_word_embeddings:
        specs["lm_head"] = P(None, t)
    if config.first_k_dense_replace:
        specs["lead"] = stack(False)
    if config.total_ut_steps > 1:
        specs.update(exit_gate_w=P(None), exit_gate_b=P())
    return specs


def init_params_sharded(config: ModelConfig, key: jax.Array,
                        mesh: Mesh) -> Params:
    """Parameters made directly on their shards (as
    ``transformer.init_params_sharded``): no device holds them whole."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             param_specs(config, mesh),
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(lambda k: init_params(config, k),
                   out_shardings=shardings)(key)


def num_parameters(config: ModelConfig) -> int:
    def period(experts):
        return sum(math.prod(shape) for kind in config.layer_types for shape
                   in _layer_shapes(config, kind, experts).values())

    lead = config.first_k_dense_replace // len(config.layer_types)
    periods = config.num_layers // len(config.layer_types) - lead
    layers = (lead * period(False)
              + periods * period(config.has_routed_experts))
    gate = config.hidden_size + 1 if config.total_ut_steps > 1 else 0
    # a tied head is the embedding table: counted once
    tables = 1 if config.tie_word_embeddings else 2
    return (layers + tables * config.vocab_size * config.hidden_size
            + config.hidden_size + gate)


# -- the block -----------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis, statistics in float32."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * scale.astype(jnp.float32)).astype(x.dtype)


def _qk_norm(t: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """OLMo's QK-norm: RMSNorm over the WHOLE projection (all heads of a
    token together); ``t``, ``scale``: ``[..., heads, d]``."""
    t32 = t.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(t32 * t32, axis=(-2, -1), keepdims=True)
                        + eps)
    return (t32 * inv * scale.astype(jnp.float32)).astype(t.dtype)


def linear_gates(x: jax.Array, layer: Params, config: ModelConfig
                 ) -> tuple[jax.Array, jax.Array]:
    """``log alpha`` (<= 0) and ``beta`` of every token and head, in
    float32: ``alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))``,
    ``beta = sigmoid(x W_b)``, doubled when the configuration allows
    negative eigenvalues."""
    a = jnp.einsum("bsh,hn->bsn", x, layer["lin_a"],
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("bsh,hn->bsn", x, layer["lin_b"],
                   preferred_element_type=jnp.float32)
    log_alpha = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        a + layer["dt_bias"])
    beta = jax.nn.sigmoid(b)
    if config.linear_allow_neg_eigval:
        beta = 2.0 * beta
    return log_alpha, beta


def split_qkv_heads(qkv: jax.Array, config: ModelConfig
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The convolved ``[..., heads, 2 d_k + d_v]`` after its SiLU as the
    delta rule takes it: ``q / ||q|| / sqrt(d_k)``, ``k / ||k||``, ``v``,
    float32."""
    dk = config.linear_key_head_dim
    act = jax.nn.silu(qkv.astype(jnp.float32))
    q, k, v = act[..., :dk], act[..., dk:2 * dk], act[..., 2 * dk:]
    return l2_normalise(q, dk ** -0.5), l2_normalise(k), v


def split_xbc(conv: jax.Array, layer: Params, config: ModelConfig
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The convolved ``[..., channels]`` (float32) as the state-space
    recurrence takes it: the convolution's bias added, the SiLU, then
    ``x`` ``[..., heads, d_head]`` and ``B``, ``C`` ``[..., d_state]``,
    in that order along the channels; float32."""
    if "ssm_conv_b" in layer:
        conv = conv + layer["ssm_conv_b"].astype(jnp.float32)
    act = jax.nn.silu(conv)
    inner, n = config.mamba_inner, config.mamba_d_state
    x = act[..., :inner].reshape(
        act.shape[:-1] + (config.mamba_n_heads, config.mamba_d_head))
    return x, act[..., inner:inner + n], act[..., inner + n:]


def gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
               eps: float) -> jax.Array:
    """The state-space layers' output norm: the gate ``silu(z)`` applied
    BEFORE an RMSNorm over the whole inner width (one group); float32."""
    return rmsnorm(y.astype(jnp.float32)
                   * jax.nn.silu(z.astype(jnp.float32)), scale, eps)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         half_split: bool = False) -> jax.Array:
    """Rotary position embedding over the last axis: pair ``i`` of a
    token at position ``t`` turns by ``t x theta^(-2i/d)``.  The pairs
    are ADJACENT values ``(2i, 2i+1)`` (the latent layers' published
    ``rope_interleave``: the source permutes pairs to halves before a
    half-split rotation, which gives the same scores), or with
    ``half_split`` the values ``(i, i + d/2)`` (``rotate_half``: the
    full-attention layers').  ``positions`` broadcasts against
    ``x.shape[:-1]``.  Float32 inside, ``x``'s dtype out."""
    with jax.named_scope(ROPE):
        d = x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = positions.astype(jnp.float32)[..., None] * inv
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x32 = x.astype(jnp.float32)
        if half_split:
            a, b = x32[..., :d // 2], x32[..., d // 2:]
            out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                                  axis=-1)
        else:
            pairs = x32.reshape(x.shape[:-1] + (d // 2, 2))
            a, b = pairs[..., 0], pairs[..., 1]
            out = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                            axis=-1).reshape(x.shape)
        return out.astype(x.dtype)


def expand_latent(c: jax.Array, wkv_b: jax.Array, config: ModelConfig
                  ) -> tuple[jax.Array, jax.Array]:
    """The per-head keys (their position-free part) and values of the
    normed latents ``c`` ``[..., kv_lora_rank]``: ``[..., heads,
    qk_nope_head_dim]`` and ``[..., heads, v_head_dim]``."""
    with jax.named_scope(MLA_KV_B):
        kv = jnp.einsum("...r,rnd->...nd", c, wkv_b)
        return (kv[..., :config.qk_nope_head_dim],
                kv[..., config.qk_nope_head_dim:])


def latent_row(c: jax.Array, k_rope: jax.Array, config: ModelConfig
               ) -> jax.Array:
    """What the cache holds of a token: ``[c', rope(k_rope), zeros]`` in
    whole lanes (``ModelConfig.latent_row``)."""
    pad = config.latent_row - config.latent_width
    return jnp.concatenate(
        [c, k_rope, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)


def _mlp(u: jax.Array, layer: Params, config: ModelConfig, mixer: Any,
         experts: bool) -> jax.Array:
    """The block's second sub-layer on ``u`` ``[B, S, hidden]``: the
    dense SwiGLU, or the routed and shared experts."""
    if experts:
        b, s, h = u.shape
        y, routing, counts = expert_layer(
            u.reshape(b * s, h), layer, config.num_experts_per_tok,
            config.routed_scaling_factor, mixer.valid(),
            layer=layer.get("stack_index"))
        mixer.routed(routing, counts)
        return y.reshape(b, s, h)
    with jax.named_scope(MLP_UP):
        up = u @ layer["mlp_up"]
        gate = u @ layer["mlp_gate"]
    with jax.named_scope(MLP_ACT):
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(u.dtype)
    with jax.named_scope(MLP_DOWN):
        return act @ layer["mlp_down"]


def hybrid_block(h: jax.Array, layer: Params, kind: str,
                 config: ModelConfig, mixer: Any, l: jax.Array,
                 state: Any, experts: bool = False
                 ) -> tuple[jax.Array, Any]:
    """One layer of ``kind`` on ``h`` ``[B, S, hidden]``; ``l`` is the
    layer's number among the layers of its kind (the index of its cache
    planes); ``experts`` says whether its MLP is the expert layer.
    Returns ``(h, state)``."""
    eps = config.rms_norm_eps
    placement = config.norm_placement
    # what a sub-layer is fed is of the weights' dtype whatever the
    # residual stream's (a looped stack's is float32: ``run_stack``)
    dtype = layer["ln1"].dtype
    rm = config.residual_multiplier

    def joins(y):
        """A sub-layer's output as it joins the residual stream."""
        return y if rm == 1.0 else rm * y

    x = h
    if placement != "post":
        with jax.named_scope(LN1):
            x = rmsnorm(h, layer["ln1"], eps).astype(dtype)
    if kind == FULL_ATTENTION:
        with jax.named_scope(ATTN_QKV):
            q = jnp.einsum("bsh,hnd->bsnd", x, layer["wq"])
            k = jnp.einsum("bsh,hnd->bsnd", x, layer["wk"])
            v = jnp.einsum("bsh,hnd->bsnd", x, layer["wv"])
            if config.qk_norm:
                q = _qk_norm(q, layer["q_norm"], eps)
                k = _qk_norm(k, layer["k_norm"], eps)
        if config.rope_theta > 0:
            # before the cache sees the key: what is written is rotated
            pos = mixer.positions()[..., None]
            q = rope(q, pos, config.rope_theta, half_split=True)
            k = rope(k, pos, config.rope_theta, half_split=True)
        with jax.named_scope(ATTN_CORE):
            attn, state = mixer.attention(q, k, v, l, state)
        with jax.named_scope(ATTN_OUT):
            y = jnp.einsum("bsnd,ndh->bsh", attn, layer["wo"])
    elif kind == LATENT_ATTENTION:
        r = config.kv_lora_rank
        with jax.named_scope(MLA_Q):
            q = jnp.einsum("bsh,hnd->bsnd", x, layer["wq"])
        with jax.named_scope(MLA_KV_A):
            kv = x @ layer["wkv_a"]
            # the latent has its own norm; the rotary key has none
            c = rmsnorm(kv[..., :r], layer["kv_norm"], eps)
        # the mixer knows the positions and owns the cache: ``rope``,
        # ``mla_kv_b``, ``latent_update``, ``latent_attend`` open in it
        attn, state = mixer.latent(q, c, kv[..., r:], layer["wkv_b"], l,
                                   state)
        with jax.named_scope(ATTN_OUT):
            y = jnp.einsum("bsnd,ndh->bsh", attn, layer["wo"])
    elif kind == LINEAR_ATTENTION:
        with jax.named_scope(LIN_PROJ):
            qkv = jnp.einsum("bsh,hnc->bsnc", x, layer["lin_qkv"])
            gate = jnp.einsum("bsh,hnv->bsnv", x, layer["lin_gate"])
            log_alpha, beta = linear_gates(x, layer, config)
        # the mixer owns the convolution's carried inputs and the
        # state: ``lin_conv`` and ``lin_core`` open inside it
        o, state = mixer.linear(qkv, log_alpha, beta, layer["lin_conv"],
                                l, state)
        with jax.named_scope(LIN_OUT):
            o = (rmsnorm(o, layer["o_norm"], eps)
                 * jax.nn.silu(gate.astype(jnp.float32))).astype(h.dtype)
            y = jnp.einsum("bsnv,nvh->bsh", o, layer["lin_out"])
    elif kind == MAMBA:
        inner, channels = config.mamba_inner, config.mamba_conv_channels
        with jax.named_scope(SSM_PROJ):
            proj = x @ layer["ssm_in"]
            z = proj[..., :inner]
            dt = jax.nn.softplus(
                (x @ layer["ssm_dt"]).astype(jnp.float32)
                + layer["dt_bias"])
        # the mixer owns the convolution's carried inputs and the
        # state: ``ssm_conv`` and ``ssm_core`` open inside it
        o, state = mixer.ssm(proj[..., inner:], dt, layer, l, state)
        with jax.named_scope(SSM_OUT):
            y = gated_norm(o.reshape(o.shape[:2] + (inner,)), z,
                           layer["ssm_norm"], eps).astype(dtype) \
                @ layer["ssm_out"]
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    if placement == "pre":
        h = h + joins(y)
        with jax.named_scope(LN2):
            u = rmsnorm(h, layer["ln2"], eps).astype(dtype)
        return h + joins(_mlp(u, layer, config, mixer, experts)), state
    # a norm on each sub-layer's output; ``sandwich`` keeps the input's
    # too, and both of a sub-layer lie under its one scope
    out1, out2 = (("ln1_out", "ln2_out") if placement == "sandwich"
                  else ("ln1", "ln2"))
    with jax.named_scope(LN1):
        h = h + joins(rmsnorm(y, layer[out1], eps))
    u = h
    if placement == "sandwich":
        with jax.named_scope(LN2):
            u = rmsnorm(h, layer["ln2"], eps).astype(dtype)
    y = _mlp(u, layer, config, mixer, experts)
    with jax.named_scope(LN2):
        h = h + joins(rmsnorm(y, layer[out2], eps))
    return h, state


def scan_periods(h: jax.Array, periods: tuple, config: ModelConfig,
                 make_mixer: Any, state: Any, xs: Any = None,
                 base: int = 0, experts: bool = False, passed: Any = None
                 ) -> tuple[jax.Array, Any, Any]:
    """``h`` through a stack of whole periods: a ``lax.scan`` over
    periods whose body runs the period's layers in order.  ``state``
    (cache planes or nothing) rides the scan's CARRY beside the period's
    number, so that a mixer's write into a plane is an in-place update
    of the loop's buffer (``serve/engine.py::_scan_layers`` says what
    the other way cost).  Layer ``i`` of period ``p`` is layer ``(base +
    p) * count + ordinal`` among the layers of its kind: ``base``
    periods lie before this stack.  In pass ``passed`` of a looped stack
    (:func:`run_stack`) its cache planes are those of that pass, ``passed
    x L_kind`` further on.

    ``make_mixer(xs_p)`` builds the period's mixer from the period's
    slice of ``xs`` (further per-period inputs with a leading period
    axis, e.g. a prompt chunk's carried prefix); the mixer's
    ``collect()`` gives the period's outputs.  Returns ``(h, state,
    ys)``."""
    kinds = config.layer_types
    count = {kind: kinds.count(kind) for kind in set(kinds)}
    ordinal = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]
    # the routed experts' weights do not ride the scan's ``xs``: the
    # grouped product is handed the whole stack and the period's number
    # (``ops/routed_experts.py::grouped_products`` says why)
    whole = tuple({name: w for name, w in sub.items()
                   if name.startswith("exp_")} for sub in periods)
    periods = tuple({name: w for name, w in sub.items()
                     if not name.startswith("exp_")} for sub in periods)

    def body(carry, inputs):
        h, p, state = carry
        layers, xs_p = inputs
        mixer = make_mixer(xs_p)
        for i, kind in enumerate(kinds):
            layer = layers[i]
            if whole[i]:
                layer = {**layer, **whole[i], "stack_index": p - base}
            l = p * count[kind] + ordinal[i]
            if passed is not None:
                l = passed * config.layers_of(kind) + l
            h, state = hybrid_block(h, layer, kind, config, mixer, l, state,
                                    experts)
        return (h, p + 1, state), mixer.collect()

    (h, _, state), ys = jax.lax.scan(body, (h, jnp.int32(base), state),
                                     (periods, xs))
    return h, state, ys


def scan_stack(h: jax.Array, params: Params, config: ModelConfig,
               make_mixer: Any, state: Any, xs: Any = None,
               passed: Any = None) -> tuple[jax.Array, Any, Any, Any]:
    """``h`` through the whole stack: the leading dense layers
    (``params["lead"]``, where there are any) and then the periods, one
    :func:`scan_periods` each.  ``xs``: per-LAYER inputs, a tuple of
    arrays ``[L_kind, ...]`` (a prompt chunk's carried prefix) or None.
    A mixer's ``collect()`` is ``(per_layer, routed)``: ``per_layer`` a
    tuple of arrays with the period's layers of a kind leading, which
    come back as ``[L_kind, ...]`` over both scans; ``routed`` what an
    expert layer's mixer kept (None in the leading layers), which comes
    back with the expert periods leading.  ``passed``: the pass of a
    looped stack this is (:func:`run_stack`), else None.  Returns ``(h,
    state, per_layer, routed)``."""
    n = len(config.layer_types)
    lead = config.first_k_dense_replace // n
    total = config.num_layers // n

    def per_period(t):          # [L_kind, ...] -> [periods, L_kind / periods]
        return t.reshape((total, t.shape[0] // total) + t.shape[1:])

    def per_layer(t, more=None):
        # a kind the period has no layer of collects ``()``
        if isinstance(t, tuple):
            return t
        if more is not None:
            t = jnp.concatenate([t, more], axis=0)
        return t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:])

    if xs is not None:
        xs = tuple(per_period(t) for t in xs)
    if not lead:
        h, state, (outs, routed) = scan_periods(
            h, params["periods"], config, make_mixer, state, xs,
            experts=config.has_routed_experts, passed=passed)
        return h, state, tuple(per_layer(t) for t in outs), routed
    first = None if xs is None else tuple(t[:lead] for t in xs)
    rest = None if xs is None else tuple(t[lead:] for t in xs)
    h, state, (outs_a, _) = scan_periods(h, params["lead"], config,
                                         make_mixer, state, first,
                                         passed=passed)
    h, state, (outs_b, routed) = scan_periods(
        h, params["periods"], config, make_mixer, state, rest, base=lead,
        experts=config.has_routed_experts, passed=passed)
    outs = tuple(per_layer(a, b) for a, b in zip(outs_a, outs_b))
    return h, state, outs, routed


def exit_gate(params: Params, h: jax.Array) -> jax.Array:
    """The looped stack's exit gate of the normed ``h`` ``[..., hidden]``:
    ``sigmoid(w . h + b)``, float32."""
    with jax.named_scope(EXIT_GATE):
        score = jnp.einsum("...h,h->...", h, params["exit_gate_w"],
                           preferred_element_type=jnp.float32)
        return jax.nn.sigmoid(score + params["exit_gate_b"])


def run_stack(h: jax.Array, params: Params, config: ModelConfig,
              make_mixer: Any, state: Any, xs: Any = None
              ) -> tuple[jax.Array, Any, Any, Any, Any]:
    """``h`` through the stack as often as the configuration says.  A
    plain stack (``total_ut_steps`` 1) is :func:`scan_stack` and no more:
    the final norm is :func:`logits_of`'s.  A looped one runs
    :func:`scan_stack` in a ``lax.scan`` over the passes (ONE layer body
    in the program, the weights read once a pass): after EACH pass the
    final norm, then the exit gate of what it gives, so the ``h`` that
    comes back is normed already.  Its residual stream is FLOAT32 from
    the embedding to the head (the sub-layers are fed, and give, the
    weights' dtype): a pass's output is the next one's input, so what
    rounding the stream to bfloat16 at each of a pass's 96 additions
    adds is carried into, and grown by, every later pass (on the chip the
    logits read 0.19-0.25 from the float32 reference with a bfloat16
    stream, ``PERF.md`` §6, PR 33).  ``state`` rides this scan's carry too;
    ``xs`` and the mixers' per-layer outputs are pass-major ``[passes x
    L_kind, ...]``, as the planes are.  Every pass runs whatever the
    gates say: ``serve/hybrid.py::check_serving`` says why.

    Returns ``(h, state, per_layer, routed, gates)``: ``gates`` the
    float32 exit gate of every pass ``[passes, B, S]``, None for a plain
    stack."""
    passes = config.total_ut_steps
    if passes == 1:
        return (*scan_stack(h, params, config, make_mixer, state, xs), None)

    def per_pass(t):                    # [passes x L, ...] -> [passes, L, ...]
        return t.reshape((passes, t.shape[0] // passes) + t.shape[1:])

    def one_pass(carry, xs_t):
        h, t, state = carry
        h, state, outs, _ = scan_stack(h, params, config, make_mixer, state,
                                       xs_t, passed=t)
        with jax.named_scope(LOOP_NORM):
            h = rmsnorm(h, params["ln_f"], config.rms_norm_eps)
        return (h, t + 1, state), (outs, exit_gate(params, h))

    (h, _, state), (outs, gates) = jax.lax.scan(
        one_pass, (h.astype(jnp.float32), jnp.int32(0), state),
        None if xs is None else tuple(per_pass(t) for t in xs),
        length=passes)
    outs = tuple(t if isinstance(t, tuple)
                 else t.reshape((-1,) + t.shape[2:]) for t in outs)
    return h, state, outs, None, gates


def embed_tokens(params: Params, ids: jax.Array,
                 config: ModelConfig) -> jax.Array:
    with jax.named_scope(EMBED):
        h = jnp.take(params["embed"], ids, axis=0)
        if config.embedding_multiplier != 1.0:
            h = h * config.embedding_multiplier
        return h


def logits_of(params: Params, h: jax.Array,
              config: ModelConfig) -> jax.Array:
    """Final RMSNorm and the output head (the embedding table where the
    head is tied), over ``logits_scaling``; float32 logits.  A looped
    stack's ``h`` has had its norm (:func:`run_stack`)."""
    with jax.named_scope(LM_HEAD):
        y = (h.astype(params["embed"].dtype) if config.total_ut_steps > 1
             else rmsnorm(h, params["ln_f"], config.rms_norm_eps))
        if config.tie_word_embeddings:
            logits = jnp.einsum("...h,vh->...v", y, params["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("...h,hv->...v", y, params["lm_head"],
                                preferred_element_type=jnp.float32)
        if config.logits_scaling != 1.0:
            logits = logits / config.logits_scaling
        return logits


# -- the whole-sequence forward (no cache) -------------------------------------


class SequenceMixer:
    """The mixer of :func:`forward`: dense causal attention (the latent
    layers in their expanded form), and the chunked delta rule or the
    chunked state-space scan from a zero state with zeros before the
    convolution's first position."""

    def __init__(self, config: ModelConfig, seq_len: int) -> None:
        self.config, self.seq_len = config, seq_len
        self.chosen: list = []

    def valid(self):
        return None

    def positions(self):
        return jnp.arange(self.seq_len)[None, :]

    def routed(self, routing, counts) -> None:
        self.chosen.append(routing.experts)

    def collect(self):
        return (), (jnp.stack(self.chosen) if self.chosen else None)

    def attention(self, q, k, v, l, state):
        qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        attn = dense_attention(qh, kh, vh, causal=True,
                               scale=self.config.attention_multiplier)
        return attn.transpose(0, 2, 1, 3), state

    def ssm(self, xbc, dt, layer, l, state):
        cfg = self.config
        with jax.named_scope(SSM_CONV):
            ext = jnp.pad(xbc, ((0, 0), (cfg.mamba_d_conv - 1, 0), (0, 0)))
            x, b, c = split_xbc(causal_conv(ext, layer["ssm_conv"]), layer,
                                cfg)
        with jax.named_scope(SSM_CORE):
            zero = jnp.zeros((xbc.shape[0], cfg.mamba_n_heads,
                              cfg.mamba_d_head, cfg.mamba_d_state),
                             STATE_DTYPE)
            y, _ = ssd_chunked(x, dt, -jnp.exp(layer["A_log"]), b, c,
                               layer["ssm_D"], zero, cfg.mamba_chunk_size)
        return y, state

    def latent(self, q, c, k_rope, wkv_b, l, state):
        cfg = self.config
        dn, heads = cfg.qk_nope_head_dim, q.shape[2]
        pos = jnp.arange(q.shape[1])
        q_rope = rope(q[..., dn:], pos[None, :, None], cfg.rope_theta)
        k_rope = rope(k_rope, pos[None, :], cfg.rope_theta)
        k_nope, v = expand_latent(c, wkv_b, cfg)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], k_rope.shape[:2]
                                      + (heads, k_rope.shape[-1]))], axis=-1)
        qh = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        attn = dense_attention(*(t.transpose(0, 2, 1, 3)
                                 for t in (qh, k, v)), causal=True)
        return attn.transpose(0, 2, 1, 3), state

    def linear(self, qkv, log_alpha, beta, conv_w, l, state):
        cfg = self.config
        k_conv = cfg.linear_conv_kernel_dim
        with jax.named_scope(LIN_CONV):
            ext = jnp.pad(qkv, ((0, 0), (k_conv - 1, 0), (0, 0), (0, 0)))
            q, k, v = split_qkv_heads(causal_conv(ext, conv_w), cfg)
        with jax.named_scope(LIN_CORE):
            b, _, nh, _ = qkv.shape
            zero = jnp.zeros((b, nh, cfg.linear_value_head_dim,
                              cfg.linear_key_head_dim), STATE_DTYPE)
            o, _ = gated_delta_chunked(q, k, v, log_alpha, beta, zero)
        return o, state


def forward(params: Params, ids: jax.Array, config: ModelConfig,
            mesh: Optional[Mesh] = None, with_routing: bool = False,
            with_gates: bool = False) -> Any:
    """Token ids ``[B, S]`` to float32 logits ``[B, S, vocab]``: the
    whole sequence at once, no cache.  ``mesh`` only refuses what the
    family cannot run (pipeline stages); sharding comes from the
    parameters' own placement.  ``with_routing`` also returns the
    experts every token chose in every expert layer, ``[expert layers,
    B * S, k]``; ``with_gates`` a looped stack's exit gates ``[passes,
    B, S]``."""
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        raise ValueError("pipeline parallelism is not implemented for "
                         "layer_types models")
    h = embed_tokens(params, ids, config)
    h, _, _, chosen, gates = run_stack(
        h, params, config,
        lambda _xs: SequenceMixer(config, ids.shape[1]), None)
    logits = logits_of(params, h, config)
    if with_routing:
        return logits, chosen.reshape((-1,) + chosen.shape[2:])
    if with_gates:
        return logits, gates
    return logits
