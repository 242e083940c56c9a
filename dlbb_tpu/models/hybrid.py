"""The hybrid block family (``ModelConfig.layer_types``): Olmo-Hybrid.

A stack that repeats one PERIOD of layers, each ``linear_attention``
(the gated delta rule of ``ops/gated_delta.py`` behind a short causal
convolution) or ``full_attention`` (causal multi-head attention with
QK-norm, no rotary embedding), every one followed by a SwiGLU MLP, in
the OLMo 2/3 arrangement: a sub-layer's OUTPUT is RMS-normalised and
added to the residual stream (``h = x + norm(mixer(x))``, ``out = h +
norm(mlp(h))``).  No biases.  Token ids in, logits over ``vocab_size``
out: an embedding table, a final RMSNorm and an untied head.

ONE definition of the block (:func:`hybrid_block`) and of the period
(:func:`scan_periods`), used by :func:`forward` here (a whole sequence,
no cache) and by every serving program (``serve/hybrid.py``).  What
differs between them is the *mixer*: an object with ``attention(q, k,
v, l, state)`` and ``linear(qkv, log_alpha, beta, conv_w, l, state)``
that owns everything that touches a cache.  ``state`` is opaque to the
block.

Parameters are stacked over periods (``lax.scan`` runs one period a
trip), one sub-tree per position of the period.  Projections keep the
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
    LINEAR_ATTENTION,
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

Params = dict[str, Any]

# The scopes this family adds to ``transformer.BLOCK_PHASES`` (which it
# keeps where they mean the same: ``attn_*`` in the full-attention
# layers, ``mlp_*``, ``ln1``/``ln2`` for the two output norms).
# ``lin_core`` holds ``state_update`` (decode) or ``state_scan``
# (prefill, forward).  docs/observability.md, "Names".
HYBRID_PHASES = ("embed", "lm_head", "lin_proj", "lin_conv", "lin_core",
                 "lin_out")
EMBED, LM_HEAD, LIN_PROJ, LIN_CONV, LIN_CORE, LIN_OUT = HYBRID_PHASES

# the recurrent state's precision, wherever it is kept or carried
STATE_DTYPE = jnp.float32


# -- parameters ----------------------------------------------------------------


def _layer_shapes(config: ModelConfig, kind: str) -> dict[str, tuple]:
    """Shapes (without the leading period axis) of one layer of ``kind``
    (``A_log`` and ``dt_bias`` are float32 whatever the model's dtype:
    they feed an exponential of an exponential)."""
    h, f = config.hidden_size, config.ffn_intermediate
    shapes: dict[str, tuple] = {
        "ln1": (h,), "ln2": (h,),
        "mlp_gate": (h, f), "mlp_up": (h, f), "mlp_down": (f, h),
    }
    if kind == FULL_ATTENTION:
        n, d = config.num_heads, config.head_dim
        shapes.update(wq=(h, n, d), wk=(h, n, d), wv=(h, n, d),
                      wo=(n, d, h), q_norm=(n, d), k_norm=(n, d))
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
    norm scales, a unit-normal embedding; the decay's ``A`` uniform in
    (1, 16) and the step's bias the inverse softplus of a step
    log-uniform in (0.001, 0.1), as Gated DeltaNet initialises them, so
    that random weights give decays spread over (0, 1)."""
    dtype = _dtype_of(config.dtype)
    periods = config.num_layers // len(config.layer_types)
    h, vocab = config.hidden_size, config.vocab_size

    def normal(key, shape, fan_in):
        # drawn in float32 and rounded once: a draw made in bfloat16
        # comes out with a mean of -0.012 deviations
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    def layer(key, kind):
        out = {}
        shapes = _layer_shapes(config, kind)
        for name, k in zip(sorted(shapes),
                           jax.random.split(key, len(shapes))):
            shape = shapes[name]
            if name == "A_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, (periods,) + shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, (periods,) + shape, jnp.float32,
                    math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name in ("ln1", "ln2", "q_norm", "k_norm", "o_norm"):
                out[name] = jnp.ones((periods,) + shape, dtype)
            else:
                fan_in = (math.prod(shape[:2]) if name in ("wo", "lin_out")
                          else shape[0])
                out[name] = normal(k, (periods,) + shape, fan_in)
        return out

    k_embed, k_head, *k_layers = jax.random.split(
        key, 2 + len(config.layer_types))
    return {
        "embed": normal(k_embed, (vocab, h), 1),
        "periods": tuple(layer(k, kind) for k, kind
                         in zip(k_layers, config.layer_types)),
        "ln_f": jnp.ones((h,), dtype),
        "lm_head": normal(k_head, (h, vocab), h),
    }


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
        "mlp_gate": P(None, None, t), "mlp_up": P(None, None, t),
        "mlp_down": P(None, t, None),
        "wq": P(None, None, t, None), "wk": P(None, None, t, None),
        "wv": P(None, None, t, None), "wo": P(None, t, None, None),
        "q_norm": P(None, t, None), "k_norm": P(None, t, None),
        "lin_qkv": P(None, None, t, None), "lin_conv": P(None, None, t, None),
        "lin_a": P(None, None, t), "lin_b": P(None, None, t),
        "lin_gate": P(None, None, t, None), "lin_out": P(None, t, None, None),
        "o_norm": P(None, None), "A_log": P(None, t), "dt_bias": P(None, t),
    }
    return {
        "embed": P(None, None),
        "periods": tuple({name: by_name[name]
                          for name in _layer_shapes(config, kind)}
                         for kind in config.layer_types),
        "ln_f": P(None),
        "lm_head": P(None, t),
    }


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
    per_kind = {kind: sum(math.prod(shape) for shape
                          in _layer_shapes(config, kind).values())
                for kind in set(config.layer_types)}
    layers = sum(config.layers_of(kind) * n for kind, n in per_kind.items())
    return (layers + 2 * config.vocab_size * config.hidden_size
            + config.hidden_size)


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


def hybrid_block(h: jax.Array, layer: Params, kind: str,
                 config: ModelConfig, mixer: Any, l: jax.Array,
                 state: Any) -> tuple[jax.Array, Any]:
    """One layer of ``kind`` on ``h`` ``[B, S, hidden]``; ``l`` is the
    layer's number among the layers of its kind (the index of its cache
    planes).  Returns ``(h, state)``."""
    eps = config.rms_norm_eps
    if kind == FULL_ATTENTION:
        with jax.named_scope(ATTN_QKV):
            q = _qk_norm(jnp.einsum("bsh,hnd->bsnd", h, layer["wq"]),
                         layer["q_norm"], eps)
            k = _qk_norm(jnp.einsum("bsh,hnd->bsnd", h, layer["wk"]),
                         layer["k_norm"], eps)
            v = jnp.einsum("bsh,hnd->bsnd", h, layer["wv"])
        with jax.named_scope(ATTN_CORE):
            attn, state = mixer.attention(q, k, v, l, state)
        with jax.named_scope(ATTN_OUT):
            y = jnp.einsum("bsnd,ndh->bsh", attn, layer["wo"])
    elif kind == LINEAR_ATTENTION:
        with jax.named_scope(LIN_PROJ):
            qkv = jnp.einsum("bsh,hnc->bsnc", h, layer["lin_qkv"])
            gate = jnp.einsum("bsh,hnv->bsnv", h, layer["lin_gate"])
            log_alpha, beta = linear_gates(h, layer, config)
        # the mixer owns the convolution's carried inputs and the
        # state: ``lin_conv`` and ``lin_core`` open inside it
        o, state = mixer.linear(qkv, log_alpha, beta, layer["lin_conv"],
                                l, state)
        with jax.named_scope(LIN_OUT):
            o = (rmsnorm(o, layer["o_norm"], eps)
                 * jax.nn.silu(gate.astype(jnp.float32))).astype(h.dtype)
            y = jnp.einsum("bsnv,nvh->bsh", o, layer["lin_out"])
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    with jax.named_scope(LN1):
        h = h + rmsnorm(y, layer["ln1"], eps)
    with jax.named_scope(MLP_UP):
        up = h @ layer["mlp_up"]
        gate = h @ layer["mlp_gate"]
    with jax.named_scope(MLP_ACT):
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(h.dtype)
    with jax.named_scope(MLP_DOWN):
        y = act @ layer["mlp_down"]
    with jax.named_scope(LN2):
        h = h + rmsnorm(y, layer["ln2"], eps)
    return h, state


def scan_periods(h: jax.Array, periods: tuple, config: ModelConfig,
                 make_mixer: Any, state: Any, xs: Any = None
                 ) -> tuple[jax.Array, Any, Any]:
    """``h`` through the whole stack: a ``lax.scan`` over periods whose
    body runs the period's layers in order.  ``state`` (cache planes or
    nothing) rides the scan's CARRY beside the period's number, so that
    a mixer's write into a plane is an in-place update of the loop's
    buffer (``serve/engine.py::_scan_layers`` says what the other way
    cost).  Layer ``i`` of period ``p`` is layer ``p * count + ordinal``
    among the layers of its kind.

    ``make_mixer(xs_p)`` builds the period's mixer from the period's
    slice of ``xs`` (further per-period inputs with a leading period
    axis, e.g. a prompt chunk's carried prefix); the mixer's
    ``collect()`` gives the period's outputs.  Returns ``(h, state,
    ys)``."""
    kinds = config.layer_types
    count = {kind: kinds.count(kind) for kind in set(kinds)}
    ordinal = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]

    def body(carry, inputs):
        h, p, state = carry
        layers, xs_p = inputs
        mixer = make_mixer(xs_p)
        for i, kind in enumerate(kinds):
            h, state = hybrid_block(h, layers[i], kind, config, mixer,
                                    p * count[kind] + ordinal[i], state)
        return (h, p + 1, state), mixer.collect()

    (h, _, state), ys = jax.lax.scan(body, (h, jnp.int32(0), state),
                                     (periods, xs))
    return h, state, ys


def embed_tokens(params: Params, ids: jax.Array) -> jax.Array:
    with jax.named_scope(EMBED):
        return jnp.take(params["embed"], ids, axis=0)


def logits_of(params: Params, h: jax.Array,
              config: ModelConfig) -> jax.Array:
    """Final RMSNorm and the output head; float32 logits."""
    with jax.named_scope(LM_HEAD):
        y = rmsnorm(h, params["ln_f"], config.rms_norm_eps)
        return jnp.einsum("...h,hv->...v", y, params["lm_head"],
                          preferred_element_type=jnp.float32)


# -- the whole-sequence forward (no cache) -------------------------------------


class SequenceMixer:
    """The mixer of :func:`forward`: dense causal attention, and the
    chunked delta rule from a zero state with zeros before the
    convolution's first position."""

    def __init__(self, config: ModelConfig) -> None:
        self.config = config

    def collect(self):
        return None

    def attention(self, q, k, v, l, state):
        qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        attn = dense_attention(qh, kh, vh, causal=True)
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
            mesh: Optional[Mesh] = None) -> jax.Array:
    """Token ids ``[B, S]`` to float32 logits ``[B, S, vocab]``: the
    whole sequence at once, no cache.  ``mesh`` only refuses what the
    family cannot run (pipeline stages); sharding comes from the
    parameters' own placement."""
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        raise ValueError("pipeline parallelism is not implemented for "
                         "layer_types models")
    h = embed_tokens(params, ids)
    mixer = SequenceMixer(config)
    h, _, _ = scan_periods(h, params["periods"], config,
                           lambda _xs: mixer, None)
    return logits_of(params, h, config)
