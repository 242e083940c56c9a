"""Plain reference of the Olmo-Hybrid forward pass: what decides
``correct`` in the benchmark's ``olmohyb_*`` cells
(``harness/kind_backlog_checked.py``) and what ``tests/test_hybrid.py``
holds the program to on the CPU.  The one reference, kept with the
yardstick so that no later change to the program can move what it is
held to.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
is otherwise one bfloat16 pass): one sequence, no batch, no cache, no
chunks, no kernels.  The linear-attention layers run the gated delta
rule as the recurrence it is, one token after another; the
full-attention layers build the whole causal score matrix.  It shares
no code with the system.  It takes the system's parameter tree, so that
both sides see the same seeded weights, and casts it up one layer at a
time so that it fits beside a serving engine on one chip.

The published description is the model's ``config.json``
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json);
what that file does not say is taken from the conventions of the model's
family and of Gated DeltaNet, each marked ASSUMED below and
listed under ``assumed`` in the benchmark's configuration file.

Per token ``x`` (hidden_size), with ``n`` heads:

linear_attention layer
    ``qkv = conv(x W_qkv)``: causal depthwise convolution over the last
    ``linear_conv_kernel_dim`` positions, then SiLU; per head ``q, k``
    (d_k) and ``v`` (d_v); ``q <- q / |q| / sqrt(d_k)``, ``k <- k / |k|``;
    ``alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))``,
    ``beta = sigmoid(x W_b)`` (x 2 with ``linear_allow_neg_eigval``);
    ``S <- alpha S``, ``u = beta (v - S k)``, ``S <- S + u k^T``,
    ``o = S q``; ``y = W_o (RMSNorm_dv(o) * silu(x W_g))``.
full_attention layer
    ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` over the whole
    projection, ``v = x W_v``; causal softmax(q k^T / sqrt(d)) v; W_o.
block
    ``h = x + RMSNorm(mixer(x))``; ``out = h + RMSNorm(W_down (silu(W_gate
    h) * W_up h))``.  Final RMSNorm, then the head.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
# columns of the output head cast up and multiplied at a time: the whole
# head in float32 is 1.5 GB at the published vocabulary
HEAD_BLOCK = 16384


def _rms(x: jax.Array, scale: jax.Array, eps: float, axes=-1) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=axes, keepdims=True)
                             + eps) * scale


def _mlp(h: jax.Array, w: dict) -> jax.Array:
    return (jax.nn.silu(h @ w["mlp_gate"]) * (h @ w["mlp_up"])) @ w["mlp_down"]


def _block_tail(x: jax.Array, y: jax.Array, w: dict, eps: float) -> jax.Array:
    # ASSUMED (OLMo 2/3 convention; config.json does not say where the
    # norms sit): the norm is applied to each sub-layer's output
    h = x + _rms(y, w["ln1"], eps)
    return h + _rms(_mlp(h, w), w["ln2"], eps)


def full_attention_layer(x: jax.Array, w: dict, eps: float) -> jax.Array:
    """``x``: ``[S, hidden]``; ``w``: one layer's float32 weights."""
    s = x.shape[0]
    d = w["wq"].shape[-1]
    # ASSUMED (OLMo 2/3): QK-norm over the whole projection, all heads
    # of a token together
    q = _rms(jnp.einsum("sh,hnd->snd", x, w["wq"]), w["q_norm"], eps,
             axes=(-2, -1))
    k = _rms(jnp.einsum("sh,hnd->snd", x, w["wk"]), w["k_norm"], eps,
             axes=(-2, -1))
    v = jnp.einsum("sh,hnd->snd", x, w["wv"])
    # ASSUMED: no rotary embedding (rope_parameters.rope_theta is null
    # in config.json; the linear layers carry position)
    scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("nqk,knd->qnd", probs, v)
    return _block_tail(x, jnp.einsum("qnd,ndh->qh", attn, w["wo"]), w, eps)


def linear_attention_layer(x: jax.Array, w: dict, state_at: jax.Array,
                           eps: float, d_k: int, neg_eigval: bool
                           ) -> tuple[jax.Array, jax.Array]:
    """Returns the layer's output and the recurrent state ``[len(state_at),
    n, d_v, d_k]`` as it stands after each of the positions ``state_at``."""
    s = x.shape[0]
    qkv = jnp.einsum("sh,hnc->snc", x, w["lin_qkv"])
    k_conv = w["lin_conv"].shape[0]
    ext = jnp.concatenate(
        [jnp.zeros((k_conv - 1,) + qkv.shape[1:], F32), qkv])
    conv = sum(ext[i:i + s] * w["lin_conv"][i] for i in range(k_conv))
    act = jax.nn.silu(conv)
    q, k, v = act[..., :d_k], act[..., d_k:2 * d_k], act[..., 2 * d_k:]
    # ASSUMED (Gated DeltaNet): l2-normalised q and k with 1e-6 under
    # the root, q scaled by d_k^-1/2
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * d_k ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    alpha = jnp.exp(-jnp.exp(w["A_log"])
                    * jax.nn.softplus(x @ w["lin_a"] + w["dt_bias"]))
    beta = jax.nn.sigmoid(x @ w["lin_b"]) * (2.0 if neg_eigval else 1.0)

    def token(carry, t):                     # state [n, d_v, d_k]
        state, kept = carry
        i, q_t, k_t, v_t, a_t, b_t = t
        state = a_t[:, None, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("nvk,nk->nv", state, k_t))
        state = state + u[:, :, None] * k_t[:, None, :]
        kept = jnp.where((state_at == i)[:, None, None, None], state, kept)
        return (state, kept), jnp.einsum("nvk,nk->nv", state, q_t)

    zero = jnp.zeros((q.shape[1], v.shape[-1], d_k), F32)
    kept = jnp.zeros((state_at.shape[0],) + zero.shape, F32)
    (_, kept), o = jax.lax.scan(
        token, (zero, kept), (jnp.arange(s), q, k, v, alpha, beta))
    gate = jnp.einsum("sh,hnv->snv", x, w["lin_gate"])
    o = _rms(o, w["o_norm"], eps) * jax.nn.silu(gate)
    y = _block_tail(x, jnp.einsum("snv,nvh->sh", o, w["lin_out"]), w, eps)
    return y, kept


def expected_shapes(model: dict) -> dict[str, dict[str, tuple]]:
    """Per kind of layer, the tensors this reference reads and their
    shapes (without the leading period axis), written down from the
    sizes of the configuration (``program.model``, which the harness
    tests tie key by key to the published ones), not taken from the
    program's own table."""
    h, f = model["hidden_size"], model["ffn_intermediate"]
    n = model["num_heads"]
    d = h // n
    nl = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    width = model["linear_conv_kernel_dim"]
    mlp = {"ln1": (h,), "ln2": (h,), "mlp_gate": (h, f), "mlp_up": (h, f),
           "mlp_down": (f, h)}
    return {
        "full_attention": {
            **mlp, "wq": (h, n, d), "wk": (h, n, d), "wv": (h, n, d),
            "wo": (n, d, h), "q_norm": (n, d), "k_norm": (n, d)},
        "linear_attention": {
            **mlp, "lin_qkv": (h, nl, 2 * dk + dv),
            "lin_conv": (width, nl, 2 * dk + dv), "lin_a": (h, nl),
            "lin_b": (h, nl), "lin_gate": (h, nl, dv),
            "lin_out": (nl, dv, h), "o_norm": (dv,), "A_log": (nl,),
            "dt_bias": (nl,)},
    }


# tensors whose fan-in is their first two axes (heads x head size)
_HEADS_IN = ("wo", "lin_out")
_SCALES = ("ln1", "ln2", "q_norm", "k_norm", "o_norm")


@jax.jit
def _moments(a: jax.Array) -> jax.Array:
    a = a.astype(F32)
    return jnp.stack([jnp.mean(a), jnp.std(a), jnp.min(a), jnp.max(a)])


def weight_faults(params: Any, model: dict) -> list[str]:
    """What is wrong with the tree this reference is handed, judged
    without the program's initialiser: every tensor there under its name
    with the shape the configuration's sizes give and nothing besides;
    kernels of mean 0 and deviation ``fan_in^-1/2`` (the embedding 1),
    norm scales 1, ``A = exp(A_log)`` in [1, 16] and the step
    ``softplus(dt_bias)`` in [0.001, 0.1], spread over those ranges
    (the configuration's ``assumed.weights``).  The reference and the
    system read the SAME tree, so a fault in its making is shared by
    both sides of the comparison; this is what holds it."""
    faults: list[str] = []
    kinds = list(model["layer_types"])
    periods = model["num_layers"] // len(kinds)
    h, vocab = model["hidden_size"], model["vocab_size"]
    want = {"embed": (vocab, h), "ln_f": (h,), "lm_head": (h, vocab)}
    table = expected_shapes(model)
    for i, kind in enumerate(kinds):
        for name, shape in table[kind].items():
            want[f"periods[{i}].{name}"] = (periods,) + shape
    have = {name: params[name] for name in ("embed", "ln_f", "lm_head")
            if name in params}
    for i, sub in enumerate(params.get("periods", ())):
        have.update({f"periods[{i}].{name}": a for name, a in sub.items()})
    for name in sorted(set(want) | set(have)):
        if name not in have or name not in want:
            faults.append(f"weights: {name} is "
                          + ("missing" if name in want else "not expected"))
            continue
        a, leaf = have[name], name.rsplit(".", 1)[-1]
        if tuple(a.shape) != want[name]:
            faults.append(f"weights: {name} has shape {tuple(a.shape)}, "
                          f"the configuration gives {want[name]}")
            continue
        mean, std, low, high = (float(v) for v in _moments(a))
        if not all(map(math.isfinite, (mean, std, low, high))):
            faults.append(f"weights: {name} is not finite")
        elif leaf in _SCALES + ("ln_f",):
            if (low, high) != (1.0, 1.0):
                faults.append(f"weights: {name} is not all ones "
                              f"({low} to {high})")
        elif leaf == "A_log":
            # a draw of 64 and more covers most of its range (log 16 =
            # 2.77; 120 draws fall short of 1.5 once in 10^9)
            spread = a.size < 64 or high - low > 1.5
            if not (0.0 <= low and high <= math.log(16.0) + 1e-5 and spread):
                faults.append(f"weights: {name}: exp(A_log) spans "
                              f"{math.exp(low):.3f} to {math.exp(high):.3f},"
                              " not (1, 16)")
        elif leaf == "dt_bias":
            step_low, step_high = (math.log1p(math.exp(v))
                                   for v in (low, high))
            spread = a.size < 64 or step_high > 20.0 * step_low
            if not (0.999e-3 <= step_low and step_high <= 1.001e-1
                    and spread):
                faults.append(f"weights: {name}: softplus(dt_bias) spans "
                              f"{step_low:.5f} to {step_high:.5f}, not "
                              "(0.001, 0.1)")
        else:
            fan_in = (1 if leaf == "embed" else
                      math.prod(a.shape[1:3]) if leaf in _HEADS_IN else
                      a.shape[0] if leaf == "lm_head" else a.shape[1])
            unit = fan_in ** -0.5
            # five deviations of a sample of this size, and bfloat16's
            # own rounding of the draw
            room = 5.0 / math.sqrt(a.size) + 0.005
            if abs(mean) > room * unit or abs(std / unit - 1.0) > room:
                faults.append(f"weights: {name} has mean {mean:.3g} and "
                              f"deviation {std:.4g}, wanted 0 and {unit:.4g}")
    return faults


_full = jax.jit(full_attention_layer, static_argnums=(2,))
_linear = jax.jit(linear_attention_layer, static_argnums=(3, 4, 5))


def forward_logits(params: Any, ids: Sequence[int], layer_types: Sequence[str],
                   *, linear_key_head_dim: int, linear_allow_neg_eigval: bool,
                   rms_norm_eps: float,
                   positions: Optional[Sequence[int]] = None,
                   state_at: Optional[Sequence[int]] = None) -> Any:
    """Float32 logits ``[len(positions), vocab]`` (every position when
    ``positions`` is None) of the token sequence ``ids``.  ``params`` is
    the system's tree (``models/hybrid.py::init_params``): per position
    of the period one stacked sub-tree; layer ``p * period + i`` takes
    index ``p`` of sub-tree ``i``.

    With ``state_at`` the result is ``(logits, states)``: the recurrent
    state of every linear-attention layer, in the order of the layers,
    after each of those positions, ``[len(state_at), L_lin, n, d_v,
    d_k]``."""
    with jax.default_matmul_precision("highest"):
        at = jnp.asarray([] if state_at is None else state_at, jnp.int32)
        states = []
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(F32)
        stacked = params["periods"]
        periods = jax.tree.leaves(stacked[0])[0].shape[0]
        for p in range(periods):
            for i, kind in enumerate(layer_types):
                # one layer's weights in float32 at a time
                w = jax.tree.map(lambda a: a[p].astype(F32), stacked[i])
                if kind == "full_attention":
                    x = _full(x, w, rms_norm_eps)
                elif kind == "linear_attention":
                    x, kept = _linear(x, w, at, rms_norm_eps,
                                      linear_key_head_dim,
                                      linear_allow_neg_eigval)
                    states.append(kept)
                else:
                    raise ValueError(f"unknown layer kind {kind!r}")
        if positions is not None:
            x = x[jnp.asarray(positions, jnp.int32)]
        y = _rms(x, params["ln_f"].astype(F32), rms_norm_eps)
        head = params["lm_head"]
        blocks = [y @ head[:, a:a + HEAD_BLOCK].astype(F32)
                  for a in range(0, head.shape[1], HEAD_BLOCK)]
        logits = jnp.concatenate(blocks, axis=-1)
        if state_at is None:
            return logits
        return logits, jnp.stack(states, axis=1)
