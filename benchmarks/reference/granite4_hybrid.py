"""Plain reference of the Granite 4.0-H forward pass
(``model_type: granitemoehybrid`` with no experts): what decides
``correct`` in the benchmark's ``granite4h_*`` cells
(``harness/kind_backlog_ssm.py``) and what ``tests/test_ssm.py`` holds
the program to on the CPU.  The one reference, kept with the yardstick
so that no later change to the program can move what it is held to.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
is otherwise one bfloat16 pass): one sequence, no batch, no cache, no
chunks, no kernels.  The state-space layers run the Mamba-2 recurrence
as the recurrence it is, one token after another (a ``lax.scan`` over
positions); the attention layers build the whole causal score matrix.
It shares no code with the system (not the norm, not the convolution).
It takes the system's parameter tree, so that both sides see the same
seeded weights, and casts it up one layer at a time so that it fits
beside a serving engine on one chip; the tied head is multiplied in
blocks of rows of the embedding table for the same reason.

The published description is the model's ``config.json``
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json);
what that file does not say is marked ASSUMED below and listed under
``assumed`` in the benchmark's configuration file.

Per token ``x`` (hidden_size), with ``rm`` = ``residual_multiplier``:

block (both kinds; ASSUMED pre-norm, the multiplier on the sub-layer's
output)
    ``a = x + rm Mixer(RMSNorm(x))``; ``out = a + rm W_down(silu(W_gate
    u) * W_up u)`` with ``u = RMSNorm(a)``.
attention layer
    ``q`` of ``num_heads`` heads, ``k``, ``v`` of ``num_kv_heads``; query
    head ``i`` reads K/V head ``i // (num_heads / num_kv_heads)``; no
    positions (``position_embedding_type: nope``), no QK-norm; causal
    softmax of ``attention_multiplier q k^T`` in float32; ``W_o``.
mamba layer (``H`` heads of ``P``, state ``N``, one group)
    ``[z | xBC | dt] = u W_in`` (ASSUMED order); ``xBC = silu(conv(xBC) +
    b_conv)``, causal, depthwise, ``mamba_d_conv`` positions; ``x = xBC[:H
    P]`` as ``[H, P]``, ``B``, ``C`` the next two ``N`` (ASSUMED order),
    shared by every head; ``dt = softplus(dt + dt_bias)`` (ASSUMED: no
    clamp), ``A = -exp(A_log)``; ``S <- exp(dt A) S + dt x B^T``, ``y = S
    C + D x``; ``g = y * silu(z)`` (ASSUMED: the gate BEFORE the norm),
    ``RMSNorm(g)`` over the whole inner width times a scale, ``W_out``.
stack
    ``h_0 = embedding_multiplier Embed(ids)``; the layers;
    ``logits = RMSNorm(h) Embed^T / logits_scaling`` (the head IS the
    embedding table).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
# rows of the embedding table cast up and multiplied at a time as the
# head: the whole table in float32 is 0.82 GB at the published sizes
HEAD_BLOCK = 16384
MAMBA, ATTENTION = "mamba", "full_attention"


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _block_tail(x: jax.Array, y: jax.Array, w: dict, eps: float,
                rm: float) -> jax.Array:
    a = x + rm * y
    u = _rms(a, w["ln2"], eps)
    mlp = (jax.nn.silu(u @ w["mlp_gate"]) * (u @ w["mlp_up"])) @ w["mlp_down"]
    return a + rm * mlp


def attention_layer(x: jax.Array, w: dict, eps: float, rm: float,
                    scale: float) -> jax.Array:
    """``x``: ``[S, hidden]``; ``w``: one layer's float32 weights."""
    s = x.shape[0]
    u = _rms(x, w["ln1"], eps)
    q = jnp.einsum("sh,hnd->snd", u, w["wq"])
    k = jnp.einsum("sh,hgd->sgd", u, w["wk"])
    v = jnp.einsum("sh,hgd->sgd", u, w["wv"])
    # query head i reads K/V head i // group: each K/V head repeated
    # ``group`` times in place
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("nqk,knd->qnd", probs, v)
    return _block_tail(x, jnp.einsum("qnd,ndh->qh", attn, w["wo"]), w, eps,
                       rm)


def mamba_layer(x: jax.Array, w: dict, state_at: jax.Array, eps: float,
                rm: float, heads: int, d_state: int
                ) -> tuple[jax.Array, jax.Array]:
    """Returns the layer's output and the recurrent state ``[len(state_at),
    H, P, N]`` as it stands after each of the positions ``state_at``."""
    s = x.shape[0]
    u = _rms(x, w["ln1"], eps)
    # W_in's columns [z | xBC] and [dt] are two tensors of the tree
    proj = u @ jnp.concatenate([w["ssm_in"], w["ssm_dt"]], axis=1)
    inner = w["ssm_out"].shape[0]
    z = proj[:, :inner]
    xbc = proj[:, inner:inner + inner + 2 * d_state]
    dt = jax.nn.softplus(proj[:, inner + inner + 2 * d_state:]
                         + w["dt_bias"])
    width = w["ssm_conv"].shape[0]
    ext = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]), F32), xbc])
    conv = sum(ext[i:i + s] * w["ssm_conv"][i] for i in range(width))
    if "ssm_conv_b" in w:
        conv = conv + w["ssm_conv_b"]
    act = jax.nn.silu(conv)
    xs = act[:, :inner].reshape(s, heads, inner // heads)
    b_in, c_out = act[:, inner:inner + d_state], act[:, inner + d_state:]
    a = -jnp.exp(w["A_log"])

    def token(carry, t):                     # state [H, P, N]
        state, kept = carry
        i, x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        kept = jnp.where((state_at == i)[:, None, None, None], state, kept)
        y_t = jnp.einsum("hpn,n->hp", state, c_t) + w["ssm_D"][:, None] * x_t
        return (state, kept), y_t

    zero = jnp.zeros((heads, inner // heads, d_state), F32)
    kept = jnp.zeros((state_at.shape[0],) + zero.shape, F32)
    (_, kept), y = jax.lax.scan(
        token, (zero, kept), (jnp.arange(s), xs, b_in, c_out, dt))
    g = _rms(y.reshape(s, inner) * jax.nn.silu(z), w["ssm_norm"], eps)
    return _block_tail(x, g @ w["ssm_out"], w, eps, rm), kept


def expected_shapes(model: dict) -> dict[str, dict[str, tuple]]:
    """Per kind of layer, the tensors this reference reads and their
    shapes (without the leading period axis), written down from the
    sizes of the configuration (``program.model``), not taken from the
    program's own table."""
    h, f = model["hidden_size"], model["ffn_intermediate"]
    n, g = model["num_heads"], model["num_kv_heads"]
    d = h // n
    nh, p, ns = (model["mamba_n_heads"], model["mamba_d_head"],
                 model["mamba_d_state"])
    inner = nh * p
    channels = inner + 2 * model["mamba_n_groups"] * ns
    mlp = {"ln1": (h,), "ln2": (h,), "mlp_gate": (h, f), "mlp_up": (h, f),
           "mlp_down": (f, h)}
    mamba = {**mlp, "ssm_in": (h, inner + channels), "ssm_dt": (h, nh),
             "ssm_conv": (model["mamba_d_conv"], channels),
             "A_log": (nh,), "dt_bias": (nh,), "ssm_D": (nh,),
             "ssm_norm": (inner,), "ssm_out": (inner, h)}
    if model.get("mamba_conv_bias", True):
        mamba["ssm_conv_b"] = (channels,)
    return {
        ATTENTION: {**mlp, "wq": (h, n, d), "wk": (h, g, d),
                    "wv": (h, g, d), "wo": (n, d, h)},
        MAMBA: mamba,
    }


_ONES = ("ln1", "ln2", "ln_f", "ssm_D")


@jax.jit
def _moments(a: jax.Array) -> jax.Array:
    a = a.astype(F32)
    return jnp.stack([jnp.mean(a), jnp.std(a), jnp.min(a), jnp.max(a)])


def weight_faults(params: Any, model: dict) -> list[str]:
    """What is wrong with the tree this reference is handed, judged
    without the program's initialiser: every tensor there under its name
    with the shape the configuration's sizes give and nothing besides (NO
    ``lm_head``: the head is the embedding table); kernels of mean 0 and
    deviation ``fan_in^-1/2`` (the embedding ``1 / embedding_multiplier``:
    of unit size once multiplied), block norms' scales and
    ``D`` 1, the gated norm's scale spread over (0.5, 1.5), the
    convolution's bias inside +/-0.1 and not zero, ``A = exp(A_log)`` in
    [1, 16] and the step ``softplus(dt_bias)`` in [0.001, 0.1], spread
    over those ranges (the configuration's ``assumed.weights``).  The
    reference and the system read the SAME tree, so a fault in its
    making is shared by both sides of the comparison; this is what holds
    it."""
    faults: list[str] = []
    kinds = list(model["layer_types"])
    periods = model["num_layers"] // len(kinds)
    h, vocab = model["hidden_size"], model["vocab_size"]
    want = {"embed": (vocab, h), "ln_f": (h,)}
    table = expected_shapes(model)
    for i, kind in enumerate(kinds):
        for name, shape in table[kind].items():
            want[f"periods[{i}].{name}"] = (periods,) + shape
    have = {name: a for name, a in params.items() if name != "periods"}
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
        elif leaf in _ONES:
            if (low, high) != (1.0, 1.0):
                faults.append(f"weights: {name} is not all ones "
                              f"({low} to {high})")
        elif leaf == "ssm_norm":
            if not (0.5 <= low and high <= 1.5 and high - low > 0.5):
                faults.append(f"weights: {name} spans {low:.3f} to "
                              f"{high:.3f}, not (0.5, 1.5)")
        elif leaf == "ssm_conv_b":
            if not (-0.1001 <= low and high <= 0.1001 and std > 0.02):
                faults.append(f"weights: {name} spans {low:.3f} to "
                              f"{high:.3f} (deviation {std:.3f}), not a "
                              "spread over +/-0.1")
        elif leaf == "A_log":
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
            fan_in = (math.prod(a.shape[1:3]) if leaf == "wo"
                      else a.shape[1])
            # the table is of unit size AFTER its multiplier
            unit = (1.0 / float(model.get("embedding_multiplier", 1.0))
                    if leaf == "embed" else fan_in ** -0.5)
            # five deviations of a sample of this size, and bfloat16's
            # own rounding of the draw
            room = 5.0 / math.sqrt(a.size) + 0.005
            if abs(mean) > room * unit or abs(std / unit - 1.0) > room:
                faults.append(f"weights: {name} has mean {mean:.3g} and "
                              f"deviation {std:.4g}, wanted 0 and {unit:.4g}")
    return faults


_attention = jax.jit(attention_layer, static_argnums=(2, 3, 4))
_mamba = jax.jit(mamba_layer, static_argnums=(3, 4, 5, 6))


def forward_logits(params: Any, ids: Sequence[int], model: dict, *,
                   positions: Optional[Sequence[int]] = None,
                   state_at: Optional[Sequence[int]] = None) -> Any:
    """Float32 logits ``[len(positions), vocab]`` (every position when
    ``positions`` is None) of the token sequence ``ids``.  ``params`` is
    the system's tree (``models/hybrid.py::init_params``): per position
    of the period one stacked sub-tree; layer ``p * period + i`` takes
    index ``p`` of sub-tree ``i``.  ``model`` is the configuration's
    ``program.model``.

    With ``state_at`` the result is ``(logits, states)``: the recurrent
    state of every state-space layer, in the order of the layers, after
    each of those positions, ``[len(state_at), L_ssm, H, P, N]``."""
    eps = float(model["rms_norm_eps"])
    rm = float(model.get("residual_multiplier", 1.0))
    d = model["hidden_size"] // model["num_heads"]
    scale = model.get("attention_multiplier")
    scale = d ** -0.5 if scale is None else float(scale)
    with jax.default_matmul_precision("highest"):
        at = jnp.asarray([] if state_at is None else state_at, jnp.int32)
        states = []
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(F32)
        x = x * float(model.get("embedding_multiplier", 1.0))
        stacked = params["periods"]
        periods = jax.tree.leaves(stacked[0])[0].shape[0]
        for p in range(periods):
            for i, kind in enumerate(model["layer_types"]):
                # one layer's weights in float32 at a time
                w = jax.tree.map(lambda a: a[p].astype(F32), stacked[i])
                if kind == ATTENTION:
                    x = _attention(x, w, eps, rm, scale)
                elif kind == MAMBA:
                    x, kept = _mamba(x, w, at, eps, rm,
                                     model["mamba_n_heads"],
                                     model["mamba_d_state"])
                    states.append(kept)
                else:
                    raise ValueError(f"unknown layer kind {kind!r}")
        if positions is not None:
            x = x[jnp.asarray(positions, jnp.int32)]
        y = _rms(x, params["ln_f"].astype(F32), eps)
        # ASSUMED from tie_word_embeddings true: the head is the table
        table = params["embed"]
        blocks = [y @ table[a:a + HEAD_BLOCK].astype(F32).T
                  for a in range(0, table.shape[0], HEAD_BLOCK)]
        logits = jnp.concatenate(blocks, axis=-1) \
            / float(model.get("logits_scaling", 1.0))
        if state_at is None:
            return logits
        return logits, jnp.stack(states, axis=1)
