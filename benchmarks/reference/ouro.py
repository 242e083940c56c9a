"""Plain reference of the Ouro (``model_type: ouro``, a LOOPED language
model) forward pass: what decides ``correct`` in the benchmark's
``ouro_*`` cells (``harness/kind_backlog_looped.py``) and what
``tests/test_looped.py`` holds the program to on the CPU.  Kept with the
yardstick so that no later change to the program can move what it is
held to.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: one sequence, no batch, no
cache, no chunks, no kernels: ``total_ut_steps`` FULL passes over the
whole sequence, each recomputing every layer's keys and values from that
pass's own input (so a pass attends to its own pass's K/V and to no
other's by construction).  It shares no code with the system (not its
rotary, not its norm).  It takes the system's parameter tree, so that
both sides see the same seeded weights, and casts it up one layer at a
time so that it fits beside a serving engine on one chip; the causal
scores are built for ``QUERY_BLOCK`` queries at a time, the head
``HEAD_BLOCK`` columns at a time.

The published description is the model's ``config.json``
(https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json), the
``ouro`` modelling code it names and the family's paper ("Scaling Latent
Reasoning via Looped Language Models"); each reading of them that the
``config.json`` does not state is marked below and listed under
``assumed`` in the benchmark's configuration file.

Per token ``x`` (hidden_size) at position ``t``, ``n`` heads of ``d``:

layer (SANDWICH norms, no biases; the four scales are the source's
``input_layernorm``, ``input_layernorm_2``, ``post_attention_layernorm``,
``post_attention_layernorm_2``: here ``ln1``, ``ln1_out``, ``ln2``,
``ln2_out``)
    ``a = x + N2(Attn(N1(x)))``; ``out = a + N4(SwiGLU(N3(a)))``.
attention ``Attn(u)``
    ``q, k, v = W_q u, W_k u, W_v u`` per head; rotary on q and k over
    the WHOLE head dimension, half-split pairs ``(i, i + d/2)``, angle
    ``t theta^(-2i/d)`` (``rotate_half``); ``softmax(q k^T / sqrt(d))``,
    causal; ``W_o concat_h(sum p v)``.
``SwiGLU(u) = W_down (silu(W_gate u) * W_up u)``.
the loop
    ``h_0 = Embed(ids)``; for ``t`` in 1..``total_ut_steps``: ``h_t =
    N_f(Stack(h_{t-1}))``: the SAME layers and the SAME final norm each
    pass.  ``logits = Head(h_T)`` (``early_exit_threshold`` 1: every
    token runs every pass).
the exit gate
    ``lambda_t = sigmoid(w_g . h_t + b_g)`` after every pass; the
    probability of leaving at ``t`` is ``lambda_t prod_{j<t} (1 -
    lambda_j)``, the remainder at the last pass (:func:`exit_distribution`).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
# columns of the output head cast up and multiplied at a time
HEAD_BLOCK = 16384
# queries whose causal scores [heads, QUERY_BLOCK, S] are held at once
QUERY_BLOCK = 256


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotate(x: jax.Array, theta: float) -> jax.Array:
    """``x`` ``[S, heads, d]``, the token at row ``t`` at position ``t``:
    ``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2, x1]`` of
    the two halves, the angle of lane ``i`` and of lane ``i + d/2`` both
    ``t theta^(-2i/d)``."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)          # [d/2]
    angle = jnp.arange(s, dtype=F32)[:, None] * inv               # [S, d/2]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]  # [S, 1, d]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return (x * jnp.cos(angle)
            + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angle))


def attention(u: jax.Array, w: dict, theta: float) -> jax.Array:
    """``u`` ``[S, hidden]`` (already normed) -> ``Attn(u)``."""
    s = u.shape[0]
    pos = jnp.arange(s)
    q = _rotate(jnp.einsum("sh,hnd->snd", u, w["wq"]), theta)
    k = _rotate(jnp.einsum("sh,hnd->snd", u, w["wk"]), theta)
    v = jnp.einsum("sh,hnd->snd", u, w["wv"])
    scale = q.shape[-1] ** -0.5
    # QUERY_BLOCK queries at a time against every key (one loop body,
    # whatever the length); queries past the end are padding
    block = min(QUERY_BLOCK, s)
    blocks = -(-s // block)
    pad = blocks * block - s

    def in_blocks(t):
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            (blocks, block) + t.shape[1:])

    def attend(_, queries):
        q_b, at = queries
        scores = jnp.einsum("qnd,knd->nqk", q_b, k) * scale
        causal = at[None, :, None] >= pos[None, None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("nqk,knd->qnd", probs, v)

    _, out = jax.lax.scan(attend, None, (in_blocks(q), in_blocks(pos)))
    out = out.reshape((blocks * block,) + out.shape[2:])[:s]
    return jnp.einsum("qnd,ndh->qh", out, w["wo"])


def _swiglu(u: jax.Array, w: dict) -> jax.Array:
    return (jax.nn.silu(u @ w["mlp_gate"]) * (u @ w["mlp_up"])) \
        @ w["mlp_down"]


def _layer(x: jax.Array, w: dict, eps: float, theta: float) -> jax.Array:
    a = x + _rms(attention(_rms(x, w["ln1"], eps), w, theta),
                 w["ln1_out"], eps)
    return a + _rms(_swiglu(_rms(a, w["ln2"], eps), w), w["ln2_out"], eps)


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))


def exit_distribution(gates: jax.Array) -> jax.Array:
    """The probability of leaving after each pass, from the gates
    ``lambda`` ``[..., passes]``: ``p_t = lambda_t prod_{j<t} (1 -
    lambda_j)`` and, at the last pass, whatever is left."""
    stay = jnp.cumprod(1.0 - gates, axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]],
                             axis=-1)
    p = gates * before
    return p.at[..., -1].set(before[..., -1])


def exit_pass(gates: jax.Array, threshold: float) -> jax.Array:
    """The pass (1-based) after which a token leaves: the first whose
    cumulative exit probability reaches ``threshold``, else the last."""
    reached = jnp.cumsum(exit_distribution(gates), axis=-1) >= threshold
    reached = reached.at[..., -1].set(True)
    return jnp.argmax(reached, axis=-1) + 1


def first_layer_keys(params: Any, ids: Sequence[int], model: dict
                     ) -> jax.Array:
    """The rotated keys ``[len(ids), heads, d]`` the FIRST layer computes
    in the FIRST pass: ``rotate(W_k N1(Embed(ids)))``.  What its cache
    plane holds of every token, and all of it float32 parts of the
    configuration around one projection (the norm, the rotary), with
    nothing upstream to compound."""
    (stack,) = params["periods"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(F32)
        u = _rms(x, stack["ln1"][0].astype(F32), model["rms_norm_eps"])
        return _rotate(jnp.einsum("sh,hnd->snd", u,
                                  stack["wk"][0].astype(F32)),
                       float(model["rope_theta"]))


def forward_logits(params: Any, ids: Sequence[int], model: dict, *,
                   positions: Optional[Sequence[int]] = None,
                   with_gates: bool = False, passes: Optional[int] = None
                   ) -> Any:
    """Float32 logits ``[len(positions), vocab]`` (every position when
    ``positions`` is None) of the token sequence ``ids``; ``model`` is
    the configuration's ``program.model``.  ``params`` is the system's
    tree (``models/hybrid.py::init_params``): ``periods`` one stacked
    sub-tree (the period is one layer).  ``with_gates``: ``(logits, exit
    gates [len(positions), passes])``.  ``passes`` (default the model's
    ``total_ut_steps``) is for the tests' loop faults alone."""
    if tuple(model["layer_types"]) != ("full_attention",) \
            or model.get("norm_placement") != "sandwich" \
            or model.get("qk_norm"):
        raise ValueError(
            "this reference is of a stack of full_attention layers with "
            f"sandwich norms and no QK-norm, not {model['layer_types']}, "
            f"{model.get('norm_placement')!r}, qk_norm="
            f"{model.get('qk_norm')}")
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    passes = model.get("total_ut_steps", 1) if passes is None else passes
    at = None if positions is None else jnp.asarray(positions, jnp.int32)
    (stack,) = params["periods"]
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(F32)
        final = params["ln_f"].astype(F32)
        gates = []
        for _ in range(passes):
            for l in range(model["num_layers"]):
                # one layer's weights in float32 at a time
                h = _layer_jit(h, {name: a[l].astype(F32)
                                   for name, a in stack.items()}, eps, theta)
            h = _rms(h, final, eps)
            if "exit_gate_w" in params:
                gates.append(jax.nn.sigmoid(
                    h @ params["exit_gate_w"].astype(F32)
                    + params["exit_gate_b"].astype(F32)))
        if at is not None:
            h = h[at]
        head = params["lm_head"]
        logits = jnp.concatenate(
            [h @ head[:, a:a + HEAD_BLOCK].astype(F32)
             for a in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)
    if not with_gates:
        return logits
    gates = jnp.stack(gates, axis=-1)
    return logits, gates if at is None else gates[at]


# -- the weights it is handed --------------------------------------------------


def expected_shapes(model: dict) -> dict[str, tuple]:
    """The tensors this reference reads of one layer, with their shapes
    (without the leading stack axis), written down from the sizes of the
    configuration, not taken from the program's own table."""
    h, n, f = (model["hidden_size"], model["num_heads"],
               model["ffn_intermediate"])
    d = h // n
    return {"ln1": (h,), "ln1_out": (h,), "ln2": (h,), "ln2_out": (h,),
            "wq": (h, n, d), "wk": (h, n, d), "wv": (h, n, d),
            "wo": (n, d, h), "mlp_gate": (h, f), "mlp_up": (h, f),
            "mlp_down": (f, h)}


_SCALES = ("ln1", "ln1_out", "ln2", "ln2_out", "ln_f")


@jax.jit
def _moments(a: jax.Array) -> jax.Array:
    a = a.astype(F32)
    return jnp.stack([jnp.mean(a), jnp.std(a), jnp.min(a), jnp.max(a)])


def weight_faults(params: Any, model: dict) -> list[str]:
    """What is wrong with the tree this reference is handed, judged
    without the program's initialiser: every tensor there under its name
    with the shape the configuration's sizes give and nothing besides;
    kernels (the exit gate's vector among them) of mean 0 and deviation
    ``fan_in^-1/2`` (the embedding 1), norm scales 1, the exit gate's
    bias one float32 inside +/-1 (the configuration's
    ``assumed.weights``).  The reference and the system read the SAME
    tree, so a fault in its making is shared by both sides of the
    comparison; this is what holds it."""
    faults: list[str] = []
    h, vocab = model["hidden_size"], model["vocab_size"]
    want = {"embed": (vocab, h), "ln_f": (h,), "lm_head": (h, vocab)}
    if model.get("total_ut_steps", 1) > 1:
        want.update(exit_gate_w=(h,), exit_gate_b=())
    want.update({f"periods[0].{name}": (model["num_layers"],) + shape
                 for name, shape in expected_shapes(model).items()})
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
        elif leaf in _SCALES:
            if (low, high) != (1.0, 1.0):
                faults.append(f"weights: {name} is not all ones "
                              f"({low} to {high})")
        elif leaf == "exit_gate_b":
            if not -1.0 <= low <= 1.0 or a.dtype != F32:
                faults.append(f"weights: {name} is {low} ({a.dtype}), not "
                              "one float32 inside (-1, 1)")
        else:
            fan_in = (1 if leaf == "embed" else
                      math.prod(a.shape[1:3]) if leaf == "wo" else
                      a.shape[0] if leaf in ("lm_head", "exit_gate_w")
                      else a.shape[1])
            unit = fan_in ** -0.5
            # five deviations of a sample of this size, and bfloat16's
            # own rounding of the draw
            room = 5.0 / math.sqrt(a.size) + 0.005
            if abs(mean) > room * unit or abs(std / unit - 1.0) > room:
                faults.append(f"weights: {name} has mean {mean:.3g} and "
                              f"deviation {std:.4g}, wanted 0 and {unit:.4g}")
    return faults
