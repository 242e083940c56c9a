"""Plain reference of the kanana-2-30b-a3b (``model_type: deepseek_v3``)
forward pass: what decides ``correct`` in the benchmark's ``kanana_*``
cells (``harness/kind_backlog_latent.py``) and what
``tests/test_latent_moe.py`` holds the program to on the CPU.  Kept with
the yardstick so that no later change to the program can move what it is
held to.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: one sequence, no batch, no
cache, no chunks, no kernels, no absorbed form (every head's keys and
values are expanded from the latents), no grouped products (EVERY expert
is applied to every token, one expert after another, and the gate, zero
for an expert a token did not choose, is the mask).  It shares no code
with the system.  It takes the system's parameter tree, so that both
sides see the same seeded weights, and casts it up one layer, and one
expert, at a time so that it fits beside a serving engine on one chip;
the causal scores are built for ``QUERY_BLOCK`` queries at a time.

The published description is the model's ``config.json``
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json)
and the ``deepseek_v3`` modelling code it names; each departure or
reading of it is marked below and listed under ``assumed`` in the
benchmark's configuration file.

Per token ``x`` (hidden_size) at position ``t``, ``n`` heads:

block (pre-norm, no biases)
    ``h = x + A(RMSNorm(x))``; ``y = h + M(RMSNorm(h))``.  Final RMSNorm,
    then the untied head.
latent attention ``A(u)``
    ``q = W_q u``: per head ``[q_nope (qk_nope_head_dim), q_rope
    (qk_rope_head_dim)]`` (no ``q_lora_rank``: one full projection).
    ``[c, k_rope] = W_kv_a u``; ``c' = RMSNorm(c)`` (its own scale;
    ``k_rope`` is not normed and is shared by all heads).  ``[k_nope, v] =
    W_kv_b c'`` per head.  Rotary on adjacent pairs ``(2i, 2i+1)`` of
    ``q_rope`` and ``k_rope``, angle ``t theta^(-2i/d_rope)``.
    ``score = (q_nope . k_nope + rope(q_rope) . rope(k_rope)) (d_nope +
    d_rope)^-1/2``, causal, softmax; ``o = W_o concat_h(sum p v)``.
expert layer ``M(u)`` (after the ``first_k_dense_replace`` leading
layers, whose ``M`` is one SwiGLU of ``intermediate_size``)
    ``s = sigmoid(W_g u)`` over the routed experts; the
    ``num_experts_per_tok`` largest ``s + b`` are chosen (``b``: the
    selection bias of ``noaux_tc``; ``n_group`` 1, so no group limit);
    ``g_i = routed_scaling_factor s_i / sum_chosen s`` (``norm_topk_prob``);
    ``M(u) = sum_chosen g_i E_i(u) + E_shared(u)``, ``E(u) = W_down
    (silu(W_gate u) * W_up u)``; the shared experts are one SwiGLU of
    ``n_shared_experts x moe_intermediate_size``.
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
QUERY_BLOCK = 512


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _swiglu(u: jax.Array, gate: jax.Array, up: jax.Array,
            down: jax.Array) -> jax.Array:
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """``x`` ``[S, ..., d]``.  The source's ``rope_interleave`` permutes
    each pair ``(2i, 2i+1)`` to ``(i, i + d/2)`` and then rotates halves
    (``rotate_half``), for q and k alike: the dot product is that of
    pairs ``(2i, 2i+1)`` rotated in place, which is what is done here."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32)[:, None] * inv            # [S, d/2]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(u: jax.Array, w: dict, eps: float, d_nope: int,
                     d_rope: int, rank: int, theta: float) -> jax.Array:
    """``u`` ``[S, hidden]`` (already normed) -> ``A(u)``."""
    s = u.shape[0]
    pos = jnp.arange(s)
    q = jnp.einsum("sh,hnd->snd", u, w["wq"])
    kv = u @ w["wkv_a"]
    c = _rms(kv[:, :rank], w["kv_norm"], eps)
    expanded = jnp.einsum("sr,rnd->snd", c, w["wkv_b"])
    k_nope, v = expanded[..., :d_nope], expanded[..., d_nope:]
    q_rope = _rope(q[..., d_nope:], pos, theta)
    k_rope = _rope(kv[:, rank:], pos, theta)                 # [S, d_rope]
    scale = (d_nope + d_rope) ** -0.5
    # QUERY_BLOCK queries at a time against every key (one loop body,
    # whatever the length); queries past the end are padding
    block = min(QUERY_BLOCK, s)
    blocks = -(-s // block)
    pad = blocks * block - s

    def in_blocks(t):
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            (blocks, block) + t.shape[1:])

    def attend(_, queries):
        q_n, q_r, at = queries
        scores = (jnp.einsum("qnd,knd->nqk", q_n, k_nope)
                  + jnp.einsum("qnd,kd->nqk", q_r, k_rope)) * scale
        causal = at[None, :, None] >= pos[None, None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("nqk,knd->qnd", probs, v)

    _, out = jax.lax.scan(attend, None, (
        in_blocks(q[..., :d_nope]), in_blocks(q_rope),
        in_blocks(pos)))
    out = out.reshape((blocks * block,) + out.shape[2:])[:s]
    return jnp.einsum("qnd,ndh->qh", out, w["wo"])


def routing(u: jax.Array, router: jax.Array, bias: jax.Array, top_k: int,
            scale: float, force_at: jax.Array, force: jax.Array
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(selection scores s + b [S, E], chosen [S, k] by descending
    selection score, gates [S, E], zero where not taken)``.  At the
    positions ``force_at`` the experts TAKEN are ``force`` (``[len, k]``),
    whatever was chosen: the routing teacher-forced, as the tokens are."""
    s = jax.nn.sigmoid(u @ router)
    select = s + bias
    _, chosen = jax.lax.top_k(select, top_k)
    taken = chosen.at[force_at].set(force)
    mask = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                taken].set(1.0)
    kept = s * mask          # the bias chooses and never weights
    return select, chosen, scale * kept / jnp.sum(kept, -1, keepdims=True)


def expert_mlp(u: jax.Array, w: dict, top_k: int, scale: float,
               force_at: jax.Array, force: jax.Array
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``M(u)`` of an expert layer, the selection scores, the chosen
    experts and the gates; ``w`` holds the router in float32 and the
    experts as stored (cast up one at a time)."""
    select, chosen, gates = routing(u, w["router"], w["router_bias"],
                                    top_k, scale, force_at, force)

    def one(total, expert):
        gate, up, down, g = expert
        y = _swiglu(u, gate.astype(F32), up.astype(F32), down.astype(F32))
        return total + g[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (w["exp_gate"], w["exp_up"], w["exp_down"],
                         gates.T))
    if "shared_gate" in w:
        y = y + _swiglu(u, w["shared_gate"], w["shared_up"],
                        w["shared_down"])
    return y, select, chosen, gates


_EXPERTS = ("exp_gate", "exp_up", "exp_down")


def _layer(x, w, force_at, force, experts, eps, d_nope, d_rope, rank, theta,
           top_k, scale):
    h = x + latent_attention(_rms(x, w["ln1"], eps), w, eps, d_nope, d_rope,
                             rank, theta)
    u = _rms(h, w["ln2"], eps)
    if not experts:
        return h + _swiglu(u, w["mlp_gate"], w["mlp_up"], w["mlp_down"]), \
            None
    y, *routed = expert_mlp(u, w, top_k, scale, force_at, force)
    return h + y, routed


_layer_jit = jax.jit(_layer, static_argnums=tuple(range(4, 12)))


def forward_logits(params: Any, ids: Sequence[int], model: dict, *,
                   positions: Optional[Sequence[int]] = None,
                   with_routing: bool = False,
                   forced_experts: Any = None) -> Any:
    """Float32 logits ``[len(positions), vocab]`` (every position when
    ``positions`` is None) of the token sequence ``ids``; ``model`` is
    the configuration's ``program.model``.  ``params`` is the system's
    tree (``models/hybrid.py::init_params``): ``lead`` and ``periods``,
    each one stacked sub-tree (the period is one layer).

    ``with_routing``: ``(logits, select, chosen, gates)``, the expert
    layers' selection scores ``s + b`` ``[len(positions), expert layers,
    E]``, chosen experts ``[len(positions), expert layers, k]`` and gates
    ``[len(positions), expert layers, E]`` (zero where not chosen)
    there.

    ``forced_experts`` ``[len(positions), expert layers, k]``: at
    ``positions`` every expert layer TAKES these experts (weighted by its
    own scores of them) instead of the ones it chose, which it still
    reports: the routing teacher-forced like the tokens, so that where a
    system's choice differs by a near-tie, the layers behind it are fed
    what the system's were and each is judged on its own."""
    if tuple(model["layer_types"]) != ("latent_attention",):
        raise ValueError("this reference is of a stack of latent_attention "
                         f"layers, not {model['layer_types']}")
    eps = model["rms_norm_eps"]
    sizes = (eps, model["qk_nope_head_dim"], model["qk_rope_head_dim"],
             model["kv_lora_rank"], float(model["rope_theta"]),
             model["num_experts_per_tok"],
             float(model["routed_scaling_factor"]))
    at = None if positions is None else jnp.asarray(positions, jnp.int32)
    top_k = model["num_experts_per_tok"]
    if forced_experts is None:
        force_at = jnp.zeros((0,), jnp.int32)
        forced = jnp.zeros((0, model["num_layers"], top_k), jnp.int32)
    else:
        force_at, forced = at, jnp.asarray(forced_experts, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(F32)
        routing_of = []
        stacks = [(params["lead"][0], False)] if "lead" in params else []
        stacks.append((params["periods"][0], True))
        for stack, experts in stacks:
            for p in range(jax.tree.leaves(stack)[0].shape[0]):
                # one layer's weights in float32 at a time; its experts
                # stay as stored and are cast up one by one
                w = {name: a[p] if name in _EXPERTS else a[p].astype(F32)
                     for name, a in stack.items()}
                x, routed = _layer_jit(x, w, force_at,
                                       forced[:, len(routing_of)], experts,
                                       *sizes)
                if experts:
                    routing_of.append([t if at is None else t[at]
                                       for t in routed])
        if at is not None:
            x = x[at]
        y = _rms(x, params["ln_f"].astype(F32), eps)
        head = params["lm_head"]
        logits = jnp.concatenate(
            [y @ head[:, a:a + HEAD_BLOCK].astype(F32)
             for a in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)
    if not with_routing:
        return logits
    return (logits,) + tuple(jnp.stack(parts, axis=1)
                             for parts in zip(*routing_of))


# -- the weights it is handed --------------------------------------------------


def expected_shapes(model: dict) -> dict[str, dict[str, tuple]]:
    """``{"lead": ..., "periods": ...}``: the tensors this reference
    reads of a leading dense layer and of an expert layer, with their
    shapes (without the leading stack axis), written down from the sizes
    of the configuration, not taken from the program's own table."""
    h, n = model["hidden_size"], model["num_heads"]
    r, dn = model["kv_lora_rank"], model["qk_nope_head_dim"]
    dr, dv = model["qk_rope_head_dim"], model["v_head_dim"]
    f, fe = model["ffn_intermediate"], model["moe_intermediate_size"]
    e, fs = model["n_routed_experts"], model["n_shared_experts"] * fe
    attention = {"ln1": (h,), "ln2": (h,), "wq": (h, n, dn + dr),
                 "wkv_a": (h, r + dr), "kv_norm": (r,),
                 "wkv_b": (r, n, dn + dv), "wo": (n, dv, h)}
    experts = {"router": (h, e), "router_bias": (e,), "exp_gate": (e, h, fe),
               "exp_up": (e, h, fe), "exp_down": (e, fe, h)}
    if fs:
        experts.update(shared_gate=(h, fs), shared_up=(h, fs),
                       shared_down=(fs, h))
    return {"lead": {**attention, "mlp_gate": (h, f), "mlp_up": (h, f),
                     "mlp_down": (f, h)},
            "periods": {**attention, **experts}}


_SCALES = ("ln1", "ln2", "ln_f")
# name -> (low, high) of a tensor drawn uniformly: a draw of 64 and more
# covers three quarters of its range
_UNIFORM = {"router_bias": (-0.01, 0.01), "kv_norm": (0.5, 1.5)}


@jax.jit
def _moments(a: jax.Array) -> jax.Array:
    a = a.astype(F32)
    return jnp.stack([jnp.mean(a), jnp.std(a), jnp.min(a), jnp.max(a)])


def weight_faults(params: Any, model: dict) -> list[str]:
    """What is wrong with the tree this reference is handed, judged
    without the program's initialiser: every tensor there under its name
    with the shape the configuration's sizes give and nothing besides;
    kernels of mean 0 and deviation ``fan_in^-1/2`` (the embedding 1),
    norm scales 1 (the latent's within 0.5 to 1.5 and spread over it),
    the selection bias within +/-0.01 and spread over it (the
    configuration's ``assumed.weights``).  The reference and the
    system read the SAME tree, so a fault in its making is shared by
    both sides of the comparison; this is what holds it."""
    faults: list[str] = []
    h, vocab = model["hidden_size"], model["vocab_size"]
    lead = model.get("first_k_dense_replace", 0)
    depth = {"lead": lead, "periods": model["num_layers"] - lead}
    want = {"embed": (vocab, h), "ln_f": (h,), "lm_head": (h, vocab)}
    for stack, shapes in expected_shapes(model).items():
        if depth[stack]:
            want.update({f"{stack}[0].{name}": (depth[stack],) + shape
                         for name, shape in shapes.items()})
    have = {name: params[name] for name in ("embed", "ln_f", "lm_head")
            if name in params}
    for stack in ("lead", "periods"):
        for i, sub in enumerate(params.get(stack, ())):
            have.update({f"{stack}[{i}].{name}": a
                         for name, a in sub.items()})
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
        elif leaf in _UNIFORM:
            least, most = _UNIFORM[leaf]
            spread = a.size < 64 or high - low > 0.75 * (most - least)
            if not (least <= low and high <= most and spread):
                faults.append(f"weights: {name} spans {low:.4f} to "
                              f"{high:.4f}, not ({least}, {most})")
        else:
            fan_in = (1 if leaf == "embed" else
                      math.prod(a.shape[1:3]) if leaf == "wo" else
                      a.shape[0] if leaf == "lm_head" else
                      a.shape[2] if leaf in _EXPERTS else a.shape[1])
            unit = fan_in ** -0.5
            # five deviations of a sample of this size, and bfloat16's
            # own rounding of the draw
            room = 5.0 / math.sqrt(a.size) + 0.005
            if abs(mean) > room * unit or abs(std / unit - 1.0) > room:
                faults.append(f"weights: {name} has mean {mean:.3g} and "
                              f"deviation {std:.4g}, wanted 0 and {unit:.4g}")
    return faults
