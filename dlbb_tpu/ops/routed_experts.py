"""The expert layer of the ``layer_types`` family: routed and shared
SwiGLU experts with no capacity and no dropped token.

``M(u) = sum_chosen g_i E_i(u) + E_shared(u)``, ``E(u) = W_down
(silu(W_gate u) * W_up u)``.  Per token: ``s = sigmoid(W_g u)`` over all
``E`` experts in float32; the ``k`` experts with the largest ``s + b``
are chosen (``b`` the selection bias of ``noaux_tc``: it chooses and
never weights; one group, so no group limit); ``g_i = scale * s_i /
sum_chosen s``.

A batch of ``T`` tokens has exactly ``T k`` assignments, whatever the
routing, so every shape is fixed for ``jit``:

1. :func:`route` (scope ``moe_router``): scores, the chosen experts
   ``[T, k]`` and their gates.
2. :func:`dispatch` (``moe_dispatch``): the assignments sorted by expert
   (a stable ``argsort`` of ``T k`` small integers), each one's token
   gathered into a row of ``[T k, hidden]``, and the number of rows each
   expert got.  A token that is not ``valid`` (padding of a prompt's last
   chunk, a decode slot that holds no request) gets no row of any expert:
   its assignments sort behind the last expert's and no product visits
   them.
3. :func:`grouped_products` (``moe_experts``): three grouped matrix
   products over the sorted rows, each expert's rows against that
   expert's weights; an expert that got no row is not read.  All tokens
   to one expert is one group of ``T k`` rows: nothing is dropped.
4. :func:`combine` (``moe_combine``): rows back to assignment order, the
   float32 weighted sum of each token's ``k`` rows.
5. The shared experts are ONE SwiGLU of ``n_shared x width`` that every
   token takes once (``moe_shared``), added in float32.

The grouped product is the Pallas ``megablox.gmm`` kernel, which walks
the (row tile, expert) pairs that hold a row and fetches only those
experts' weights (``PERF.md`` §6, PR 31, has the readings that chose it
over ``jax.lax.ragged_dot``).  Off the TPU it runs interpreted, as
``ops/decode_attention.py``'s kernel does.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

MOE_PHASES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")
MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE, MOE_SHARED = MOE_PHASES

# gmm's (rows, contraction, columns) tile.  Few rows: a decode step has
# about 3 rows an expert, and every visited (row tile, expert) pair
# costs a whole row tile of arithmetic; the whole contraction and the
# whole expert width, so that an expert's matrix is one or two tiles
GMM_TILING = (128, 2048, 768)


class Routing(NamedTuple):
    """What :func:`route` decided for ``T`` tokens."""

    experts: jax.Array   # [T, k] int32, by descending ``s + b``
    gates: jax.Array     # [T, k] float32, ``scale * s_i / sum_chosen s``
    scores: jax.Array    # [T, E] float32, ``s`` (before the bias)


def route(u: jax.Array, w_router: jax.Array, bias: jax.Array, top_k: int,
          scale: float) -> Routing:
    """``u`` ``[T, hidden]``, ``w_router`` ``[hidden, E]``, ``bias``
    ``[E]`` float32."""
    with jax.named_scope(MOE_ROUTER):
        # the gate in float32: the model's dtype only ever rounds ``u``
        logits = jnp.einsum("th,he->te", u.astype(jnp.float32),
                            w_router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        gates = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        return Routing(experts.astype(jnp.int32), gates, scores)


class Dispatch(NamedTuple):
    rows: jax.Array         # [T k, hidden]: tokens in expert order
    order: jax.Array        # [T k] int32: sorted position -> assignment
    group_sizes: jax.Array  # [E] int32: rows each expert got
    live: jax.Array         # [T k] bool: sorted rows that an expert takes


def dispatch(u: jax.Array, experts: jax.Array, num_experts: int,
             valid: Optional[jax.Array] = None) -> Dispatch:
    """Sort the ``T k`` assignments by expert and gather their tokens."""
    with jax.named_scope(MOE_DISPATCH):
        t, k = experts.shape
        flat = experts.reshape(t * k)
        if valid is not None:
            flat = jnp.where(jnp.repeat(valid, k), flat, num_experts)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        rows = jnp.take(u, order // k, axis=0)
        sizes = jnp.zeros((num_experts + 1,), jnp.int32).at[flat].add(1)
        live = jnp.take(flat, order) < num_experts
        return Dispatch(rows, order, sizes[:num_experts], live)


def _gmm(rows: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    n = w.shape[-1]
    tm, tk, tn = GMM_TILING
    pad = -m % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, sizes, preferred_element_type=rows.dtype,
              tiling=(tm, min(tk, k), min(tn, n)),
              interpret=jax.default_backend() != "tpu")
    return out[:m] if pad else out


def grouped_products(d: Dispatch, w_gate: jax.Array, w_up: jax.Array,
                     w_down: jax.Array,
                     layer: Optional[jax.Array] = None) -> jax.Array:
    """Every sorted row through ITS expert's SwiGLU: ``w_gate``/``w_up``
    ``[E, hidden, f]``, ``w_down`` ``[E, f, hidden]`` -> ``[T k,
    hidden]``; rows no expert takes come out as zeros.

    With ``layer`` the weights are those of a whole STACK of expert
    layers, ``[layers, E, ...]``, and ``layer`` says which one this is:
    the stack is handed to the product as ``layers x E`` groups of which
    only this layer's get rows.  Inside a layer scan that is how the
    weights reach a kernel without a copy: a layer sliced out of the
    stack in front of a custom call is materialised, 1.2 GB a layer at
    the published widths (``PERF.md`` §6, PR 31)."""
    sizes = d.group_sizes
    if layer is not None:
        stack, e = w_gate.shape[:2]
        w_gate, w_up, w_down = (w.reshape((stack * e,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((stack * e,), sizes.dtype), sizes, (layer * e,))
    with jax.named_scope(MOE_EXPERTS):
        def dot(a, w):
            return _gmm(a, w, sizes)

        gate, up = dot(d.rows, w_gate), dot(d.rows, w_up)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(d.rows.dtype)
        # rows past the last expert's are not visited: whatever the
        # product left there is replaced, never multiplied
        act = jnp.where(d.live[:, None], act, 0)
        return jnp.where(d.live[:, None], dot(act, w_down), 0)


def combine(out: jax.Array, d: Dispatch, gates: jax.Array) -> jax.Array:
    """Sorted rows back to their tokens: float32 ``sum_k g y`` ``[T,
    hidden]``."""
    with jax.named_scope(MOE_COMBINE):
        t, k = gates.shape
        back = jnp.zeros_like(d.order).at[d.order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        by_token = jnp.take(out, back, axis=0).reshape(t, k, -1)
        return jnp.einsum("tkh,tk->th", by_token.astype(jnp.float32), gates)


def shared_expert(u: jax.Array, w: dict[str, Any]) -> jax.Array:
    with jax.named_scope(MOE_SHARED):
        act = (jax.nn.silu((u @ w["shared_gate"]).astype(jnp.float32))
               * (u @ w["shared_up"]).astype(jnp.float32)).astype(u.dtype)
        return act @ w["shared_down"]


def load_counts(d: Dispatch) -> jax.Array:
    """``[assignments, experts that got a row, the fullest expert's
    rows]`` of one expert layer, int32."""
    return jnp.stack([jnp.sum(d.group_sizes),
                      jnp.sum((d.group_sizes > 0).astype(jnp.int32)),
                      jnp.max(d.group_sizes)]).astype(jnp.int32)


def expert_layer(u: jax.Array, w: dict[str, Any], top_k: int, scale: float,
                 valid: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None
                 ) -> tuple[jax.Array, Routing, jax.Array]:
    """``u`` ``[T, hidden]`` through one expert layer with the weights
    ``w`` (``router`` ``[hidden, E]``, ``router_bias`` ``[E]``,
    ``exp_gate``/``exp_up`` ``[E, hidden, f]``, ``exp_down`` ``[E, f,
    hidden]``, ``shared_*``).  Returns ``(M(u) [T, hidden] in u's dtype,
    the routing, load_counts)``.  ``valid`` ``[T]`` names the tokens that
    are real; the others take no expert's time and their output rows
    mean nothing.  ``layer``: ``exp_*`` are a stack's, see
    :func:`grouped_products`."""
    routing = route(u, w["router"], w["router_bias"], top_k, scale)
    d = dispatch(u, routing.experts, w["router"].shape[-1], valid)
    out = grouped_products(d, w["exp_gate"], w["exp_up"], w["exp_down"],
                           layer)
    y = combine(out, d, routing.gates)
    if "shared_gate" in w:
        y = y + shared_expert(u, w).astype(jnp.float32)
    return y.astype(u.dtype), routing, load_counts(d)
