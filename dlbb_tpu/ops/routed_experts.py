"""The expert layer of the ``layer_types`` family: routed and shared
SwiGLU (or ReGLU) experts with no capacity and no dropped token.

``M(u) = sum_chosen g_i E_i(u) + E_shared(u)``, ``E(u) = W_down
(silu(W_gate u) * W_up u)`` (ReGLU: ``relu`` for ``silu``, SmallThinker's
"sparse ReGLU").  Per token: ``s = sigmoid(W_g u)`` over all ``E``
experts in float32; the ``k`` experts with the largest ``s + b`` are
chosen (``b`` the selection bias of ``noaux_tc``: it chooses and never
weights; one group, so no group limit); ``g_i = scale * s_i /
sum_chosen s``.  With ``softmax`` scoring ``s = softmax(W_g u)``, the
``k`` largest ``s`` are chosen (no bias) and ``g_i = s_i / sum_chosen
s`` (no scale).

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

# gmm's tiling, chosen from the product's shape by :func:`_gmm_tiling`.
# ``megablox.gmm`` walks ``(column tiles, visited (row tile, expert)
# pairs, contraction tiles)`` with the contraction innermost, so a
# contraction in two tiles changes the expert's block at every step and
# fetches the expert's matrix again for every row tile that visits it;
# in one tile, consecutive visits of one expert keep the block and the
# fetch is skipped.  Megablox sets no ``vmem_limit_bytes``: the kernel
# has the v5e's default scoped 16 MiB, of which the double-buffered
# lhs, rhs and out blocks and the float32 accumulator may take
# ``GMM_VMEM_BUDGET``; the rest is the kernel's own temporaries (the
# dot's float32 result, the store's mask).  Rows: a decode step has
# 3 to 5 rows an expert, and every visited (row tile, expert) pair
# costs a whole row tile of arithmetic.  A sweep at the two expert
# cells' shapes (TPU v5 lite, ``scripts/gmm_tiling_bench.py``, the
# stacked form the layer scan calls, loads up to 16 x the mean; PERF.md
# §6) chose these: SmallThinker's chunk gate/up 0.510 ms at (128, 2560,
# 768) against 0.877 at the old fixed (128, 2048, 768), down 0.529 at
# (128, 768, 2560) against 0.642 at (128, 768, 768); kanana's chunk down
# 0.846 at (128, 768, 2048) against 0.968.  At every shape the widest
# column tile that fits was the fastest, and rows of 256 were slower
# than rows of 128 (SmallThinker's chunk gate/up 0.566, down 0.617).
GMM_ROWS = 128
GMM_VMEM_BUDGET = 12 * 2**20
_LANES = 128


class Routing(NamedTuple):
    """What :func:`route` decided for ``T`` tokens."""

    experts: jax.Array   # [T, k] int32, by descending ``s + b``
    gates: jax.Array     # [T, k] float32, ``scale * s_i / sum_chosen s``
    scores: jax.Array    # [T, E] float32, ``s`` (before the bias)


def route(u: jax.Array, w_router: jax.Array, bias: Optional[jax.Array],
          top_k: int, scale: float, scoring: str = "sigmoid") -> Routing:
    """``u`` ``[T, hidden]``, ``w_router`` ``[hidden, E]``, ``bias``
    ``[E]`` float32 (None with ``softmax`` scoring)."""
    with jax.named_scope(MOE_ROUTER):
        # the gate in float32: the model's dtype only ever rounds ``u``
        logits = jnp.einsum("th,he->te", u.astype(jnp.float32),
                            w_router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            _, experts = jax.lax.top_k(scores, top_k)
        else:
            scores = jax.nn.sigmoid(logits)
            _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                       top_k)
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


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM of one ``gmm`` grid step: the lhs ``[tm, tk]``, rhs ``[tk,
    tn]`` and out ``[tm, tn]`` blocks double-buffered in the operands'
    dtype, and the float32 accumulator ``[tm, tn]``."""
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 4 * tm * tn


def _lane_tiles(x: int) -> list[int]:
    """Tiles of a dimension ``x``, largest first: the multiples of 128
    that divide it, or ``x`` itself where none does (a width under 128
    or off the lanes is taken whole)."""
    tiles = [t for t in range(x - x % _LANES, 0, -_LANES) if x % t == 0]
    return tiles or [x]


def _gmm_tiling(m: int, k: int, n: int, itemsize: int = 2
                ) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for a grouped product of ``m`` rows ``[m, k]``
    by ``[k, n]`` experts: the whole contraction (``tk = k``) with the
    widest column tile that divides ``n`` and fits
    :data:`GMM_VMEM_BUDGET`; only where no column tile fits beside the
    whole contraction, the widest contraction tile that divides ``k``
    that lets one fit.  ``m`` does not move it: :data:`GMM_ROWS`."""
    del m
    tm = GMM_ROWS
    for tk in _lane_tiles(k):
        for tn in _lane_tiles(n):
            if gmm_vmem_bytes(tm, tk, tn, itemsize) <= GMM_VMEM_BUDGET:
                return tm, tk, tn
    return tm, _lane_tiles(k)[-1], _lane_tiles(n)[-1]


def gmm_counts(tokens: int, top_k: int, hidden: int, width: int,
               itemsize: int) -> tuple[int, int]:
    """The grouped products of one expert layer over ``tokens`` tokens
    (gate and up ``[hidden, width]``, down ``[width, hidden]``) whose
    contraction is one tile, and those whose contraction is split."""
    m = tokens * top_k
    whole = sum(_gmm_tiling(m, k, n, itemsize)[1] == k
                for k, n in ((hidden, width), (hidden, width),
                             (width, hidden)))
    return whole, 3 - whole


def _gmm(rows: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    tiling = _gmm_tiling(m, k, w.shape[-1], rows.dtype.itemsize)
    pad = -m % tiling[0]
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, sizes, preferred_element_type=rows.dtype,
              tiling=tiling, interpret=jax.default_backend() != "tpu")
    return out[:m] if pad else out


def grouped_products(d: Dispatch, w_gate: jax.Array, w_up: jax.Array,
                     w_down: jax.Array,
                     layer: Optional[jax.Array] = None,
                     act: Any = jax.nn.silu) -> jax.Array:
    """Every sorted row through ITS expert's gated unit (``act`` on the
    gate: SwiGLU, or ``jax.nn.relu`` for ReGLU): ``w_gate``/``w_up``
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
        hid = (act(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(d.rows.dtype)
        # rows past the last expert's are not visited: whatever the
        # product left there is replaced, never multiplied
        hid = jnp.where(d.live[:, None], hid, 0)
        return jnp.where(d.live[:, None], dot(hid, w_down), 0)


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
                 layer: Optional[jax.Array] = None,
                 routing: Optional[Routing] = None,
                 act: Any = jax.nn.silu, scoring: str = "sigmoid"
                 ) -> tuple[jax.Array, Routing, jax.Array]:
    """``u`` ``[T, hidden]`` through one expert layer with the weights
    ``w`` (``router`` ``[hidden, E]``, ``router_bias`` ``[E]``,
    ``exp_gate``/``exp_up`` ``[E, hidden, f]``, ``exp_down`` ``[E, f,
    hidden]``, ``shared_*``).  Returns ``(M(u) [T, hidden] in u's dtype,
    the routing, load_counts)``.  ``valid`` ``[T]`` names the tokens that
    are real; the others take no expert's time and their output rows
    mean nothing.  ``layer``: ``exp_*`` are a stack's, see
    :func:`grouped_products`.  ``routing``: what :func:`route` decided
    already, from another input (a router placed before the attention);
    ``act``: the experts' gate activation; ``scoring``: :func:`route`'s."""
    if routing is None and scoring == "sigmoid":
        routing = route(u, w["router"], w["router_bias"], top_k, scale)
    elif routing is None:
        routing = route(u, w["router"], None, top_k, scale, scoring)
    d = dispatch(u, routing.experts, w["router"].shape[-1], valid)
    out = grouped_products(d, w["exp_gate"], w["exp_up"], w["exp_down"],
                           layer, act)
    y = combine(out, d, routing.gates)
    if "shared_gate" in w:
        y = y + shared_expert(u, w).astype(jnp.float32)
    return y.astype(u.dtype), routing, load_counts(d)
