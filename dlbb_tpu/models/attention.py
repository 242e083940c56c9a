"""Dense attention — the single shared kernel.

Used by the model's "full" mode and as the per-head-group kernel inside
Ulysses sequence parallelism.  fp32 softmax and PV accumulation, cast back
to the input dtype at the end.  Causal (decoder) masking is the default;
``causal=False`` gives bidirectional attention.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """q: ``[B, num_heads, S, head_dim]`` -> same shape.

    k, v: ``[B, num_heads, S, head_dim]``, or grouped-query
    ``[B, kv_heads, S, head_dim]`` with ``num_heads % kv_heads == 0`` —
    query-head groups then share K/V heads via einsum broadcasting, with no
    materialised repeat (K/V stay at kv_heads width in memory).
    ``scale`` multiplies the scores in place of ``1 / sqrt(head_dim)``.
    """
    b, n, s, d = q.shape
    kvh = k.shape[1]
    q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
    grouped = kvh != n
    if grouped:
        q32 = q32.reshape(b, kvh, n // kvh, s, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32)
    else:
        logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32)
    logits = logits / math.sqrt(d) if scale is None else logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    if grouped:
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32)
        out = out.reshape(b, n, s, d)
    else:
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v32)
    return out.astype(q.dtype)


def dense_causal(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal ``dense_attention`` (back-compat name)."""
    return dense_attention(q, k, v, causal=True)
