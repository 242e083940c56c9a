"""What the serving programs of every block family read the paged K/V
cache with: a layer of a carried plane (``_layer_of``, ``_layer_tokens``),
the head-major view of an activation (``_heads``), and the three dense
fp32 attentions over cached keys that are not the decode kernel of
``ops/decode_attention.py``: ``_chunk_attention`` (a prompt chunk over the
carried prefix, static offset-causal mask; past ``CHUNK_SCORES_BYTES`` of
scores :func:`chunk_attention` runs the kernel of
``ops/chunk_attention.py`` instead), ``_cached_attention`` (one
token a slot over the whole layer, per-slot length mask: the int8
layout's path) and ``_verify_attention`` (its gamma+1-position
generalisation for a speculative verify).

Below both families (``serve/gpt.py``, ``serve/hybrid.py``) and beside
``serve/kvcache.py``; it imports neither, and nothing of the scheduler.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dlbb_tpu.models.transformer import SERVE_PHASES
from dlbb_tpu.ops.chunk_attention import chunk_attend

KV_UPDATE, KV_ATTEND = SERVE_PHASES
# the dense float32 scores a prompt chunk's attention may write to HBM;
# past them it runs as the ``chunk_attend`` kernel (:func:`chunk_attention`):
# a global layer's chunk at offset 15,360 of 16,384 would hold 1.9 GB a
# layer
CHUNK_SCORES_BYTES = 1 << 28


def _layer_of(plane: jax.Array, l: jax.Array) -> jax.Array:
    """Layer ``l`` of a carried cache plane ``[L, ...]``."""
    return jax.lax.dynamic_index_in_dim(plane, l, 0, keepdims=False)


def _layer_tokens(plane: jax.Array, l: jax.Array) -> jax.Array:
    """Layer ``l`` of a carried K/V plane as attention reads it,
    token-major ``[B, S_max, kvh, d]``.  The plane is flattened BEFORE
    the slice: then the v5e compiler takes the dynamic slice as the
    prologue of the attention reduce.  Sliced first and flattened
    after, it wrote the layer out in fp32 and read it back, per plane
    and layer (``PERF.md`` §6, PR 26)."""
    nl, b, nb, bs, kvh, d = plane.shape
    return _layer_of(plane.reshape(nl, b, nb * bs, kvh, d), l)


def _heads(t: jax.Array, nh: int, d: int) -> jax.Array:
    """[B, S, nh*d] -> [B, nh, S, d]."""
    b, s, _ = t.shape
    return t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)


@jax.named_scope(KV_ATTEND)
def _cached_attention(q: jax.Array, k_flat: jax.Array, v_flat: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Length-masked decode attention over the flattened cache.

    q: ``[B, n, 1, d]``; k_flat/v_flat: ``[B, S_max, kvh, d]``;
    valid: ``[B, S_max]`` bool.  Same math as
    ``models.attention.dense_attention`` (fp32 softmax, 1/sqrt(d),
    grouped-query einsum broadcasting) with the causal mask replaced by
    the per-slot validity mask — positions past a slot's length
    contribute exactly zero (softmax of -inf)."""
    b, n, _, d = q.shape
    kvh = k_flat.shape[2]
    q32 = q.astype(jnp.float32)
    k32 = k_flat.transpose(0, 2, 1, 3).astype(jnp.float32)  # [B, kvh, S, d]
    v32 = v_flat.transpose(0, 2, 1, 3).astype(jnp.float32)
    if kvh != n:
        q32 = q32.reshape(b, kvh, n // kvh, 1, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, None, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32)
        out = out.reshape(b, n, 1, d)
    else:
        logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v32)
    return out.astype(k_flat.dtype)


@jax.named_scope(KV_ATTEND)
def _chunk_attention(qh: jax.Array, k_all: jax.Array, v_all: jax.Array,
                     start: int, scale: Optional[float] = None,
                     mask: Optional[jax.Array] = None) -> jax.Array:
    """Offset-causal fp32 attention for one prefill chunk.

    qh: ``[1, n, C, d]`` (the chunk's queries, global positions
    ``start..start+C``); k_all/v_all: ``[start+C, kvh, d]`` (prefix +
    chunk keys).  Same math as ``_cached_attention`` (fp32 softmax,
    1/sqrt(d), grouped-query broadcasting) with the per-slot validity
    mask replaced by the STATIC offset-causal mask ``j <= start + qi``
    — for real query positions this reaches only real keys, so pad
    positions in a final partial chunk never contaminate a real
    output (their own rows are discarded by the caller).  ``scale``
    multiplies the scores in place of ``1 / sqrt(d)``; ``mask`` ``[C,
    S]`` replaces the offset-causal one (a window layer's keys are a
    ring and the chunk's own, not a prefix)."""
    b, n, c, d = qh.shape
    kvh = k_all.shape[1]
    s_tot = k_all.shape[0]
    q32 = qh.astype(jnp.float32)
    k32 = k_all.transpose(1, 0, 2).astype(jnp.float32)[None]  # [1,kvh,S,d]
    v32 = v_all.transpose(1, 0, 2).astype(jnp.float32)[None]
    if mask is None:
        mask = (jnp.arange(s_tot)[None, :]
                <= (start + jnp.arange(c))[:, None])        # [C, S]
    if kvh != n:
        q32 = q32.reshape(b, kvh, n // kvh, c, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32)
        logits = logits / math.sqrt(d) if scale is None else logits * scale
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32)
        out = out.reshape(b, n, c, d)
    else:
        logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32)
        logits = logits / math.sqrt(d) if scale is None else logits * scale
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v32)
    return out.astype(k_all.dtype)


def chunk_on_kernel(heads: int, chunk: int, keys: int) -> bool:
    """Whether a prompt chunk's attention runs as the ``chunk_attend``
    kernel: where its dense float32 scores, query heads x chunk x keys x
    4 B, would pass ``CHUNK_SCORES_BYTES``."""
    return heads * chunk * keys * 4 > CHUNK_SCORES_BYTES


def chunk_attention(qh: jax.Array, k_all: jax.Array, v_all: jax.Array,
                    start: int, scale: Optional[float] = None,
                    keys_at: Optional[np.ndarray] = None, window: int = 0,
                    dense: Callable = _chunk_attention) -> jax.Array:
    """A prompt chunk's attention over keys at the static positions
    ``keys_at`` (-1: none; the prefix ``0 .. S`` where none are given):
    query ``p`` sees key positions ``0 <= j <= p``, and ``j > p -
    window`` with a ``window``.  Past ``CHUNK_SCORES_BYTES`` of dense
    scores (:func:`chunk_on_kernel`) the online-softmax kernel
    ``ops/chunk_attention.py::chunk_attend``, its scores in VMEM; below
    it ``dense`` (:func:`_chunk_attention`) over the same boolean mask."""
    _, n, c, _ = qh.shape
    if chunk_on_kernel(n, c, k_all.shape[0]):
        with jax.named_scope(KV_ATTEND):
            return chunk_attend(
                qh, k_all, v_all,
                np.arange(k_all.shape[0]) if keys_at is None else keys_at,
                start, window, scale)
    if keys_at is None:
        return dense(qh, k_all, v_all, start, scale)
    keys = jnp.asarray(keys_at)[None, :]
    at = (start + jnp.arange(c))[:, None]
    mask = (keys >= 0) & (keys <= at)
    if window:
        mask &= keys > at - window
    return dense(qh, k_all, v_all, start, scale, mask)


@jax.named_scope(KV_ATTEND)
def _verify_attention(q: jax.Array, k_flat: jax.Array, v_flat: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Offset-causal length-masked attention for one verify step.

    q: ``[B, n, G, d]`` (G = gamma+1 verify positions per slot);
    k_flat/v_flat: ``[B, S_max, kvh, d]``; valid: ``[B, G, S_max]`` bool
    — query ``i`` of slot ``b`` reaches keys ``j <= lengths[b] + i``
    (the per-slot offset-causal mask, ``_chunk_attention``'s static mask
    made per-slot dynamic).  Same math as ``_cached_attention`` (fp32
    softmax, 1/sqrt(d), grouped-query broadcasting), of which it is the
    G>1 generalisation."""
    b, n, g, d = q.shape
    kvh = k_flat.shape[2]
    q32 = q.astype(jnp.float32)
    k32 = k_flat.transpose(0, 2, 1, 3).astype(jnp.float32)  # [B, kvh, S, d]
    v32 = v_flat.transpose(0, 2, 1, 3).astype(jnp.float32)
    if kvh != n:
        q32 = q32.reshape(b, kvh, n // kvh, g, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, None, :, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32)
        out = out.reshape(b, n, g, d)
    else:
        logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, :, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v32)
    return out.astype(k_flat.dtype)
