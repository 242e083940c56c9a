"""The gated delta rule (Gated DeltaNet, Yang et al. 2024) and the short
causal convolution in front of it: the mixer of a ``linear_attention``
layer (``models/hybrid.py``).

Per head, with a state ``S`` of ``[d_v, d_k]`` floats, a token's
normalised query and key ``q, k`` (``[d_k]``), value ``v`` (``[d_v]``),
decay ``alpha`` in (0, 1) and write strength ``beta`` in (0, 2)::

    S' = alpha * S
    u  = beta * (v - S' k)
    S  = S' + u k^T
    o  = S q

Two forms of the same recurrence:

- :func:`gated_delta_step`: one token for every slot of a decode batch,
  elementwise products and reductions over the state in float32 (no
  matmul, so no reduced-precision pass over the state);
- :func:`gated_delta_chunked`: a whole sequence in chunks of ``chunk``
  tokens.  Inside a chunk the ``u`` of all positions solve one
  unit-lower-triangular system (the WY form; :func:`unit_lower_inverse`),
  across chunks only the state is carried by a ``lax.scan``.  With
  ``g_t`` the running sum of ``log alpha`` inside the chunk and ``S_0``
  the state at its start::

      (I + diag(beta) A) U = diag(beta) V - diag(beta e^g) K S_0^T,
          A_tj = e^(g_t - g_j) k_t.k_j  (j < t)
      O    = diag(e^g) Q S_0^T + (e^(g_t - g_j) q_t.k_j)_(j<=t) U
      S_C  = e^(g_C) S_0 + U^T diag(e^(g_C - g)) K

  Every exponent is of a non-positive number.  All products run in
  float32 at ``Precision.HIGHEST``: on a TPU a float32 ``dot`` is one
  bfloat16 pass by default, which would round the state at every use.

No Pallas kernel: this is the plain ``jax.numpy`` baseline whose share a
device trace shows (scopes ``state_update`` and ``state_scan``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# tokens a chunk of the chunked form holds: the triangular system is
# ``chunk x chunk`` per head, the scan over chunks S / chunk trips long
CHUNK = 64

STATE_UPDATE = "state_update"
STATE_SCAN = "state_scan"


def causal_conv(ext: jax.Array, weight: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the last ``K`` positions.

    ``ext``: ``[B, K - 1 + S, ...channels]``, the ``K - 1`` inputs that
    came before the sequence (zeros at its very start) followed by the
    sequence; ``weight``: ``[K, ...channels]``, ``weight[K - 1]``
    multiplying the current position.  Returns ``[B, S, ...channels]``
    in float32."""
    k = weight.shape[0]
    s = ext.shape[1] - (k - 1)
    w32 = weight.astype(jnp.float32)
    out = jnp.zeros((ext.shape[0], s) + ext.shape[2:], jnp.float32)
    for i in range(k):
        out = out + ext[:, i:i + s].astype(jnp.float32) * w32[i]
    return out


def l2_normalise(x: jax.Array, scale: float = 1.0) -> jax.Array:
    """``x / ||x||_2 * scale`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return x32 * (inv * scale)


@jax.named_scope(STATE_UPDATE)
def gated_delta_step(q: jax.Array, k: jax.Array, v: jax.Array,
                     alpha: jax.Array, beta: jax.Array,
                     state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token a slot.  ``q, k``: ``[B, H, d_k]``; ``v``: ``[B, H,
    d_v]``; ``alpha, beta``: ``[B, H]``; ``state``: ``[B, H, d_v, d_k]``;
    all float32.  Returns ``(o [B, H, d_v], state)``.

    ``o = S q`` is taken as ``alpha (S_old q) + u (k.q)``, so that both
    reductions over the old state can share one pass over it."""
    kb, qb = k[:, :, None, :], q[:, :, None, :]
    s_k = jnp.sum(state * kb, axis=-1)                     # S_old k
    s_q = jnp.sum(state * qb, axis=-1)                     # S_old q
    a = alpha[..., None]
    u = beta[..., None] * (v - a * s_k)
    o = a * s_q + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new_state = a[..., None] * state + u[..., None] * kb
    return o, new_state


def unit_lower_inverse(m: jax.Array) -> jax.Array:
    """Inverse of unit lower-triangular matrices ``[..., n, n]`` (``n`` a
    power of two; what is on or above the diagonal is not read): the
    diagonal blocks' inverses are doubled in size ``log2 n`` times,
    ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``, every
    pair of blocks at once, in float32 matmuls at ``HIGHEST``.  (XLA's
    own triangular solve inverts a block one row at a time: on the v5e it
    took 14% of the device's busy time in the first traced run of the
    cell that uses this, PERF.md section 6, PR 27.)"""
    n = m.shape[-1]
    if n & (n - 1):
        raise ValueError(f"the chunk must be a power of two, got {n}")
    lead = m.shape[:-2]
    inv = jnp.ones(lead + (n, 1, 1), m.dtype)               # 1x1 blocks
    size = 1
    while size < n:
        pairs = n // (2 * size)
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        blocks = m.reshape(lead + (pairs, 2 * size, pairs, 2 * size))
        diag = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)
        c = diag[..., size:, :size]                          # [.., pairs, s, s]
        lower = -jnp.matmul(b, jnp.matmul(c, a, precision=HIGHEST),
                            precision=HIGHEST)
        top = jnp.concatenate([a, jnp.zeros_like(a)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([lower, b], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


@jax.named_scope(STATE_SCAN)
def gated_delta_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                        log_alpha: jax.Array, beta: jax.Array,
                        state: jax.Array, chunk: int = CHUNK
                        ) -> tuple[jax.Array, jax.Array]:
    """A whole sequence.  ``q, k``: ``[B, S, H, d_k]``; ``v``: ``[B, S,
    H, d_v]``; ``log_alpha`` (<= 0) and ``beta``: ``[B, S, H]``;
    ``state``: ``[B, H, d_v, d_k]``; all float32.  Returns ``(o [B, S,
    H, d_v], state after position S - 1)``.

    A position with ``log_alpha = 0`` and ``beta = 0`` leaves the state
    as it was: that is how callers mask padding, and how a sequence that
    is no whole number of chunks is filled up here."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        log_alpha, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                           for t in (log_alpha, beta))
    n = (s + pad) // chunk

    def chunks(t):      # [B, S, H, ...] -> [B, H, N, C, ...]
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(t, 3, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    g = jnp.cumsum(chunks(log_alpha), axis=-1)             # [B, H, N, C]
    bc = chunks(beta)
    t_idx = jnp.arange(chunk)
    lower = t_idx[:, None] >= t_idx[None, :]               # j <= t
    strict = t_idx[:, None] > t_idx[None, :]
    # e^(g_t - g_j) where j <= t; masked before the exponential, whose
    # argument would be positive above the diagonal
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))

    def dot(spec, x, y):
        return jnp.einsum(spec, x, y, precision=HIGHEST)

    kk = dot("bhnck,bhnjk->bhncj", kc, kc)
    system = jnp.where(strict, bc[..., :, None] * decay * kk, 0.0)
    rhs = jnp.concatenate([bc[..., None] * vc,
                           (bc * jnp.exp(g))[..., None] * kc], axis=-1)
    solved = dot("bhncj,bhnjd->bhncd", unit_lower_inverse(system), rhs)
    w_v, w_k = solved[..., :dv], solved[..., dv:]
    attn = decay * dot("bhnck,bhnjk->bhncj", qc, kc)
    q_g = qc * jnp.exp(g)[..., None]
    k_end = kc * jnp.exp(g[..., -1:] - g)[..., None]
    g_end = jnp.exp(g[..., -1])                            # [B, H, N]

    def body(st, xs):
        w_v_n, w_k_n, attn_n, q_g_n, k_end_n, g_end_n = xs
        u = w_v_n - dot("bhck,bhvk->bhcv", w_k_n, st)
        o = (dot("bhck,bhvk->bhcv", q_g_n, st)
             + dot("bhcj,bhjv->bhcv", attn_n, u))
        st = (g_end_n[..., None, None] * st
              + dot("bhcv,bhck->bhvk", u, k_end_n))
        return st, o

    per_chunk = tuple(jnp.moveaxis(t, 2, 0)
                      for t in (w_v, w_k, attn, q_g, k_end, g_end))
    state, o = jax.lax.scan(body, state, per_chunk)        # o [N,B,H,C,dv]
    o = jnp.moveaxis(o, 0, 2)                              # [B,H,N,C,dv]
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)
    return o[:, :s], state
