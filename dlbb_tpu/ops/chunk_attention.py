"""Pallas TPU attention of one prompt chunk over the keys it reads: the
carried prefix of a global layer, or a window layer's ring and the
chunk's own keys.

The dense path (``serve/attend.py::_chunk_attention``) writes the whole
chunk's float32 scores to HBM, reads them back for the softmax's
reductions, writes the probabilities and reads them for ``P.V``: at
1,024 queries of 28 heads over 5,120 keys that is 587 MB of scores a
layer, moved about four times, for 10.5 MB of K/V.  This kernel keeps
one ``[rows, block_k]`` tile of scores in VMEM at a time and carries the
softmax across K blocks online (running maximum, sum and accumulator in
float32 scratch), as ``ops/flash_attention.py``'s forward does.

What it is handed, and what it masks:

- ``q`` ``[1, n, C, d]`` at global positions ``start .. start + C``;
  K/V token-major ``[S, kvh, d]`` (``n % kvh == 0``).  The ``n / kvh``
  query heads of a group are folded into the rows of one grid step, so
  a K/V block is read once for the whole group.
- ``keys_at`` ``[S]``: the position each key holds, -1 for none (a ring
  place no position of the prompt has reached).  The ring is read in
  its rotated order; the softmax does not depend on the keys' order.
  Key ``j`` is visible to query ``p`` where ``0 <= keys_at[j] <= p``
  and, with a ``window`` ``W``, ``keys_at[j] > p - W``: exactly the
  boolean mask the dense path builds.
- ``start``, ``window`` and ``keys_at`` are static (a chunk program's
  own), and every K block holds consecutive positions or none (a
  window is a whole number of chunks and a K block divides a chunk), so
  which (query block, K block) pairs see nothing, see everything or need
  the mask is worked out here when the program is traced
  (:func:`block_plan`).  Pairs that see nothing are skipped and fetch
  nothing new; pairs that see everything skip the mask.

The arithmetic is the dense program's on the chip: ``q.k`` accumulates
in float32 from the stored dtype, the softmax statistics and the
accumulator are float32, and ``P`` enters ``P.V`` in V's dtype, which is
the single bfloat16 pass that the dense path's float32 ``einsum`` takes
at default precision on a TPU (checked in its program compiled for the
chip, ``PERF.md`` §6).  Off the TPU the kernel runs in interpret
mode.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlbb_tpu.ops.flash_attention import NEG_INF, _fit_block

# the kernel's name in a device trace: ``name=`` of the ``pallas_call``
# and a ``jax.named_scope`` around it, inside the caller's ``kv_attend``
KERNEL_NAME = "chunk_attend"
# query rows (of one head) and keys of a grid step.  A sweep at the
# SmallThinker cell's widths (28 heads over 4 of 128, chunks of 1,024; TPU
# v5 lite, ``scripts/chunk_attention_bench.py``, PERF.md §6): a
# window layer's chunk past the window 0.620 ms at 256 x 1024, 0.614 at
# 512 x 1024, 0.767 at 512 x 512, 0.894 at 256 x 512, 1.58 at 256 x 256;
# a global layer's at offset 14,336 1.72, 1.70, 2.37, 2.75, 5.28 ms.
# 512 x 1024 needs more than the default scoped VMEM for 1%.
BLOCK_Q = 256
BLOCK_K = 1024
# what a (query block, K block) pair does
SKIP, WHOLE, MASKED = 0, 1, 2

_LANES = 128


def block_plan(keys_at: np.ndarray, start: int, chunk: int, window: int,
               block_q: int, block_k: int
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static plan of the grid: ``kind`` ``[nq, nk]`` (:data:`SKIP`,
    :data:`WHOLE` or :data:`MASKED`), ``fetch`` ``[nq, nk]`` the K block
    each step holds in VMEM (its own where it computes, else the one
    fetched last, so that a skipped step copies nothing) and ``base``
    ``[nk]`` the first position of each K block (-1: none)."""
    keys_at = np.asarray(keys_at)
    blocks = keys_at.reshape(-1, block_k)
    base = blocks[:, 0]
    held = base >= 0
    if ((blocks[~held] >= 0).any()
            or not (blocks[held] == base[held, None]
                    + np.arange(block_k)).all()):
        raise ValueError(
            f"every block of {block_k} keys must hold consecutive "
            "positions, or none")
    q_lo = start + np.arange(chunk // block_q)[:, None] * block_q
    q_hi = q_lo + block_q - 1
    k_lo, k_hi = base[None, :], base[None, :] + block_k - 1
    seen = held & (k_lo <= q_hi)
    whole = held & (k_hi <= q_lo)
    if window:
        seen &= k_hi > q_lo - window
        whole &= k_lo > q_hi - window
    kind = np.where(whole, WHOLE, np.where(seen, MASKED, SKIP))
    flat = kind.reshape(-1)
    fetch = np.empty_like(flat)
    last = int(np.argmax(flat != SKIP)) % len(base)
    for i, k in enumerate(flat):
        if k != SKIP:
            last = i % len(base)
        fetch[i] = last
    return kind.astype(np.int32), fetch.reshape(kind.shape).astype(
        np.int32), base.astype(np.int32)


def _kernel(kind_ref, fetch_ref, base_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, start: int, window: int, scale: float,
            block_q: int, block_k: int, nk: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    group, _, d = q_ref.shape[1:]
    rows = group * block_q
    kind = kind_ref[qi * nk + ki]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        v = v_ref[0]
        s = jax.lax.dot_general(
            q_ref[0].reshape(rows, d), k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            at = start + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            key = base_ref[ki] + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = key <= at
            if window:
                seen &= key > at - window
            s = jnp.where(seen[None], s.reshape(group, block_q, block_k),
                          NEG_INF).reshape(rows, block_k)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    pl.when(kind == WHOLE)(lambda: step(False))
    pl.when(kind == MASKED)(lambda: step(True))

    @pl.when(ki == nk - 1)
    def _finish():
        # every query sees at least its own key: l > 0
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).reshape(
            group, block_q, d).astype(o_ref.dtype)


def chunk_attend(qh: jax.Array, k_all: jax.Array, v_all: jax.Array,
                 keys_at: np.ndarray, start: int, window: int = 0,
                 scale: Optional[float] = None, block_q: int = BLOCK_Q,
                 block_k: int = BLOCK_K) -> jax.Array:
    """Attention of one prompt chunk, ``qh`` ``[1, n, C, d]``, over
    ``k_all`` / ``v_all`` ``[S, kvh, d]`` whose keys lie at ``keys_at``
    (module docstring) -> ``[1, n, C, d]`` in K's dtype.  The scores are
    multiplied by ``scale``, ``1 / sqrt(d)`` where none is given."""
    _, n, c, d = qh.shape
    kvh = k_all.shape[1]
    group = n // kvh
    # a K block divides the chunk, so it never straddles two chunks' keys
    block_q, block_k = _fit_block(c, block_q), _fit_block(c, block_k)
    kind, fetch, base = block_plan(keys_at, start, c, window, block_q,
                                   block_k)
    nq, nk = kind.shape
    kernel = functools.partial(
        _kernel, start=start, window=window,
        scale=1.0 / math.sqrt(d) if scale is None else float(scale),
        block_q=block_q, block_k=block_k, nk=nk)
    q_spec = pl.BlockSpec((1, group, block_q, d),
                          lambda h, i, j, kind, fetch, base: (h, 0, i, 0))
    kv_spec = pl.BlockSpec(
        (1, block_k, d),
        lambda h, i, j, kind, fetch, base: (h, fetch[i * nk + j], 0))
    rows = group * block_q
    call = pl.pallas_call(
        kernel,
        name=KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(kvh, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),   # running max
                pltpu.VMEM((rows, _LANES), jnp.float32),   # running sum
                pltpu.VMEM((rows, d), jnp.float32),        # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((kvh, group, c, d), k_all.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
    )
    with jax.named_scope(KERNEL_NAME):
        out = call(jnp.asarray(kind.reshape(-1)),
                   jnp.asarray(fetch.reshape(-1)), jnp.asarray(base),
                   qh.reshape(kvh, group, c, d),
                   k_all.transpose(1, 0, 2), v_all.transpose(1, 0, 2))
    return out.reshape(1, n, c, d)
