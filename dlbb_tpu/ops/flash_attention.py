"""Pallas TPU flash attention (causal or full), online-softmax, O(S) memory.

Replaces the dense path (``models/attention.py``) for long sequences: dense
attention materialises the ``[B, N, S, S]`` score matrix in HBM — at
S=8192 that is 4 GiB per head-batch in fp32 — while this kernel streams
K/V blocks through VMEM and keeps only the ``[block_q, head_dim]``
accumulator plus running max/sum on chip (the online-softmax recurrence).

Design notes (standard blocked-attention scheme: Dao et al., FlashAttention-2):

- grid ``(B*N, S/block_q, S/block_k)`` — the K dimension is innermost, so
  the VMEM scratch accumulator persists across K iterations of one Q row;
- QK^T and PV ride the MXU via ``dot_general`` with
  ``preferred_element_type=float32``; probabilities are cast back to the
  value dtype for the PV matmul (bf16 MXU passes);
- causal masking uses a 2-D ``broadcasted_iota`` of *global* positions
  with the diagonal anchored at the END of the key axis (``offset =
  sk - s``), so kv-cache decode (``sk > s``) masks correctly; fully-masked
  K blocks are skipped with ``pl.when`` — for causal attention this halves
  the FLOPs;
- the log-sum-exp per query row is emitted as a second output (needed by
  the custom-VJP backward, and useful for numerics debugging);
- block sizes auto-fit to the sequence length (largest divisor ≤ the
  requested block, preferring lane-aligned multiples of 128);
- off-TPU (the CPU-simulated test mesh) the kernel runs in interpret mode.

Reference parity note: the reference has no attention kernel at all — its
benchmark model skips attention entirely (``models.py:162-167``).  This is
capability the TPU framework adds for the long-context configs
(SURVEY §5.7).

What compiles (jax/jaxlib 0.9.0, libtpu 0.0.34, v5e; PR 21): all three
kernels as written — no ``compiler_params``, so under Mosaic's default
scoped-VMEM limit, and no ``dimension_semantics`` — at the default
1024 x 1024 blocks, causal and not, bf16 and fp32, forward and backward,
for S = 512 / 1024 / 2048 at 32 heads x 128 (compiled for v5e on a CPU
host), and the ``tpu``-marked tests run them on the chip against the dense
oracle at S = 512 and 1024 (32 x 128 bf16 forward + backward, GQA
``b // g`` index maps, the ``[:, :1]`` lane slices).  At S = 1024 that is
one block holding s, p, dp, ds in fp32 (4 MB each) plus two int32 iotas,
which this Mosaic accepts; nothing had to be lowered.  Speed on this
JAX: not measured (the block-size sweep behind the defaults was taken
with jaxlib 0.4's Mosaic).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Best of a {256,512,1024}^2 sweep at S in 2048..8192, D=128, taken with an
# earlier jaxlib's Mosaic (not re-measured); the module docstring says
# what compiles at these blocks today.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# Finite stand-in for -inf: exp(NEG_INF - m) underflows to 0 without
# generating nans in the m_prev - m_new subtraction on fully-masked rows.
NEG_INF = -1e30

_LANES = 128  # TPU vector lane count — row-stat arrays carry this axis

# The kernels' names in a device trace: each ``pl.pallas_call`` gets the
# name as ``name=`` and runs under a ``jax.named_scope`` of the same
# name, so whichever of the two the profile keeps tells forward, dq and
# dkv apart (they used to show as three ``closed_call.N``).
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV = KERNEL_NAMES


def mosaic_call_count(jitted, *args) -> int:
    """How many Mosaic kernels (compiled Pallas, custom-call target
    ``tpu_custom_call``) the program ``jitted(*args)`` lowers to.  Zero
    means every ``flash_attention`` in it ran in interpret mode or was
    routed to the dense einsum — fine on the simulated mesh, a silent
    fallback on the chip; harnesses record it so a run can prove which
    it was.  Lowering only (the trace is shared with the call that
    follows); nothing executes."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def _fit_block(n: int, requested: int) -> int:
    """Largest divisor of ``n`` that is <= ``requested``, preferring
    lane-aligned (multiple-of-128) divisors."""
    cap = min(requested, n)
    divisors = [d for d in range(1, cap + 1) if n % d == 0]
    aligned = [d for d in divisors if d % _LANES == 0]
    return max(aligned) if aligned else max(divisors)


def _masked_scores(q, k, qi, ki, *, sm_scale, block_q, block_k, causal,
                   offset):
    """fp32 ``[block_q, block_k]`` scores for Q block ``qi`` x K block
    ``ki``, causal-masked on global positions (query row r attends to key
    columns c with ``c <= r + offset``; ``offset = sk - s`` anchors the
    diagonal at the end of the key axis).  Shared by the forward and both
    backward kernels so the mask convention cannot diverge."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(rows + offset >= cols, s, NEG_INF)
    return s


def _block_visible(qi, ki, *, block_q, block_k, causal, offset):
    """Whether K block ``ki`` intersects the visible region of Q block
    ``qi`` (max global row + offset >= min global col)."""
    if not causal:
        return qi >= 0  # always true, as a traced bool
    return (qi + 1) * block_q - 1 + offset >= ki * block_k


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale: float, block_q: int, block_k: int, causal: bool,
                offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_block_visible(qi, ki, block_q=block_q, block_k=block_k,
                            causal=causal, offset=offset))
    def _compute():
        v = v_ref[0]
        s = _masked_scores(q_ref[0], k_ref[0], qi, ki, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k, causal=causal,
                           offset=offset)
        m_prev = m_ref[:, :1]                                   # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                         # [bq, 1]
        p = jnp.exp(s - m_new)                                  # [bq, bk]
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l_safe), lse_ref.shape[1:]
        )


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    """q: [BN, S, D]; k, v: [BKV, Sk, D] with BN % BKV == 0 (grouped-query
    attention folds kv_heads into BKV; the group size ``g = BN // BKV``
    makes ``g`` consecutive Q rows of the grid share one K/V row via the
    ``b // g`` index map — K/V stay at kv_heads width in HBM and VMEM).
    Returns (o [BN, S, D], lse [BN, S, LANES] fp32).

    The row-stat (lse) output carries a broadcast 128-lane axis: TPU vector
    memory is (sublane, lane)-tiled, so a dense [BN, S] layout would be
    written through a transposed 1-lane path; the lane-replicated form keeps
    the store vectorised.  It is transient for inference (freed after the
    pallas_call) and live only across the backward for training.
    """
    bn, s, d = q.shape
    bkv, sk, _ = k.shape
    g = bn // bkv
    block_q = _fit_block(s, block_q)
    block_k = _fit_block(sk, block_k)
    offset = sk - s
    grid = (bn, s // block_q, sk // block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, offset=offset,
    )
    call = pl.pallas_call(
        kernel,
        name=FLASH_FWD,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, s, d), q.dtype),
            jax.ShapeDtypeStruct((bn, s, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope(FLASH_FWD):
        return call(q, k, v)


def _p_from_lse(s, lse_row):
    """Recompute probabilities ``exp(s - lse)`` for the backward kernels.

    Fully-masked query rows (causal with ``offset < 0``, i.e. ``sk < s``)
    carry ``lse = NEG_INF``; there ``s - lse = NEG_INF - NEG_INF = 0`` would
    yield p = 1 across the whole block and inject garbage into dq/dk/dv.
    Such rows produced o = 0 in the forward, so their true gradient
    contribution is 0 — force p to 0.
    """
    p = jnp.exp(s - lse_row)
    return jnp.where(lse_row <= NEG_INF / 2, 0.0, p)


# ---------------------------------------------------------------------------
# backward — recompute p blockwise from (q, k, lse); two passes:
#   dq kernel:  grid over Q blocks (outer), K blocks inner — accumulates dq;
#   dkv kernel: grid over K blocks (outer), Q blocks inner — accumulates
#               dk, dv for one K block across all visible Q blocks.
# delta = rowsum(do * o) is precomputed outside (one fused XLA reduction).
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, sm_scale, block_q, block_k, causal, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_visible(qi, ki, block_q=block_q, block_k=block_k,
                            causal=causal, offset=offset))
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _masked_scores(q_ref[0], k, qi, ki, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k, causal=causal,
                           offset=offset)
        p = _p_from_lse(s, lse_ref[0][:, :1])                   # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * sm_scale
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, sm_scale, block_q, block_k, causal, offset, q_blocks):
    """Accumulates dk, dv for one K/V block.  The inner grid dim flattens
    (query-head group, Q block) — ``q_blocks`` Q blocks per group — so
    under grouped-query attention one K/V block accumulates gradient from
    every query head that shares it."""
    ki = pl.program_id(1)
    it = pl.program_id(2)       # flattened (group, q-block) index
    qi = it % q_blocks          # Q block index within the group
    nit = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_visible(qi, ki, block_q=block_q, block_k=block_k,
                            causal=causal, offset=offset))
    def _compute():
        q = q_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _masked_scores(q, k_ref[0], qi, ki, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k, causal=causal,
                           offset=offset)
        p = _p_from_lse(s, lse_ref[0][:, :1])                   # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0][:, :1]) * sm_scale          # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # [bk, d]

    @pl.when(it == nit - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    bn, s, d = q.shape
    bkv, sk, _ = k.shape
    g = bn // bkv
    block_q = _fit_block(s, block_q)
    block_k = _fit_block(sk, block_k)
    offset = sk - s
    nq = s // block_q

    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [bn, s, 1]
    delta = jnp.broadcast_to(delta, (bn, s, _LANES))

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))

    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, causal=causal, offset=offset),
        name=FLASH_BWD_DQ,
        grid=(bn, s // block_q, sk // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope(FLASH_BWD_DQ):
        dq = dq_call(q, k, v, do, lse, delta)

    # dkv: swap loop order — K blocks outer; the inner dim flattens
    # (query-head group, Q block) so each of the bkv K/V rows accumulates
    # over its g sharing query heads (grid row b serves Q rows b*g..b*g+g-1)
    q_spec_t = pl.BlockSpec(
        (1, block_q, d), lambda b, j, i: (b * g + i // nq, i % nq, 0)
    )
    k_spec_t = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row_spec_t = pl.BlockSpec(
        (1, block_q, _LANES), lambda b, j, i: (b * g + i // nq, i % nq, 0)
    )
    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, causal=causal, offset=offset,
                          q_blocks=nq),
        name=FLASH_BWD_DKV,
        grid=(bkv, sk // block_k, g * nq),
        in_specs=[q_spec_t, k_spec_t, k_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[k_spec_t, k_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope(FLASH_BWD_DKV):
        dk, dv = dkv_call(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return o


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked attention, ``q: [B, num_heads, S, head_dim] -> same``.

    ``k, v`` may be full ``[B, num_heads, Sk, head_dim]`` or grouped-query
    ``[B, kv_heads, Sk, head_dim]`` with ``num_heads % kv_heads == 0`` —
    query-head groups share K/V blocks inside the kernel (``b // g`` index
    maps), so grouped K/V stay at kv_heads width in HBM and VMEM: the
    KV-bandwidth saving GQA exists for, not just a smaller projection.

    Differentiable (custom VJP with blockwise recompute — no [S, S]
    residuals; dk/dv accumulate over the sharing query heads).  ``sk != s``
    is supported; with ``causal=True`` the diagonal anchors at the end of
    the key axis (kv-cache decode convention).  ``interpret=None``
    auto-selects pallas interpret mode off TPU so the same model code runs
    on the CPU-simulated dev mesh.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, N, S, D], got {q.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n, s, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if n % kvh != 0:
        raise ValueError(
            f"num_heads {n} not divisible by kv_heads {kvh}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    fold = lambda t, nh, sl: t.reshape(b * nh, sl, d)  # noqa: E731
    o = _flash(
        fold(q, n, s), fold(k, kvh, sk), fold(v, kvh, sk),
        sm_scale, causal, block_q, block_k, interpret,
    )
    return o.reshape(b, n, s, d)
