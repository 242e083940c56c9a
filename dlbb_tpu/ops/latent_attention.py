"""Pallas TPU decode attention over a LATENT plane (MLA, absorbed form):
one query a slot and head against the ONE cached row a token has, reading
only the tokens a slot holds, each row once for all heads.

The plane ``[L, B, nb, bs, W]`` holds per token ``[c' (kv_lora_rank),
rope(k_rope), zeros]`` (``W`` = ``ModelConfig.latent_row``, whole lanes).
The query of head ``h`` is ``[q_nope W_kv_b^K (kv_lora_rank),
rope(q_rope), zeros]``, so ``q . row`` is the head's whole score, and
the value is the row's first ``v_width`` = ``kv_lora_rank`` lanes: the
head's output is ``sum p c'``, expanded to ``v_head_dim`` by the caller.
``ops/decode_attention.py::kv_attend_decode`` fetches a K tile and a V
tile a head group; here one tile serves as both, for every head: 32
heads x ``W`` against ``T`` rows is one matmul on the MXU, ``P . C`` a
second.

The walk is ``kv_attend_decode``'s: the WHOLE plane is handed over as it
rides the layer scan's carry (an HBM operand, never a sliced layer), one
flat list of live ``(slot, tile)`` pairs is laid out in SMEM from
``lengths`` and ``active``, the next pair's tile is in flight while this
one is computed, online softmax across a slot's tiles in float32
scratch.  The arithmetic is that kernel's too: rows read as stored,
``q . row`` accumulated in float32, float32 softmax, ``P`` entering ``P .
C`` unrounded (its exact three-way bfloat16 split for bfloat16 planes,
``Precision.HIGHEST`` otherwise).

Off the TPU the kernel runs interpreted.  On it ``W`` and ``v_width``
must be whole lanes of 128 (:func:`check_kernel_takes`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlbb_tpu.compat import shard_map
from dlbb_tpu.ops.decode_attention import (
    _LANES,
    live_tile_counts,
    tile_tokens,
)
from dlbb_tpu.ops.flash_attention import NEG_INF

# the phase scopes of the latent plane (``serve/hybrid.py`` opens them
# around the write and around this kernel, as ``kv_update``/``kv_attend``)
LATENT_PHASES = ("latent_update", "latent_attend")
LATENT_UPDATE, LATENT_ATTEND = LATENT_PHASES
KERNEL_NAME = "latent_attend_decode"


def latent_spec(mesh: Mesh) -> P:
    """The latent plane ``[L, B, nb, bs, W]``: slots over ``dp``; one
    row a token has no head dim to lay over ``tp``."""
    axes = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    dp = "dp" if "dp" in axes and mesh.shape["dp"] > 1 else None
    return P(None, dp, None, None, None)


def plane_tile_tokens(plane) -> int:
    """``T``, the tokens of one tile the kernel fetches, from the plane's
    shape (``decode_attention.tile_tokens`` with one row a token)."""
    _, _, nb, bs, w = plane.shape
    return tile_tokens(nb, bs, 1, w, jnp.dtype(plane.dtype).itemsize)


def check_kernel_takes(plane, v_width: int) -> None:
    """Refuse, with the reason, a plane the kernel cannot read on the
    chip (interpreted, any shape runs)."""
    w = plane.shape[-1]
    if jax.default_backend() == "tpu" and (w % _LANES or v_width % _LANES):
        raise ValueError(
            f"latent decode attention on the TPU reads rows of whole "
            f"lanes of {_LANES} and a latent (kv_lora_rank) in whole "
            f"lanes; this engine's rows hold {w} values, {v_width} of "
            "them the latent: serve a model of real latent widths")


def _kernel(count_ref, len_ref, layer_ref, q_ref, plane_hbm, o_ref,
            slot_ref, tile_ref, buf, sem, tok_ref, m_ref, l_ref, acc_ref,
            *, tile: int, v_width: int, sm_scale: float):
    layer = layer_ref[0]
    tile_blocks, _, w = buf.shape[1:]
    n = q_ref.shape[1]
    exact_bf16 = q_ref.dtype == buf.dtype == jnp.bfloat16

    def list_slot(b, first):
        def put(t, carry):
            slot_ref[first + t] = b
            tile_ref[first + t] = t
            return carry

        jax.lax.fori_loop(0, count_ref[b], put, 0)
        return first + count_ref[b]

    total = jax.lax.fori_loop(0, q_ref.shape[0], list_slot, 0)

    def copy(i, which):
        b, t = slot_ref[i], tile_ref[i]
        return pltpu.make_async_copy(
            plane_hbm.at[layer, b, pl.ds(t * tile_blocks, tile_blocks)],
            buf.at[which], sem.at[which])

    @pl.when(total > 0)
    def _first():
        copy(0, 0).start()

    tok_ref[...] = jax.lax.broadcasted_iota(jnp.int32, (n, tile), 1)
    o_ref[...] = jnp.zeros_like(o_ref)

    def step(i, carry):
        which = jax.lax.rem(i, 2)

        @pl.when(i + 1 < total)
        def _next():
            copy(i + 1, 1 - which).start()

        copy(i, which).wait()
        b, t = slot_ref[i], tile_ref[i]
        last_pos = len_ref[b] - t * tile      # in this tile's numbering

        @pl.when(t == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[b]
        rows = buf[which].reshape(tile, w)
        c = rows[:, :v_width]
        if exact_bf16:
            s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        else:
            s = jax.lax.dot_general(
                q.astype(jnp.float32), rows.astype(jnp.float32),
                (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        s = jnp.where(tok_ref[...] <= last_pos, s * sm_scale, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                              # [n, T] f32
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
        m_ref[:, :1] = m_new
        if exact_bf16:
            # P unrounded: hi + mid + lo is the float32 P bit for bit
            hi = p.astype(jnp.bfloat16)
            rest = p - hi.astype(jnp.float32)
            mid = rest.astype(jnp.bfloat16)
            lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            pv3 = jax.lax.dot_general(
                jnp.concatenate([hi, mid, lo], axis=0), c,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pv = pv3[:n] + pv3[n:2 * n] + pv3[2 * n:]
        else:
            pv = jax.lax.dot_general(
                p, c.astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

        @pl.when(t == count_ref[b] - 1)    # the slot's last live tile
        def _finish():
            o_ref[b] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, total, step, 0)


@functools.partial(jax.jit, static_argnames=("tile", "v_width", "sm_scale",
                                             "interpret"))
def _attend_local(q, plane, layer, lengths, active, *, tile: int,
                  v_width: int, sm_scale: float, interpret: bool):
    """One shard's slots: ``q`` ``[B, n, W]``, ``plane`` ``[L, B, nb, bs,
    W]`` -> ``[B, n, v_width]``.  Jitted so that one engine's decode
    programs trace the kernel once between them."""
    b_dim, n, w = q.shape
    _, _, nb, bs, _ = plane.shape
    tile_blocks = tile // bs
    max_tiles = nb // tile_blocks
    counts = live_tile_counts(lengths, active, tile, max_tiles)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        functools.partial(_kernel, tile=tile, v_width=v_width,
                          sm_scale=sm_scale),
        name=KERNEL_NAME,
        in_specs=[smem, smem, smem, vmem, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((b_dim, n, v_width), plane.dtype),
        scratch_shapes=[
            pltpu.SMEM((b_dim * max_tiles,), jnp.int32),    # pair -> slot
            pltpu.SMEM((b_dim * max_tiles,), jnp.int32),    # pair -> tile
            pltpu.VMEM((2, tile_blocks, bs, w), plane.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((n, tile), jnp.int32),       # column -> token
            pltpu.VMEM((n, _LANES), jnp.float32),   # running max
            pltpu.VMEM((n, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((n, v_width), jnp.float32),  # accumulator
        ],
        interpret=interpret,
    )
    with jax.named_scope(KERNEL_NAME):
        return call(counts.astype(jnp.int32), lengths.astype(jnp.int32),
                    layer.astype(jnp.int32).reshape(1), q, plane)


@jax.named_scope(LATENT_ATTEND)
def latent_decode_attention(q: jax.Array, plane: jax.Array,
                            layer: jax.Array, lengths: jax.Array,
                            active: jax.Array, mesh: Mesh, v_width: int,
                            sm_scale: float) -> jax.Array:
    """Length-masked absorbed decode attention over layer ``layer`` of
    the carried latent plane: ``q`` ``[B, n, W]`` (zeros past the row's
    counted width), ``plane`` ``[L, B, nb, bs, W]``, ``lengths``/
    ``active`` ``[B]`` -> ``[B, n, v_width]`` in the plane's dtype, ``sum
    p c'`` of every head.  Slot ``b`` attends positions ``0 ..
    lengths[b]`` (the row appended at ``lengths[b]`` included) when
    active; an inactive slot's rows are zeros.  Under ``shard_map`` over
    the plane's own spec (slots over ``dp``), no collective."""
    spec = latent_spec(mesh)
    dp = spec[1]
    return shard_map(
        functools.partial(_attend_local, tile=plane_tile_tokens(plane),
                          v_width=v_width, sm_scale=float(sm_scale),
                          interpret=jax.default_backend() != "tpu"),
        mesh=mesh,
        in_specs=(P(dp, None, None), spec, P(), P(dp), P(dp)),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(q, plane, layer, lengths, active)
