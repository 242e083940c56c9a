"""Pallas TPU decode attention: one query a slot over the carried K/V
planes, reading only the tokens a slot holds.

The dense path (``serve/engine.py::_cached_attention``) reads a layer's
whole ``[B, S_max, kvh, d]`` plane every step and masks what lies past
``lengths``; XLA runs it at the speed of the memory, so the only gain
left is not to read (``PERF.md`` §5-6, PR 28).  This kernel is handed
the WHOLE planes ``[L, B, nb, bs, kvh, d]`` as they ride the layer
scan's carry (HBM operands, never a sliced layer: a slice in front of a
custom call would be a copy of the layer, a layer and step) and copies
into VMEM, double-buffered, only the tiles of ``T`` tokens under each
active slot's length: tiles ``0 .. lengths[b] // T`` of slot ``b``,
nothing of an inactive slot (its output row is zeros).

One invocation walks one flat list of live ``(slot, tile)`` pairs, laid
out in SMEM from the tile counts the wrapper reckons from ``lengths``
and ``active`` (:func:`live_tile_counts`), so the copy of the next
pair's tile is in flight across slot boundaries too while this pair is
computed.  A tile
is ``T x kvh`` rows of ``d``; the scores of all ``n`` query heads
against all rows are ONE matmul ``[n, d] x [T*kvh, d]^T`` on the
otherwise idle MXU, of which a query head keeps the columns of its own
kv-head (grouped-query heads share them) and of positions ``<=
lengths[b]``, exactly as ``valid = pos <= lengths`` does in the dense
path.  Online softmax across a slot's tiles, with the running maximum,
the sum and the accumulator in float32 scratch.

The arithmetic is what the configuration states: K and V are read as
stored, ``q.K`` accumulates in float32 (exact for bf16 inputs), the
softmax is float32, and the probabilities enter ``P.V`` unrounded: for
bf16 planes as the exact three-way bf16 split of the float32 ``P``
stacked into one matmul (the kernel is bound by bytes; rounding ``P``
buys no time), otherwise at ``Precision.HIGHEST``.

Heads narrower than the 128 lanes are read from planes that hold a
token's K (or V) of a layer as ONE row, ``[L, B, nb, bs, kvh x d]``
(``models.configs.kv_rows``: 8 heads of 64 are a row of 512, four whole
lanes-rows, where a ``(kvh, d)`` tile would widen each head to 128 and
hold twice the bytes).  The walk and the arithmetic are the same
(:func:`_rows_kernel`); what differs is how a query head finds its
kv-head.  The wrapper lays query head ``i`` into lanes ``(i // group) x
d ...`` of an otherwise zero row, so ``q_row . K_row`` IS the head's
score against its own kv-head (no column of another head's to mask),
and of ``P . V_rows`` ``[n, kvh x d]`` it keeps each head's own ``d``
lanes.

Off the TPU the kernel runs in interpret mode, as
``ops/flash_attention.py`` does.  On it a shard's last two dims must be
whole (8, 128) tiles (:func:`check_kernel_takes`, which the engine calls
when it is built).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlbb_tpu.compat import shard_map
from dlbb_tpu.models.transformer import SERVE_PHASES
from dlbb_tpu.ops.flash_attention import NEG_INF

KV_ATTEND = SERVE_PHASES[1]
# the kernel's name in a device trace: ``name=`` of the ``pallas_call``
# and a ``jax.named_scope`` around it, inside the ``kv_attend`` phase
KERNEL_NAME = "kv_attend_decode"
# a tile of K (and one of V) in VMEM, twice each
TILE_BYTES = 1 << 19

_LANES = 128


def tile_tokens(num_blocks: int, block_size: int, kv_heads: int,
                head_dim: int, itemsize: int) -> int:
    """``T``: the tokens of one tile, chosen once from the plane's
    shape: the most whole blocks that divide a slot's ring and make a
    tile of at most ``TILE_BYTES`` (at least one block)."""
    cap = max(1, TILE_BYTES // (block_size * kv_heads * head_dim * itemsize))
    blocks = max(b for b in range(1, min(cap, num_blocks) + 1)
                 if num_blocks % b == 0)
    return blocks * block_size


def _shard_shape(k_plane, mesh: Mesh) -> tuple[int, int, int, int, int]:
    """``(num_blocks, block_size, kv_heads, head_dim, itemsize)`` of one
    shard of a carried plane ``[L, B, nb, bs, kvh, d]`` (an array or its
    shape) laid over ``mesh`` by the cache's own specs."""
    from dlbb_tpu.serve.kvcache import cache_specs

    itemsize = jnp.dtype(k_plane.dtype).itemsize
    if len(k_plane.shape) == 5:
        # whole rows (never under tp): one "head" of the row's width
        _, _, nb, bs, w = k_plane.shape
        return nb, bs, 1, w, itemsize
    tp = cache_specs(mesh).k[4]
    _, _, nb, bs, kvh, d = k_plane.shape
    return nb, bs, kvh // (mesh.shape[tp] if tp else 1), d, itemsize


def plane_tile_tokens(k_plane, mesh: Mesh) -> int:
    """``T`` of a carried plane on ``mesh``: what the kernel fetches by,
    and what the engine's tile counters reckon by."""
    return tile_tokens(*_shard_shape(k_plane, mesh))


def check_kernel_takes(k_plane, mesh: Mesh) -> None:
    """Refuse, with the reason, planes the kernel cannot read on the
    chip: its copies move whole (8, 128) tiles of a shard's last two
    dims, so Mosaic takes kv-heads a shard in eights and a head_dim in
    128s (the serving cells' shards and those of the chip smoke), not
    the toy widths of a CPU test or 30 heads split in two.  There is no
    dense path to fall back to.  Interpreted (off the TPU) any shape
    runs."""
    _, bs, kvh, d, itemsize = _shard_shape(k_plane, mesh)
    if jax.default_backend() != "tpu":
        return
    if len(k_plane.shape) == 5:
        # a tile is ``(block_size, row)``: 8 sublanes of 32 bits
        if d % _LANES or bs % (32 // itemsize):
            raise ValueError(
                f"decode attention on the TPU reads K/V rows of whole "
                f"lanes of {_LANES} in blocks of whole sublanes; this "
                f"engine's planes hold rows of {d} in blocks of {bs}")
        return
    if kvh % 8 or d % _LANES:
        raise ValueError(
            f"decode attention on the TPU reads K/V planes of kv-heads a "
            f"shard in eights and a head_dim in {_LANES}s (whole tiles for "
            f"the kernel's copies); this engine's shards hold {kvh} "
            f"kv-heads of {d}: serve a model of real head widths, or the "
            "int8 layout")


def live_tile_counts(lengths, active, tile: int, max_tiles: int):
    """Tiles the kernel fetches for each slot: those holding positions
    ``0 .. lengths[b]`` of an active slot, none of an inactive one.
    Works on numpy arrays (the engine's counters, on the host) as on
    traced ones."""
    return (lengths // tile + 1).clip(0, max_tiles) * active


def _kernel(count_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
            slot_ref, tile_ref, k_buf, v_buf, sem, bias_ref, tok_ref, m_ref,
            l_ref, acc_ref, *, tile: int, group: int, sm_scale: float):
    layer = layer_ref[0]
    tile_blocks, _, kvh, d = k_buf.shape[1:]
    n = q_ref.shape[1]
    cols = tile * kvh
    exact_bf16 = q_ref.dtype == k_buf.dtype == jnp.bfloat16

    # the live (slot, tile) pairs, slot by slot
    def list_slot(b, first):
        def put(t, carry):
            slot_ref[first + t] = b
            tile_ref[first + t] = t
            return carry

        jax.lax.fori_loop(0, count_ref[b], put, 0)
        return first + count_ref[b]

    total = jax.lax.fori_loop(0, q_ref.shape[0], list_slot, 0)

    def copies(i, buf):
        b, t = slot_ref[i], tile_ref[i]
        return [
            pltpu.make_async_copy(
                plane.at[layer, b, pl.ds(t * tile_blocks, tile_blocks)],
                dst.at[buf], sem.at[j, buf])
            for j, (plane, dst) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf)))
        ]

    @pl.when(total > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    # column j of a tile's scores is token j // kvh of the tile at
    # kv-head j % kvh; a query head keeps the columns of its own kv-head
    col = jax.lax.broadcasted_iota(jnp.int32, (n, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, cols), 0)
    own = jax.lax.rem(col, jnp.int32(kvh)) == jax.lax.div(row,
                                                          jnp.int32(group))
    bias_ref[...] = jnp.where(own, 0.0, NEG_INF)
    tok_ref[...] = jax.lax.div(col, jnp.int32(kvh))
    o_ref[...] = jnp.zeros_like(o_ref)

    def step(i, carry):
        buf = jax.lax.rem(i, 2)

        @pl.when(i + 1 < total)
        def _next():
            for c in copies(i + 1, 1 - buf):
                c.start()

        for c in copies(i, buf):
            c.wait()
        b, t = slot_ref[i], tile_ref[i]
        last_pos = len_ref[b] - t * tile      # in this tile's numbering

        @pl.when(t == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[b]
        k = k_buf[buf].reshape(cols, d)
        v = v_buf[buf].reshape(cols, d)
        if exact_bf16:
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        else:
            s = jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
                (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        s = jnp.where(tok_ref[...] <= last_pos,
                      s * sm_scale + bias_ref[...], NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                              # [n, cols] f32
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
        m_ref[:, :1] = m_new
        if exact_bf16:
            # P unrounded: hi + mid + lo is the float32 P bit for bit,
            # and each part times a bf16 V accumulates in float32
            hi = p.astype(jnp.bfloat16)
            rest = p - hi.astype(jnp.float32)
            mid = rest.astype(jnp.bfloat16)
            lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            pv3 = jax.lax.dot_general(
                jnp.concatenate([hi, mid, lo], axis=0), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pv = pv3[:n] + pv3[n:2 * n] + pv3[2 * n:]
        else:
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

        @pl.when(t == count_ref[b] - 1)    # the slot's last live tile
        def _finish():
            o_ref[b] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, total, step, 0)


def _rows_kernel(count_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                 slot_ref, tile_ref, k_buf, v_buf, sem, tok_ref, m_ref,
                 l_ref, acc_ref, *, tile: int, sm_scale: float):
    """:func:`_kernel` over planes of whole rows ``[L, B, nb, bs, W]``:
    ``q_ref`` ``[B, n, W]`` holds each query head in the lanes of its
    own kv-head, ``o_ref`` ``[B, n, W]`` takes ``P . V_rows`` whole."""
    layer = layer_ref[0]
    tile_blocks, _, w = k_buf.shape[1:]
    n = q_ref.shape[1]
    exact_bf16 = q_ref.dtype == k_buf.dtype == jnp.bfloat16

    def list_slot(b, first):
        def put(t, carry):
            slot_ref[first + t] = b
            tile_ref[first + t] = t
            return carry

        jax.lax.fori_loop(0, count_ref[b], put, 0)
        return first + count_ref[b]

    total = jax.lax.fori_loop(0, q_ref.shape[0], list_slot, 0)

    def copies(i, buf):
        b, t = slot_ref[i], tile_ref[i]
        return [
            pltpu.make_async_copy(
                plane.at[layer, b, pl.ds(t * tile_blocks, tile_blocks)],
                dst.at[buf], sem.at[j, buf])
            for j, (plane, dst) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf)))
        ]

    @pl.when(total > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    tok_ref[...] = jax.lax.broadcasted_iota(jnp.int32, (n, tile), 1)
    o_ref[...] = jnp.zeros_like(o_ref)

    def step(i, carry):
        buf = jax.lax.rem(i, 2)

        @pl.when(i + 1 < total)
        def _next():
            for c in copies(i + 1, 1 - buf):
                c.start()

        for c in copies(i, buf):
            c.wait()
        b, t = slot_ref[i], tile_ref[i]
        last_pos = len_ref[b] - t * tile      # in this tile's numbering

        @pl.when(t == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[b]
        k = k_buf[buf].reshape(tile, w)
        v = v_buf[buf].reshape(tile, w)
        if exact_bf16:
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        else:
            s = jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
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
            # P unrounded, as in ``_kernel``
            hi = p.astype(jnp.bfloat16)
            rest = p - hi.astype(jnp.float32)
            mid = rest.astype(jnp.bfloat16)
            lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            pv3 = jax.lax.dot_general(
                jnp.concatenate([hi, mid, lo], axis=0), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pv = pv3[:n] + pv3[n:2 * n] + pv3[2 * n:]
        else:
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

        @pl.when(t == count_ref[b] - 1)    # the slot's last live tile
        def _finish():
            o_ref[b] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, total, step, 0)


@functools.partial(jax.jit, static_argnames=("tile", "sm_scale",
                                             "interpret"))
def _attend_rows_local(q, k_plane, v_plane, layer, lengths, active, *,
                       tile: int, sm_scale: float, interpret: bool):
    """:func:`_attend_local` over planes of whole rows: ``q`` ``[B, n, 1,
    d]``, planes ``[L, B, nb, bs, kvh x d]`` -> ``[B, n, 1, d]``."""
    b_dim, n, _, d = q.shape
    _, _, nb, bs, w = k_plane.shape
    kvh = w // d
    tile_blocks = tile // bs
    max_tiles = nb // tile_blocks
    counts = live_tile_counts(lengths, active, tile, max_tiles)
    # query head i in the lanes of kv-head i // group, zeros elsewhere
    own = (jnp.arange(n)[:, None] // (n // kvh)
           == jnp.arange(kvh)[None, :]).astype(q.dtype)      # [n, kvh]
    q_rows = (q[:, :, 0, None, :] * own[None, :, :, None]).reshape(
        b_dim, n, w)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = (2, tile_blocks, bs, w)
    call = pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile, sm_scale=sm_scale),
        name=KERNEL_NAME,
        in_specs=[smem, smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((b_dim, n, w), k_plane.dtype),
        scratch_shapes=[
            pltpu.SMEM((b_dim * max_tiles,), jnp.int32),    # pair -> slot
            pltpu.SMEM((b_dim * max_tiles,), jnp.int32),    # pair -> tile
            pltpu.VMEM(buf, k_plane.dtype),
            pltpu.VMEM(buf, v_plane.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n, tile), jnp.int32),       # column -> token
            pltpu.VMEM((n, _LANES), jnp.float32),   # running max
            pltpu.VMEM((n, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((n, w), jnp.float32),        # accumulator
        ],
        interpret=interpret,
    )
    with jax.named_scope(KERNEL_NAME):
        out = call(counts.astype(jnp.int32), lengths.astype(jnp.int32),
                   layer.astype(jnp.int32).reshape(1), q_rows, k_plane,
                   v_plane)
    # of a head's [kvh x d] the d lanes of its own kv-head (the others
    # times exact zeros)
    out = (out.reshape(b_dim, n, kvh, d) * own[None, :, :, None]
           .astype(out.dtype)).sum(axis=2)
    return out[:, :, None]


@functools.partial(jax.jit, static_argnames=("tile", "sm_scale",
                                             "interpret"))
def _attend_local(q, k_plane, v_plane, layer, lengths, active, *,
                  tile: int, interpret: bool,
                  sm_scale: Optional[float] = None):
    """One shard's slots and heads: ``q`` ``[B, n, 1, d]``, planes
    ``[L, B, nb, bs, kvh, d]`` -> ``[B, n, 1, d]``.  Jitted so that the
    decode programs of one engine (the step and every fused scan) trace
    the kernel once between them: a quarter of a second each on the
    chip's host, inside every program's warm-up (``PERF.md`` §6, PR 28)."""
    b_dim, n, _, d = q.shape
    _, _, nb, bs, kvh, _ = k_plane.shape
    tile_blocks = tile // bs
    max_tiles = nb // tile_blocks
    cols = tile * kvh
    counts = live_tile_counts(lengths, active, tile, max_tiles)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = (2, tile_blocks, bs, kvh, d)
    call = pl.pallas_call(
        functools.partial(_kernel, tile=tile, group=n // kvh,
                          sm_scale=(1.0 / math.sqrt(d) if sm_scale is None
                                    else sm_scale)),
        name=KERNEL_NAME,
        in_specs=[smem, smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((b_dim, n, d), k_plane.dtype),
        scratch_shapes=[
            pltpu.SMEM((b_dim * max_tiles,), jnp.int32),    # pair -> slot
            pltpu.SMEM((b_dim * max_tiles,), jnp.int32),    # pair -> tile
            pltpu.VMEM(buf, k_plane.dtype),
            pltpu.VMEM(buf, v_plane.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n, cols), jnp.float32),     # own-head bias
            pltpu.VMEM((n, cols), jnp.int32),       # column -> token
            pltpu.VMEM((n, _LANES), jnp.float32),   # running max
            pltpu.VMEM((n, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((n, d), jnp.float32),        # accumulator
        ],
        interpret=interpret,
    )
    with jax.named_scope(KERNEL_NAME):
        out = call(counts.astype(jnp.int32), lengths.astype(jnp.int32),
                   layer.astype(jnp.int32).reshape(1), q[:, :, 0], k_plane,
                   v_plane)
    return out[:, :, None]


@jax.named_scope(KV_ATTEND)
def decode_attention(q: jax.Array, k_plane: jax.Array, v_plane: jax.Array,
                     layer: jax.Array, lengths: jax.Array,
                     active: jax.Array, mesh: Mesh,
                     sm_scale: Optional[float] = None) -> jax.Array:
    """Length-masked decode attention over layer ``layer`` of the
    carried planes: ``q`` ``[B, n, 1, d]``, ``k_plane``/``v_plane``
    ``[L, B, nb, bs, kvh, d]`` (``n % kvh == 0``), or of whole rows ``[L,
    B, nb, bs, kvh x d]``, ``lengths``/``active``
    ``[B]`` -> ``[B, n, 1, d]`` in the planes' dtype.  Slot ``b`` attends
    positions ``0 .. lengths[b]`` (its token appended at ``lengths[b]``
    included) when active; an inactive slot's row is zeros.  The scores
    are multiplied by ``sm_scale``, ``1 / sqrt(d)`` where none is given.

    Runs under ``shard_map`` over the cache's own specs, as
    ``append_token_rows`` does (slots over ``dp``, kv-heads over ``tp``):
    every shard attends its own slots and heads, no collective, in tiles
    of :func:`plane_tile_tokens`."""
    from dlbb_tpu.serve.kvcache import cache_specs

    kv_spec = cache_specs(mesh).k
    dp, tp = kv_spec[1], kv_spec[4]
    interpret = jax.default_backend() != "tpu"
    tile = plane_tile_tokens(k_plane, mesh)
    if k_plane.ndim == 5:
        row_spec = P(None, dp, None, None, None)
        scale = 1.0 / math.sqrt(q.shape[-1]) if sm_scale is None \
            else float(sm_scale)
        return shard_map(
            functools.partial(_attend_rows_local, tile=tile,
                              sm_scale=scale, interpret=interpret),
            mesh=mesh,
            in_specs=(P(dp, None, None, None), row_spec, row_spec, P(),
                      P(dp), P(dp)),
            out_specs=P(dp, None, None, None),
            check_vma=False,
        )(q, k_plane, v_plane, layer, lengths, active)
    local = functools.partial(_attend_local, tile=tile, interpret=interpret)
    if sm_scale is not None:
        local = functools.partial(local, sm_scale=float(sm_scale))
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp, tp, None, None), kv_spec, kv_spec, P(), P(dp),
                  P(dp)),
        out_specs=P(dp, tp, None, None),
        check_vma=False,
    )(q, k_plane, v_plane, layer, lengths, active)
