"""The walk of a Pallas TPU kernel over the carried recurrent-state
plane, in place: one decode step of a recurrent layer reads and writes a
slot's state once, and only where a slot holds a request.

The plane ``[L_rec, B, H, P, N]`` (``serve/kvcache.py::HybridCache.
state``) is handed over WHOLE as it rides the layer scan's carry, an HBM
operand that is also the call's result (``input_output_aliases``), never
a sliced layer: a slice in front of a custom call would be a copy of the
layer, a layer and step (``ops/decode_attention.py`` reads its planes the
same way).  The layer index and the step's active mask lie in SMEM.  The
kernel lists the active slots, and for each brings the slot's state of
that layer into VMEM in blocks of :func:`block_heads` heads (the next
block's copy in flight while this one is computed, the last one's result
on its way out), hands each block to the recurrence's ``body`` and
copies what the body made of it back to where it came from.  An inactive
slot's state, and every other layer's, is never fetched and never
written: it stays bit for bit as it was.

What is computed of a block is the caller's (``ops/ssd.py``: the
Mamba-2 step); the walk knows nothing of it, so that another recurrence
(the gated delta rule's erase and write) can set its own body into it.

Off the TPU the kernel runs interpreted.  On it a block is copied as
whole (8, 128) tiles of the plane's last two dims
(:func:`check_kernel_takes`, which the engine calls when it is built).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a block of heads in VMEM: one on its way in, one being computed, one
# being written and one on its way out (4 x this)
BLOCK_BYTES = 1 << 20

_SUBLANES, _LANES = 8, 128


def block_heads(heads: int, p: int, n: int, itemsize: int) -> int:
    """Heads of one block: the most that divide a slot's ``heads`` and
    make a block of at most ``BLOCK_BYTES`` (at least one head)."""
    cap = max(1, BLOCK_BYTES // (p * n * itemsize))
    return max(h for h in range(1, min(cap, heads) + 1) if heads % h == 0)


def check_kernel_takes(plane) -> None:
    """Refuse, with the reason, a state plane (an array or its shape
    ``[L, B, H, P, N]``) whose blocks the kernel cannot copy on the chip:
    a head's ``[P, N]`` must be whole tiles, (8, 128) of 32 bits.
    There is no dense path to fall back to.  Interpreted (off the TPU)
    any shape runs."""
    if jax.default_backend() != "tpu":
        return
    p, n = plane.shape[-2:]
    sublanes = _SUBLANES * 4 // jnp.dtype(plane.dtype).itemsize
    if p % sublanes or n % _LANES:
        raise ValueError(
            f"the recurrent decode step on the TPU copies a head's state "
            f"as whole ({sublanes}, {_LANES}) tiles; this engine's state "
            f"plane holds heads of [{p}, {n}] "
            f"{jnp.dtype(plane.dtype).name}: serve a model of real state "
            "widths")


def _walk(layer_ref, active_ref, *refs, n_in: int, n_out: int, block: int,
          body: Callable) -> None:
    """The kernel: ``refs`` are the body's inputs, the plane, the plane
    again (the result that aliases it), the body's outputs, and the
    scratch (the list of active slots, the blocks in, the blocks out,
    the copies' semaphores)."""
    ins, (plane_in, plane_out) = refs[:n_in], refs[n_in:n_in + 2]
    outs = refs[n_in + 2:n_in + 2 + n_out]
    slot_ref, in_buf, out_buf, sem = refs[n_in + 2 + n_out:]
    layer = layer_ref[0]
    blocks = plane_in.shape[2] // block

    # the slots that hold a request, in their order
    def list_slot(b, n):
        slot_ref[n] = b
        return n + active_ref[b]

    live = jax.lax.fori_loop(0, active_ref.shape[0], list_slot, 0)

    def copy_in(i, j, which):
        return pltpu.make_async_copy(
            plane_in.at[layer, slot_ref[i], pl.ds(j * block, block)],
            in_buf.at[which], sem.at[0, which])

    def copy_out(i, j, which):
        return pltpu.make_async_copy(
            out_buf.at[which],
            plane_out.at[layer, slot_ref[i], pl.ds(j * block, block)],
            sem.at[1, which])

    @pl.when(live > 0)
    def _first():
        copy_in(0, 0, 0).start()

    for o in outs:
        o[...] = jnp.zeros_like(o)

    def per_slot(i, carry):
        for j in range(blocks):
            # block ``i x blocks + j`` of the walk, in buffer ``which``
            which = jax.lax.rem(i * blocks + j, 2)
            if j + 1 < blocks:
                copy_in(i, j + 1, 1 - which).start()
            else:
                @pl.when(i + 1 < live)
                def _next():
                    copy_in(i + 1, 0, 1 - which).start()

            copy_in(i, j, which).wait()

            # what the block before the last one left in ``out_buf`` is
            # on its way out: wait for it before writing over it
            @pl.when(i * blocks + j >= 2)
            def _free():
                copy_out(i, j, which).wait()

            body(slot_ref[i], j * block, in_buf.at[which],
                 out_buf.at[which], ins, outs)
            copy_out(i, j, which).start()
        return carry

    jax.lax.fori_loop(0, live, per_slot, 0)

    # the last two blocks' results
    for back in (1, 2):
        @pl.when(live * blocks >= back)
        def _drain():
            copy_out(0, 0, jax.lax.rem(live * blocks - back, 2)).wait()


def step_plane(body: Callable, name: str, plane: jax.Array,
               layer: jax.Array, active: jax.Array,
               ins: Sequence[tuple[jax.Array, str]],
               outs: Sequence[jax.ShapeDtypeStruct], *, block: int,
               interpret: bool) -> tuple[jax.Array, ...]:
    """One step of layer ``layer`` of ``plane`` ``[L, B, H, P, N]`` for
    the slots of ``active`` ``[B]``, in place; returns ``(plane,
    *outs)``.

    ``body(slot, head, block_in, block_out, ins, outs)`` is traced once a
    block of a slot's walk: ``slot`` the slot (traced), ``head`` the
    block's first head (a Python int), ``block_in`` a VMEM ref ``[block,
    P, N]`` of the state as it was, ``block_out`` the one to fill with
    the state as it is to be; ``ins`` its own operands' refs, each in
    ``"smem"`` or ``"vmem"`` as given, ``outs`` its results' refs (VMEM,
    zeros before the first block).  ``block`` heads must divide ``H``."""
    heads, p, n = plane.shape[2:]
    if heads % block:
        raise ValueError(f"blocks of {block} heads do not divide {heads}")
    space = {"smem": pl.BlockSpec(memory_space=pltpu.SMEM),
             "vmem": pl.BlockSpec(memory_space=pltpu.VMEM)}
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, block, p, n), plane.dtype)
    call = pl.pallas_call(
        functools.partial(_walk, n_in=len(ins), n_out=len(outs),
                          block=block, body=body),
        name=name,
        in_specs=[space["smem"], space["smem"],
                  *(space[where] for _, where in ins), hbm],
        out_specs=[hbm, *(space["vmem"] for _ in outs)],
        out_shape=[jax.ShapeDtypeStruct(plane.shape, plane.dtype), *outs],
        # the plane is its own result
        input_output_aliases={2 + len(ins): 0},
        scratch_shapes=[
            pltpu.SMEM((active.shape[0],), jnp.int32),  # active slots
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )
    return tuple(call(layer.astype(jnp.int32).reshape(1),
                      active.astype(jnp.int32),
                      *(t for t, _ in ins), plane))
