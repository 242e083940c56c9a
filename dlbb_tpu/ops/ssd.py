"""The Mamba-2 recurrence (state-space duality, Dao & Gu 2024): the
mixer of a ``mamba`` layer (``models/hybrid.py``), behind the short
causal convolution of ``ops/gated_delta.py::causal_conv``.

Per head, with a state ``S`` of ``[P, N]`` floats (``P`` the head's
values, ``N`` the state size), a token's values ``x`` (``[P]``), step
``dt`` > 0, and ``B``, ``C`` (``[N]``) shared by EVERY head (one group);
``A`` < 0 and ``D`` are one scalar a head::

    S = exp(dt A) S + dt x B^T
    y = S C + D x

A scalar decay and an outer product: no erase, no normalised keys (the
gated delta rule of ``ops/gated_delta.py`` is another recurrence).  Three
forms of it:

- :func:`ssd_step`: one token for every slot of a decode batch,
  elementwise products and one reduction over the state in float32: the
  plain definition the other two are tested against;
- :func:`ssd_plane_step`: the same step over the carried state plane of
  a serving cache, in place: a Pallas TPU kernel (``ssm_state_step``)
  that reads and writes an ACTIVE slot's state once and no other
  (``ops/state_plane.py`` is its walk over the plane);
- :func:`ssd_chunked`: a whole sequence in chunks of ``chunk`` tokens.
  With ``g_t`` the running sum of ``dt A`` inside a chunk (<= 0) and
  ``S_0`` the state at its start::

      Y   = ((C B^T) * e^(g_i - g_j))_(j<=i) (dt X) + diag(e^g) C S_0^T + D X
      S_C = e^(g_C) S_0 + (dt X)^T diag(e^(g_C - g)) B

  Across chunks only the state is carried, by a ``lax.scan``.  Every
  exponent is of a non-positive number.  All products run in float32 at
  ``Precision.HIGHEST``, as the delta rule's do and for its reason.

A device trace shows their shares under the scopes ``state_update`` and
``state_scan`` (the delta rule's names: a model has one of the two
recurrences).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from dlbb_tpu.compat import shard_map
from dlbb_tpu.ops import state_plane
from dlbb_tpu.ops.gated_delta import HIGHEST, STATE_SCAN, STATE_UPDATE

# the decode kernel's name in a device trace: ``name=`` of the
# ``pallas_call``, inside the ``state_update`` scope
KERNEL_NAME = "ssm_state_step"


@jax.named_scope(STATE_UPDATE)
def ssd_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, state: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """One token a slot.  ``x``: ``[B, H, P]``; ``dt``: ``[B, H]``;
    ``a``, ``d``: ``[H]``; ``b``, ``c``: ``[B, N]``; ``state``: ``[B, H,
    P, N]``; all float32.  Returns ``(y [B, H, P], state)``.

    ``y = S C`` is taken as ``e^(dt A) (S_old C) + dt x (B . C)``, so
    that the one reduction runs over the OLD state beside the update
    (one pass over the state, as ``gated_delta_step`` does)."""
    decay = jnp.exp(dt * a)[..., None]                      # [B, H, 1]
    dx = dt[..., None] * x                                  # [B, H, P]
    s_c = jnp.sum(state * c[:, None, None, :], axis=-1)     # S_old C
    y = (decay * s_c + dx * jnp.sum(b * c, axis=-1)[:, None, None]
         + d[:, None] * x)
    new_state = (decay[..., None] * state
                 + dx[..., None] * b[:, None, None, :])
    return y, new_state


def _step_block(slot, head, block_in, block_out, ins, outs) -> None:
    """:func:`ssd_step` of one block of a slot's heads, from the block
    resident in VMEM: ``S_old C`` and the new state, head by head.  A
    head's values lie on the sublanes of its ``[P, N]`` state, so what is
    a value's own (``dt x``, and ``S_old C`` on its way out) is read and
    written as a column, ``[B, P, H]``; the head's decay is a scalar."""
    decay_ref, dx_ref, b_ref, c_ref = ins
    (s_c_ref,) = outs
    b_row = b_ref[pl.ds(slot, 1), :]                        # [1, N]
    c_row = c_ref[pl.ds(slot, 1), :]
    for j in range(block_in.shape[0]):
        h = head + j
        s = block_in[j].astype(jnp.float32)                 # [P, N]
        s_c_ref[slot, :, h:h + 1] = jnp.sum(s * c_row, axis=-1,
                                            keepdims=True)
        block_out[j] = (decay_ref[slot, h] * s
                        + dx_ref[slot, :, h:h + 1] * b_row
                        ).astype(block_out.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _plane_step_local(x, dt, a, b, c, d, plane, layer, active, *,
                      block: int, interpret: bool):
    """One shard's slots and heads.  Jitted so that the decode programs
    of one engine trace the kernel once between them."""
    slots, heads, p = x.shape
    decay = jnp.exp(dt * a)                                 # [B, H]
    dx = dt[..., None] * x                                  # [B, H, P]
    plane, s_c = state_plane.step_plane(
        _step_block, KERNEL_NAME, plane, layer, active,
        ((decay, "smem"), (dx.transpose(0, 2, 1), "vmem"), (b, "vmem"),
         (c, "vmem")),
        (jax.ShapeDtypeStruct((slots, p, heads), jnp.float32),),
        block=block, interpret=interpret)
    y = (decay[..., None] * s_c.transpose(0, 2, 1)
         + dx * jnp.sum(b * c, axis=-1)[:, None, None] + d[:, None] * x)
    return y, plane


@jax.named_scope(STATE_UPDATE)
def ssd_plane_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, d: jax.Array, plane: jax.Array,
                   layer: jax.Array, active: jax.Array, mesh: Mesh
                   ) -> tuple[jax.Array, jax.Array]:
    """:func:`ssd_step` of layer ``layer`` of the carried state plane
    ``[L, B, H, P, N]``, in place, for the slots of ``active``
    ``[B]``; the small operands are :func:`ssd_step`'s.  Returns ``(y [B,
    H, P], plane)``: an active slot's ``y`` and state are
    :func:`ssd_step`'s (the same expressions in float32, the one
    reduction in the kernel's order), an inactive slot's state is not
    touched and its ``y`` is ``D x`` and the write's share alone (nobody
    reads it).

    Runs under ``shard_map`` over the cache's own specs (slots over
    ``dp``, heads over ``tp``): every shard steps its own slots and
    heads, no collective, in blocks of ``state_plane.block_heads``."""
    from dlbb_tpu.serve.kvcache import hybrid_cache_specs

    spec = hybrid_cache_specs(mesh).state
    dp, tp = spec[1], spec[2]
    heads = plane.shape[2] // (mesh.shape[tp] if tp else 1)
    block = state_plane.block_heads(heads, *plane.shape[3:],
                                    jnp.dtype(plane.dtype).itemsize)
    return shard_map(
        functools.partial(_plane_step_local, block=block,
                          interpret=jax.default_backend() != "tpu"),
        mesh=mesh,
        in_specs=(P(dp, tp, None), P(dp, tp), P(tp), P(dp, None),
                  P(dp, None), P(tp), spec, P(), P(dp)),
        out_specs=(P(dp, tp, None), spec),
        check_vma=False,
    )(x, dt, a, b, c, d, plane, layer, active)


@jax.named_scope(STATE_SCAN)
def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, state: jax.Array, chunk: int
                ) -> tuple[jax.Array, jax.Array]:
    """A whole sequence.  ``x``: ``[B, S, H, P]``; ``dt``: ``[B, S, H]``;
    ``a``, ``d``: ``[H]``; ``b``, ``c``: ``[B, S, N]``; ``state``: ``[B,
    H, P, N]``; all float32.  Returns ``(y [B, S, H, P], state after
    position S - 1)``.

    A position with ``dt = 0`` leaves the state as it was (no decay, no
    write): that is how callers mask padding, and how a sequence that is
    no whole number of chunks is filled up here."""
    bsz, s, h, p = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (b, c))
    n = (s + pad) // chunk

    def chunks(t):                  # [B, S, ...] -> [N, B, C, ...]
        return jnp.moveaxis(t.reshape((bsz, n, chunk) + t.shape[2:]), 1, 0)

    def dot(spec, u, v):
        return jnp.einsum(spec, u, v, precision=HIGHEST)

    xc, bc, cc = chunks(x), chunks(b), chunks(c)
    dtc = chunks(dt)                                        # [N, B, C, H]
    g = jnp.cumsum(dtc * a, axis=2)
    dx = dtc[..., None] * xc                                # [N, B, C, H, P]
    t_idx = jnp.arange(chunk)
    lower = (t_idx[:, None] >= t_idx[None, :])[:, :, None]  # j <= i
    g_end = g[:, :, -1]                                     # [N, B, H]

    def body(st, inputs):
        x_n, dx_n, b_n, c_n, g_n, g_end_n = inputs
        # e^(g_i - g_j) where j <= i; masked before the exponential,
        # whose argument would be positive above the diagonal
        decay = jnp.exp(jnp.where(lower, g_n[:, :, None] - g_n[:, None, :],
                                  -jnp.inf))                # [B, C, C, H]
        scores = dot("bin,bjn->bij", c_n, b_n)[..., None] * decay
        y = (dot("bijh,bjhp->bihp", scores, dx_n)
             + jnp.exp(g_n)[..., None] * dot("bin,bhpn->bihp", c_n, st)
             + d[:, None] * x_n)
        k_end = jnp.exp(g_end_n[:, None] - g_n)             # [B, C, H]
        st = (jnp.exp(g_end_n)[..., None, None] * st
              + dot("bjhp,bjn->bhpn", dx_n * k_end[..., None], b_n))
        return st, y

    state, y = jax.lax.scan(body, state, (xc, dx, bc, cc, g, g_end))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, n * chunk, h, p)
    return y[:, :s], state
