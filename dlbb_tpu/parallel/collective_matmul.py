"""Overlapped collective-matmul: ring-decomposed TP projections.

The GSPMD Megatron layout (``models/sharding.py``) leaves tensor-parallel
collective time *exposed*: each row-parallel matmul ends in an all-reduce
that sits serially between the matmul producing its operand and the next
matmul consuming its result.  The round-5 chip artifacts put the 7B full
forward at 163.3 TFLOP/s vs 176.9 for the comm-free simplified variant —
the gap is that serial collective time.

This module applies the decomposition of Wang et al., ASPLOS 2023
("Overlap Communication with Dependent Computation via Decomposition")
and the collective-matmul schedules of Pope et al. 2022: split each
TP projection into per-shard partial matmuls interleaved with a
``lax.ppermute`` ring, so the transfer of one shard rides under the
matmul of another.  The per-layer all-reduce pair becomes an
all-gather-matmul (column parallel) + matmul-reduce-scatter (row
parallel) pair — same total wire bytes (AG + RS = AR), but every hop is
a neighbour ``collective-permute`` that XLA's async scheduler can start
before, and finish after, an independent partial matmul.  Activations
between blocks live *sequence-sharded over tp* (the Megatron
sequence-parallel layout), which is what gives each ring step an
independent chunk to compute on.

Two schedules:

- ``ring``  — unidirectional: P-1 hops, full chunk per hop, one ICI
  direction.
- ``bidir`` — bidirectional: both ICI directions at once.  The
  all-gather ring halves the *hop count* (two chunks arrive per step);
  the reduce-scatter ring splits every chunk's rows in half and
  reduces each half around opposite directions (half-sized messages
  both ways).  Wins when the schedule is latency-bound (small chunks,
  long rings) or when both link directions are otherwise idle.

Both carry a **custom VJP** so the backward pass overlaps the same way:
the cotangent of an all-gather-matmul is a matmul-reduce-scatter (and
vice versa), and the weight gradient is its own ring over the saved
activations — no fused-path all-reduces reappear under ``jax.grad``.
Weight gradients are psum'd over the batch-carrying mesh axes (dp, sp)
inside the ring body, exactly the reduction GSPMD would insert for
replicated parameters.

The ring bodies are Python-unrolled (the tp degree is static and small),
so the lowered HLO shows the literal collective-permute chain — which is
what the comm-lint HLO audit pins (``analysis/expectations.py``:
overlapped targets must show the permute chain and no residual oversized
all-gather; see docs/overlap.md for the audit contract).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dlbb_tpu.compat import shard_map

SCHEDULES = ("ring", "bidir")

# mesh axes that may carry the batch/sequence dims alongside tp; weight
# grads psum over the ones present (the replicated-param reduction GSPMD
# would otherwise insert)
_BATCH_AXES = ("dp", "sp")


# -- when a ring pays: the rule behind ``model.tp_overlap: auto`` ------------
#
# A ring moves the bytes the fused all-reduce moves; what it wins is the
# share of its hops that a partial matmul runs beside, and what it can lose
# is a hop too small to be anything but latency.  Both are read from shapes.
# The numbers are from ``scripts/overlap_candidates.py`` on the four chips
# of a v5e 2x2 host (PERF.md §6, PR 34, calls 1 and 2).
#
# One ICI link in one direction: bare ppermute chains took 0.0233 ms more a
# hop for every MB between 2.6 and 10.5 MB, one way and both ways alike
# (call 1).  The MXU's rate is the chip's published bf16 peak; a float32
# product runs the same single bf16 pass under jax's default precision, so
# only its bytes differ.
LINK_BYTES_PER_S = 43e9
MXU_FLOPS_PER_S = 197e12
# The smallest hop and the least hidden share a chip run has shown to win:
# the source's 1B widths at tp 4, batch 8 x sequence 512 (hops of 4 MiB,
# share 0.335: 31.05 -> 24.12 ms a forward, call 2; its 7B, 0.67: 117.68 ->
# 90.81; its 13B, 0.78: 216.67 -> 163.83).  Nothing smaller or narrower has
# been measured, so there the program stays the fused one.
MIN_HOP_BYTES = 4 << 20
MIN_HIDDEN_SHARE = 0.33
# The schedule the rule returns.  Call 2, ms a 13B forward: fused 216.67,
# the compiler's own windowed decomposition of the sequence-sharded layout
# 196.01, ``ring`` 186.41 (the first hop of both reduce-scatter rings runs
# bare: XLA fuses the add into the next partial matmul), ``bidir`` 163.83.
AUTO_SCHEDULE = "bidir"


def hidden_share(shard_widths: Sequence[int], itemsize: int) -> float:
    """Mean over a layer's rings of the share of a hop that the partial
    matmul beside it covers, each capped at 1.

    A ring chunk of ``r`` rows and ``h`` columns takes ``r * h * itemsize
    / LINK_BYTES_PER_S`` to hop; the partial product beside it is ``2 * r
    * h * w`` operations, ``w`` being the shard's output width in an
    all-gather ring and its contraction width in a reduce-scatter ring.
    The ratio is ``2 * w * link / (itemsize * mxu)``: no rows, no ``h``.
    At bf16 it is ``w / 4,581``."""
    per_width = 2 * LINK_BYTES_PER_S / (itemsize * MXU_FLOPS_PER_S)
    return sum(min(1.0, w * per_width) for w in shard_widths) / len(
        shard_widths)


def auto_schedule(mesh: Mesh, x_shape: Sequence[int], itemsize: int,
                  widths: Sequence[int], tp_axis: str = "tp"
                  ) -> str | None:
    """The schedule a layer's TP projections should take on ``mesh`` for a
    residual stream of global shape ``x_shape``, or None for the fused
    route.  ``widths`` are the global widths the rings shard: each
    column-parallel projection's output and each row-parallel projection's
    contraction.  Never raises: a shape no ring can take is fused."""
    p = mesh.shape[tp_axis]
    b_axis, _, sp, _ = _mesh_layout(mesh, tp_axis)
    seq_shards = p * (mesh.shape["sp"] if sp else 1)
    dp = mesh.shape[b_axis] if b_axis else 1
    batch, seq, hidden = x_shape
    if (p < 2 or seq % seq_shards or batch % dp
            or any(w % p for w in widths)):
        return None
    hop_bytes = (batch // dp) * (seq // seq_shards) * hidden * itemsize
    if hop_bytes < MIN_HOP_BYTES:
        return None
    if hidden_share([w // p for w in widths], itemsize) < MIN_HIDDEN_SHARE:
        return None
    return AUTO_SCHEDULE


def _check_schedule(schedule: str) -> bool:
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown tp_overlap schedule {schedule!r}; known: {SCHEDULES}"
        )
    return schedule == "bidir"


def _ring_perms(p: int):
    """(forward, backward) ring permutations: forward sends i -> i+1 (each
    device receives from its left neighbour), backward the reverse."""
    fwd = [(i, (i + 1) % p) for i in range(p)]
    bwd = [(i, (i - 1) % p) for i in range(p)]
    return fwd, bwd


# ---------------------------------------------------------------------------
# local ring kernels (run inside shard_map; x/w/dy are per-device blocks)
# ---------------------------------------------------------------------------


def _ring_visit(travelling, axis: str, p: int, bidir: bool, visit):
    """Circulate ``travelling`` (this device's chunk of some ring-sharded
    array) and call ``visit(chunk, src)`` once per source rank, own chunk
    first.  The shared travel loop of every gather-style ring here: each
    ppermute is independent of the visit consuming the chunk in hand, so
    XLA overlaps the hop with the visit's matmul.

    Unidirectional: p-1 forward hops.  Bidirectional: chunks arrive from
    both neighbours each step — ceil((p-1)/2) hops, both ICI directions.
    """
    r = lax.axis_index(axis)
    fwd, bwd = _ring_perms(p)
    visit(travelling, r)
    # every hop runs under a ``ring_hop*`` named scope: the name lands in
    # the HLO op_name metadata, which is how the schedule auditor
    # (analysis/schedule_audit.py) pins exactly these permutes for the
    # serialized-collective gate — each must have a straddling matmul
    if not bidir:
        cur = travelling
        for j in range(1, p):
            with jax.named_scope(f"ring_hop_fwd{j}"):
                cur = lax.ppermute(cur, axis, fwd)  # holds block (r - j)
            visit(cur, (r - j) % p)
        return
    n_fwd = (p - 1 + 1) // 2
    n_bwd = (p - 1) // 2
    cur_f = cur_b = travelling
    for j in range(1, max(n_fwd, n_bwd) + 1):
        if j <= n_fwd:
            with jax.named_scope(f"ring_hop_fwd{j}"):
                cur_f = lax.ppermute(cur_f, axis, fwd)   # block (r - j)
            visit(cur_f, (r - j) % p)
        if j <= n_bwd:
            with jax.named_scope(f"ring_hop_bwd{j}"):
                cur_b = lax.ppermute(cur_b, axis, bwd)   # block (r + j)
            visit(cur_b, (r + j) % p)


def _ag_matmul_body(x, w, axis: str, p: int, bidir: bool):
    """All-gather-matmul: x [b, s, h] (this device's sequence chunk),
    w [h, f] (this device's column shard) -> [b, p*s, f] (full sequence,
    column shard).  Row block ``src`` of the output is ``x_src @ w``;
    x chunks travel the ring while the chunk in hand is multiplied."""
    b, s, h = x.shape
    # one row block per source rank, written on axis 1 of a 4-D buffer:
    # XLA:TPU then folds each write into its partial matmul's own fusion,
    # in place, as its own windowed einsum does.  Written along the rows
    # of a [b, p * s, f] buffer the dynamic_update_slice stayed an op of
    # its own that copied the whole buffer at every visit: 38.5 ms of the
    # 13B forward's 209 ms step (PERF.md §6, PR 34)
    out = jnp.zeros((b, p, s, w.shape[1]), dtype=x.dtype)

    def visit(chunk, src):
        nonlocal out
        out = lax.dynamic_update_index_in_dim(out, chunk @ w, src, axis=1)

    _ring_visit(x, axis, p, bidir, visit)
    return out.reshape(b, p * s, w.shape[1])


def _matmul_rs_body(x, w, axis: str, p: int, bidir: bool):
    """Matmul-reduce-scatter: x [b, s, f] (full sequence, this device's
    feature shard), w [f, h] (row shard) -> [b, s/p, h] (this device's
    sequence chunk of the cross-shard sum).

    The accumulator travels the ring: at each step a device adds its own
    partial product for the chunk the accumulator is destined to, so the
    partial matmul for step j+1 is independent of step j's permute."""
    s = x.shape[1]
    if s % p != 0:
        raise ValueError(
            f"matmul_reducescatter: local sequence {s} not divisible by "
            f"ring size {p}"
        )
    s_out = s // p
    r = lax.axis_index(axis)
    fwd, bwd = _ring_perms(p)

    def partial(c, lo=0, rows=s_out):
        xc = lax.dynamic_slice_in_dim(x, c * s_out + lo, rows, axis=1)
        return xc @ w

    if not bidir or s_out < 2:
        # target of the accumulator on this device at add-step j is
        # (r + p - 1 - j) mod p; after the last add it is chunk r, fully
        # reduced.  ring_hop named scopes: see _ring_visit
        acc = partial((r + p - 1) % p)
        for j in range(1, p):
            with jax.named_scope(f"ring_hop_fwd{j}"):
                acc = lax.ppermute(acc, axis, fwd)
            acc = acc + partial((r + p - 1 - j) % p)
        return acc
    # bidirectional: the front half of every chunk's rows reduces
    # clockwise, the back half counter-clockwise — half-sized messages on
    # both ICI directions every step, and two chains whose adds wait
    # behind each other's matmuls.  Halves of the ROWS: a half of w's
    # columns is a copy of the layer's kernel out of the stack, every
    # layer (7.1 ms of the 13B forward's step, PERF.md §6, PR 34)
    half = s_out // 2
    back = s_out - half
    acc_f = partial((r + p - 1) % p, 0, half)
    acc_b = partial((r + 1) % p, half, back)
    for j in range(1, p):
        with jax.named_scope(f"ring_hop_fwd{j}"):
            acc_f = lax.ppermute(acc_f, axis, fwd)
        acc_f = acc_f + partial((r + p - 1 - j) % p, 0, half)
        with jax.named_scope(f"ring_hop_bwd{j}"):
            acc_b = lax.ppermute(acc_b, axis, bwd)
        acc_b = acc_b + partial((r + 1 + j) % p, half, back)
    return jnp.concatenate([acc_f, acc_b], axis=1)


def _ag_grad_w_body(x, dy, axis: str, p: int, bidir: bool,
                    batch_axes: tuple[str, ...]):
    """Weight gradient of the all-gather-matmul: dw [h, f] = sum over the
    gathered sequence of x_src^T @ dy[src rows].  The saved x chunks
    travel the same ring (a re-gather, overlapped with the contraction);
    the result is psum'd over the batch-carrying axes — the
    replicated-parameter reduction."""
    s = x.shape[1]
    dw = None

    def visit(chunk, src):
        nonlocal dw
        dyc = lax.dynamic_slice_in_dim(dy, src * s, s, axis=1)
        term = jnp.einsum("bsh,bsf->hf", chunk, dyc)
        dw = term if dw is None else dw + term

    _ring_visit(x, axis, p, bidir, visit)
    if batch_axes:
        dw = lax.psum(dw, batch_axes)
    return dw


def _rs_grad_w_body(x, dy, axis: str, p: int, bidir: bool,
                    batch_axes: tuple[str, ...]):
    """Weight gradient of the matmul-reduce-scatter: dw [f, h] = x^T @
    AG(dy) over the sequence — the dy chunks travel the ring while the
    stationary x rows they pair with are contracted."""
    s_out = dy.shape[1]
    dw = None

    def visit(dy_chunk, src):
        nonlocal dw
        xc = lax.dynamic_slice_in_dim(x, src * s_out, s_out, axis=1)
        term = jnp.einsum("bsf,bsh->fh", xc, dy_chunk)
        dw = term if dw is None else dw + term

    _ring_visit(dy, axis, p, bidir, visit)
    if batch_axes:
        dw = lax.psum(dw, batch_axes)
    return dw


# ---------------------------------------------------------------------------
# global wrappers (shard_map + custom VJP)
# ---------------------------------------------------------------------------


def _mesh_layout(mesh: Mesh, tp_axis: str):
    """(batch spec entry, sharded-seq spec entry, gathered-seq spec entry,
    batch-carrying axes present) for this mesh."""
    axes = mesh.axis_names
    if tp_axis not in axes:
        raise ValueError(
            f"mesh {tuple(axes)} has no {tp_axis!r} axis for overlapped "
            "collective matmul"
        )
    b = "dp" if "dp" in axes else None
    sp = "sp" if "sp" in axes and mesh.shape["sp"] > 1 else None
    seq_sharded = (sp, tp_axis) if sp else tp_axis
    # size-1 axes stay in the psum set: the reduction is free there but it
    # is what lets shard_map's replication checker prove the P(None, tp)
    # weight-grad out_spec
    batch_axes = tuple(
        a for a in _BATCH_AXES if a in axes and a != tp_axis
    )
    return b, seq_sharded, sp, batch_axes


def _validate(x, w, mesh, tp_axis, col_parallel: bool):
    _, seq_sharded, sp, _ = _mesh_layout(mesh, tp_axis)
    p = mesh.shape[tp_axis]
    seq_div = p * (mesh.shape["sp"] if sp else 1)
    if x.ndim != 3 or w.ndim != 2:
        raise ValueError(
            f"collective matmul expects x [B, S, features] and w 2D; got "
            f"x {x.shape}, w {w.shape}"
        )
    if x.shape[1] % seq_div != 0:
        raise ValueError(
            f"sequence length {x.shape[1]} not divisible by the "
            f"sequence-shard count {seq_div} "
            f"(tp={p}{f' x sp={mesh.shape[sp]}' if sp else ''}); "
            "tp_overlap needs evenly divisible sequence chunks"
        )
    w_dim = 1 if col_parallel else 0
    if w.shape[w_dim] % p != 0:
        raise ValueError(
            f"weight dim {w.shape[w_dim]} not divisible by tp={p}"
        )


def _apply_ag(x, w, mesh, tp_axis, bidir):
    """shard_map'd all-gather-matmul on global arrays: x sequence-sharded
    over (sp, tp), w column-sharded over tp -> y with the full-tp sequence
    and tp-sharded features."""
    p = mesh.shape[tp_axis]
    b, seq_sharded, sp, _ = _mesh_layout(mesh, tp_axis)
    return shard_map(
        lambda x_, w_: _ag_matmul_body(x_, w_, tp_axis, p, bidir),
        mesh=mesh,
        in_specs=(P(b, seq_sharded, None), P(None, tp_axis)),
        out_specs=P(b, sp, tp_axis),
    )(x, w)


def _apply_rs(x, w, mesh, tp_axis, bidir):
    """shard_map'd matmul-reduce-scatter on global arrays: x with tp-sharded
    features, w row-sharded over tp -> y sequence-sharded over (sp, tp)."""
    p = mesh.shape[tp_axis]
    b, seq_sharded, sp, _ = _mesh_layout(mesh, tp_axis)
    return shard_map(
        lambda x_, w_: _matmul_rs_body(x_, w_, tp_axis, p, bidir),
        mesh=mesh,
        in_specs=(P(b, sp, tp_axis), P(tp_axis, None)),
        out_specs=P(b, seq_sharded, None),
    )(x, w)


def _apply_ag_grad_w(x, dy, mesh, tp_axis, bidir):
    p = mesh.shape[tp_axis]
    b, seq_sharded, sp, batch_axes = _mesh_layout(mesh, tp_axis)
    return shard_map(
        lambda x_, dy_: _ag_grad_w_body(
            x_, dy_, tp_axis, p, bidir, batch_axes
        ),
        mesh=mesh,
        in_specs=(P(b, seq_sharded, None), P(b, sp, tp_axis)),
        out_specs=P(None, tp_axis),
    )(x, dy)


def _apply_rs_grad_w(x, dy, mesh, tp_axis, bidir):
    p = mesh.shape[tp_axis]
    b, seq_sharded, sp, batch_axes = _mesh_layout(mesh, tp_axis)
    return shard_map(
        lambda x_, dy_: _rs_grad_w_body(
            x_, dy_, tp_axis, p, bidir, batch_axes
        ),
        mesh=mesh,
        in_specs=(P(b, sp, tp_axis), P(b, seq_sharded, None)),
        out_specs=P(tp_axis, None),
    )(x, dy)


# one custom-VJP closure per (mesh, tp axis, schedule) — jitted callers
# retrace per closure identity, so repeated lookups must return the same
# object (the same reason comm/mesh.py memoises meshes)
_FN_CACHE: dict[tuple, jax.custom_vjp] = {}


def _make_ag_matmul(mesh: Mesh, tp_axis: str, bidir: bool):
    key = ("ag", mesh, tp_axis, bidir)
    fn = _FN_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.custom_vjp
    def ag_matmul(x, w):
        return _apply_ag(x, w, mesh, tp_axis, bidir)

    def fwd(x, w):
        return _apply_ag(x, w, mesh, tp_axis, bidir), (x, w)

    def bwd(res, dy):
        x, w = res
        # the cotangent of an all-gather-matmul is a matmul-reduce-scatter
        # of dy against w^T — the backward overlaps with the same ring
        dx = _apply_rs(dy, jnp.swapaxes(w, 0, 1), mesh, tp_axis, bidir)
        dw = _apply_ag_grad_w(x, dy, mesh, tp_axis, bidir)
        return dx, dw

    ag_matmul.defvjp(fwd, bwd)
    _FN_CACHE[key] = ag_matmul
    return ag_matmul


def _make_matmul_rs(mesh: Mesh, tp_axis: str, bidir: bool):
    key = ("rs", mesh, tp_axis, bidir)
    fn = _FN_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.custom_vjp
    def matmul_rs(x, w):
        return _apply_rs(x, w, mesh, tp_axis, bidir)

    def fwd(x, w):
        return _apply_rs(x, w, mesh, tp_axis, bidir), (x, w)

    def bwd(res, dy):
        x, w = res
        # mirror image: the cotangent of a matmul-reduce-scatter is an
        # all-gather-matmul of dy against w^T
        dx = _apply_ag(dy, jnp.swapaxes(w, 0, 1), mesh, tp_axis, bidir)
        dw = _apply_rs_grad_w(x, dy, mesh, tp_axis, bidir)
        return dx, dw

    matmul_rs.defvjp(fwd, bwd)
    _FN_CACHE[key] = matmul_rs
    return matmul_rs


def allgather_matmul(
    x: jax.Array,
    w: jax.Array,
    mesh: Mesh,
    tp_axis: str = "tp",
    schedule: str = "ring",
) -> jax.Array:
    """Column-parallel projection with the activation all-gather hidden
    behind per-shard partial matmuls.

    x: global ``[B, S, H]``, sequence-sharded over ``(sp?, tp)``;
    w: global ``[H, F]``, column-sharded over ``tp``.
    Returns ``[B, S, F]`` with the sequence gathered over ``tp`` (still
    sp-sharded if the mesh has sp) and features tp-sharded — the layout
    attention and elementwise ops consume directly.

    Differentiable via a custom VJP whose backward uses the mirrored
    overlapped schedules (see module docstring).
    """
    bidir = _check_schedule(schedule)
    _validate(x, w, mesh, tp_axis, col_parallel=True)
    return _make_ag_matmul(mesh, tp_axis, bidir)(x, w)


def matmul_reducescatter(
    x: jax.Array,
    w: jax.Array,
    mesh: Mesh,
    tp_axis: str = "tp",
    schedule: str = "ring",
) -> jax.Array:
    """Row-parallel projection with the partial-sum reduce-scatter hidden
    behind per-shard partial matmuls.

    x: global ``[B, S, F]``, features tp-sharded; w: global ``[F, H]``,
    row-sharded over ``tp``.  Returns ``[B, S, H]`` sequence-sharded over
    ``(sp?, tp)`` — the residual-stream layout of the overlapped block.
    """
    bidir = _check_schedule(schedule)
    _validate(x, w, mesh, tp_axis, col_parallel=False)
    return _make_matmul_rs(mesh, tp_axis, bidir)(x, w)


def activation_spec(mesh: Mesh, tp_axis: str = "tp") -> P:
    """PartitionSpec of the overlapped residual stream: batch over dp,
    sequence over (sp?, tp) — what ``forward`` constrains the scan carry
    to on an overlapped route."""
    b, seq_sharded, _, _ = _mesh_layout(mesh, tp_axis)
    return P(b, seq_sharded, None)
