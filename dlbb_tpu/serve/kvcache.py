"""Paged KV-cache: an explicit, mesh-sharded pytree + host block ledger.

Device side, the cache is a :class:`KVCache` NamedTuple (automatically a
JAX pytree) of fixed-shape arrays — jit-stable across the whole serving
run:

- ``k``/``v``: ``[L, max_batch, num_blocks, block_size, kv_heads,
  head_dim]`` — every layer, every decode *slot*, the slot's block ring.
  GQA-aware: K/V are stored at ``kv_heads`` width (never broadcast to
  ``num_heads``).  Sharded per the :class:`~dlbb_tpu.parallel.plan.
  ParallelismPlan`: the slot (batch) dim over ``dp``, the kv-head dim
  over ``tp`` — the same Megatron split the QKV projection produces, so
  cache writes and decode reads are shard-local and the audit's byte
  ceiling can prove no step ever re-gathers the cache
  (``docs/serving.md``).
- ``lengths``: ``[max_batch] int32``, tokens currently valid per slot —
  replicated (tiny; every shard needs it to build attention masks).

Writes touch only the rows they write (:func:`append_token_rows`,
:func:`write_slot_blocks`, :func:`copy_slot_blocks` — the one set of
helpers every cache-writing program calls).  The planes ride the
layer loop's carry, indexed by the layer number, and each write is a
scatter of ``[kvh, d]`` rows or one ``dynamic_update_slice`` of whole
blocks into that carry, which XLA updates in place.  Both stay on the
shard that owns the slot and add no collective (audited): the scatter
runs under ``shard_map`` over :func:`cache_specs`, and GSPMD
partitions a ``dynamic_update_slice`` at a sharded slot index into a
local write of the update or of what was there.  Until PR 26 the
writes were masked selects over the whole ``[slots, S_max, kvh, d]``
layer and the planes went through the layer ``lax.scan`` as
``xs``/``ys``; donation only aliases a program's argument with its
result, and the v5e trace showed the plane rewritten by the select (45%
of device time in ``serve7b_backlog``) and copied whole twice a program
run (28%) inside it (``PERF.md`` §5-6).

Host side, :class:`BlockLedger` does the alloc/free/append accounting
against a global block budget: admission *reserves* a request's
worst-case blocks (``ceil((prompt+output)/block_size)``) so a trace can
never OOM the cache mid-run (the build-time HBM gate is
``models.configs.validate_serving``), appends track blocks actually
holding tokens (the occupancy the report plots), and completion frees
both.  The ledger raising on over-use is a *bug* invariant, not a load
condition — reservation-based admission makes it unreachable.

Two capacity levers layer on top (``docs/serving.md``, "Prefix cache &
quantized KV"):

- **Shared-prefix blocks** (``serving.prefix_caching``): full prompt
  blocks are content-addressed by their token-id chain in a host-side
  :class:`PrefixTrie` inside the ledger.  A trie node is one *logical*
  block, charged ONCE against the pool no matter how many resident
  slots hold a physical copy; its refcount is the set of those slots,
  so a block is only returned to the pool when the last reader frees
  (`free` can never tear a live reader).  A request whose prompt
  matches an indexed chain attaches to the shared blocks and prefills
  only the suffix; the blocks past the attach point that the trie also
  matched are rewritten privately — the copy-on-write on first
  divergent append, counted in ``cow_blocks``.  Trie + refcounts
  snapshot/restore WITH the ledger, so a dispatch rollback can never
  double-free or leak a shared block.
- **int8 KV planes** (``serving.kv_quantization="int8"``):
  :class:`QuantKVCache` stores K/V as int8 blocks plus per-block
  per-kv-head fp32 scales as a side-channel plane (the symmetric-amax
  codec of ``comm/compression.py``), quartering the cache bytes the
  HBM admission gate prices — ``models.configs.
  kv_cache_bytes_per_device`` knows the layout, and the static memory
  audit's ``serving-cache-drift`` rule pins it to the compiled decode
  carry.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.compat import shard_map
from dlbb_tpu.models.configs import (
    FULL_ATTENTION,
    LATENT_ATTENTION,
    LINEAR_ATTENTION,
    MAMBA,
    ModelConfig,
    cache_kv_heads,
    kv_rows,
)
from dlbb_tpu.models.transformer import SERVE_PHASES, _dtype_of
from dlbb_tpu.ops.latent_attention import latent_spec


class KVCache(NamedTuple):
    """The device half of the paged cache (see module docstring)."""

    k: jax.Array        # [L, max_batch, num_blocks, block_size, kvh, d]
    v: jax.Array        # same
    lengths: jax.Array  # [max_batch] int32

    @property
    def max_batch(self) -> int:
        return self.k.shape[1]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[2]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_seq(self) -> int:
        return self.num_blocks * self.block_size


def cache_specs(mesh: Optional[Mesh]) -> KVCache:
    """PartitionSpecs matching :class:`KVCache`'s structure for ``mesh``:
    slot dim over ``dp``, kv-head dim over ``tp`` (each only when the
    mesh has that axis with size > 1); lengths replicated."""
    axes = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    dp = "dp" if "dp" in axes and mesh.shape["dp"] > 1 else None
    tp = "tp" if "tp" in axes and mesh.shape["tp"] > 1 else None
    kv_spec = P(None, dp, None, None, tp, None)
    return KVCache(k=kv_spec, v=kv_spec, lengths=P(None))


def cache_shardings(mesh: Mesh) -> KVCache:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), cache_specs(mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


def create_kv_cache(
    config: ModelConfig,
    max_batch: int,
    num_blocks: int,
    block_size: int,
    mesh: Optional[Mesh] = None,
) -> KVCache:
    """Zero-initialised cache, created *directly sharded* onto the mesh
    (jit with explicit out-shardings — same trick as
    ``init_params_sharded``: no device ever holds the replicated cache)."""
    dtype = _dtype_of(config.dtype)
    shape = (config.layers_of(FULL_ATTENTION), max_batch, num_blocks,
             block_size, config.kv_heads, config.head_dim)

    def build() -> KVCache:
        return KVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            lengths=jnp.zeros((max_batch,), jnp.int32),
        )

    if mesh is None:
        return build()
    return jax.jit(build, out_shardings=cache_shardings(mesh))()


KV_UPDATE = SERVE_PHASES[0]


@jax.named_scope(KV_UPDATE)
def append_token_rows(plane: jax.Array, rows: jax.Array, layer: jax.Array,
                      lengths: jax.Array, active: jax.Array,
                      mesh: Mesh) -> jax.Array:
    """The decode append: write ``rows[b, i]`` (``[B, G, kvh, d]``, G
    consecutive tokens a slot — 1 for a decode step, gamma+1 for a
    verify) into ``plane`` ``[L, B, nb, bs, kvh, d]`` at ``(layer, b,
    p // bs, p % bs)`` with ``p = lengths[b] + i``.  One scatter of
    B x G rows; an inactive slot's rows, and any position past the
    slot's last block, are given an out-of-range block index and
    dropped, so what is not written stays bit for bit as it was.

    The scatter runs under ``shard_map`` over the cache's own specs,
    each shard writing its own slots' rows at its own kv-heads: left to
    GSPMD, a scatter with a sharded slot dim all-gathers its indices
    and rows over ``dp`` first (two collectives a layer and plane)."""
    kv_spec = cache_specs(mesh).k
    dp, tp = kv_spec[1], kv_spec[4]

    def write(plane, rows, layer, lengths, active):
        b_dim, nb, bs = plane.shape[1:4]
        pos = lengths[:, None] + jnp.arange(rows.shape[1])[None, :]  # [B, G]
        blk = jnp.where(active[:, None], pos // bs, nb)
        slot = jnp.arange(b_dim)[:, None]
        return plane.at[layer, slot, blk, pos % bs].set(
            rows.astype(plane.dtype), mode="drop", unique_indices=True)

    return shard_map(
        write, mesh=mesh,
        in_specs=(kv_spec, P(dp, None, tp, None), P(), P(dp), P(dp)),
        out_specs=kv_spec,
    )(plane, rows, layer, lengths, active)


@jax.named_scope(KV_UPDATE)
def write_slot_blocks(plane: jax.Array, blocks: jax.Array, layer: jax.Array,
                      slot: jax.Array, start_blk: int = 0) -> jax.Array:
    """The prompt write: one ``dynamic_update_slice`` of a prefill
    bucket's (or chunk's) ``wb`` whole blocks into ``plane`` ``[L, B,
    nb, ...]`` at ``(layer, slot, start_blk)``.  ``blocks`` is ``[wb,
    ...]`` with the plane's trailing dims — ``[wb, bs, kvh, d]`` for a
    K/V plane, ``[wb, kvh]`` for the int8 layout's scale plane."""
    start = (layer, slot, start_blk) + (0,) * (plane.ndim - 3)
    return jax.lax.dynamic_update_slice(
        plane, blocks[None, None].astype(plane.dtype), start)


@jax.named_scope(KV_UPDATE)
def write_slot_planes(plane: jax.Array, blocks: jax.Array, slot: jax.Array,
                      start_blk: int = 0) -> jax.Array:
    """A prompt chunk's write into EVERY plane at once: ``blocks`` ``[L,
    wb, bs, kvh, d]``, each layer's ``wb`` whole blocks, into ``plane``
    ``[L, B, nb, ...]`` at ``(:, slot, start_blk)``: one
    ``dynamic_update_slice`` after the chunk's layer loops, in place in
    the donated plane.  (A plane that rode those loops' carry for a
    write a layer was re-laid out whole, and back, by the v5e compiler
    once the loops were nested, pass around layers: 3.75 GB of
    temporaries, found by compiling for the chip without it, PR 33.)"""
    start = (0, slot, start_blk) + (0,) * (plane.ndim - 3)
    return jax.lax.dynamic_update_slice(
        plane, blocks[:, None].astype(plane.dtype), start)


@jax.named_scope(KV_UPDATE)
def copy_slot_blocks(plane: jax.Array, src: jax.Array, dst: jax.Array,
                     num_blocks: int) -> tuple[jax.Array, jax.Array]:
    """The shared-prefix attach: copy slot ``src``'s first
    ``num_blocks`` blocks of every layer into slot ``dst`` (any plane
    ``[L, B, nb, ...]``) — one slice read and one
    ``dynamic_update_slice``.  Returns the plane and the copied blocks
    ``[L, num_blocks, ...]``, read back from ``dst``: the caller wants
    them as the chunk-prefill carry, and a second read of the plane it
    came in as, beside the write, made XLA:CPU copy the whole plane
    twice (the simulated-mesh memory audit counts it)."""
    zeros = (0,) * (plane.ndim - 2)
    size = (plane.shape[0], 1, num_blocks) + plane.shape[3:]
    donor = jax.lax.dynamic_slice(plane, (0, src) + zeros, size)
    plane = jax.lax.dynamic_update_slice(plane, donor, (0, dst) + zeros)
    return plane, jax.lax.dynamic_slice(plane, (0, dst) + zeros, size)[:, 0]


# ---------------------------------------------------------------------------
# several kinds of state in one cache (``ModelConfig.layer_types``)
# ---------------------------------------------------------------------------


class HybridCache(NamedTuple):
    """The cache of a ``layer_types`` model: every kind of layer keeps
    its own planes, and a kind the model has no layer of keeps planes of
    no layers (zero bytes).  The ``latent_attention`` layers keep ONE
    paged plane ``latent``: per layer, slot and token one row ``[c',
    rope(k_rope), zeros]`` (``ModelConfig.latent_row`` values, whole
    lanes), in the same blocks of the same :class:`BlockLedger` as K/V
    (a block is ``block_size`` tokens of a slot, whatever a token keeps).
    The ``full_attention`` layers keep paged K/V planes exactly as
    :class:`KVCache` does, but only for themselves (``L_full`` of the
    layers), and in a looped stack (``total_ut_steps`` passes over the
    same layers) a plane for every (pass, layer), pass-major: a token's
    pass ``t`` attends to what pass ``t`` of the earlier tokens wrote.
    Where a head is narrower than the TPU's 128 lanes and a token's
    heads together are whole lanes (``models.configs.kv_rows``) the K/V
    planes hold that ROW, ``[planes, max_batch, num_blocks, block_size,
    kvh x d]``: head by head each head would take a whole lane-row's
    room.  The RECURRENT layers (``linear_attention``, or the
    state-space ``mamba``: a model has one of the two kinds) keep, per
    layer and SLOT,
    a float32 recurrent state and the last ``conv_kernel - 1`` inputs of
    their short convolution: not paged and not growing with the slot's
    length, so the :class:`BlockLedger` never counts them (its blocks
    are K/V blocks of the full layers) and the build-time gate prices
    them apart (``models.configs.state_cache_bytes``).  ONE pair of
    planes serves either kind, by shape: the state is ``[heads, values,
    keys]`` of a head for both (``[heads, d_head, d_state]`` of a
    state-space layer); the state-space layers' convolution runs over x,
    B and C together, which no head owns, so their inputs lie flat,
    ``[L, max_batch, (d_conv - 1) x channels]`` (a decode step shifts
    whole lanes; ``[.., d_conv - 1, channels]`` would pad three rows to a
    tile of eight or sixteen).

    A slot's state is valid from the prefill that claimed the slot: the
    first prompt chunk starts from a ZERO state
    (``serve/hybrid.py::create_prefix``) and every chunk overwrites the
    slot's state with what it carried, so nothing of the slot's previous
    request survives."""

    # kvh: ``models.configs.cache_kv_heads`` (whole tiles of 8 heads)
    # planes: ``ModelConfig.kv_planes`` = passes x L_full
    k: jax.Array        # [planes, max_batch, num_blocks, block_size, kvh, d]
    v: jax.Array        # same
    # L_rec: the linear-attention or the state-space layers
    state: jax.Array    # f32 [L_rec, max_batch, heads, d_v, d_k]
    # [L_lin, max_batch, conv_kernel - 1, heads, 2 d_k + d_v], or
    # [L_ssm, max_batch, (d_conv - 1) x channels]
    conv: jax.Array
    latent: jax.Array   # [L_lat, max_batch, num_blocks, block_size, row]
    lengths: jax.Array  # [max_batch] int32

    @property
    def max_batch(self) -> int:
        return self.k.shape[1]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[2]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_seq(self) -> int:
        return self.num_blocks * self.block_size


def hybrid_cache_specs(mesh: Optional[Mesh],
                       config: Optional[ModelConfig] = None) -> HybridCache:
    """PartitionSpecs for :class:`HybridCache`: K/V as
    :func:`cache_specs`; state and convolution inputs with their slots
    over ``dp`` and their heads over ``tp``.  ``config`` says which
    planes have another rank than those (K/V of whole rows; a
    state-space model's flat convolution inputs): slots over ``dp`` and
    nothing over ``tp``, which serving refuses for both."""
    kv = cache_specs(mesh)
    dp, tp = kv.k[1], kv.k[4]
    k_spec, conv_spec = kv.k, P(None, dp, None, tp, None)
    if config is not None and kv_rows(config, mesh.shape.get("tp", 1)
                                      if mesh is not None else 1):
        k_spec = P(None, dp, None, None, None)
    if config is not None and config.layers_of(MAMBA):
        conv_spec = P(None, dp, None)
    return HybridCache(k=k_spec, v=k_spec,
                       state=P(None, dp, tp, None, None),
                       conv=conv_spec,
                       latent=latent_spec(mesh),
                       lengths=kv.lengths)


def hybrid_cache_shardings(mesh: Mesh,
                           config: Optional[ModelConfig] = None
                           ) -> HybridCache:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), hybrid_cache_specs(mesh, config),
        is_leaf=lambda x: isinstance(x, P),
    )


def create_hybrid_cache(config: ModelConfig, max_batch: int,
                        num_blocks: int, block_size: int,
                        mesh: Optional[Mesh] = None,
                        state_dtype=jnp.float32) -> HybridCache:
    """Zero-initialised, created directly on its shards."""
    dtype = _dtype_of(config.dtype)
    n_lin = config.layers_of(LINEAR_ATTENTION)
    heads = config.linear_num_value_heads
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    kv_shape = (config.kv_planes, max_batch, num_blocks, block_size)
    if kv_rows(config, tp):
        kv_shape += (config.kv_heads * config.head_dim,)
    else:
        kv_shape += (cache_kv_heads(config, tp), config.head_dim)
    n_ssm = config.layers_of(MAMBA)
    if n_ssm:
        state_shape = (n_ssm, max_batch, config.mamba_n_heads,
                       config.mamba_d_head, config.mamba_d_state)
        conv_shape = (n_ssm, max_batch, (config.mamba_d_conv - 1)
                      * config.mamba_conv_channels)
    else:
        state_shape = (n_lin, max_batch, heads,
                       config.linear_value_head_dim,
                       config.linear_key_head_dim)
        conv_shape = (n_lin, max_batch,
                      max(config.linear_conv_kernel_dim - 1, 0), heads,
                      config.linear_conv_channels // max(heads, 1))
    latent_shape = (config.layers_of(LATENT_ATTENTION), max_batch,
                    num_blocks, block_size, config.latent_row)

    def build() -> HybridCache:
        return HybridCache(
            k=jnp.zeros(kv_shape, dtype), v=jnp.zeros(kv_shape, dtype),
            state=jnp.zeros(state_shape, state_dtype),
            conv=jnp.zeros(conv_shape, dtype),
            latent=jnp.zeros(latent_shape, dtype),
            lengths=jnp.zeros((max_batch,), jnp.int32),
        )

    if mesh is None:
        return build()
    return jax.jit(build,
                   out_shardings=hybrid_cache_shardings(mesh, config))()


def append_latent_rows(plane: jax.Array, rows: jax.Array, layer: jax.Array,
                       lengths: jax.Array, active: jax.Array,
                       mesh: Mesh) -> jax.Array:
    """The decode append of the latent plane: ``rows[b]`` ``[B, row]``
    into ``plane`` ``[L, B, nb, bs, row]`` at ``(layer, b, p // bs, p %
    bs)`` with ``p = lengths[b]``: :func:`append_token_rows` for one row
    a token (an inactive slot's row, and a position past the slot's last
    block, are dropped; each ``dp`` shard writes its own slots)."""
    spec = hybrid_cache_specs(mesh).latent
    dp = spec[1]

    def write(plane, rows, layer, lengths, active):
        b_dim, nb, bs = plane.shape[1:4]
        blk = jnp.where(active, lengths // bs, nb)
        return plane.at[layer, jnp.arange(b_dim), blk, lengths % bs].set(
            rows.astype(plane.dtype), mode="drop", unique_indices=True)

    return shard_map(
        write, mesh=mesh,
        in_specs=(spec, P(dp, None), P(), P(dp), P(dp)),
        out_specs=spec,
    )(plane, rows, layer, lengths, active)


def write_slot_state(plane: jax.Array, value: jax.Array, layer: jax.Array,
                     slot: jax.Array) -> jax.Array:
    """One slot's state (or convolution inputs) of one layer into a
    plane ``[L, B, ...]``: a ``dynamic_update_slice`` at ``(layer,
    slot)``, in place in a carried plane, as :func:`write_slot_blocks`
    writes a slot's K/V blocks."""
    start = (layer, slot) + (0,) * (plane.ndim - 2)
    return jax.lax.dynamic_update_slice(
        plane, value[None, None].astype(plane.dtype), start)


# ---------------------------------------------------------------------------
# int8-quantized cache plane (serving.kv_quantization="int8")
# ---------------------------------------------------------------------------

KV_QMAX = 127.0  # symmetric int8, same codec as comm/compression.py


class QuantKVCache(NamedTuple):
    """The int8 variant of :class:`KVCache`: K/V blocks stored as int8
    with per-block per-kv-head fp32 scales as a side-channel plane.
    Scales shard exactly like the data they scale (slot dim over dp,
    kv-head dim over tp), so dequantisation inside the decode step is an
    elementwise broadcast — shard-local, zero collectives."""

    k: jax.Array         # int8 [L, max_batch, num_blocks, block_size, kvh, d]
    v: jax.Array         # same
    k_scale: jax.Array   # f32  [L, max_batch, num_blocks, kvh]
    v_scale: jax.Array   # same
    lengths: jax.Array   # [max_batch] int32

    @property
    def max_batch(self) -> int:
        return self.k.shape[1]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[2]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_seq(self) -> int:
        return self.num_blocks * self.block_size


def quant_cache_specs(mesh: Optional[Mesh]) -> QuantKVCache:
    """PartitionSpecs for :class:`QuantKVCache`: data like
    :func:`cache_specs`, scales dropping the in-block dims."""
    axes = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    dp = "dp" if "dp" in axes and mesh.shape["dp"] > 1 else None
    tp = "tp" if "tp" in axes and mesh.shape["tp"] > 1 else None
    kv_spec = P(None, dp, None, None, tp, None)
    sc_spec = P(None, dp, None, tp)
    return QuantKVCache(k=kv_spec, v=kv_spec, k_scale=sc_spec,
                        v_scale=sc_spec, lengths=P(None))


def quant_cache_shardings(mesh: Mesh) -> QuantKVCache:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), quant_cache_specs(mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


def create_quant_kv_cache(
    config: ModelConfig,
    max_batch: int,
    num_blocks: int,
    block_size: int,
    mesh: Optional[Mesh] = None,
) -> QuantKVCache:
    """Zero-initialised int8 cache (scales start at 1.0 so an untouched
    block dequantises to exact zeros), created directly sharded."""
    shape = (config.num_layers, max_batch, num_blocks, block_size,
             config.kv_heads, config.head_dim)
    sc_shape = (config.num_layers, max_batch, num_blocks, config.kv_heads)

    def build() -> QuantKVCache:
        return QuantKVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.ones(sc_shape, jnp.float32),
            v_scale=jnp.ones(sc_shape, jnp.float32),
            lengths=jnp.zeros((max_batch,), jnp.int32),
        )

    if mesh is None:
        return build()
    return jax.jit(build, out_shardings=quant_cache_shardings(mesh))()


def quantize_kv_blocks(blocks: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 over paged K/V blocks ``[..., block_size, kvh,
    head_dim]`` with one fp32 scale per (block, kv-head): ``scale =
    amax / 127`` guarded to 1.0 on all-zero blocks (the
    ``comm/compression.py`` idiom).  Returns ``(int8 blocks, f32 scales
    [..., kvh])``.  The round-trip is bit-stable: requantising a
    dequantised block reproduces the int8 codes exactly (|q·s/s − q| <
    2⁻²²·127 ≪ 0.5), so rewriting a whole cache layer never drifts the
    blocks that were not touched."""
    a = jnp.max(jnp.abs(blocks.astype(jnp.float32)), axis=(-3, -1))
    s = jnp.where(a > 0.0, a / KV_QMAX, 1.0)
    q = jnp.clip(jnp.round(blocks.astype(jnp.float32) / s[..., None, :, None]),
                 -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), s.astype(jnp.float32)


def dequantize_kv_blocks(q: jax.Array, scales: jax.Array,
                         dtype: jnp.dtype) -> jax.Array:
    """Inverse of :func:`quantize_kv_blocks` (broadcast multiply —
    elementwise, shard-local under the cache sharding contract)."""
    return (q.astype(jnp.float32) * scales[..., None, :, None]).astype(dtype)


class CacheOverflow(RuntimeError):
    """A slot used more blocks than were reserved for it — an engine bug
    (reservation-based admission makes this unreachable under load)."""


class PrefixTrie:
    """Host-side radix index over full-block token-id chains.

    One node per *logical* full block, keyed by the tuple of token ids
    it holds under its parent chain — content-addressing, so identical
    prompts dedupe even across trace groups.  A node's refcount is the
    set of slots physically holding that block content; a slot always
    holds a contiguous prefix of its chain starting at the root, so the
    refs at any matched node are valid donors for the WHOLE path above
    it (child refs ⊆ parent refs), and a node with an empty refcount
    has no live reader and is pruned.  Entirely host-side dict walking
    — the device programs never see it (``host-transfer-in-loop``
    stays clean)."""

    def __init__(self) -> None:
        # (parent_node, block token tuple) -> node id; root is node 0
        self._children: dict[tuple[int, tuple[int, ...]], int] = {}
        self._parent: dict[int, tuple[int, tuple[int, ...]]] = {}
        self._refs: dict[int, set[int]] = {}        # node -> holder slots
        self._slot_nodes: dict[int, list[int]] = {}  # slot -> chain nodes
        self._next_id = 1

    @property
    def num_nodes(self) -> int:
        """Logical shared blocks currently indexed (the pool charge)."""
        return len(self._refs)

    def total_refs(self) -> int:
        return sum(len(r) for r in self._refs.values())

    def shared_depth(self, slot: int) -> int:
        return len(self._slot_nodes.get(slot, ()))

    def match(self, chain: list[tuple[int, ...]]) -> tuple[int, Optional[int]]:
        """Longest indexed prefix of ``chain``: returns ``(blocks
        matched, donor slot)`` — the donor (lowest resident slot id, for
        determinism) physically holds every matched block."""
        node, depth, donors = 0, 0, None
        for key in chain:
            child = self._children.get((node, tuple(key)))
            if child is None:
                break
            node, depth, donors = child, depth + 1, self._refs[child]
        if depth == 0 or not donors:
            return 0, None
        return depth, min(donors)

    def attach(self, slot: int, chain: list[tuple[int, ...]],
               depth: int) -> None:
        """Record ``slot`` as a resident holder of the first ``depth``
        blocks of ``chain`` (which must already be indexed — callers
        attach only what :meth:`match` returned)."""
        if slot in self._slot_nodes:
            raise CacheOverflow(f"slot {slot} already holds a chain")
        node, nodes = 0, []
        for key in chain[:depth]:
            node = self._children[(node, tuple(key))]
            self._refs[node].add(slot)
            nodes.append(node)
        self._slot_nodes[slot] = nodes

    def extend(self, slot: int, chain: list[tuple[int, ...]]) -> tuple[int, int]:
        """Index ``slot``'s full chain past what it already holds,
        creating nodes as needed.  Returns ``(created, newly_ref)``:
        ``created`` nodes are new logical pool blocks; ``newly_ref``
        counts every block that moved from the slot's private
        reservation into shared accounting (``created`` ⊆ it — an
        existing node newly ref'd is a dedupe, freeing one block of
        budget)."""
        nodes = self._slot_nodes.setdefault(slot, [])
        node = nodes[-1] if nodes else 0
        created = newly = 0
        for key in chain[len(nodes):]:
            key = tuple(key)
            child = self._children.get((node, key))
            if child is None:
                child = self._next_id
                self._next_id += 1
                self._children[(node, key)] = child
                self._parent[child] = (node, key)
                self._refs[child] = set()
                created += 1
            if slot not in self._refs[child]:
                self._refs[child].add(slot)
                newly += 1
            nodes.append(child)
            node = child
        return created, newly

    def release(self, slot: int) -> int:
        """Drop ``slot``'s residency; prune (deepest-first) every node
        no live slot still holds.  Returns the pruned count — the
        logical blocks actually returned to the pool; blocks other
        slots still read stay charged, so eviction never tears a live
        reader."""
        pruned = 0
        for node in reversed(self._slot_nodes.pop(slot, [])):
            refs = self._refs.get(node)
            if refs is None:
                continue
            refs.discard(slot)
            if not refs:
                parent, key = self._parent.pop(node)
                del self._children[(parent, key)]
                del self._refs[node]
                pruned += 1
        return pruned

    def snapshot(self) -> dict:
        return {
            "children": dict(self._children),
            "parent": dict(self._parent),
            "refs": {n: set(r) for n, r in self._refs.items()},
            "slot_nodes": {s: list(n)
                           for s, n in self._slot_nodes.items()},
            "next_id": self._next_id,
        }

    def restore(self, snap: dict) -> None:
        self._children = dict(snap["children"])
        self._parent = dict(snap["parent"])
        self._refs = {n: set(r) for n, r in snap["refs"].items()}
        self._slot_nodes = {s: list(n)
                            for s, n in snap["slot_nodes"].items()}
        self._next_id = snap["next_id"]


class BlockLedger:
    """Host-side alloc/free/append accounting for the block pool.

    ``total_blocks`` is the global budget (defaults to the physical pool,
    ``max_batch * num_blocks``; configurable lower to model cache
    pressure).  Reservation is all-or-nothing per request; ``append``
    moves a block from reserved to in-use when a token crosses a block
    boundary; ``free`` returns everything.

    With ``prefix_caching`` the ledger carries a :class:`PrefixTrie`:
    every trie node is a logical block charged ONCE to the pool
    (``blocks_reserved`` = private reservations + trie nodes), a slot's
    private reservation shrinks by the blocks it shares, and ``free``
    returns a shared block only when the trie prunes it (refcount hit
    zero)."""

    def __init__(self, total_blocks: int, block_size: int,
                 prefix_caching: bool = False) -> None:
        if total_blocks < 1 or block_size < 1:
            raise ValueError(
                f"ledger needs positive sizes (total_blocks="
                f"{total_blocks}, block_size={block_size})"
            )
        self.total_blocks = total_blocks
        self.block_size = block_size
        self._reserved: dict[int, int] = {}   # slot -> PRIVATE blocks
        self._tokens: dict[int, int] = {}     # slot -> tokens appended
        self._shared: dict[int, int] = {}     # slot -> shared blocks held
        self.trie: Optional[PrefixTrie] = (
            PrefixTrie() if prefix_caching else None)
        self.cow_blocks = 0   # copy-on-write rewrites (monotone)
        self.peak_reserved = 0
        self.peak_in_use = 0
        self.peak_shared = 0

    def blocks_for(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.block_size))

    @property
    def shared_blocks(self) -> int:
        """Logical blocks in the shared pool (one per trie node)."""
        return self.trie.num_nodes if self.trie is not None else 0

    @property
    def blocks_reserved(self) -> int:
        return sum(self._reserved.values()) + self.shared_blocks

    @property
    def blocks_in_use(self) -> int:
        private = sum(
            max(0, self.blocks_for(t) - self._shared.get(s, 0)) if t else 0
            for s, t in self._tokens.items())
        return private + self.shared_blocks

    @property
    def blocks_free(self) -> int:
        return self.total_blocks - self.blocks_reserved

    def can_reserve(self, total_tokens: int,
                    shared_blocks: int = 0) -> bool:
        need = max(0, self.blocks_for(total_tokens) - shared_blocks)
        return need <= self.blocks_free

    def match_prefix(self, chain: list[tuple[int, ...]]
                     ) -> tuple[int, Optional[int]]:
        """Longest indexed block-chain prefix → ``(blocks, donor slot)``
        (``(0, None)`` when prefix caching is off or nothing matches)."""
        if self.trie is None or not chain:
            return 0, None
        return self.trie.match(chain)

    def reserve(self, slot: int, total_tokens: int,
                chain: Optional[list[tuple[int, ...]]] = None,
                attach_blocks: int = 0) -> int:
        """Reserve a request's worst-case blocks for ``slot``; returns
        the PRIVATE count.  With ``attach_blocks`` > 0 the slot also
        becomes a refcounted holder of the first ``attach_blocks``
        blocks of ``chain`` (already charged to the shared pool), so
        only the remainder is drawn from the free budget.  Raises when
        the slot is already occupied or the budget cannot cover it
        (callers gate on :meth:`can_reserve`)."""
        if slot in self._reserved:
            raise CacheOverflow(f"slot {slot} already holds a reservation")
        if attach_blocks and self.trie is None:
            raise CacheOverflow("attach requires prefix_caching")
        need = max(0, self.blocks_for(total_tokens) - attach_blocks)
        if need > self.blocks_free:
            raise CacheOverflow(
                f"cannot reserve {need} blocks for slot {slot}: only "
                f"{self.blocks_free}/{self.total_blocks} free"
            )
        if attach_blocks:
            self.trie.attach(slot, chain, attach_blocks)
        self._reserved[slot] = need
        self._tokens[slot] = 0
        self._shared[slot] = attach_blocks
        self.peak_reserved = max(self.peak_reserved, self.blocks_reserved)
        self.peak_shared = max(self.peak_shared, self.shared_blocks)
        return need

    def register(self, slot: int, chain: list[tuple[int, ...]]) -> int:
        """Index ``slot``'s full prompt block-chain in the trie (after
        its prefill completed, so the slot physically holds every
        block).  Blocks newly shared move from the slot's private
        reservation into the pool charge; an already-indexed block this
        slot now also holds is a dedupe that *frees* budget.  Returns
        the number of blocks that moved to shared accounting."""
        if self.trie is None or not chain:
            return 0
        if slot not in self._reserved:
            raise CacheOverflow(f"register of unreserved slot {slot}")
        _, newly = self.trie.extend(slot, chain)
        if newly > self._reserved[slot]:
            raise CacheOverflow(
                f"slot {slot} shared {newly} blocks beyond its private "
                f"reservation of {self._reserved[slot]}"
            )
        self._reserved[slot] -= newly
        self._shared[slot] = self._shared.get(slot, 0) + newly
        self.peak_shared = max(self.peak_shared, self.shared_blocks)
        return newly

    def note_cow(self, blocks: int) -> None:
        """Count copy-on-write block rewrites (the trie matched deeper
        than the request could attach, so the divergent tail is
        recomputed into private blocks).  Monotone, like the peaks."""
        self.cow_blocks += blocks

    def append(self, slot: int, tokens: int = 1) -> None:
        """Account ``tokens`` written into ``slot`` (prefill passes the
        prompt length, decode passes 1)."""
        if slot not in self._reserved:
            raise CacheOverflow(f"append to unreserved slot {slot}")
        self._tokens[slot] += tokens
        entitled = self._reserved[slot] + self._shared.get(slot, 0)
        if self.blocks_for(self._tokens[slot]) > entitled:
            raise CacheOverflow(
                f"slot {slot} outgrew its reservation "
                f"({self._tokens[slot]} tokens > "
                f"{entitled} blocks x {self.block_size})"
            )
        self.peak_in_use = max(self.peak_in_use, self.blocks_in_use)

    def tokens(self, slot: int) -> int:
        """Tokens appended to ``slot`` so far: the slot's length on the
        device before the next decode step."""
        return self._tokens[slot]

    def free(self, slot: int) -> int:
        """Release a slot's reservation; returns the blocks actually
        returned to the pool: its private blocks plus every shared
        block whose refcount dropped to zero (blocks other live slots
        still read stay charged — no torn readers, no double-free)."""
        if slot not in self._reserved:
            raise CacheOverflow(f"free of unreserved slot {slot}")
        blocks = self._reserved.pop(slot)
        self._tokens.pop(slot)
        self._shared.pop(slot, None)
        if self.trie is not None:
            blocks += self.trie.release(slot)
        return blocks

    def snapshot(self) -> dict:
        """Copy of the alloc/append accounting — the serving engine's
        pre-dispatch rollback point (``docs/resilience.md``): a failed
        or torn decode unit restores this before re-issuing.  Includes
        the trie + refcounts, so a retry can never double-free or leak
        a shared block."""
        return {"reserved": dict(self._reserved),
                "tokens": dict(self._tokens),
                "shared": dict(self._shared),
                "trie": (self.trie.snapshot()
                         if self.trie is not None else None)}

    def restore(self, snap: dict) -> None:
        """Roll the accounting back to a :meth:`snapshot`.  The peak
        counters deliberately stay monotone (a rolled-back peak was
        still a real high-water mark of host bookkeeping)."""
        self._reserved.clear()
        self._reserved.update(snap["reserved"])
        self._tokens.clear()
        self._tokens.update(snap["tokens"])
        self._shared.clear()
        self._shared.update(snap.get("shared", {}))
        if self.trie is not None and snap.get("trie") is not None:
            self.trie.restore(snap["trie"])

    def stats(self) -> dict[str, int]:
        return {
            "total_blocks": self.total_blocks,
            "blocks_reserved": self.blocks_reserved,
            "blocks_in_use": self.blocks_in_use,
            "peak_blocks_reserved": self.peak_reserved,
            "peak_blocks_in_use": self.peak_in_use,
            "shared_blocks": self.shared_blocks,
            "peak_shared_blocks": self.peak_shared,
            "prefix_refs": (self.trie.total_refs()
                            if self.trie is not None else 0),
            "cow_blocks": self.cow_blocks,
        }
