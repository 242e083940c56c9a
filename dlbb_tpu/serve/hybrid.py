"""The serving programs of a ``layer_types`` model (``models/hybrid.py``),
one of the two block families the scheduler of ``serve/engine.py`` serves:
the same three kinds of program as ``serve/gpt.py`` holds for the GPT
block, under the same names, over a :class:`~dlbb_tpu.serve.kvcache.
HybridCache`.

- ``serve_prefill_chunk_o<offset>``: one prompt chunk of token ids.  The
  full-attention layers do what the GPT chunk does (offset-causal
  attention over the carried prefix K/V; the chunk's K/V of every layer
  written as whole blocks in ONE update after the layer loops,
  ``write_slot_planes``); the linear-attention layers run the chunked
  gated delta rule from the state the previous chunk handed on and write
  the slot's state and convolution inputs, and the state-space
  (``mamba``) layers do the same with the chunked scan of
  ``ops/ssd.py``; the latent-attention layers
  expand per-head keys and values from the carried prefix of latent rows
  and the chunk's own (the EXPANDED form: 2.4 x fewer operations than
  the absorbed one over a chunk) and write the chunk's rows as whole
  blocks.  The carried ``prefix`` is ``(k, v, state, conv, latent)``, K
  and V of a looped stack for every (pass, layer) as its planes are; the
  one a prompt starts from is all zeros (:func:`create_prefix`), which
  is what clears a recycled slot.  The window (``sliding_attention``)
  layers carry no prefix: a chunk attends to the slot's RING (what the
  earlier chunks wrote of the last ``sliding_window`` positions) and to
  its own keys, and writes its real tokens into the ring after the
  layer loops (``write_ring_blocks``).
- ``serve_decode_step`` / ``serve_decode_k<K>``: embed each slot's
  pending token, one recurrent step (state, K/V and latent rows updated
  in place in the carried planes; latent attention in its ABSORBED form,
  ``ops/latent_attention.py``), logits, greedy ``argmax`` fed back on
  the device.
- ``serve_inject``: a finished prefill's first token into its slot.
- ``serve_probe_state``: a copy of one slot's recurrent state (or, where
  a model has none, of its rows of the first K plane), for a probed
  request only (twice in its life).

The decode carry is ``(cache, tokens [max_batch] int32)``.  Every decode
program also returns the float32 logits of the two slots named by its
``probe`` argument, each step: what a checker (``ServingEngine.probe``)
holds on the device to compare with a reference.  The programs are the
same whether or not anything is probed.  A model with routed experts
returns beside them the experts those slots chose in every expert layer
and the gates it gave them (``seen = (logits, experts, gates)``; with
window layers also the FIRST window layer's attention output there,
``window_attn``, what a checker holds the window's edge by) and
three small integers a step
(``counts``: assignments, experts that got a token, the fullest expert's
tokens, over the expert layers), and its chunk program the same for the
prompt's last position and the chunk's real tokens (``last = (logits,
experts, gates, counts)``).  A looped stack (``total_ut_steps`` > 1)
returns beside the logits the exit gate of every pass at those
positions (``seen = (logits, exit gates [..., passes])``, ``last =
(logits, exit gates [passes], counts)``) and as ``counts`` the passes
through the stack the step or chunk ran.  A model with state-space
layers returns from its chunk program ``last = (logits, [real tokens,
rows])``: what the chunk's scan was asked and what it was padded to.

The block and the period are ``models/hybrid.py``'s; this file holds the
three mixers that touch the cache, and at its end what the scheduler
asks of a family (the seam: ``docs/serving.md``, "Adding a block
family").
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.data.synthetic import prompt_ids_from_seed
from dlbb_tpu.models import hybrid
from dlbb_tpu.models.configs import (
    FULL_ATTENTION,
    LATENT_ATTENTION,
    LINEAR_ATTENTION,
    MAMBA,
    SLIDING_ATTENTION,
    ModelConfig,
    cache_kv_heads,
    kv_cache_bytes,
    kv_cache_bytes_raw,
    kv_rows,
    latent_cache_bytes,
    state_cache_bytes,
    window_cache_bytes,
)
from dlbb_tpu.models.hybrid import (
    LIN_CONV,
    LIN_CORE,
    MLA_KV_B,
    SSM_CONV,
    SSM_CORE,
    WIN_ATTEND,
    WIN_UPDATE,
)
from dlbb_tpu.models.transformer import _dtype_of, named
from dlbb_tpu.ops import decode_attention as kv_kernel
from dlbb_tpu.ops import latent_attention as latent_kernel
from dlbb_tpu.ops import routed_experts, state_plane
from dlbb_tpu.ops.decode_attention import decode_attention
from dlbb_tpu.ops.latent_attention import (
    LATENT_ATTEND,
    LATENT_UPDATE,
    latent_decode_attention,
)
from dlbb_tpu.ops.gated_delta import (
    causal_conv,
    gated_delta_chunked,
    gated_delta_step,
)
from dlbb_tpu.ops.ssd import ssd_chunked, ssd_plane_step
from dlbb_tpu.obs import spans
from dlbb_tpu.serve.attend import (
    KV_UPDATE,
    _chunk_attention,
    _layer_of,
    chunk_attention,
    chunk_on_kernel,
)
from dlbb_tpu.serve.kvcache import (
    HybridCache,
    append_latent_rows,
    append_ring_rows,
    append_token_rows,
    create_hybrid_cache,
    hybrid_cache_shardings,
    hybrid_cache_specs,
    ring_positions,
    write_ring_blocks,
    write_slot_blocks,
    write_slot_planes,
    write_slot_state,
)
from dlbb_tpu.serve.traffic import Request


def token_spec(mesh: Mesh) -> P:
    """Decode tokens ``[max_batch]``: slots over dp."""
    return P(hybrid_cache_specs(mesh).k[1])


def prefix_specs(mesh: Mesh, config: ModelConfig
                 ) -> tuple[P, P, P, P, P]:
    """The chunk carry ``(k, v, state, conv[, latent])``: no slot dim,
    heads over tp (``gpt.prefix_spec`` for K/V).  The carry has its
    fifth part only for a model with latent-attention layers
    (:func:`create_prefix`).  A state-space model's convolution inputs
    ``[L, d_conv - 1, channels]`` have no head dim."""
    tp = hybrid_cache_specs(mesh).k[4]
    kv = P(None, None, tp, None)
    conv = (P(None, None, None) if config.layers_of(MAMBA)
            else P(None, None, tp, None))
    return kv, kv, P(None, tp, None, None), conv, P(None, None, None)


def create_prefix(config: ModelConfig, mesh: Mesh) -> tuple:
    """The carry a prompt's first chunk starts from: no prefix K/V, a
    ZERO recurrent state and zeros before the convolution, ``(k, v,
    state, conv)``; with latent-attention layers a fifth part, no prefix
    rows."""
    dtype = _dtype_of(config.dtype)
    n_lin = config.layers_of(LINEAR_ATTENTION)
    n_ssm = config.layers_of(MAMBA)
    heads = config.linear_num_value_heads
    kv = jnp.zeros((config.kv_planes, 0, config.kv_heads, config.head_dim),
                   dtype)
    if n_ssm:
        state = jnp.zeros((n_ssm, config.mamba_n_heads, config.mamba_d_head,
                           config.mamba_d_state), hybrid.STATE_DTYPE)
        conv = jnp.zeros((n_ssm, config.mamba_d_conv - 1,
                          config.mamba_conv_channels), dtype)
    else:
        state = jnp.zeros((n_lin, heads, config.linear_value_head_dim,
                           config.linear_key_head_dim), hybrid.STATE_DTYPE)
        conv = jnp.zeros((n_lin, max(config.linear_conv_kernel_dim - 1, 0),
                          heads,
                          config.linear_conv_channels // max(heads, 1)),
                         dtype)
    parts = (kv, kv, state, conv)
    if config.layers_of(LATENT_ATTENTION):
        parts += (jnp.zeros((config.layers_of(LATENT_ATTENTION), 0,
                             config.latent_row), dtype),)
    return tuple(jax.device_put(t, NamedSharding(mesh, s))
                 for t, s in zip(parts, prefix_specs(mesh, config)))


def _pad_heads(t: jax.Array, heads: int) -> jax.Array:
    """``[..., kvh, d]`` with zero heads added up to the ``heads`` a
    cache plane holds (``models.configs.cache_kv_heads``)."""
    extra = heads - t.shape[-2]
    if not extra:
        return t
    return jnp.pad(t, [(0, 0)] * (t.ndim - 2) + [(0, extra), (0, 0)])


def _as_blocks(own: jax.Array, plane: jax.Array) -> jax.Array:
    """A chunk's own K (or V) of every plane ``[L, C, kvh, d]`` as the
    whole blocks ``plane`` holds: ``[L, C / bs, bs, kvh', d]`` with zero
    heads added, or ``[L, C / bs, bs, kvh x d]`` where it holds whole
    rows."""
    blocks = (own.shape[0], own.shape[1] // plane.shape[3]) + plane.shape[3:]
    if plane.ndim == 6:
        own = _pad_heads(own, plane.shape[-2])
    return own.reshape(blocks)


def _conv_flat(ext: jax.Array, weight: jax.Array) -> jax.Array:
    """``ops.gated_delta.causal_conv`` of ONE position over inputs that
    lie flat: ``ext`` ``[B, K x channels]``, position by position, the
    current one last; ``weight`` ``[K, channels]``.  Returns ``[B,
    channels]`` float32.  Flat, because that is how the cache holds a
    state-space layer's inputs (``HybridCache``): the shift by one
    position is then a slice of whole lanes."""
    k, channels = weight.shape
    w32 = weight.astype(jnp.float32)
    return sum(ext[:, i * channels:(i + 1) * channels].astype(jnp.float32)
               * w32[i] for i in range(k))


def _cache_rows(c: jax.Array, k_rope: jax.Array, positions: jax.Array,
                config: ModelConfig) -> jax.Array:
    """What both mixers put into the latent plane for tokens at
    ``positions``: the normed latent and the ROTATED shared key, in whole
    lanes."""
    return hybrid.latent_row(
        c, hybrid.rope(k_rope, positions, config.rope_theta), config)


def planes_of(cache: HybridCache) -> tuple:
    """Every plane of the cache, ``lengths`` left out: ``(k, v, state,
    conv, latent, wk, wv)``, what rides the layer loops' carry."""
    return tuple(cache[:5]) + (cache.wk, cache.wv)


def with_planes(planes: tuple, lengths: jax.Array) -> HybridCache:
    """:func:`planes_of` and the lengths back into a cache."""
    return HybridCache(*planes[:5], lengths, *planes[5:])


def _sum_counts(counts: jax.Array) -> jax.Array:
    """The expert layers' ``load_counts`` ``[..., 3]`` as one triple:
    assignments and experts touched summed, the fullest expert's rows
    the largest."""
    c = counts.reshape(-1, 3)
    return jnp.stack([jnp.sum(c[:, 0]), jnp.sum(c[:, 1]), jnp.max(c[:, 2])])


class _Mixer:
    """What every serving mixer keeps of a period: its per-layer cache
    outputs by kind ``(k, v, state, conv, latent, window k, window v,
    window attention at the probed positions)`` and what its expert
    layers chose."""

    def __init__(self) -> None:
        self.out: tuple[list, ...] = ([], [], [], [], [], [], [], [])
        self.kept: list = []
        self.counts: list = []

    def keep(self, per_token: jax.Array) -> jax.Array:
        """Of an expert layer's choices ``[T, k]``, what a checker reads."""
        raise NotImplementedError

    def routed(self, routing, counts) -> None:
        self.kept.append((self.keep(routing.experts),
                          self.keep(routing.gates)))
        self.counts.append(counts)

    def collect(self):
        routed = None
        if self.kept:
            routed = (jnp.stack([e for e, _ in self.kept]),
                      jnp.stack([g for _, g in self.kept]),
                      jnp.stack(self.counts))
        return tuple(jnp.stack(o) if o else () for o in self.out), routed


class ChunkMixer(_Mixer):
    """One period of one prompt chunk (batch 1) at static offset
    ``start``: ``xs`` are the period's slices of the carried prefix."""

    def __init__(self, config: ModelConfig, xs: tuple, slot, n_valid,
                 start: int, chunk_len: int, block_size: int) -> None:
        super().__init__()
        self.config, self.xs = config, xs
        self.slot, self.n_valid = slot, n_valid
        self.start, self.chunk_len, self.bs = start, chunk_len, block_size

    def valid(self):
        # padding takes no expert's time
        return jnp.arange(self.chunk_len) < self.n_valid

    def positions(self):
        return (self.start + jnp.arange(self.chunk_len))[None, :]

    def keep(self, per_token):
        # the prompt's last position, where it lies in this chunk
        last = jnp.clip(self.n_valid - 1, 0, self.chunk_len - 1)
        return jax.lax.dynamic_index_in_dim(per_token, last, 0,
                                            keepdims=False)

    def attention(self, q, k, v, l, planes):
        pk, pv = self.xs[0], self.xs[1]
        j = len(self.out[0])
        k_all = jnp.concatenate([pk[j], k[0]], axis=0)
        v_all = jnp.concatenate([pv[j], v[0]], axis=0)
        attn = chunk_attention(q.transpose(0, 2, 1, 3), k_all, v_all,
                               self.start, self.config.attention_multiplier,
                               dense=_chunk_attention)
        # the K/V planes ride along untouched: the chunk program writes
        # every layer's blocks at once from what is handed on here
        # (``write_slot_planes``)
        self.out[0].append(k_all)
        self.out[1].append(v_all)
        return attn.transpose(0, 2, 1, 3), planes

    def window(self, q, k, v, l, planes):
        """A window layer's chunk: the slot's ring as it stands (the
        earlier chunks' last ``sliding_window`` positions) and the chunk's
        own keys, under the window mask ``p - W < j <= p``; the chunk's
        K/V handed on for ``write_ring_blocks``."""
        cfg = self.config
        w, kvh, d = cfg.sliding_window, k.shape[2], k.shape[3]
        with jax.named_scope(WIN_ATTEND):
            keys_at = self.start + np.arange(self.chunk_len)
            if self.start:
                # what the ring holds of this slot, token-major: ONE
                # slice at (layer, slot) (a layer sliced first is every
                # slot's ring, which XLA copies out)
                ring_k, ring_v = (
                    jax.lax.dynamic_slice(
                        plane, (l, self.slot) + (0,) * (plane.ndim - 2),
                        (1, 1) + plane.shape[2:]).reshape(w, kvh, d)
                    for plane in planes[5:])
                k_all = jnp.concatenate([ring_k.astype(k.dtype), k[0]], 0)
                v_all = jnp.concatenate([ring_v.astype(v.dtype), v[0]], 0)
                keys_at = np.concatenate(
                    [ring_positions(self.start, w), keys_at])
            else:
                k_all, v_all = k[0], v[0]
            attn = chunk_attention(q.transpose(0, 2, 1, 3), k_all, v_all,
                                   self.start, cfg.attention_multiplier,
                                   keys_at, w, dense=_chunk_attention)
        self.out[5].append(k[0])
        self.out[6].append(v[0])
        attn = attn.transpose(0, 2, 1, 3)
        self.out[7].append(self.keep(attn[0]))
        return attn, planes

    def latent(self, q, c, k_rope, wkv_b, l, planes):
        cfg = self.config
        dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        heads = q.shape[2]
        j = len(self.out[4])
        pos = self.start + jnp.arange(self.chunk_len)
        q_rope = hybrid.rope(q[..., dn:], pos[None, :, None],
                             cfg.rope_theta)
        rows = _cache_rows(c, k_rope, pos[None, :], cfg)[0]
        rows_all = jnp.concatenate([self.xs[4][j], rows], axis=0)
        # the expanded form: every head's keys and values of the tokens
        # the chunk attends over, from their rows
        k_nope, v = hybrid.expand_latent(rows_all[:, :r], wkv_b, cfg)
        k_all = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                rows_all[:, None, r:cfg.latent_width],
                (rows_all.shape[0], heads, cfg.qk_rope_head_dim))], axis=-1)
        qh = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        with jax.named_scope(LATENT_ATTEND):
            attn = _chunk_attention(qh.transpose(0, 2, 1, 3), k_all, v,
                                    self.start)
        *rest, lat, wk, wv = planes
        with jax.named_scope(LATENT_UPDATE):
            lat = write_slot_blocks(
                lat, rows.reshape(self.chunk_len // self.bs, self.bs, -1),
                l, self.slot, self.start // self.bs)
        self.out[4].append(rows_all)
        return attn.transpose(0, 2, 1, 3), (*rest, lat, wk, wv)

    def linear(self, qkv, log_alpha, beta, conv_w, l, planes):
        cfg = self.config
        j = len(self.out[2])
        real = (jnp.arange(self.chunk_len) < self.n_valid)[None, :, None]
        with jax.named_scope(LIN_CONV):
            ext = jnp.concatenate([self.xs[3][j][None], qkv], axis=1)
            q, k, v = hybrid.split_qkv_heads(causal_conv(ext, conv_w), cfg)
            # the last inputs of REAL positions: the next chunk's, or the
            # first decode step's
            tail = jax.lax.dynamic_slice_in_dim(
                ext, self.n_valid, cfg.linear_conv_kernel_dim - 1, axis=1)[0]
        with jax.named_scope(LIN_CORE):
            # padding leaves the state as it was: no decay, no write
            o, state = gated_delta_chunked(
                q, k, v, jnp.where(real, log_alpha, 0.0),
                jnp.where(real, beta, 0.0),
                self.xs[2][j][None].astype(jnp.float32))
            state = state[0].astype(hybrid.STATE_DTYPE)
            k_c, v_c, st, cv, *rest = planes
            st = write_slot_state(st, state, l, self.slot)
            cv = write_slot_state(cv, tail, l, self.slot)
        self.out[2].append(state)
        self.out[3].append(tail)
        return o, (k_c, v_c, st, cv, *rest)

    def ssm(self, xbc, dt, layer, l, planes):
        cfg = self.config
        j = len(self.out[2])
        real = (jnp.arange(self.chunk_len) < self.n_valid)[None, :, None]
        with jax.named_scope(SSM_CONV):
            ext = jnp.concatenate([self.xs[3][j][None], xbc], axis=1)
            x, b, c = hybrid.split_xbc(
                causal_conv(ext, layer["ssm_conv"]), layer, cfg)
            # the last inputs of REAL positions, as in ``linear``
            tail = jax.lax.dynamic_slice_in_dim(
                ext, self.n_valid, cfg.mamba_d_conv - 1, axis=1)[0]
        with jax.named_scope(SSM_CORE):
            # padding leaves the state as it was: a step of zero
            y, state = ssd_chunked(
                x, jnp.where(real, dt, 0.0), -jnp.exp(layer["A_log"]), b, c,
                layer["ssm_D"], self.xs[2][j][None].astype(jnp.float32),
                cfg.mamba_chunk_size)
            state = state[0].astype(hybrid.STATE_DTYPE)
            k_c, v_c, st, cv, *rest = planes
            st = write_slot_state(st, state, l, self.slot)
            cv = write_slot_state(cv, tail.reshape(-1), l, self.slot)
        self.out[2].append(state)
        self.out[3].append(tail)
        return y, (k_c, v_c, st, cv, *rest)


def build_prefill_chunk(config: ModelConfig, mesh: Mesh, chunk_len: int,
                        start: int, quantized: bool = False):
    """Jitted ``prefill_chunk(cache, prefix, params, ids [1, chunk],
    slot, length) -> (cache, prefix, last)``, the signature of
    ``gpt.build_prefill_chunk`` with token ids for embeddings; ``last``
    is the float32 logits ``[vocab]`` of the prompt's last position,
    with routed experts ``(logits, experts chosen there [expert layers,
    k], their gates, counts [3])``, of a looped stack ``(logits, exit
    gates there [passes], passes run)``, with state-space layers
    ``(logits, [the chunk's real tokens, its rows])``.  ``quantized`` is the seam's: this family has the
    fp layout only (``models.configs.validate_serving`` refuses int8)."""

    @named(f"serve_prefill_chunk_o{start}")
    def prefill_chunk(cache, prefix, params, ids, slot, length):
        n_valid = jnp.clip(length - start, 0, chunk_len)
        h = hybrid.embed_tokens(params, ids, config)
        h, planes, ys, routed, gates = hybrid.run_stack(
            h, params, config,
            lambda xs_p: ChunkMixer(config, xs_p, slot, n_valid, start,
                                    chunk_len, cache.block_size),
            planes_of(cache), prefix)
        k_c, v_c, *rest, wk, wv = planes
        if k_c.shape[0]:
            # the chunk's own K/V of every (pass, layer), off the end of
            # what it hands the next chunk, as whole blocks of the slot
            k_c, v_c = (
                write_slot_planes(
                    plane, _as_blocks(own[:, start:], plane), slot,
                    start // cache.block_size)
                for plane, own in ((k_c, ys[0]), (v_c, ys[1])))
        if wk is not None:
            # the window layers' own K/V of the chunk into their rings
            with jax.named_scope(WIN_UPDATE):
                wk, wv = (
                    write_ring_blocks(plane, _as_blocks(own, plane), slot,
                                      start, length)
                    for plane, own in ((wk, ys[5]), (wv, ys[6])))
        planes = (k_c, v_c, *rest, wk, wv)
        local = jnp.clip(length - 1 - start, 0, chunk_len - 1)
        h_last = jax.lax.dynamic_index_in_dim(h[0], local, 0, keepdims=False)
        new_len = jnp.minimum(length, start + chunk_len)
        lengths = jnp.where(jnp.arange(cache.max_batch) == slot,
                            new_len, cache.lengths).astype(jnp.int32)
        last = hybrid.logits_of(params, h_last, config)
        if routed is not None:
            chosen, gates, counts = routed
            last = (last, chosen.reshape((-1,) + chosen.shape[2:]),
                    gates.reshape((-1,) + gates.shape[2:]))
            if wk is not None:
                # the first window layer's attention at the last position
                last += (ys[7][0],)
            last += (_sum_counts(counts),)
        elif gates is not None:
            last = (last, jax.lax.dynamic_index_in_dim(
                gates[:, 0], local, 1, keepdims=False),
                jnp.int32(gates.shape[0]))
        elif config.layers_of(MAMBA):
            last = (last, jnp.stack([n_valid, jnp.int32(chunk_len)]))
        # a kind the model has no layer of hands its empty prefix on
        ys = tuple(y if len(y) else p for y, p in zip(ys, prefix))
        return with_planes(planes, lengths), ys, last

    parts = 5 if config.layers_of(LATENT_ATTENTION) else 4
    pre_sh = tuple(NamedSharding(mesh, s)
                   for s in prefix_specs(mesh, config)[:parts])
    return jax.jit(
        prefill_chunk, donate_argnums=(0,),
        out_shardings=(hybrid_cache_shardings(mesh, config), pre_sh,
                       NamedSharding(mesh, P())))


class DecodeMixer(_Mixer):
    """One token a slot: append and attend in the K/V or latent planes,
    one recurrent step in the state planes, all in place in the carry."""

    def __init__(self, config: ModelConfig, mesh: Mesh, lengths, active,
                 probe) -> None:
        super().__init__()
        self.config, self.mesh = config, mesh
        self.lengths, self.active, self.probe = lengths, active, probe

    def valid(self):
        # a slot that holds no request takes no expert's time
        return self.active

    def positions(self):
        # the token a slot appends lies at the slot's length
        return self.lengths[:, None]

    def keep(self, per_token):
        return jnp.take(per_token, self.probe, axis=0)

    def attention(self, q, k, v, l, planes):
        k_c, v_c, *rest = planes
        if k_c.ndim == 5:
            # planes of whole rows (``models.configs.kv_rows``): a
            # token's heads are written, and read, as one row
            slots = q.shape[0]
            with jax.named_scope(KV_UPDATE):
                k_c, v_c = (
                    append_latent_rows(plane, t.reshape(slots, -1), l,
                                       self.lengths, self.active, self.mesh)
                    for plane, t in ((k_c, k), (v_c, v)))
        else:
            held = k_c.shape[-2]
            k_c = append_token_rows(k_c, _pad_heads(k, held), l,
                                    self.lengths, self.active, self.mesh)
            v_c = append_token_rows(v_c, _pad_heads(v, held), l,
                                    self.lengths, self.active, self.mesh)
        return self._attend(q, k.shape[-2], k_c, v_c, l), (k_c, v_c, *rest)

    def window(self, q, k, v, l, planes):
        """A window layer's step: the token into its slot's ring, over
        the one that left the window, and the decode kernel over the
        ring's live tiles (all of them once the slot holds ``W``
        tokens: the kernel's length mask then keeps every place)."""
        *rest, wk, wv = planes
        with jax.named_scope(WIN_UPDATE):
            wk, wv = (
                append_ring_rows(
                    plane, (t if plane.ndim == 5
                            else _pad_heads(t, plane.shape[-2])).reshape(
                        (t.shape[0],) + plane.shape[4:]),
                    l, self.lengths, self.active, self.mesh)
                for plane, t in ((wk, k), (wv, v)))
        with jax.named_scope(WIN_ATTEND):
            attn = self._attend(q, k.shape[-2], wk, wv, l)
        self.out[7].append(self.keep(attn[:, 0]))
        return attn, (*rest, wk, wv)

    def _attend(self, q, kv_heads, k_c, v_c, l):
        """The decode kernel over planes ``k_c``, ``v_c`` that hold the
        step's token already: ``[B, 1, heads, d]``."""
        scale = self.config.attention_multiplier
        if k_c.ndim == 5:
            attn = decode_attention(
                q.transpose(0, 2, 1, 3), k_c, v_c, l, self.lengths,
                self.active, self.mesh, scale)
            return attn.transpose(0, 2, 1, 3)
        heads, held = q.shape[2], k_c.shape[-2]
        # the plane's added heads are attended by zero queries and cut
        # ... and a group of zero queries for each of them
        group = heads // kv_heads
        attn = decode_attention(
            _pad_heads(q, held * group).transpose(0, 2, 1, 3), k_c, v_c, l,
            self.lengths, self.active, self.mesh, scale)
        return attn.transpose(0, 2, 1, 3)[:, :, :heads]

    def latent(self, q, c, k_rope, wkv_b, l, planes):
        cfg = self.config
        dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q, pos = q[:, 0], self.lengths
        q_rope = hybrid.rope(q[..., dn:], pos[:, None], cfg.rope_theta)
        rows = _cache_rows(c[:, 0], k_rope[:, 0], pos, cfg)
        *rest, lat, wk, wv = planes
        with jax.named_scope(LATENT_UPDATE):
            lat = append_latent_rows(lat, rows, l, self.lengths,
                                     self.active, self.mesh)
        # the ABSORBED form: the query taken into the latent's space, so
        # that one row a token is key and value for every head
        with jax.named_scope(MLA_KV_B):
            q_abs = jnp.einsum("bnd,rnd->bnr", q[..., :dn],
                               wkv_b[..., :dn])
        pad = cfg.latent_row - cfg.latent_width
        q_row = jnp.concatenate(
            [q_abs, q_rope, jnp.zeros(q_abs.shape[:2] + (pad,),
                                      q_abs.dtype)], axis=-1)
        o = latent_decode_attention(
            q_row, lat, l, self.lengths, self.active, self.mesh, r,
            (dn + cfg.qk_rope_head_dim) ** -0.5)
        with jax.named_scope(MLA_KV_B):
            attn = jnp.einsum("bnr,rnd->bnd", o, wkv_b[..., dn:])
        return attn[:, None], (*rest, lat, wk, wv)

    def linear(self, qkv, log_alpha, beta, conv_w, l, planes):
        k_c, v_c, st, cv, *rest = planes
        keep = self.active[:, None, None, None]
        with jax.named_scope(LIN_CONV):
            before = _layer_of(cv, l)
            ext = jnp.concatenate([before, qkv], axis=1)
            q, k, v = hybrid.split_qkv_heads(causal_conv(ext, conv_w),
                                             self.config)
            cv = jax.lax.dynamic_update_index_in_dim(
                cv, jnp.where(keep, ext[:, 1:], before), l, 0)
        with jax.named_scope(LIN_CORE):
            old = _layer_of(st, l)
            o, new = gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                                      jnp.exp(log_alpha[:, 0]), beta[:, 0],
                                      old.astype(jnp.float32))
            # an inactive slot's state stays bit for bit as it was
            st = jax.lax.dynamic_update_index_in_dim(
                st, jnp.where(keep, new.astype(st.dtype), old), l, 0)
        return o[:, None], (k_c, v_c, st, cv, *rest)

    def ssm(self, xbc, dt, layer, l, planes):
        cfg = self.config
        k_c, v_c, st, cv, *rest = planes
        with jax.named_scope(SSM_CONV):
            before = _layer_of(cv, l)           # [B, (K - 1) x channels]
            ext = jnp.concatenate([before, xbc[:, 0]], axis=-1)
            x, b, c = hybrid.split_xbc(_conv_flat(ext, layer["ssm_conv"]),
                                       layer, cfg)
            cv = jax.lax.dynamic_update_index_in_dim(
                cv, jnp.where(self.active[:, None],
                              ext[:, cfg.mamba_conv_channels:], before),
                l, 0)
        with jax.named_scope(SSM_CORE):
            # in place in the carried plane, the active slots alone: an
            # inactive slot's state stays bit for bit as it was
            y, st = ssd_plane_step(x, dt[:, 0], -jnp.exp(layer["A_log"]), b,
                                   c, layer["ssm_D"], st, l, self.active,
                                   self.mesh)
        return y[:, None], (k_c, v_c, st, cv, *rest)


def _decode_math(carry, params, active, probe, config: ModelConfig,
                 mesh: Mesh):
    """One decode step, shared verbatim by the per-step program and
    every trip of the fused scan.  Returns ``(carry, tokens, seen,
    counts)``: ``seen`` the logits of the probed slots ``[PROBES,
    vocab]``, with routed experts ``(logits, experts those slots chose
    [PROBES, expert layers, k], their float32 gates)``, of a looped
    stack ``(logits, those slots' exit gates [PROBES, passes])``;
    ``counts`` the step's ``[3]`` with routed experts, the passes the
    step ran of a looped stack, else None."""
    cache, tok = carry
    h = hybrid.embed_tokens(params, tok, config)[:, None, :]
    h, planes, ys, routed, gates = hybrid.run_stack(
        h, params, config,
        lambda _xs: DecodeMixer(config, mesh, cache.lengths, active, probe),
        planes_of(cache))
    logits = hybrid.logits_of(params, h[:, 0], config)
    new_tok = jnp.where(active, jnp.argmax(logits, axis=-1).astype(tok.dtype),
                        tok)
    lengths = cache.lengths + active.astype(jnp.int32)
    seen, counts = jnp.take(logits, probe, axis=0), None
    if routed is not None:
        # [periods, in a period, PROBES, k] -> [PROBES, expert layers, k]
        chosen, gates, counts = routed
        seen = (seen,) + tuple(
            t.reshape((-1,) + t.shape[2:]).transpose(1, 0, 2)
            for t in (chosen, gates))
        if cache.wk is not None:
            # the first window layer's attention of the probed slots
            seen += (ys[7][0],)
        counts = _sum_counts(counts)
    elif gates is not None:
        seen = (seen, jnp.take(gates[:, :, 0].T, probe, axis=0))
        counts = jnp.int32(gates.shape[0])
    return (with_planes(planes, lengths), new_tok), new_tok, seen, counts


def _decode_shardings(mesh: Mesh, config: ModelConfig):
    tok_sh = NamedSharding(mesh, token_spec(mesh))
    return (hybrid_cache_shardings(mesh, config), tok_sh), tok_sh


def build_decode_step(config: ModelConfig, mesh: Mesh):
    """Jitted ``decode_step(carry, params, active, probe) -> (carry,
    tokens [B], seen, counts)``; the carry is donated."""

    @named("serve_decode_step")
    def decode_step(carry, params, active, probe):
        return _decode_math(carry, params, active, probe, config, mesh)

    carry_sh, tok_sh = _decode_shardings(mesh, config)
    rep = NamedSharding(mesh, P())
    return jax.jit(decode_step, donate_argnums=(0,),
                   out_shardings=(carry_sh, tok_sh, rep, rep))


def build_decode_fused(config: ModelConfig, mesh: Mesh, k: int):
    """``k`` decode steps in one ``lax.scan``, as
    ``gpt.build_decode_fused``: lengths recomputed each trip from the
    replicated inputs, planes and tokens in the carry.  Returns
    ``(carry, tokens [k, B], seen, counts)``, the last two with the
    steps leading."""

    @named(f"serve_decode_k{k}")
    def decode_fused(carry, params, active, remaining, probe):
        cache0, tok0 = carry
        lengths0 = cache0.lengths
        act_i32 = active.astype(jnp.int32)

        def step(c, _):
            *planes, tok, i = c
            lengths_i = lengths0 + act_i32 * jnp.minimum(i, remaining)
            (cache, tok2), out, seen, counts = _decode_math(
                (with_planes(planes, lengths_i), tok), params,
                active & (i < remaining), probe, config, mesh)
            return (*planes_of(cache), tok2, i + 1), (out, seen, counts)

        (*planes, tok, _i), (toks, seen, counts) = jax.lax.scan(
            step, (*planes_of(cache0), tok0, jnp.int32(0)), None, length=k)
        lengths_f = lengths0 + act_i32 * jnp.minimum(jnp.int32(k), remaining)
        return (with_planes(planes, lengths_f), tok), toks, seen, counts

    carry_sh, tok_sh = _decode_shardings(mesh, config)
    toks_sh = NamedSharding(mesh, P(None, *token_spec(mesh)))
    rep = NamedSharding(mesh, P())
    return jax.jit(decode_fused, donate_argnums=(0,),
                   out_shardings=(carry_sh, toks_sh, rep, rep))


@named("serve_inject")
def inject_token(carry, slot, last):
    """A finished prefill's first token, the ``argmax`` of its last
    position's logits (``last``, or its first part), into the decode
    token buffer."""
    cache, tok = carry
    first = jnp.argmax(probe_parts(last)[0]).astype(tok.dtype)
    return cache, jnp.where(jnp.arange(tok.shape[0]) == slot, first, tok)


@jax.jit
@named("serve_probe_state")
def slot_state(cache: HybridCache, slot) -> jax.Array:
    """A copy of one slot's recurrent state ``[L_lin, heads, d_v, d_k]``:
    what ``ServingEngine.probe`` keeps of a probed request, after its
    prompt and after its last decode step.  Of a model that has no such
    state but K/V planes, the slot's rows of the FIRST K plane
    ``[num_blocks, block_size, kvh, d]``: what the first layer wrote of
    every token (its norm, its projection, its rotary), before anything
    compounds."""
    if not cache.state.shape[0] and cache.k.shape[0]:
        return jax.lax.dynamic_index_in_dim(cache.k[0], slot, axis=0,
                                            keepdims=False)
    return jax.lax.dynamic_index_in_dim(cache.state, slot, axis=1,
                                        keepdims=False)


def probe_parts(last: Any) -> tuple:
    """A chunk program's ``last`` as the decode programs' ``seen`` is
    laid out for one slot: ``(logits,)``, ``(logits, experts, gates)``
    or ``(logits, exit gates)``."""
    return tuple(last[:-1]) if isinstance(last, tuple) else (last,)


def probe_names(config: ModelConfig) -> tuple[str, ...]:
    """What ``seen`` holds behind the logits, as
    ``ServingEngine.probe_results`` names it."""
    if config.has_routed_experts:
        return ("experts", "gates") + (
            ("window_attn",) if config.layers_of(SLIDING_ATTENTION) else ())
    return ("exit_gates",) if config.total_ut_steps > 1 else ()


def fresh_carry(config: ModelConfig, serving: Any, mesh: Mesh):
    """The decode carry of an empty engine: zeroed cache, zero tokens."""
    cache = create_hybrid_cache(config, serving.max_batch,
                                serving.num_blocks, serving.block_size,
                                mesh=mesh, state_dtype=hybrid.STATE_DTYPE)
    tok = jax.device_put(jnp.zeros((serving.max_batch,), jnp.int32),
                         NamedSharding(mesh, token_spec(mesh)))
    return cache, tok


# ---------------------------------------------------------------------------
# what the scheduler asks of a family (``serve/engine.py::family_for``)
# ---------------------------------------------------------------------------

# the decode programs feed token ids back on the device
TOKENS_FED_BACK = True
# slots whose logits a decode program returns each step
PROBES = 2
# what this family's serving path does not have, and the reason given
LACKS = {
    "monolithic_prefill":
        "capture_device_traces is not wired for layer_types models (it "
        "replays a monolithic prefill, which they do not have); trace a "
        "run with benchmarks/run.py --trace 1",
}


def check_serving(config: ModelConfig, serving: Any) -> None:
    """Refuse what this family's serving path does not have yet, each by
    its mechanism (ROADMAP.md, Queue 2); int8 KV, a draft model and
    ``tp`` over latents or experts are refused in
    ``models.configs.validate_serving``, ``ep`` in
    ``validate_expert_parallelism``.  What it takes: every
    ``layer_types`` model ``ModelConfig`` builds (``norm_placement``
    ``post``, ``pre`` or ``sandwich``; full-attention layers with
    QK-norm, with rotary positions, with both or with neither, their K
    and V of ``num_kv_heads``; state-space ``mamba`` layers; a looped
    stack of ``total_ut_steps`` passes that every token runs to the
    end), chunked prefill, fused decode scans, the fp K/V layout."""
    latent = LATENT_ATTENTION in config.layer_types
    if config.early_exit_threshold < 1:
        raise ValueError(
            f"early_exit_threshold={config.early_exit_threshold} is not "
            "implemented: a pass through the stack is ONE program over "
            "every slot of the batch, so a token can leave the loop early "
            "only if the scheduler groups slots by the pass they are in "
            "(and a later token's pass t needs K/V its predecessor never "
            "computed); every token runs all "
            f"{config.total_ut_steps} passes (early_exit_threshold=1)")
    ring = SLIDING_ATTENTION in config.layer_types
    if serving.speculation != "off":
        raise ValueError(
            f"serving.speculation={serving.speculation!r} is not "
            "implemented for layer_types models: a rejected draft "
            "needs the recurrent state rolled back, and the state "
            "cache keeps no snapshots"
            + ("; over latents the verify step would need the absorbed "
               "attention for several positions a slot" if latent else "")
            + ("; a window layer's ring has written the draft's tokens "
               "over positions still in the window, which a rollback "
               "would need back" if ring else ""))
    if serving.prefix_caching:
        raise ValueError(
            "serving.prefix_caching is not implemented for "
            "layer_types models: attaching to shared blocks needs "
            "the recurrent state as it was at the block boundary, "
            "and the state cache keeps no snapshots"
            + ("; the latent plane has no attach program (the copy of a "
               "donor's rows and the prefix they give a chunk)"
               if latent else "")
            + ("; a window layer's ring holds the last W positions of the "
               "donor, not the blocks of a shared prefix" if ring else ""))
    if serving.prefill_chunk is None:
        raise ValueError(
            "layer_types models are prefilled in chunks: set "
            "serving.prefill_chunk (the chunk program hands the "
            "recurrent state and the latent rows from chunk to chunk; "
            "there is no monolithic prefill program)")
    window = config.sliding_window
    if SLIDING_ATTENTION in config.layer_types and (
            window % serving.prefill_chunk or window % serving.block_size):
        raise ValueError(
            f"sliding_window={window} must be a whole number of prompt "
            f"chunks ({serving.prefill_chunk}) and of blocks "
            f"({serving.block_size}): a chunk writes its tokens into the "
            "ring as one run of whole blocks that does not wrap")


def attend_tiles(config: ModelConfig, cache: HybridCache,
                 mesh: Mesh) -> tuple[str, int]:
    """Which paged plane the decode kernel of this model fetches, and by
    tiles of how many tokens: ``("latent", T)`` or ``("kv", T)`` (what
    the scheduler's ``serve_<name>_tiles_live`` / ``_held`` count by).
    Refuses, with the reason, planes the decode kernels cannot read on
    the chip: the paged one, and a state-space model's state plane
    (``ops/state_plane.py``)."""
    if config.layers_of(MAMBA):
        state_plane.check_kernel_takes(cache.state)
    if config.layers_of(LATENT_ATTENTION):
        latent_kernel.check_kernel_takes(cache.latent, config.kv_lora_rank)
        return "latent", latent_kernel.plane_tile_tokens(cache.latent)
    if cache.wk is not None:
        kv_kernel.check_kernel_takes(cache.wk, mesh)
    kv_kernel.check_kernel_takes(cache.k, mesh)
    return "kv", kv_kernel.plane_tile_tokens(cache.k, mesh)


_MOE_COUNTERS = (
    ("serve_moe_assignments",
     "(token, expert) assignments the expert layers computed (real "
     "tokens only)"),
    ("serve_moe_experts_touched",
     "experts that got at least one token, summed over expert layers "
     "and decode steps or prompt chunks"),
)


def register_metrics(registry: Any, config: ModelConfig, serving: Any,
                     tp: int) -> None:
    """This family's own counters and gauges: slot recycling, what each
    kind of cache holds, and what the expert layers were asked."""
    registry.inc(
        "serve_state_resets", 0,
        help="recycled slots whose recurrent state a new "
             "request's first prompt chunk cleared")
    registry.set_gauge(
        "serve_state_bytes",
        state_cache_bytes(config, serving.max_batch),
        help="bytes of slot-indexed recurrent state and "
             "convolution inputs the cache holds")
    registry.set_gauge(
        "serve_kv_bytes",
        kv_cache_bytes(config, serving.max_batch, serving.max_seq,
                       tp=tp),
        help="bytes of paged K/V the cache holds (full-attention "
             "layers only; of a looped stack one plane a pass and layer)")
    registry.set_gauge(
        "serve_latent_bytes",
        latent_cache_bytes(config, serving.max_batch, serving.max_seq),
        help="bytes of paged latent rows the cache holds "
             "(latent-attention layers only; whole lanes a row)")
    if config.has_routed_experts:
        for name, hlp in _MOE_COUNTERS:
            registry.inc(name, 0, help=hlp)
        registry.set_gauge(
            "serve_moe_load_max", 0,
            help="the fullest expert's tokens in one expert layer of one "
                 "decode step or prompt chunk (largest seen)")
    if config.total_ut_steps > 1:
        registry.inc(
            "serve_loop_passes", 0,
            help="passes through the looped stack the decode steps and "
                 "prompt chunks ran (each reads the stack's weights once)")
    if config.layers_of(SLIDING_ATTENTION):
        registry.set_gauge(
            "serve_window_kv_bytes",
            window_cache_bytes(config, serving.max_batch),
            help="bytes of K/V the window layers' rings hold "
                 "(sliding_window tokens a slot and layer)")
        fit = slot_fit(config, serving)
        for name, key, hlp in (
                ("serve_slots_fitted", "slots_fit",
                 "slots whose K/V fits the memory the weights leave, "
                 "window layers held as rings"),
                ("serve_slots_fitted_flat", "slots_fit_flat",
                 "slots that would fit if every attention layer held "
                 "max_seq tokens with heads in whole tiles of 8")):
            if key in fit:
                registry.set_gauge(name, fit[key], help=hlp)
        for name, hlp in (
                ("serve_window_tiles_live",
                 "ring tiles of a window layer the decode steps fetched, "
                 "times window layers"),
                ("serve_window_tiles_held",
                 "ring tiles the window layers' rings hold, times decode "
                 "steps and window layers")):
            registry.inc(name, 0, help=hlp)
    if config.layers_of(MAMBA):
        registry.inc(
            "serve_state_slots_stepped", 0,
            help="slots' states of a state-space layer the decode steps "
                 "read and wrote (active slots x steps x layers)")
        registry.inc(
            "serve_state_slots_held", 0,
            help="slots' states of a state-space layer the plane holds, "
                 "times decode steps (max_batch x steps x layers)")


def _moe_counted(registry: Any, config: ModelConfig,
                 samples: dict[str, list], kind: str, counts: Any) -> None:
    """Book the ``[steps, 3]`` counts of one decode unit or prompt chunk
    (``kind``): counters, the gauge, and the report's samples."""
    counts = np.asarray(counts).reshape(-1, 3)
    samples["_moe_layer_runs"] = (samples.get("_moe_layer_runs", 0)
                                  + len(counts) * config.expert_layers)
    assigned, touched = int(counts[:, 0].sum()), int(counts[:, 1].sum())
    fullest = int(counts[:, 2].max())
    registry.inc("serve_moe_assignments", assigned)
    registry.inc("serve_moe_experts_touched", touched)
    samples["_load_max"] = max(fullest, samples.get("_load_max", 0))
    registry.set_gauge("serve_moe_load_max", samples["_load_max"])
    samples.setdefault(f"moe_{kind}_assignments", []).append(assigned)
    samples.setdefault(f"moe_{kind}_touched", []).append(touched)
    samples.setdefault(f"moe_{kind}_load_max", []).append(fullest)


def _counted(registry: Any, config: ModelConfig, samples: dict[str, list],
             kind: str, counts: Any) -> None:
    if config.has_routed_experts:
        _moe_counted(registry, config, samples, kind, counts)
    elif config.layers_of(MAMBA):
        # a prompt chunk's: the real tokens its scan ran over and the
        # rows they were padded to (a ``serve-prefill-chunk`` span's
        # ``seq`` is the samples' index)
        real, rows = (int(v) for v in np.asarray(counts).reshape(2))
        samples.setdefault("chunk_real_tokens", []).append(real)
        samples.setdefault("chunk_rows", []).append(rows)
    else:
        # a looped stack's: the passes each step (or the chunk) ran
        counts = np.asarray(counts).reshape(-1)
        registry.inc("serve_loop_passes", int(counts.sum()))
        samples["_loop_passes"] = (samples.get("_loop_passes", 0)
                                   + int(counts.sum()))
        samples["_loop_runs"] = samples.get("_loop_runs", 0) + len(counts)


def slot_fit(config: ModelConfig, serving: Any,
             memory: Optional[int] = None) -> dict[str, int]:
    """What a slot's K/V costs with the window layers held as rings of
    ``sliding_window`` tokens (``kv_slot_bytes``, the global layers'
    planes ``kv_bytes_global`` and the rings ``kv_bytes_window`` over all
    slots) and what it would cost if every attention layer held
    ``max_seq`` tokens with its kv-heads rounded up to whole tiles of 8
    (``kv_slot_bytes_flat``: the allocator before rings and rows); and,
    where the device's memory is known (``memory``, else the device's
    own limit: none on the CPU), how many slots each fits beside the
    weights (``slots_fit``, ``slots_fit_flat``)."""
    b = serving.max_batch
    held = kv_cache_bytes(config, b, serving.max_seq)
    window = window_cache_bytes(config, b)
    flat = kv_cache_bytes_raw(
        config.kv_planes + config.layers_of(SLIDING_ATTENTION), 1,
        serving.max_seq, -(-config.kv_heads // 8) * 8, config.head_dim,
        config.dtype)
    out = {"kv_bytes_global": held, "kv_bytes_window": window,
           "kv_slot_bytes": (held + window) // b, "kv_slot_bytes_flat": flat}
    if memory is None:
        memory = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if memory:
        left = memory - hybrid.num_parameters(config) * jnp.dtype(
            _dtype_of(config.dtype)).itemsize
        out["slots_fit"] = max(0, left // out["kv_slot_bytes"])
        out["slots_fit_flat"] = max(0, left // flat)
    return out


def _ring_tile(config: ModelConfig, block_size: int) -> int:
    """The tokens of one tile the decode kernel fetches of a ring
    (``ops/decode_attention.py::tile_tokens`` of its shape)."""
    itemsize = jnp.dtype(_dtype_of(config.dtype)).itemsize
    heads, width = ((1, config.kv_heads * config.head_dim)
                    if kv_rows(config) else
                    (cache_kv_heads(config), config.head_dim))
    return kv_kernel.tile_tokens(config.sliding_window // block_size,
                                 block_size, heads, width, itemsize)


def unit_dispatched(registry: Any, config: ModelConfig,
                    samples: dict[str, list], slot_steps: int,
                    steps: int, slots: int, lengths: Any = None,
                    stepping: Any = None, block_size: int = 0) -> None:
    """A decode unit of ``steps`` steps over a batch of ``slots`` goes
    out, ``slot_steps`` (slot, step) pairs of it active by the
    scheduler's ledger: what the state-space layers' kernel moves of the
    state plane (``ops/state_plane.py``), and what the plane holds; of a
    model with window layers, what their kernel reads of the rings
    (``lengths`` ``[steps, slots']`` each active pair's length before
    its step, ``stepping`` which pairs step, ``block_size`` the cache's):
    the unit's samples ``unit_ring_tiles`` (all window layers) and
    ``unit_ring_tokens`` (live ring tokens, one layer)."""
    n_win = config.layers_of(SLIDING_ATTENTION)
    if n_win and block_size and lengths is not None:
        window = config.sliding_window
        ring_tile = _ring_tile(config, block_size)
        tiles = int(kv_kernel.live_tile_counts(
            lengths, stepping, ring_tile, window // ring_tile).sum()) * n_win
        tokens = int((np.minimum(lengths + 1, window) * stepping).sum())
        held = steps * slots * (window // ring_tile) * n_win
        registry.inc("serve_window_tiles_live", tiles)
        registry.inc("serve_window_tiles_held", held)
        samples.setdefault("unit_ring_tiles", []).append(tiles)
        samples.setdefault("unit_ring_tokens", []).append(tokens)
        samples["_ring_tiles_live"] = samples.get("_ring_tiles_live",
                                                  0) + tiles
        samples["_ring_tiles_held"] = samples.get("_ring_tiles_held",
                                                  0) + held
    layers = config.layers_of(MAMBA)
    if layers:
        for name, n in (("stepped", slot_steps * layers),
                        ("held", slots * steps * layers)):
            registry.inc(f"serve_state_slots_{name}", n)
            samples[f"_state_slots_{name}"] = (
                samples.get(f"_state_slots_{name}", 0) + n)


def unit_counted(registry: Any, config: ModelConfig,
                 samples: dict[str, list], counts: Any) -> None:
    """A decode unit is done and its ``counts`` (None for a plain dense
    stack) are on the host's side of the sync."""
    if counts is not None:
        _counted(registry, config, samples, "unit", counts)


def chunk_counted(registry: Any, config: ModelConfig,
                  samples: dict[str, list], last: Any) -> None:
    """A prompt chunk's ``last`` is ready."""
    if isinstance(last, tuple):
        _counted(registry, config, samples, "chunk", last[-1])


def chunk_attn_counts(config: ModelConfig, start: int,
                      chunk_len: int) -> tuple[int, int]:
    """The layer attentions of the chunk program at ``start`` that run as
    the ``chunk_attend`` kernel and those that run dense, by
    ``serve/attend.py::chunk_on_kernel``: a full-attention layer's (every
    pass of a looped stack) over the prefix and the chunk, a window
    layer's over the ring and the chunk, a latent layer's always dense."""
    heads = config.num_heads
    attends = ((config.kv_planes, start + chunk_len),
               (config.layers_of(SLIDING_ATTENTION),
                chunk_len + (config.sliding_window if start else 0)))
    kernel = sum(n for n, keys in attends
                 if chunk_on_kernel(heads, chunk_len, keys))
    return kernel, (sum(n for n, _ in attends) - kernel
                    + config.layers_of(LATENT_ATTENTION))


def gmm_counts(config: ModelConfig, tokens: int) -> tuple[int, int]:
    """The grouped expert products of one step or chunk over ``tokens``
    tokens whose contraction is one tile and those whose contraction is
    split, by ``ops/routed_experts.py::_gmm_tiling``: three an expert
    layer (every pass of a looped stack), none without experts."""
    if not config.has_routed_experts:
        return 0, 0
    whole, split = routed_experts.gmm_counts(
        tokens, config.num_experts_per_tok, config.hidden_size,
        config.moe_intermediate_size, jnp.dtype(config.dtype).itemsize)
    layers = config.expert_layers * config.total_ut_steps
    return whole * layers, split * layers


def report_shares(config: ModelConfig, samples: dict[str, list],
                  serving: Any = None) -> dict[str, float]:
    """What the report says of the expert layers over the whole run:
    ``experts_touched_share`` (experts that got a token over experts
    held, a layer and decode step or chunk) and
    ``expert_load_max_over_mean`` (the fullest expert's tokens over the
    mean of the experts that got any, largest single layer); of a
    looped stack ``exit_pass_mean``, the passes a decode step or chunk
    ran before its ``h`` went to the head, on average; of a model with
    state-space layers ``chunk_real_token_share`` and
    ``state_live_share`` (slots' states the decode steps moved over
    those the plane held); of a model with window layers beside the
    expert layers' also :func:`slot_fit` and ``window_live_share`` (ring
    tiles the window kernel read over those the rings held)."""
    out: dict[str, float] = {}
    if config.layers_of(SLIDING_ATTENTION):
        if serving is not None:
            out.update(slot_fit(config, serving))
        if samples.get("_ring_tiles_held"):
            out["window_live_share"] = (samples["_ring_tiles_live"]
                                        / samples["_ring_tiles_held"])
    return {**out, **_report_shares(config, samples)}


def _report_shares(config: ModelConfig, samples: dict[str, list]
                   ) -> dict[str, float]:
    if samples.get("_loop_runs"):
        return {"exit_pass_mean":
                samples["_loop_passes"] / samples["_loop_runs"]}
    if config.layers_of(MAMBA):
        out = {}
        if samples.get("chunk_rows"):
            # of the rows the chunked scans ran, the share that were
            # prompt tokens (the rest is a last chunk's padding)
            out["chunk_real_token_share"] = (
                sum(samples["chunk_real_tokens"])
                / sum(samples["chunk_rows"]))
        if samples.get("_state_slots_held"):
            out["state_live_share"] = (samples["_state_slots_stepped"]
                                       / samples["_state_slots_held"])
        return out
    if not config.has_routed_experts:
        return {}
    touched = sum(samples.get("moe_unit_touched", ())) \
        + sum(samples.get("moe_chunk_touched", ()))
    assigned = sum(samples.get("moe_unit_assignments", ())) \
        + sum(samples.get("moe_chunk_assignments", ()))
    layers = samples.get("_moe_layer_runs", 0)
    out = {}
    if layers:
        out["experts_touched_share"] = touched / (
            layers * config.n_routed_experts)
    if touched:
        out["expert_load_max_over_mean"] = samples.get("_load_max", 0) / (
            assigned / touched)
    return out


def slot_recycled(registry: Any, rid: int, slot: int) -> None:
    """A slot that served a request is given to ``rid``: the prompt's
    first chunk started from a zero state (:func:`create_prefix`) and
    overwrote what the slot's last request left."""
    spans.instant("state-reset", cat="request", rid=rid, slot=slot)
    registry.inc("serve_state_resets")


def prompt_input(config: ModelConfig, req: Request, pad_to: int,
                 dtype: Any) -> jax.Array:
    """A request's prompt as the chunk programs take it: token ids
    ``[1, pad_to]``, embedded on the device."""
    return jnp.asarray(prompt_ids_from_seed(
        req.seed, req.prompt_len, config.vocab_size, pad_to=pad_to))


def decode_programs(config: ModelConfig, mesh: Mesh, ks: tuple[int, ...],
                    quantized: bool = False, probe: Any = None):
    """The single step and the fused ladder ``{k: program}`` under the
    scheduler's signature ``(carry, params, active[, remaining]) ->
    (carry, ys)``: ``probe()`` gives the probed slots ``[PROBES]`` each
    program takes as its last argument, and ``ys`` is the triple
    ``(tokens, seen of the probed slots, counts)``.  Each goes by the
    name of the jitted program it calls (``TraceRun._launch`` reads
    it)."""
    def bound(program):
        @named(program.__name__)
        def call(carry, params, *masks):
            carry, *ys = program(carry, params, *masks, probe())
            return carry, tuple(ys)
        return call

    return (bound(build_decode_step(config, mesh)),
            {k: bound(build_decode_fused(config, mesh, k)) for k in ks})
