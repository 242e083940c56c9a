"""The serving programs of the GPT block (``models/transformer.py``:
LayerNorm with bias, GELU, full causal attention, no vocabulary), one of
the two block families the scheduler of ``serve/engine.py`` serves; the
other is ``serve/hybrid.py``.  All of them run ``_serve_block`` through
``_scan_layers`` over a :class:`~dlbb_tpu.serve.kvcache.KVCache` (or its
int8 layout) whose planes ride the layer loop's carry:

- ``serve_prefill_chunk_o<offset>`` (:func:`build_prefill_chunk`): one
  prompt chunk at a static offset, attending the carried prefix K/V;
  ``serve_prefill_b<bucket>`` (:func:`build_prefill`): a whole padded
  prompt at once; ``serve_prefix_attach``: a donor slot's matched blocks
  copied for a prefix-cache hit.
- ``serve_decode_step`` / ``serve_decode_k<K>``: one token a slot, or K
  in one ``lax.scan``, over the donated carry ``(cache, x [max_batch, 1,
  H])``.  The model is its own next-token function: a step's output
  hidden state is the next step's input.  The fp layout appends by
  ``append_token_rows`` and attends through ``ops/decode_attention.py``,
  which fetches only the tiles of tokens a slot holds.
- ``serve_inject``: a finished prefill's last output into its slot.
- the token-feedback and draft-and-verify programs of speculative
  decoding (``serve_decode_token_*``, ``serve_spec_*``), which quantise
  through the greedy token table of ``data/synthetic.py``.

What the scheduler asks of a family is at the end of the file (the
seam: ``docs/serving.md``, "Adding a block family").  A decode step may
contain only the per-token tp collectives; the cache never crosses the
wire (audited: ``analysis/hlo_audit.py``, the ``serve/engine.py::*``
targets).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.data.synthetic import request_embeddings
from dlbb_tpu.models.attention import dense_attention
from dlbb_tpu.models.configs import FULL_ATTENTION, ModelConfig
from dlbb_tpu.models.transformer import (
    ATTN_CORE,
    ATTN_OUT,
    ATTN_QKV,
    LN1,
    LN2,
    MLP_ACT,
    MLP_DOWN,
    MLP_UP,
    _dtype_of,
    _layernorm,
    named,
    split_qkv,
)
from dlbb_tpu.ops.decode_attention import (
    check_kernel_takes,
    decode_attention,
    plane_tile_tokens,
)
from dlbb_tpu.serve.attend import (
    KV_UPDATE,
    _cached_attention,
    _chunk_attention,
    _heads,
    _layer_of,
    _layer_tokens,
    _verify_attention,
)
from dlbb_tpu.serve.kvcache import (
    KVCache,
    QuantKVCache,
    append_token_rows,
    cache_shardings,
    copy_slot_blocks,
    create_kv_cache,
    create_quant_kv_cache,
    dequantize_kv_blocks,
    quant_cache_shardings,
    quantize_kv_blocks,
    write_slot_blocks,
)
from dlbb_tpu.serve.traffic import Request


def _split_qkv(qkv: jax.Array, config: ModelConfig):
    """[..., S, qkv_width] -> q [..., S, H], k/v [..., S, kv_heads *
    head_dim], heads in order, from the grouped columns ``split_qkv``
    reads."""
    return tuple(jnp.swapaxes(t, -2, -3).reshape(*qkv.shape[:-1], -1)
                 for t in split_qkv(qkv, config))


def _serve_block(h, layer, config: ModelConfig, attention_step,
                 cache_state):
    """One transformer block with a pluggable attention step — the ONE
    copy of the ln1/qkv/out/ln2/ffn structure every serving program
    shares (the serving twin of ``transformer._block``, whose math the
    equivalence tests pin it against).  ``attention_step(q, k, v,
    cache_state) -> (attn [B, S, n*d], cache_state)`` owns everything
    that differs between prefill (dense causal + block write), decode
    (cached append + length-masked read), and chunked prefill (prefix
    carry + offset block write); ``cache_state`` is opaque to the block
    (``_scan_layers`` says what the cache-writing programs put in it)."""
    with jax.named_scope(LN1):
        y = _layernorm(h, layer["ln1"]["scale"], layer["ln1"]["bias"])
    with jax.named_scope(ATTN_QKV):
        qkv = y @ layer["qkv"]["kernel"] + layer["qkv"]["bias"]
        q, k, v = _split_qkv(qkv, config)
    with jax.named_scope(ATTN_CORE):
        attn, cache_state = attention_step(q, k, v, cache_state)
    with jax.named_scope(ATTN_OUT):
        h = attn @ layer["out"]["kernel"] + layer["out"]["bias"] + h
    residual = h
    with jax.named_scope(LN2):
        y2 = _layernorm(h, layer["ln2"]["scale"], layer["ln2"]["bias"])
    with jax.named_scope(MLP_UP):
        y2 = y2 @ layer["ffn_up"]["kernel"] + layer["ffn_up"]["bias"]
    with jax.named_scope(MLP_ACT):
        y2 = jax.nn.gelu(y2)
    with jax.named_scope(MLP_DOWN):
        h = (y2 @ layer["ffn_down"]["kernel"]
             + layer["ffn_down"]["bias"] + residual)
    return h, cache_state


def _scan_layers(h, layers, planes, config: ModelConfig, attention_step,
                 xs=()):
    """The layer loop of every cache-writing program: ``h`` through the
    stacked ``layers``, with the cache ``planes`` (each ``[L, ...]``)
    riding the scan's CARRY beside the layer number, so that a write
    into them (``serve/kvcache.py``'s helpers) is an in-place update of
    the loop's buffer.  Scanned as ``xs``/``ys`` instead, a plane
    enters the loop as one buffer and leaves as another, which cost two
    whole-cache copies a program run on the v5e (``PERF.md`` §6, PR 26).

    ``attention_step(q, k, v, (l, planes, *xs_l)) -> (attn, (planes,
    ys_l))`` reads layer ``l`` of a plane by ``decode_attention`` (or
    ``_layer_tokens``) and writes it by the helpers; ``xs`` are further
    per-layer inputs (a chunk's prefix K/V), ``ys_l`` per-layer outputs.
    Returns ``(h, planes, ys)``."""
    def body(carry, layer_xs):
        h, l, planes = carry
        layer, *extra = layer_xs
        h, (planes, ys) = _serve_block(h, layer, config, attention_step,
                                       (l, planes, *extra))
        return (h, l + 1, planes), ys

    (h, _, planes), ys = jax.lax.scan(
        body, (h, jnp.int32(0), tuple(planes)), (layers, *xs))
    return h, planes, ys


def build_prefill(config: ModelConfig, mesh: Mesh,
                  quantized: bool = False, name: str = "serve_prefill"):
    """Jitted ``prefill(cache, params, x, slot, length) -> (cache,
    y_last)`` — retraces once per prompt bucket (x's static shape).  The
    cache is donated (argnum 0), so the carried protocol matches the
    train-step convention the audit and calibration understand.
    ``name`` is the program's name in a device trace: the engine builds
    one jit per bucket, ``serve_prefill_b<bucket>``.

    ``quantized`` writes the int8 layout (``QuantKVCache``): each
    freshly-computed K/V block is quantised per (block, kv-head) and
    the fp32 scales land in the side-channel plane by the same
    ``write_slot_blocks``.  Prefill attention runs over the chunk's
    own fp K/V (it never reads the cache), so quantisation touches
    only the write."""
    n, d, kvh = config.num_heads, config.head_dim, config.kv_heads

    @named(name)
    def prefill(cache, params, x, slot, length):
        bs = cache.block_size
        s_bucket = x.shape[1]
        wb = s_bucket // bs

        def attention_step(q, k, v, cache_state):
            l, planes = cache_state
            qh, kh, vh = (_heads(q, n, d), _heads(k, kvh, d),
                          _heads(v, kvh, d))
            attn = dense_attention(qh, kh, vh, causal=config.causal)
            # write this layer's K/V blocks into the slot ([S, kvh, d]
            # token-major, re-tiled to whole blocks)
            k_blocks = kh.transpose(0, 2, 1, 3)[0].reshape(wb, bs, kvh, d)
            v_blocks = vh.transpose(0, 2, 1, 3)[0].reshape(wb, bs, kvh, d)
            if quantized:
                kq, ks = quantize_kv_blocks(k_blocks)
                vq, vs = quantize_kv_blocks(v_blocks)
                updates = (kq, vq, ks, vs)
            else:
                updates = (k_blocks, v_blocks)
            planes = tuple(write_slot_blocks(p, u, l, slot)
                           for p, u in zip(planes, updates))
            return (attn.transpose(0, 2, 1, 3).reshape(1, s_bucket, n * d),
                    (planes, None))

        h, new_planes, _ = _scan_layers(
            x, params["layers"], cache[:-1], config, attention_step)
        y = _layernorm(h, params["ln_f"]["scale"], params["ln_f"]["bias"])
        y_last = jax.lax.dynamic_slice(
            y, (0, length - 1, 0), (1, 1, y.shape[-1])
        )[0, 0]
        lengths = jnp.where(jnp.arange(cache.max_batch) == slot,
                            length, cache.lengths).astype(jnp.int32)
        cache_cls = QuantKVCache if quantized else KVCache
        return cache_cls(*new_planes, lengths), y_last

    cache_sh = (quant_cache_shardings(mesh) if quantized
                else cache_shardings(mesh))
    return jax.jit(
        prefill,
        donate_argnums=(0,),
        out_shardings=(cache_sh, NamedSharding(mesh, P())),
    )


def prefix_spec(mesh: Mesh) -> P:
    """Chunked-prefill prefix K/V ``[L, start, kvh, d]``: kv-head dim
    over tp (the cache's own head split), no slot dim at all — the
    prefix never touches the dp shard."""
    axes = getattr(mesh, "axis_names", ())
    tp = "tp" if "tp" in axes and mesh.shape["tp"] > 1 else None
    return P(None, None, tp, None)


def create_prefix(config: ModelConfig, mesh: Mesh) -> tuple[jax.Array,
                                                            jax.Array]:
    """The empty (start=0) prefix carry for a chunked prefill."""
    from dlbb_tpu.models.transformer import _dtype_of as _dt

    shape = (config.layers_of(FULL_ATTENTION), 0, config.kv_heads,
             config.head_dim)
    zeros = jnp.zeros(shape, _dt(config.dtype))
    sh = NamedSharding(mesh, prefix_spec(mesh))
    return (jax.device_put(zeros, sh), jax.device_put(zeros, sh))


def build_prefill_chunk(config: ModelConfig, mesh: Mesh, chunk_len: int,
                        start: int, quantized: bool = False):
    """Jitted ``prefill_chunk(cache, prefix, params, x, slot, length) ->
    (cache, prefix, y_last)`` — one chunk of a chunked prefill at STATIC
    global offset ``start`` (a block multiple; one retrace per chunk
    index, the "bucketed chunk jit").

    The chunk's K/V blocks are written into the slot exactly as
    monolithic prefill writes its bucket (``write_slot_blocks`` at
    block offset ``start/block_size`` — one in-place block write);
    attention runs over the explicitly-carried prefix K/V (``[L, start,
    kvh, d]``, no slot dim) concatenated with the chunk, so the
    dp-sharded cache is never re-read.  ``length`` is the TRUE prompt
    length; ``y_last`` is the output at the last real position when it
    falls inside this chunk (the engine uses only the final chunk's).
    Only the cache is donated (the returned prefix is larger than the
    input one, so its buffers can never alias).

    ``quantized`` writes the chunk's blocks in the int8 layout (scales
    into the side-channel plane); the carried prefix K/V stays fp —
    attention always runs over exact chunk values, so quantisation
    touches only the cache write, exactly as in monolithic prefill."""
    n, d, kvh = config.num_heads, config.head_dim, config.kv_heads

    @named(f"serve_prefill_chunk_o{start}")
    def prefill_chunk(cache, prefix, params, x, slot, length):
        bs = cache.block_size
        wb = chunk_len // bs
        start_blk = start // bs

        def attention_step(q, k, v, cache_state):
            l, planes, pk_l, pv_l = cache_state
            qh = _heads(q, n, d)                        # [1, n, C, d]
            k_chunk = k[0].reshape(chunk_len, kvh, d)
            v_chunk = v[0].reshape(chunk_len, kvh, d)
            k_all = jnp.concatenate([pk_l, k_chunk], axis=0)
            v_all = jnp.concatenate([pv_l, v_chunk], axis=0)
            attn = _chunk_attention(qh, k_all, v_all, start)
            k_blocks = k_chunk.reshape(wb, bs, kvh, d)
            v_blocks = v_chunk.reshape(wb, bs, kvh, d)
            if quantized:
                kq, ks = quantize_kv_blocks(k_blocks)
                vq, vs = quantize_kv_blocks(v_blocks)
                updates = (kq, vq, ks, vs)
            else:
                updates = (k_blocks, v_blocks)
            planes = tuple(write_slot_blocks(p, u, l, slot, start_blk)
                           for p, u in zip(planes, updates))
            return (attn.transpose(0, 2, 1, 3).reshape(1, chunk_len,
                                                       n * d),
                    (planes, (k_all, v_all)))

        h, new_planes, (pk_new, pv_new) = _scan_layers(
            x, params["layers"], cache[:-1], config, attention_step,
            xs=prefix)
        y = _layernorm(h, params["ln_f"]["scale"], params["ln_f"]["bias"])
        local = jnp.clip(length - 1 - start, 0, chunk_len - 1)
        y_last = jax.lax.dynamic_slice(
            y, (0, local, 0), (1, 1, y.shape[-1])
        )[0, 0]
        new_len = jnp.minimum(length, start + chunk_len)
        lengths = jnp.where(jnp.arange(cache.max_batch) == slot,
                            new_len, cache.lengths).astype(jnp.int32)
        cache_cls = QuantKVCache if quantized else KVCache
        return (cache_cls(*new_planes, lengths), (pk_new, pv_new), y_last)

    pre_sh = NamedSharding(mesh, prefix_spec(mesh))
    cache_sh = (quant_cache_shardings(mesh) if quantized
                else cache_shardings(mesh))
    # only the cache is donated: the returned prefix is LARGER than the
    # input one (start -> start + C), so its buffers can never alias
    return jax.jit(
        prefill_chunk,
        donate_argnums=(0,),
        out_shardings=(cache_sh, (pre_sh, pre_sh),
                       NamedSharding(mesh, P())),
    )


def build_prefix_attach(config: ModelConfig, mesh: Mesh,
                        matched_len: int, block_size: int,
                        quantized: bool = False):
    """Jitted ``attach(cache, src, dst) -> (cache, prefix)`` — the
    copy-on-attach step of the shared-prefix cache (one retrace per
    matched chunk count, like the bucketed chunk jits).

    Copies the donor slot ``src``'s first ``matched_len/block_size``
    blocks (every plane — K/V, and the scale side-channel in the int8
    layout) into the admitted slot ``dst`` by ``copy_slot_blocks`` — a
    slice read and one in-place block write on a dp=1 slot dim
    (``ServingConfig.validate`` pins prefix_caching to dp=1), so the
    attach lowers to ZERO collectives (audited).  Also returns
    the matched prefix as the fp chunk-prefill carry ``[L, matched_len,
    kvh, d]``, exactly what the chunk jits would have produced for the
    same token blocks (bit-identical in the fp layout — the cache
    blocks ARE the chunk values; dequantised in the int8 layout), so
    the suffix chunks resume at static offset ``matched_len`` with no
    recompute.  The engine's scheduler replaces the matched chunks'
    prefill dispatches with this single copy — that is the TTFT win."""
    nb_m = matched_len // block_size
    kvh, d = config.kv_heads, config.head_dim
    dtype = _dtype_of(config.dtype)

    @named("serve_prefix_attach")
    def attach(cache, src, dst):
        nl = cache.k.shape[0]
        planes, donors = zip(*(copy_slot_blocks(p, src, dst, nb_m)
                               for p in cache[:-1]))
        if quantized:
            k_q, v_q, ks, vs = donors
            pk = dequantize_kv_blocks(k_q, ks, dtype)
            pv = dequantize_kv_blocks(v_q, vs, dtype)
        else:
            pk, pv = donors
        new_cache = type(cache)(*planes, cache.lengths)
        prefix = (pk.reshape(nl, matched_len, kvh, d),
                  pv.reshape(nl, matched_len, kvh, d))
        return new_cache, prefix

    pre_sh = NamedSharding(mesh, prefix_spec(mesh))
    cache_sh = (quant_cache_shardings(mesh) if quantized
                else cache_shardings(mesh))
    return jax.jit(
        attach,
        donate_argnums=(0,),
        out_shardings=(cache_sh, (pre_sh, pre_sh)),
    )


def decode_batch_spec(mesh: Mesh) -> P:
    """Decode activations ``[max_batch, 1, H]``: slots over dp."""
    axes = getattr(mesh, "axis_names", ())
    dp = "dp" if "dp" in axes and mesh.shape["dp"] > 1 else None
    return P(dp, None, None)


def _decode_step_math(carry, params, active, config: ModelConfig,
                      mesh: Mesh, quantized: bool = False):
    """The decode-step computation shared VERBATIM by the per-step jit
    and every trip of the fused scan (the equivalence contract between
    the two engines is that this is the one copy of the math).

    ``quantized`` reads/writes the int8 layout: each layer's blocks are
    dequantised to fp32 (exact — int8 times an fp32 scale), the token
    appended in fp, attention length-masked as ever, and the layer
    requantised with an active-slot select so an INACTIVE slot's int8/
    scale planes pass through verbatim.  An active slot's untouched
    blocks survive the dequant->requant round trip bit-stably: every
    stored value is ``q*s`` with ``|q| <= 127``, the recomputed scale
    differs from ``s`` only by fp32 rounding, so the re-rounded code is
    the same ``q`` (error ~2^-22 * 127, far below the 0.5 rounding
    threshold)."""
    n, d, kvh = config.num_heads, config.head_dim, config.kv_heads
    cache, x = carry
    b_dim, s_max = cache.max_batch, cache.max_seq
    lengths = cache.lengths
    pos = jnp.arange(s_max)[None, :]
    valid = pos <= lengths[:, None]

    def attention_step(q, k, v, cache_state):
        l, planes = cache_state
        qh = _heads(q, n, d)                        # [B, n, 1, d]
        k_new = k.reshape(b_dim, 1, kvh, d)
        v_new = v.reshape(b_dim, 1, kvh, d)
        if quantized:
            attn, planes = quant_append_attend(qh, k_new, v_new, l, planes)
        else:
            # append at each active slot's own length, in place in the
            # carried planes, then attend the tokens each slot holds
            k_c, v_c = planes
            k_c = append_token_rows(k_c, k_new, l, lengths, active, mesh)
            v_c = append_token_rows(v_c, v_new, l, lengths, active, mesh)
            attn = decode_attention(qh, k_c, v_c, l, lengths, active, mesh)
            planes = (k_c, v_c)
        return (attn.transpose(0, 2, 1, 3).reshape(b_dim, 1, n * d),
                (planes, None))

    def quant_append_attend(qh, k_new, v_new, l, planes):
        """The int8 layout's append still rewrites its whole layer:
        dequantise, masked-select append, attend, requantise, and put
        the layer back into the carried planes."""
        nb, bs = cache.num_blocks, cache.block_size
        write_mask = (pos == lengths[:, None]) & active[:, None]
        k_l, v_l, ks_l, vs_l = (_layer_of(p, l) for p in planes)
        k_fp = dequantize_kv_blocks(k_l, ks_l, jnp.float32)
        v_fp = dequantize_kv_blocks(v_l, vs_l, jnp.float32)
        with jax.named_scope(KV_UPDATE):
            k_flat = jnp.where(write_mask[..., None, None],
                               k_new.astype(jnp.float32),
                               k_fp.reshape(b_dim, s_max, kvh, d))
            v_flat = jnp.where(write_mask[..., None, None],
                               v_new.astype(jnp.float32),
                               v_fp.reshape(b_dim, s_max, kvh, d))
        attn = _cached_attention(qh, k_flat.astype(x.dtype),
                                 v_flat.astype(x.dtype), valid)
        with jax.named_scope(KV_UPDATE):
            kq, ks = quantize_kv_blocks(
                k_flat.reshape(b_dim, nb, bs, kvh, d))
            vq, vs = quantize_kv_blocks(
                v_flat.reshape(b_dim, nb, bs, kvh, d))
            sel5 = active[:, None, None, None, None]
            sel3 = active[:, None, None]
            layer = (jnp.where(sel5, kq, k_l), jnp.where(sel5, vq, v_l),
                     jnp.where(sel3, ks, ks_l), jnp.where(sel3, vs, vs_l))
            planes = tuple(
                jax.lax.dynamic_update_index_in_dim(p, new, l, 0)
                for p, new in zip(planes, layer))
        return attn, planes

    h, new_planes, _ = _scan_layers(
        x, params["layers"], cache[:-1], config, attention_step)
    y = _layernorm(h, params["ln_f"]["scale"], params["ln_f"]["bias"])
    lengths = lengths + active.astype(jnp.int32)
    cache_cls = QuantKVCache if quantized else KVCache
    new_cache = cache_cls(*new_planes, lengths)
    return (new_cache, y), y


def build_decode_step(config: ModelConfig, mesh: Mesh,
                      quantized: bool = False):
    """Jitted ``decode_step(carry, params, active) -> (carry, y)`` with
    ``carry = (cache, x)`` — ONE fixed-shape compile for the whole run.
    The carry is donated; its returned ``x`` is this step's output, so
    the engine (and the calibration harness's carry protocol) feeds
    ``out[0]`` straight back in."""

    @named("serve_decode_step")
    def decode_step(carry, params, active):
        return _decode_step_math(carry, params, active, config, mesh,
                                 quantized=quantized)

    x_sh = NamedSharding(mesh, decode_batch_spec(mesh))
    cache_sh = (quant_cache_shardings(mesh) if quantized
                else cache_shardings(mesh))
    return jax.jit(
        decode_step,
        donate_argnums=(0,),
        out_shardings=((cache_sh, x_sh), x_sh),
    )


def build_decode_fused(config: ModelConfig, mesh: Mesh, k: int,
                       quantized: bool = False):
    """Jitted ``decode_fused(carry, params, active, remaining) ->
    (carry, ys)`` — ``k`` decode steps fused into ONE ``lax.scan``
    dispatch over the donated ``(cache, x)`` carry (static ``k``; the
    engine keeps a power-of-two ladder of these).

    ``remaining[b]`` is slot ``b``'s step budget within this scan
    (``min(k, tokens_left)``, 0 for inactive slots): step ``i`` runs
    with ``active & (i < remaining)``, so a slot that completes
    mid-scan is masked inactive for the rest of the trips — its cache
    stops advancing exactly as if the per-step engine had deactivated
    it, and the ledger frees its blocks at scan exit.  ``ys`` stacks
    every step's output ``[k, max_batch, 1, H]`` (step t's row is the
    token each then-active slot generated at trip t)."""
    cache_cls = QuantKVCache if quantized else KVCache

    @named(f"serve_decode_k{k}")
    def decode_fused(carry, params, active, remaining):
        # the slot-lengths vector deliberately stays OUT of the scan
        # carry: its trajectory is fully determined by the replicated
        # (lengths0, active, remaining) inputs — lengths at trip i are
        # ``lengths0 + active * min(i, remaining)`` — so recomputing it
        # per trip keeps it replicated everywhere.  Carried through the
        # loop instead, GSPMD propagates the cache's dp sharding onto
        # it and re-gathers at the loop boundary — a (tiny, but
        # contract-breaking) collective the decode kind-set forbids.
        # The trip index rides the carry as a scalar for the same
        # reason (an arange-xs array invites an iota reshard).  The
        # cache's data planes ride positionally (``cache[:-1]`` — K/V,
        # plus the int8 scale planes when quantized), lengths excluded.
        cache0, x0 = carry
        lengths0 = cache0.lengths
        act_i32 = active.astype(jnp.int32)

        def step(c, _):
            *planes, x, i = c
            step_active = active & (i < remaining)
            lengths_i = lengths0 + act_i32 * jnp.minimum(i, remaining)
            (cache, x2), y = _decode_step_math(
                (cache_cls(*planes, lengths_i), x), params, step_active,
                config, mesh, quantized=quantized)
            return (*cache[:-1], x2, i + 1), y

        final, ys = jax.lax.scan(
            step, (*cache0[:-1], x0, jnp.int32(0)), None, length=k)
        *planes, x, _i = final
        lengths_f = lengths0 + act_i32 * jnp.minimum(jnp.int32(k),
                                                     remaining)
        return (cache_cls(*planes, lengths_f), x), ys

    x_sh = NamedSharding(mesh, decode_batch_spec(mesh))
    ys_sh = NamedSharding(mesh, P(None, *decode_batch_spec(mesh)))
    cache_sh = (quant_cache_shardings(mesh) if quantized
                else cache_shardings(mesh))
    return jax.jit(
        decode_fused,
        donate_argnums=(0,),
        out_shardings=((cache_sh, x_sh), ys_sh),
    )


@named("serve_inject")
def inject_token(carry, slot, vec):
    """Place a freshly-prefilled request's first token into the decode
    input buffer: ``x[slot, 0] = vec``."""
    cache, x = carry
    mask = (jnp.arange(x.shape[0]) == slot)[:, None, None]
    return cache, jnp.where(mask, vec[None, None, :].astype(x.dtype), x)


@named("serve_inject_greedy")
def inject_token_greedy(carry, slot, vec, table):
    """Token-mode admission inject: quantise the prefill's last output
    through the greedy token table (``tok = argmax(vec)``, ``x[slot, 0]
    = table[tok]``) and return the token id — the 4-byte scalar is the
    only thing that ever comes to host (the n-gram drafter's history
    seed + the equivalence gate's capture)."""
    cache, x = carry
    tok = jnp.argmax(vec).astype(jnp.int32)
    emb = jnp.take(table, tok, axis=0)
    return ((cache,
             jnp.where((jnp.arange(x.shape[0]) == slot)[:, None, None],
                       emb[None, None, :].astype(x.dtype), x)),
            tok)


@named("serve_inject_sampled")
def inject_token_sampled(carry, slot, tok, table):
    """Sampled-mode admission inject: the HOST already sampled the
    first token from the prefill's softmax (``temperature > 0``), so
    the device only embeds the committed id — ``x[slot, 0] =
    table[tok]`` (the greedy inject with the argmax replaced by the
    host's draw)."""
    cache, x = carry
    emb = jnp.take(table, tok.astype(jnp.int32), axis=0)
    return (cache,
            jnp.where((jnp.arange(x.shape[0]) == slot)[:, None, None],
                      emb[None, None, :].astype(x.dtype), x))


def build_decode_token_step(config: ModelConfig, mesh: Mesh):
    """Jitted token-feedback decode step: the per-step decode math
    (verbatim ``_decode_step_math``) followed by the greedy token
    quantisation — ``tok = argmax(y)``, next input ``table[tok]``.
    Returns ``(carry, tok [B])``; the token ids are the committed
    output (device argmax, never a host float transfer).  This is the
    speculative modes' pinned per-step oracle."""

    @named("serve_decode_token_step")
    def decode_token_step(carry, params, table, active):
        (cache, y), _ = _decode_step_math(carry, params, active, config,
                                              mesh)
        tok = jnp.argmax(y[:, 0, :], axis=-1).astype(jnp.int32)
        x2 = jnp.take(table, tok, axis=0)[:, None, :].astype(y.dtype)
        return (cache, x2), tok

    x_sh = NamedSharding(mesh, decode_batch_spec(mesh))
    dp_ax = decode_batch_spec(mesh)[0]
    return jax.jit(
        decode_token_step,
        donate_argnums=(0,),
        out_shardings=((cache_shardings(mesh), x_sh),
                       NamedSharding(mesh, P(dp_ax))),
    )


def build_decode_fused_token(config: ModelConfig, mesh: Mesh, k: int):
    """The fused K-step scan in token-feedback mode: identical trip
    structure to ``build_decode_fused`` (lengths recomputed per trip
    from the replicated inputs — same dp-reshard hazard, same fix) with
    the greedy token quantisation between trips.  Returns ``(carry,
    toks [k, B])``."""

    @named(f"serve_decode_token_k{k}")
    def decode_fused_token(carry, params, table, active, remaining):
        cache0, x0 = carry
        lengths0 = cache0.lengths
        act_i32 = active.astype(jnp.int32)

        def step(c, _):
            k_c, v_c, x, i = c
            step_active = active & (i < remaining)
            lengths_i = lengths0 + act_i32 * jnp.minimum(i, remaining)
            (cache, _x2), y = _decode_step_math(
                (KVCache(k_c, v_c, lengths_i), x), params, step_active,
                config, mesh)
            tok = jnp.argmax(y[:, 0, :], axis=-1).astype(jnp.int32)
            x2 = jnp.take(table, tok, axis=0)[:, None, :].astype(x.dtype)
            return (cache.k, cache.v, x2, i + 1), tok

        (k_c, v_c, x, _i), toks = jax.lax.scan(
            step, (cache0.k, cache0.v, x0, jnp.int32(0)), None, length=k)
        lengths_f = lengths0 + act_i32 * jnp.minimum(jnp.int32(k),
                                                     remaining)
        return (KVCache(k_c, v_c, lengths_f), x), toks

    x_sh = NamedSharding(mesh, decode_batch_spec(mesh))
    dp_ax = decode_batch_spec(mesh)[0]
    return jax.jit(
        decode_fused_token,
        donate_argnums=(0,),
        out_shardings=((cache_shardings(mesh), x_sh),
                       NamedSharding(mesh, P(None, dp_ax))),
    )


def _verify_forward(carry, params, table, draft_ids, active,
                    config: ModelConfig, mesh: Mesh):
    """The batched verify forward both verify programs run: the carry
    token and the γ drafted tokens of every slot through ONE ``[B, γ+1,
    H]`` ``_serve_block`` stack.  Per layer the γ+1 positions append
    their K/V at ``lengths + i`` (``append_token_rows``, the decode
    step's in-place row write with γ+1 rows a slot), exactly as γ+1
    sequential decode steps would, and attend under the per-slot
    offset-causal mask.  Returns ``(k, v, y [B, γ+1, H])``; what is
    committed of it is the caller's business."""
    n, d, kvh = config.num_heads, config.head_dim, config.kv_heads
    cache, x = carry
    b_dim, s_max = cache.max_batch, cache.max_seq
    g1 = draft_ids.shape[1] + 1
    lengths = cache.lengths
    d_emb = jnp.take(table, draft_ids, axis=0).astype(x.dtype)
    h0 = jnp.concatenate([x, d_emb], axis=1)        # [B, γ+1, H]
    pos = jnp.arange(s_max)[None, :]                # [1, S]
    offs = lengths[:, None] + jnp.arange(g1)[None, :]   # [B, γ+1]
    valid = pos[:, None, :] <= offs[:, :, None]     # [B, γ+1, S]

    def attention_step(q, k, v, cache_state):
        l, (k_c, v_c) = cache_state
        qh = _heads(q, n, d)                        # [B, n, γ+1, d]
        k_c = append_token_rows(k_c, k.reshape(b_dim, g1, kvh, d), l,
                                lengths, active, mesh)
        v_c = append_token_rows(v_c, v.reshape(b_dim, g1, kvh, d), l,
                                lengths, active, mesh)
        attn = _verify_attention(qh, _layer_tokens(k_c, l),
                                 _layer_tokens(v_c, l), valid)
        return (attn.transpose(0, 2, 1, 3).reshape(b_dim, g1, n * d),
                ((k_c, v_c), None))

    h, (k_new, v_new), _ = _scan_layers(
        h0, params["layers"], (cache.k, cache.v), config, attention_step)
    y = _layernorm(h, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return k_new, v_new, y


def build_verify_step(config: ModelConfig, mesh: Mesh, gamma: int):
    """Jitted draft-and-verify target forward: the γ proposed tokens of
    every slot run through ONE batched ``[max_batch, γ+1, H]``
    ``_serve_block`` stack under the per-slot offset-causal mask
    (``_verify_attention``) — one fused forward per verify unit, zero
    per-draft-token dispatches or collectives (audited:
    ``verify_step_expectation``).

    Inputs: the donated ``(cache, x)`` carry, the token table, the
    drafters' ``draft_ids [B, γ]``, ``active`` and ``remaining`` (each
    slot's output-token budget).  Per layer, all γ+1 positions append
    K/V at ``lengths + i`` (``append_token_rows``, the decode-step
    append with γ+1 rows a slot), exactly as γ+1 sequential decode
    steps would.

    Greedy acceptance: ``tok = argmax(y)`` gives the target's true
    token at every position; the accepted prefix length is the run of
    leading draft/target matches, and ``commits = min(accepted+1,
    remaining)`` (the +1 is the verify's own bonus token — the target
    output at the first mismatch position, whose input was still a
    verified token).  New lengths advance by ``commits``; the rejected
    suffix's cache entries are DEAD BY CONSTRUCTION — attention is
    length-masked, and the next unit's writes land at the committed
    lengths, overwriting every rejected position before any later
    query's mask can reach it (asserted by the token-identity tests,
    never copied or zeroed).  ``x'`` is the last committed token's
    embedding, so the carry protocol is unchanged.

    Returns ``(carry, tok [B, γ+1], commits [B])``; tok/commits stay
    dp-sharded (no boundary gather — the host reads them at the unit's
    sync)."""

    @named(f"serve_spec_verify_g{gamma}")
    def verify_step(carry, params, table, draft_ids, active, remaining):
        cache, x = carry
        lengths = cache.lengths
        k_new, v_new, y = _verify_forward(carry, params, table, draft_ids,
                                          active, config, mesh)
        tok = jnp.argmax(y, axis=-1).astype(jnp.int32)  # [B, γ+1]
        match = (tok[:, :gamma] == draft_ids).astype(jnp.int32)
        accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [B]
        commits = jnp.where(active,
                            jnp.minimum(accepted + 1, remaining),
                            0).astype(jnp.int32)
        lengths_f = (lengths + commits).astype(jnp.int32)
        last = jnp.take_along_axis(
            tok, jnp.maximum(commits - 1, 0)[:, None], axis=1)[:, 0]
        x_new = jnp.take(table, last, axis=0)[:, None, :].astype(x.dtype)
        x_f = jnp.where(active[:, None, None], x_new, x)
        return (KVCache(k_new, v_new, lengths_f), x_f), tok, commits

    x_sh = NamedSharding(mesh, decode_batch_spec(mesh))
    dp_ax = decode_batch_spec(mesh)[0]
    return jax.jit(
        verify_step,
        donate_argnums=(0,),
        out_shardings=((cache_shardings(mesh), x_sh),
                       NamedSharding(mesh, P(dp_ax, None)),
                       NamedSharding(mesh, P(dp_ax))),
    )


def build_verify_probs(config: ModelConfig, mesh: Mesh, gamma: int):
    """The SAMPLED verify's device half: ``build_verify_step``'s exact
    batched γ+1-position forward (same K/V appends at ``lengths +
    i``, same offset-causal mask), but acceptance moves to
    the HOST — the program returns the raw verify logits ``y [B, γ+1,
    H]`` and commits NOTHING: lengths and ``x`` come back unchanged,
    so the appended-but-uncommitted cache positions sit past every
    slot's length (dead by the usual mask construction) until the
    host's residual-sampling pass decides the true commits and the
    tiny ``build_spec_commit`` program advances the carry.  Re-running
    the program on the returned carry is therefore idempotent — the
    retry ladder's contract.

    ``gamma=0`` degenerates to a plain decode step that returns its
    softmax-able logits without committing — the sampled path's
    cold-drafter fallback unit (one sampled token per trip)."""

    @named(f"serve_spec_probs_g{gamma}")
    def verify_probs(carry, params, table, draft_ids, active):
        cache, x = carry
        k_new, v_new, y = _verify_forward(carry, params, table, draft_ids,
                                          active, config, mesh)
        return (KVCache(k_new, v_new, cache.lengths), x), y

    x_sh = NamedSharding(mesh, decode_batch_spec(mesh))
    dp_ax = decode_batch_spec(mesh)[0]
    return jax.jit(
        verify_probs,
        donate_argnums=(0,),
        out_shardings=((cache_shardings(mesh), x_sh),
                       NamedSharding(mesh, P(dp_ax, None, None))),
    )


def build_spec_commit(config: ModelConfig, mesh: Mesh):
    """The sampled verify's commit half: the host's residual-sampling
    pass decided ``commits`` (per-slot committed window length) and
    ``next_ids`` (each slot's LAST committed token — the next unit's
    input); this tiny program advances lengths by the commits and
    re-embeds ``x`` from the token table, completing exactly the carry
    protocol ``build_verify_step`` applies on device for the greedy
    law.  The rejected suffix needs no cleanup — same dead-by-
    construction argument as the greedy verify."""

    @named("serve_spec_commit")
    def spec_commit(carry, table, next_ids, commits, active):
        cache, x = carry
        lengths_f = (cache.lengths + commits).astype(jnp.int32)
        emb = jnp.take(table, next_ids, axis=0)[:, None, :].astype(x.dtype)
        x_f = jnp.where(active[:, None, None], emb, x)
        return (KVCache(cache.k, cache.v, lengths_f), x_f)

    x_sh = NamedSharding(mesh, decode_batch_spec(mesh))
    return jax.jit(
        spec_commit,
        donate_argnums=(0,),
        out_shardings=(cache_shardings(mesh), x_sh),
    )


def build_draft_scan(config: ModelConfig, mesh: Mesh, gamma: int):
    """Jitted draft-model proposal scan: γ greedy token-feedback decode
    steps of the SHALLOW draft transformer over its own donated paged
    cache plane — ``draft_scan(cache, params, table, x, lengths,
    active) -> (cache, draft_ids [B, γ])``.

    ``x`` is the TARGET's current carry input (the draft shares the
    target's hidden size and token table, so the committed-token
    embedding is the right draft input); ``lengths`` are the HOST'S
    committed lengths, passed explicitly — this IS the draft plane's
    rejection rollback: the cache's own lengths leaf (advanced by γ
    last unit) is simply overridden, and entries past the committed
    lengths are dead by the same length-mask construction as the
    target's.  The ids stay on device (dp-sharded) and flow straight
    into the verify step — no host round-trip in the draft-verify
    chain."""

    @named(f"serve_spec_draft_g{gamma}")
    def draft_scan(cache, params, table, x, lengths, active):
        act_i32 = active.astype(jnp.int32)

        def step(c, _):
            k_c, v_c, x_c, i = c
            lengths_i = lengths + act_i32 * i
            (cache_i, _x2), y = _decode_step_math(
                (KVCache(k_c, v_c, lengths_i), x_c), params, active,
                config, mesh)
            tok = jnp.argmax(y[:, 0, :], axis=-1).astype(jnp.int32)
            x2 = jnp.take(table, tok, axis=0)[:, None, :].astype(x_c.dtype)
            return (cache_i.k, cache_i.v, x2, i + 1), tok

        (k_c, v_c, _x, _i), toks = jax.lax.scan(
            step, (cache.k, cache.v, x, jnp.int32(0)), None, length=gamma)
        lengths_f = lengths + act_i32 * gamma
        return KVCache(k_c, v_c, lengths_f), toks.T    # ids [B, γ]

    dp_ax = decode_batch_spec(mesh)[0]
    return jax.jit(
        draft_scan,
        donate_argnums=(0,),
        out_shardings=(cache_shardings(mesh),
                       NamedSharding(mesh, P(dp_ax, None))),
    )


# ---------------------------------------------------------------------------
# what the scheduler asks of a family (``serve/engine.py::family_for``)
# ---------------------------------------------------------------------------

# the decode programs feed hidden states back, not token ids
TOKENS_FED_BACK = False
# slots whose logits a decode program returns each step: the GPT block
# has no vocabulary
PROBES = 0
# what this family's serving path does not have, and the reason given
LACKS = {
    "probe": "probe() needs a layer_types model: the GPT block has no "
             "vocabulary and no logits",
}


def check_serving(config: ModelConfig, serving: Any) -> None:
    """Refuse what this family cannot serve: nothing beyond what
    ``ServingConfig.validate`` refuses for every family."""


def register_metrics(registry: Any, config: ModelConfig, serving: Any,
                     tp: int) -> None:
    """This family's own counters and gauges: none."""


def slot_recycled(registry: Any, rid: int, slot: int) -> None:
    """A slot that served a request is given to ``rid``: nothing to
    clear, K/V past a slot's length is dead by the length mask."""


def attend_tiles(config: ModelConfig, cache: Any,
                 mesh: Mesh) -> tuple[str, int]:
    """The decode kernel fetches the fp layout's K/V planes by tiles of
    this many tokens (``serve_kv_tiles_live`` / ``_held`` count by
    them); refuses planes it cannot read on the chip."""
    check_kernel_takes(cache.k, mesh)
    return "kv", plane_tile_tokens(cache.k, mesh)


def chunk_attn_counts(config: ModelConfig, start: int,
                      chunk_len: int) -> tuple[int, int]:
    """The layer attentions of the chunk program at ``start`` that run as
    the ``chunk_attend`` kernel (none: this family's chunk is
    ``attend._chunk_attention``) and those that run dense (every
    layer)."""
    return 0, config.num_layers


def gmm_counts(config: ModelConfig, tokens: int) -> tuple[int, int]:
    """The grouped expert products of one step or chunk by contraction
    tiles (whole, split): none, this family's block has no routed
    experts."""
    return 0, 0


def report_shares(config: ModelConfig, samples: dict,
                  serving: Any = None) -> dict:
    """What the report says of this family's own layers: nothing."""
    return {}


def fresh_carry(config: ModelConfig, serving: Any, mesh: Mesh):
    """The decode carry of an empty engine: zeroed cache (the int8
    layout under ``kv_quantization="int8"``), zero input."""
    create = (create_quant_kv_cache if serving.kv_quantization == "int8"
              else create_kv_cache)
    cache = create(
        config, serving.max_batch, serving.num_blocks,
        serving.block_size, mesh=mesh,
    )
    x = jax.device_put(
        jnp.zeros((serving.max_batch, 1, config.hidden_size),
                  _dtype_of(config.dtype)),
        NamedSharding(mesh, decode_batch_spec(mesh)),
    )
    return (cache, x)


def prompt_input(config: ModelConfig, req: Request, pad_to: int,
                 dtype: Any) -> jax.Array:
    """A request's prompt as the chunk programs take it: seeded
    embeddings ``[1, pad_to, hidden]``."""
    return request_embeddings(
        req.seed, req.prompt_len, config.hidden_size,
        dtype=dtype, pad_to=pad_to,
        prefix_len=req.prefix_len, prefix_seed=req.prefix_seed)


def decode_programs(config: ModelConfig, mesh: Mesh, ks: tuple[int, ...],
                    quantized: bool = False, probe: Any = None):
    """The single step and the fused ladder ``{k: program}``, each
    ``(carry, params, active[, remaining]) -> (carry, ys)``.  ``probe``
    (the slots whose logits to return) is for a family with ``PROBES``."""
    return (build_decode_step(config, mesh, quantized=quantized),
            {k: build_decode_fused(config, mesh, k, quantized=quantized)
             for k in ks})
