"""The serving programs of a ``layer_types`` model (``models/hybrid.py``),
one of the two block families the scheduler of ``serve/engine.py`` serves:
the same three kinds of program as ``serve/gpt.py`` holds for the GPT
block, under the same names, over a :class:`~dlbb_tpu.serve.kvcache.
HybridCache`.

- ``serve_prefill_chunk_o<offset>``: one prompt chunk of token ids.  The
  full-attention layers do what the GPT chunk does (offset-causal
  attention over the carried prefix K/V, one block write through
  ``write_slot_blocks``); the linear-attention layers run the chunked
  gated delta rule from the state the previous chunk handed on and write
  the slot's state and convolution inputs.  The carried ``prefix`` is
  ``(k, v, state, conv)``; the one a prompt starts from is all zeros
  (:func:`create_prefix`), which is what clears a recycled slot.
- ``serve_decode_step`` / ``serve_decode_k<K>``: embed each slot's
  pending token, one recurrent step (state and K/V updated in place in
  the carried planes), logits, greedy ``argmax`` fed back on the device.
- ``serve_inject``: a finished prefill's first token into its slot.
- ``serve_probe_state``: a copy of one slot's recurrent state, for a
  probed request only (twice in its life).

The decode carry is ``(cache, tokens [max_batch] int32)``.  Every decode
program also returns the float32 logits of the two slots named by its
``probe`` argument, each step: what a checker (``ServingEngine.probe``)
holds on the device to compare with a reference.  The programs are the
same whether or not anything is probed.

The block and the period are ``models/hybrid.py``'s; this file holds the
three mixers that touch the cache, and at its end what the scheduler
asks of a family (the seam: ``docs/serving.md``, "Adding a block
family").
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.data.synthetic import prompt_ids_from_seed
from dlbb_tpu.models import hybrid
from dlbb_tpu.models.configs import (
    FULL_ATTENTION,
    LINEAR_ATTENTION,
    ModelConfig,
    kv_cache_bytes,
    state_cache_bytes,
)
from dlbb_tpu.models.hybrid import LIN_CONV, LIN_CORE
from dlbb_tpu.models.transformer import _dtype_of, named
from dlbb_tpu.ops.decode_attention import decode_attention
from dlbb_tpu.ops.gated_delta import (
    causal_conv,
    gated_delta_chunked,
    gated_delta_step,
)
from dlbb_tpu.obs import spans
from dlbb_tpu.serve.attend import _chunk_attention, _layer_of
from dlbb_tpu.serve.kvcache import (
    HybridCache,
    append_token_rows,
    create_hybrid_cache,
    hybrid_cache_shardings,
    hybrid_cache_specs,
    write_slot_blocks,
    write_slot_state,
)
from dlbb_tpu.serve.traffic import Request


def token_spec(mesh: Mesh) -> P:
    """Decode tokens ``[max_batch]``: slots over dp."""
    return P(hybrid_cache_specs(mesh).k[1])


def prefix_specs(mesh: Mesh) -> tuple[P, P, P, P]:
    """The chunk carry ``(k, v, state, conv)``: no slot dim, heads over
    tp (``gpt.prefix_spec`` for K/V)."""
    tp = hybrid_cache_specs(mesh).k[4]
    kv = P(None, None, tp, None)
    return (kv, kv, P(None, tp, None, None), P(None, None, tp, None))


def create_prefix(config: ModelConfig, mesh: Mesh) -> tuple:
    """The carry a prompt's first chunk starts from: no prefix K/V, a
    ZERO recurrent state and zeros before the convolution."""
    dtype = _dtype_of(config.dtype)
    n_lin = config.layers_of(LINEAR_ATTENTION)
    heads = config.linear_num_value_heads
    kv = jnp.zeros((config.layers_of(FULL_ATTENTION), 0, config.kv_heads,
                    config.head_dim), dtype)
    state = jnp.zeros((n_lin, heads, config.linear_value_head_dim,
                       config.linear_key_head_dim), hybrid.STATE_DTYPE)
    conv = jnp.zeros((n_lin, config.linear_conv_kernel_dim - 1, heads,
                      config.linear_conv_channels // heads), dtype)
    return tuple(jax.device_put(t, NamedSharding(mesh, s))
                 for t, s in zip((kv, kv, state, conv), prefix_specs(mesh)))


def _pad_heads(t: jax.Array, heads: int) -> jax.Array:
    """``[..., kvh, d]`` with zero heads added up to the ``heads`` a
    cache plane holds (``models.configs.cache_kv_heads``)."""
    extra = heads - t.shape[-2]
    if not extra:
        return t
    return jnp.pad(t, [(0, 0)] * (t.ndim - 2) + [(0, extra), (0, 0)])


def _per_period(t: jax.Array, periods: int) -> jax.Array:
    """``[L_kind, ...]`` as the period scan's ``xs``: ``[periods,
    L_kind / periods, ...]``."""
    return t.reshape((periods, t.shape[0] // periods) + t.shape[1:])


def _per_layer(t: jax.Array) -> jax.Array:
    return t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:])


class ChunkMixer:
    """One period of one prompt chunk (batch 1) at static offset
    ``start``: ``xs`` are the period's slices of the carried prefix."""

    def __init__(self, config: ModelConfig, xs: tuple, slot, n_valid,
                 start: int, chunk_len: int, block_size: int) -> None:
        self.config, self.xs = config, xs
        self.slot, self.n_valid = slot, n_valid
        self.start, self.chunk_len, self.bs = start, chunk_len, block_size
        self.out: tuple[list, list, list, list] = ([], [], [], [])

    def collect(self):
        return tuple(jnp.stack(o) for o in self.out)

    def attention(self, q, k, v, l, planes):
        pk, pv = self.xs[0], self.xs[1]
        j = len(self.out[0])
        k_all = jnp.concatenate([pk[j], k[0]], axis=0)
        v_all = jnp.concatenate([pv[j], v[0]], axis=0)
        attn = _chunk_attention(q.transpose(0, 2, 1, 3), k_all, v_all,
                                self.start)
        k_c, v_c, st, cv = planes
        blocks = (self.chunk_len // self.bs, self.bs) + k_c.shape[-2:]
        k_c = write_slot_blocks(
            k_c, _pad_heads(k[0], k_c.shape[-2]).reshape(blocks), l,
            self.slot, self.start // self.bs)
        v_c = write_slot_blocks(
            v_c, _pad_heads(v[0], v_c.shape[-2]).reshape(blocks), l,
            self.slot, self.start // self.bs)
        self.out[0].append(k_all)
        self.out[1].append(v_all)
        return attn.transpose(0, 2, 1, 3), (k_c, v_c, st, cv)

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
            k_c, v_c, st, cv = planes
            st = write_slot_state(st, state, l, self.slot)
            cv = write_slot_state(cv, tail, l, self.slot)
        self.out[2].append(state)
        self.out[3].append(tail)
        return o, (k_c, v_c, st, cv)


def build_prefill_chunk(config: ModelConfig, mesh: Mesh, chunk_len: int,
                        start: int, quantized: bool = False):
    """Jitted ``prefill_chunk(cache, prefix, params, ids [1, chunk],
    slot, length) -> (cache, prefix, logits_last [vocab])``, the
    signature of ``gpt.build_prefill_chunk`` with token ids for
    embeddings and float32 logits for the last hidden state.
    ``quantized`` is the seam's: this family has the fp layout only
    (``models.configs.validate_serving`` refuses int8)."""
    periods = config.num_layers // len(config.layer_types)

    @named(f"serve_prefill_chunk_o{start}")
    def prefill_chunk(cache, prefix, params, ids, slot, length):
        n_valid = jnp.clip(length - start, 0, chunk_len)
        xs = tuple(_per_period(t, periods) for t in prefix)
        h = hybrid.embed_tokens(params, ids)
        h, planes, ys = hybrid.scan_periods(
            h, params["periods"], config,
            lambda xs_p: ChunkMixer(config, xs_p, slot, n_valid, start,
                                    chunk_len, cache.block_size),
            cache[:-1], xs)
        local = jnp.clip(length - 1 - start, 0, chunk_len - 1)
        h_last = jax.lax.dynamic_index_in_dim(h[0], local, 0, keepdims=False)
        new_len = jnp.minimum(length, start + chunk_len)
        lengths = jnp.where(jnp.arange(cache.max_batch) == slot,
                            new_len, cache.lengths).astype(jnp.int32)
        return (HybridCache(*planes, lengths),
                tuple(_per_layer(t) for t in ys),
                hybrid.logits_of(params, h_last, config))

    pre_sh = tuple(NamedSharding(mesh, s) for s in prefix_specs(mesh))
    return jax.jit(
        prefill_chunk, donate_argnums=(0,),
        out_shardings=(hybrid_cache_shardings(mesh), pre_sh,
                       NamedSharding(mesh, P())))


class DecodeMixer:
    """One token a slot: append and attend in the K/V planes, one
    recurrent step in the state planes, both in place in the carry."""

    def __init__(self, config: ModelConfig, mesh: Mesh, lengths, active
                 ) -> None:
        self.config, self.mesh = config, mesh
        self.lengths, self.active = lengths, active

    def collect(self):
        return None

    def attention(self, q, k, v, l, planes):
        k_c, v_c, st, cv = planes
        heads, held = q.shape[2], k_c.shape[-2]
        k_c = append_token_rows(k_c, _pad_heads(k, held), l, self.lengths,
                                self.active, self.mesh)
        v_c = append_token_rows(v_c, _pad_heads(v, held), l, self.lengths,
                                self.active, self.mesh)
        # the plane's added heads are attended by zero queries and cut
        attn = decode_attention(
            _pad_heads(q, held).transpose(0, 2, 1, 3), k_c, v_c, l,
            self.lengths, self.active, self.mesh)
        return (attn.transpose(0, 2, 1, 3)[:, :, :heads],
                (k_c, v_c, st, cv))

    def linear(self, qkv, log_alpha, beta, conv_w, l, planes):
        k_c, v_c, st, cv = planes
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
        return o[:, None], (k_c, v_c, st, cv)


def _decode_math(carry, params, active, probe, config: ModelConfig,
                 mesh: Mesh):
    """One decode step, shared verbatim by the per-step program and
    every trip of the fused scan.  Returns ``(carry, tokens, logits of
    the probed slots [PROBES, vocab])``."""
    cache, tok = carry
    mixer = DecodeMixer(config, mesh, cache.lengths, active)
    h = hybrid.embed_tokens(params, tok)[:, None, :]
    h, planes, _ = hybrid.scan_periods(h, params["periods"], config,
                                       lambda _xs: mixer, cache[:-1])
    logits = hybrid.logits_of(params, h[:, 0], config)
    new_tok = jnp.where(active, jnp.argmax(logits, axis=-1).astype(tok.dtype),
                        tok)
    lengths = cache.lengths + active.astype(jnp.int32)
    return ((HybridCache(*planes, lengths), new_tok), new_tok,
            jnp.take(logits, probe, axis=0))


def _decode_shardings(mesh: Mesh):
    tok_sh = NamedSharding(mesh, token_spec(mesh))
    return (hybrid_cache_shardings(mesh), tok_sh), tok_sh


def build_decode_step(config: ModelConfig, mesh: Mesh):
    """Jitted ``decode_step(carry, params, active, probe) -> (carry,
    tokens [B], probe logits [PROBES, vocab])``; the carry is donated."""

    @named("serve_decode_step")
    def decode_step(carry, params, active, probe):
        return _decode_math(carry, params, active, probe, config, mesh)

    carry_sh, tok_sh = _decode_shardings(mesh)
    return jax.jit(decode_step, donate_argnums=(0,),
                   out_shardings=(carry_sh, tok_sh,
                                  NamedSharding(mesh, P())))


def build_decode_fused(config: ModelConfig, mesh: Mesh, k: int):
    """``k`` decode steps in one ``lax.scan``, as
    ``gpt.build_decode_fused``: lengths recomputed each trip from the
    replicated inputs, planes and tokens in the carry.  Returns
    ``(carry, tokens [k, B], probe logits [k, PROBES, vocab])``."""

    @named(f"serve_decode_k{k}")
    def decode_fused(carry, params, active, remaining, probe):
        cache0, tok0 = carry
        lengths0 = cache0.lengths
        act_i32 = active.astype(jnp.int32)

        def step(c, _):
            *planes, tok, i = c
            lengths_i = lengths0 + act_i32 * jnp.minimum(i, remaining)
            (cache, tok2), out, seen = _decode_math(
                (HybridCache(*planes, lengths_i), tok), params,
                active & (i < remaining), probe, config, mesh)
            return (*cache[:-1], tok2, i + 1), (out, seen)

        (*planes, tok, _i), (toks, seen) = jax.lax.scan(
            step, (*cache0[:-1], tok0, jnp.int32(0)), None, length=k)
        lengths_f = lengths0 + act_i32 * jnp.minimum(jnp.int32(k), remaining)
        return (HybridCache(*planes, lengths_f), tok), toks, seen

    carry_sh, tok_sh = _decode_shardings(mesh)
    toks_sh = NamedSharding(mesh, P(None, *token_spec(mesh)))
    return jax.jit(decode_fused, donate_argnums=(0,),
                   out_shardings=(carry_sh, toks_sh,
                                  NamedSharding(mesh, P())))


@named("serve_inject")
def inject_token(carry, slot, logits):
    """A finished prefill's first token, the ``argmax`` of its last
    position's logits, into the decode token buffer."""
    cache, tok = carry
    first = jnp.argmax(logits).astype(tok.dtype)
    return cache, jnp.where(jnp.arange(tok.shape[0]) == slot, first, tok)


@jax.jit
@named("serve_probe_state")
def slot_state(cache: HybridCache, slot) -> jax.Array:
    """A copy of one slot's recurrent state ``[L_lin, heads, d_v, d_k]``:
    what ``ServingEngine.probe`` keeps of a probed request, after its
    prompt and after its last decode step."""
    return jax.lax.dynamic_index_in_dim(cache.state, slot, axis=1,
                                        keepdims=False)


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
    its mechanism (ROADMAP.md, Queue 2); int8 KV is refused in
    ``models.configs.validate_serving``."""
    if serving.speculation != "off":
        raise ValueError(
            f"serving.speculation={serving.speculation!r} is not "
            "implemented for layer_types models: a rejected draft "
            "needs the recurrent state rolled back, and the state "
            "cache keeps no snapshots")
    if serving.prefix_caching:
        raise ValueError(
            "serving.prefix_caching is not implemented for "
            "layer_types models: attaching to shared blocks needs "
            "the recurrent state as it was at the block boundary, "
            "and the state cache keeps no snapshots")
    if serving.prefill_chunk is None:
        raise ValueError(
            "layer_types models are prefilled in chunks: set "
            "serving.prefill_chunk (the chunk program hands the "
            "recurrent state from chunk to chunk; there is no "
            "monolithic prefill program)")


def register_metrics(registry: Any, config: ModelConfig, serving: Any,
                     tp: int) -> None:
    """This family's own counter and gauges: slot recycling, and what
    each kind of cache holds."""
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
             "layers only)")


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
    program takes as its last argument, and ``ys`` is the pair
    ``(tokens, logits of the probed slots)``."""
    def bound(program):
        def call(carry, params, *masks):
            carry, toks, seen = program(carry, params, *masks, probe())
            return carry, (toks, seen)
        return call

    return (bound(build_decode_step(config, mesh)),
            {k: bound(build_decode_fused(config, mesh, k)) for k in ks})
