"""Continuous-batching inference engine over the paged KV-cache.

Two jitted device programs, fixed shapes for the whole run:

- **prefill** (one compile per sequence-length *bucket*): runs the full
  transformer stack over one request's ``[1, bucket, H]`` prompt with
  ordinary causal attention, writes its K/V into the request's cache
  slot (one in-place block write — see ``serve/kvcache.py``), sets
  the slot length, and returns the last real token's output — the
  request's FIRST generated token (TTFT stops here).
- **decode_step** (one compile, ``[max_batch, 1, H]``): appends each
  active slot's pending token to the cache at its own length, attends
  over the slot's valid prefix (GQA-grouped at ``kv_heads`` width; the
  fp layout through ``ops/decode_attention.py``, which fetches only the
  tiles of tokens a slot holds), and produces every active slot's next
  token.
  The output hidden state IS the next step's input embedding (the model
  is its own next-token function — same convention as the chained
  timing loop), so the decode carry ``(cache, x)`` feeds back without
  any host round-trip, and both leaves are donated.

Around them, a host-side continuous-batching scheduler (Orca-style
iteration-level scheduling): arrivals from a ``TrafficTrace`` pass
admission control (bounded queue — overflow is a *rejected* request),
waiting requests are granted slots + worst-case block reservations at
step boundaries, completed requests free both immediately, and the next
decode step runs with whatever mix of old and new requests is resident.
Per-phase obs spans (``serve-admission`` / ``serve-prefill`` /
``serve-decode``), request-lifecycle events into the resilience journal,
and live MetricsRegistry counters/gauges come for free from the
machinery the sweep engine already has.

Communication contract (audited — ``analysis/hlo_audit.py`` decode and
prefill targets, ``plan_expected_kinds(decode=True)``): a decode step
may contain only the tiny per-token TP collectives (row-parallel psums
of ``[max_batch, 1, H]`` + QKV realignment permutes); the cache never
crosses the wire.  A byte ceiling of activation size proves no step
accidentally re-gathers the KV-cache.

Decode fast path (``docs/serving.md``, all off by default so the
engine's legacy per-step behaviour is bit-for-bit preserved):

- **fused multi-step decode** (``decode_horizon > 1``): when the ledger
  knows no scheduling event is imminent, the next K decode steps run as
  ONE jitted ``lax.scan`` over the donated ``(cache, x)`` carry — one
  host dispatch instead of K.  K is chosen per step as
  ``min(horizon_cap, steps_until_next_event)`` (next event = the
  earliest completion while anything is waiting for a slot, else the
  batch's full drain), rounded down to a power-of-two bucket so the
  scan retraces at most ``log2(horizon)`` times.  Slots that complete
  mid-scan are masked inactive INSIDE the scan by a per-slot
  ``remaining`` step budget, so logits stay equivalent to the per-step
  engine; their block frees happen at scan exit.
- **host-overlap dispatch** (``inflight_window > 1``): decode units are
  dispatched without ``block_until_ready`` into a bounded in-flight
  window (dispatch N+1 while N computes); syncs happen only at scan
  boundaries — window full, an admission about to prefill, idle, or
  run end.  TTFT stays honest: the first token is synced exactly as in
  the per-step engine (prefill blocks on ``y_last``).
- **chunked prefill** (``prefill_chunk``): long prompts split into
  fixed-size chunks (block-multiples, one jit per static chunk offset
  reusing ``_serve_block``) interleaved with decode steps, so a long
  admission no longer head-of-line-blocks the resident decode batch.
  Each chunk writes its K/V blocks exactly as monolithic prefill does
  and carries the running prefix K/V explicitly ([L, start, kvh, d],
  no slot dim) so the cache is never re-read across the slot shard.
- **slot compaction** (``compact_threshold``, dp=1 meshes only): when
  occupancy drops to or below the threshold, active slots are
  gather-repacked into a half-size decode batch bucket for the fused
  scan and scattered back at scan exit — priced as a measured variant
  (``scripts/bench_serving.py``), never assumed to win.

Resilience (``docs/resilience.md``, serving faults): every fault site
fires strictly on the HOST side of a dispatch boundary — the jitted
programs above are byte-identical with or without an active plan
(statically pinned).  A transiently-failed prefill/decode dispatch
rolls the host ledger/slot bookkeeping back to a pre-dispatch snapshot
and re-issues with exponential backoff; exhausted retries fail only
the affected requests, journaled ``request-failed`` with full
exception chains — never the run.  ``dispatch_deadline_factor`` arms
an EMA-scaled watchdog (the PR-5 daemon-thread pattern) that abandons
a hung dispatch or window sync and continues on a fresh carry.
Requests may carry per-arrival SLO deadlines (blown queue heads shed
as ``request-rejected[reason=deadline]``, late completions counted).
SIGTERM under the run's ``PreemptionGuard`` drains gracefully:
admission stops, the in-flight window settles, resident requests are
journaled ``request-preempted``, and the report carries the
remaining-rid cursor ``serve/bench.py`` checkpoints for
``cli serve --resume``.
"""

from __future__ import annotations

import math
import os
import signal
import threading
import time
from collections import deque
from dataclasses import dataclass, field, replace as dc_replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.data.synthetic import (
    prompt_ids_from_seed,
    prompt_token_ids,
    request_embeddings,
    token_embedding_table,
)
from dlbb_tpu.models.configs import (
    FULL_ATTENTION,
    ModelConfig,
    state_cache_bytes,
    kv_cache_bytes,
    validate_serving,
)
from dlbb_tpu.models.attention import dense_attention
from dlbb_tpu.models.transformer import (
    ATTN_CORE,
    ATTN_OUT,
    ATTN_QKV,
    LN1,
    LN2,
    MLP_ACT,
    MLP_DOWN,
    MLP_UP,
    SERVE_PHASES,
    _dtype_of,
    _layernorm,
    init_params_sharded,
    named,
)
from dlbb_tpu.obs import spans
from dlbb_tpu.obs.export import MetricsRegistry
from dlbb_tpu.ops.decode_attention import (
    check_kernel_takes,
    decode_attention,
    live_tile_counts,
    plane_tile_tokens,
)
from dlbb_tpu.resilience import inject
from dlbb_tpu.resilience.errors import (
    CorruptStats,
    DeadlineExceeded,
    InjectedFault,
    TransientFault,
    exception_chain,
    is_transient,
)
from dlbb_tpu.resilience.preempt import PreemptionGuard
from dlbb_tpu.serve.kvcache import (
    BlockLedger,
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
from dlbb_tpu.serve.traffic import Request, TrafficTrace
from dlbb_tpu.utils.metrics import Timer, summarize

SERVING_REPORT_SCHEMA = "dlbb_serving_report_v1"

# decode feedback / drafting modes (ServingConfig.speculation):
# "off" = legacy continuous hidden-state feedback; "greedy" = token
# feedback without drafting (the speculative modes' pinned oracle);
# "ngram" / "draft-model" = draft-and-verify speculative decoding
SPECULATION_MODES = ("off", "greedy", "ngram", "draft-model")


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


def _default_buckets(block_size: int, max_seq: int) -> tuple[int, ...]:
    """Doubling bucket ladder: block_size, 2x, 4x, ... up to max_seq."""
    buckets = []
    b = block_size
    while b < max_seq:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq)
    return tuple(buckets)


@dataclass(frozen=True)
class ServingConfig:
    """The serving envelope (YAML ``serving:`` section).

    max_batch:       decode slots (the fixed decode batch dim).
    block_size:      tokens per cache block.
    max_seq:         per-slot capacity (prompt + output ceiling); must be
                     a block multiple — ``num_blocks = max_seq/block_size``.
    prefill_buckets: sequence-length buckets prefill compiles at
                     (block-multiples; default: doubling ladder up to
                     max_seq).  A prompt pads to the smallest bucket >= it.
    queue_capacity:  admission-control bound; an arrival finding the
                     queue full is REJECTED (counted, journaled).
    blocks_budget:   global cache-block budget the ledger enforces
                     (default: the physical pool, max_batch x num_blocks;
                     set lower to model cache pressure).
    hbm_budget_gb:   per-device HBM budget the build-time footprint gate
                     (``models.configs.validate_serving``) checks the
                     KV-cache against; None disables the gate.
    decode_horizon:  fused-scan horizon cap K (1 = the legacy per-step
                     engine; >1 fuses up to K decode steps into one
                     jitted lax.scan dispatch, bucketed by powers of 2).
    inflight_window: bounded in-flight decode dispatch window (1 = sync
                     every unit, the legacy behaviour; >1 dispatches the
                     next unit while the previous computes and syncs
                     only at scan boundaries).
    prefill_chunk:   tokens per prefill chunk (a block multiple; None =
                     monolithic bucketed prefill).  Long prompts are
                     processed chunk-by-chunk, interleaved with decode
                     steps for the resident batch.
    compact_threshold: occupancy fraction (0, 0.5] at or below which the
                     fused decode scan runs on a gather-compacted
                     half-size batch bucket (dp=1 meshes only; None
                     disables).  A measured variant, not a default win.
    reject_infeasible: reject-and-journal requests the envelope cannot
                     serve (reason="infeasible") instead of failing the
                     whole trace up front (the strict default).
    max_dispatch_retries: bounded retries (exponential backoff) for a
                     transiently-failed prefill/decode dispatch; each
                     retry rolls the host ledger/slot state back to the
                     pre-dispatch snapshot first.  Exhaustion fails only
                     the affected requests (journaled ``request-failed``
                     with the exception chain), never the run.
    retry_backoff_s: base backoff delay; attempt N sleeps
                     ``retry_backoff_s * 2**(N-1)``.
    dispatch_deadline_factor: arms the in-flight dispatch watchdog: a
                     decode unit (or its sync) exceeding
                     ``max(dispatch_deadline_min_s, factor * k *
                     per-step-EMA)`` wall seconds is abandoned on its
                     daemon thread (the PR-5 pattern), its slots'
                     requests journaled ``request-failed[reason=
                     hung-dispatch]`` and freed, and the engine
                     continues on a fresh carry.  None (default)
                     disables — zero threads, zero overhead.
    dispatch_deadline_min_s: watchdog floor while the per-step EMA is
                     still cold (and for tiny EMAs).
    speculation:     decode feedback / drafting mode ("off" = the legacy
                     continuous hidden-state feedback, bit-for-bit
                     preserved).  The token modes quantise decode
                     through the deterministic greedy token table
                     (``data.synthetic.token_embedding_table``):
                     "greedy" is token feedback WITHOUT drafting (the
                     pinned per-step/fused oracle the speculative modes
                     are token-identical to); "ngram" adds host-side
                     prompt-lookup self-speculation (zero extra model);
                     "draft-model" adds a shallow draft transformer on
                     the same ParallelismPlan with its own paged KV
                     plane (docs/serving.md, "Speculative decoding").
    spec_gamma:      draft tokens proposed per verify step (the γ of
                     draft-and-verify); requires a drafting mode.
    spec_adaptive:   per-request adaptive γ — back off to a smaller
                     verify ladder bucket on low acceptance EMA, climb
                     back on high (requires a drafting mode).
    spec_draft_layers: draft-model depth (layers of the shallow draft
                     transformer; every other dim matches the target).
    spec_draft_kv_heads: draft-model GQA kv_heads override (None =
                     the target's; must keep kv_heads % tp == 0).
    prefix_caching:  refcounted content-addressed shared-prefix KV
                     blocks (docs/serving.md, "Prefix cache & quantized
                     KV").  Full prompt blocks are indexed by their
                     token-block chain in a host-side radix trie inside
                     the ``BlockLedger``; an admitted request whose
                     prompt matches an existing chain attaches to the
                     matched blocks (one copy-on-attach jit replaces
                     the matched chunks' prefills — TTFT drops by the
                     matched fraction) and pays blocks only for its
                     unmatched suffix.  Requires ``prefill_chunk`` (the
                     suffix-only prefill IS the chunk machinery),
                     dp=1 (the donor->slot block copy must stay
                     shard-local, like compaction), and
                     speculation="off".
    kv_quantization: "none" (fp cache, bit-identical legacy layout) or
                     "int8": K/V planes stored as int8 blocks with a
                     per-(block, kv-head) fp32 scale side-channel
                     plane, dequantised inside the length-masked
                     attention — ~3.9x smaller cache, so
                     ``hbm_budget_gb`` admits proportionally more
                     resident requests (``kv_cache_bytes_per_device``
                     prices the quantized layout statically).
                     Requires speculation="off" and no
                     compact_threshold (fp-cache-only programs).
    temperature:     softmax temperature of the SAMPLED decode path
                     (0.0 = the greedy argmax law, bit-for-bit
                     untouched).  temperature > 0 routes every decode
                     unit through the residual-sampling verify
                     (``speculative_sample`` — Leviathan et al. 2023):
                     the target's verify logits come to host, each
                     drafted position is accepted with probability
                     ``p[draft]`` and rejected positions resample from
                     ``residual_distribution`` — the composite law is
                     exactly the temperature-``T`` softmax of the
                     target, so sampled speculative decode is
                     distribution-identical (not token-identical) to a
                     sequential sampler.  Requires a drafting
                     speculation mode, decode_horizon=1 and no
                     prefill_chunk (the fused/chunk-interleave token
                     programs are greedy-argmax only — running them
                     would silently emit greedy tokens mid-sampled-run).
    sample_seed:     host RNG seed of the sampled path (with the trace
                     seed this makes sampled runs replayable); only
                     meaningful with temperature > 0.
    hedge_factor:    fleet-level straggler hedging knob (``serve/
                     fleet.py``; ignored by a single-engine run): a
                     request still outstanding past ``hedge_factor`` x
                     the observed p99 end-to-end latency is duplicated
                     onto a second replica — first completion wins, the
                     loser is canceled and its blocks freed.  Greedy
                     token sequences depend only on (params, request
                     seed), so the committed tokens are identical
                     whichever copy wins.  None (default) disables
                     hedging; must be > 1.0 when set.
    """

    max_batch: int = 8
    block_size: int = 16
    max_seq: int = 256
    prefill_buckets: tuple[int, ...] = ()
    queue_capacity: int = 64
    blocks_budget: Optional[int] = None
    hbm_budget_gb: Optional[float] = 12.0
    decode_horizon: int = 1
    inflight_window: int = 1
    prefill_chunk: Optional[int] = None
    compact_threshold: Optional[float] = None
    reject_infeasible: bool = False
    max_dispatch_retries: int = 2
    retry_backoff_s: float = 0.05
    dispatch_deadline_factor: Optional[float] = None
    dispatch_deadline_min_s: float = 0.25
    speculation: str = "off"
    spec_gamma: int = 0
    spec_adaptive: bool = False
    spec_draft_layers: int = 1
    spec_draft_kv_heads: Optional[int] = None
    prefix_caching: bool = False
    kv_quantization: str = "none"
    temperature: float = 0.0
    sample_seed: int = 0
    hedge_factor: Optional[float] = None

    def __post_init__(self) -> None:
        if not self.prefill_buckets:
            object.__setattr__(
                self, "prefill_buckets",
                _default_buckets(self.block_size, self.max_seq),
            )
        else:
            # normalise: bucket_for's first-match walk and every
            # "buckets[-1] is the largest" consumer assume ascending
            # unique buckets
            object.__setattr__(
                self, "prefill_buckets",
                tuple(sorted(set(self.prefill_buckets))),
            )

    @property
    def num_blocks(self) -> int:
        return self.max_seq // self.block_size

    @property
    def total_blocks(self) -> int:
        return (self.blocks_budget if self.blocks_budget is not None
                else self.max_batch * self.num_blocks)

    def validate(self, config: ModelConfig, dp: int = 1,
                 tp: int = 1) -> None:
        budget = (None if self.hbm_budget_gb is None
                  else int(self.hbm_budget_gb * 2**30))
        if self.speculation not in SPECULATION_MODES:
            raise ValueError(
                f"serving.speculation={self.speculation!r} must be one "
                f"of {SPECULATION_MODES}"
            )
        if config.is_hybrid:
            # what the hybrid family's serving path does not have yet,
            # each by its mechanism (ROADMAP.md, Queue 2); int8 KV is
            # refused in validate_serving below
            if self.speculation != "off":
                raise ValueError(
                    f"serving.speculation={self.speculation!r} is not "
                    "implemented for layer_types models: a rejected draft "
                    "needs the recurrent state rolled back, and the state "
                    "cache keeps no snapshots")
            if self.prefix_caching:
                raise ValueError(
                    "serving.prefix_caching is not implemented for "
                    "layer_types models: attaching to shared blocks needs "
                    "the recurrent state as it was at the block boundary, "
                    "and the state cache keeps no snapshots")
            if self.prefill_chunk is None:
                raise ValueError(
                    "layer_types models are prefilled in chunks: set "
                    "serving.prefill_chunk (the chunk program hands the "
                    "recurrent state from chunk to chunk; there is no "
                    "monolithic prefill program)")
        # speculation with tp_overlap != off or non-dense attention is
        # rejected inside validate_serving (those envelopes cannot serve
        # at all); the draft plane re-runs the same gate on its own
        # config below, so a draft kv plane breaking kv_heads % tp
        # fails here at build time too
        draft = (self.draft_model_config(config)
                 if self.speculation == "draft-model" else None)
        validate_serving(config, self.max_batch, self.max_seq,
                         self.block_size, dp=dp, tp=tp,
                         hbm_budget_bytes=budget, draft_config=draft,
                         kv_quantization=self.kv_quantization)
        for b in self.prefill_buckets:
            if b % self.block_size != 0 or not 0 < b <= self.max_seq:
                raise ValueError(
                    f"prefill bucket {b} must be a block_size="
                    f"{self.block_size} multiple in (0, {self.max_seq}]"
                )
        if self.queue_capacity < 1:
            raise ValueError(
                f"serving.queue_capacity must be >= 1, got "
                f"{self.queue_capacity}"
            )
        if self.hedge_factor is not None and self.hedge_factor <= 1.0:
            raise ValueError(
                f"serving.hedge_factor must be > 1.0 (it scales the "
                f"observed p99 latency), got {self.hedge_factor}"
            )
        if self.total_blocks < 1:
            raise ValueError(
                f"serving.blocks_budget must be >= 1, got "
                f"{self.total_blocks}"
            )
        if self.decode_horizon < 1:
            raise ValueError(
                f"serving.decode_horizon must be >= 1, got "
                f"{self.decode_horizon}"
            )
        if self.inflight_window < 1:
            raise ValueError(
                f"serving.inflight_window must be >= 1, got "
                f"{self.inflight_window}"
            )
        if self.inflight_window > 1 and self.decode_horizon < 2:
            raise ValueError(
                "serving.inflight_window > 1 requires decode_horizon "
                ">= 2: per-step (k=1) units never stay in flight (their "
                "y may alias the donated carry), so the window would be "
                "a silent no-op on the per-step engine"
            )
        if self.prefill_chunk is not None:
            if (self.prefill_chunk % self.block_size != 0
                    or not 0 < self.prefill_chunk <= self.max_seq):
                raise ValueError(
                    f"serving.prefill_chunk={self.prefill_chunk} must be "
                    f"a block_size={self.block_size} multiple in "
                    f"(0, {self.max_seq}]"
                )
            if self.max_seq % self.prefill_chunk != 0:
                # a prompt near max_seq pads to ceil(prompt/chunk)*chunk;
                # unless the chunk divides max_seq that rounding can
                # overrun the slot's block ring for a perfectly feasible
                # request — reject the geometry up front
                raise ValueError(
                    f"serving.prefill_chunk={self.prefill_chunk} must "
                    f"divide serving.max_seq={self.max_seq} (chunk "
                    "rounding of a near-max_seq prompt would overrun "
                    "the slot's block ring)"
                )
        if self.compact_threshold is not None:
            if not 0.0 < self.compact_threshold <= 0.5:
                raise ValueError(
                    f"serving.compact_threshold must be in (0, 0.5] — "
                    f"compaction repacks into the half-size batch bucket "
                    f"(got {self.compact_threshold})"
                )
            if self.decode_horizon < 2:
                raise ValueError(
                    "serving.compact_threshold requires decode_horizon "
                    ">= 2: compaction only engages on fused scans, so "
                    "with the per-step engine it would be a silent no-op "
                    "that still pays the gather/scatter compiles"
                )
            if self.max_batch < 2:
                raise ValueError(
                    "serving.compact_threshold needs max_batch >= 2 "
                    "(nothing to compact into)"
                )
            if dp > 1:
                raise ValueError(
                    "serving.compact_threshold requires dp=1: the slot "
                    "gather/scatter must stay shard-local, and the slot "
                    f"dim is sharded over dp={dp}"
                )
        if self.max_dispatch_retries < 0:
            raise ValueError(
                f"serving.max_dispatch_retries must be >= 0, got "
                f"{self.max_dispatch_retries}"
            )
        if self.retry_backoff_s < 0:
            raise ValueError(
                f"serving.retry_backoff_s must be >= 0, got "
                f"{self.retry_backoff_s}"
            )
        if (self.dispatch_deadline_factor is not None
                and self.dispatch_deadline_factor <= 0):
            raise ValueError(
                f"serving.dispatch_deadline_factor must be > 0, got "
                f"{self.dispatch_deadline_factor}"
            )
        if self.dispatch_deadline_min_s <= 0:
            raise ValueError(
                f"serving.dispatch_deadline_min_s must be > 0 seconds, "
                f"got {self.dispatch_deadline_min_s}"
            )
        # -- speculation ladder (same no-op-trap contract as
        #    compact_threshold/inflight_window: a knob that would
        #    silently do nothing is a config error) --
        if self.spec_drafting:
            if self.spec_gamma < 1:
                raise ValueError(
                    f"serving.speculation={self.speculation!r} requires "
                    f"spec_gamma >= 1 (got {self.spec_gamma}): a drafter "
                    "with zero proposals per verify is a silent no-op "
                    "that still pays the verify compiles"
                )
            if self.spec_gamma + 1 > self.max_seq:
                raise ValueError(
                    f"serving.spec_gamma={self.spec_gamma} cannot exceed "
                    f"max_seq-1={self.max_seq - 1}: a verify step "
                    "appends gamma+1 positions to one slot"
                )
        else:
            if self.spec_gamma:
                raise ValueError(
                    f"serving.spec_gamma={self.spec_gamma} requires a "
                    "drafting speculation mode ('ngram' or "
                    "'draft-model'); with speculation="
                    f"{self.speculation!r} no verify step ever runs, so "
                    "the knob would be a silent no-op"
                )
            if self.spec_adaptive:
                raise ValueError(
                    "serving.spec_adaptive requires a drafting "
                    "speculation mode ('ngram' or 'draft-model'): "
                    "there is no acceptance EMA to adapt to with "
                    f"speculation={self.speculation!r}"
                )
        if self.speculation != "off" and self.compact_threshold is not None:
            raise ValueError(
                "serving.compact_threshold cannot combine with "
                f"speculation={self.speculation!r}: token-feedback and "
                "verify units run on the full decode batch (no "
                "compacted token/verify program exists), so compaction "
                "would be a silent no-op that still pays the gather/"
                "scatter compiles"
            )
        if self.speculation == "draft-model":
            if self.spec_draft_layers < 1:
                raise ValueError(
                    f"serving.spec_draft_layers must be >= 1, got "
                    f"{self.spec_draft_layers}"
                )
            if self.prefill_chunk is not None:
                raise ValueError(
                    "serving.prefill_chunk cannot combine with "
                    "speculation='draft-model': the draft KV plane is "
                    "prefilled monolithically at admission, and a "
                    "chunked target prefill would leave it silently "
                    "unfilled"
                )
        # -- shared-prefix cache + quantized KV planes (same no-op-trap
        #    contract: a knob that cannot engage is a config error) --
        if self.prefix_caching:
            if self.prefill_chunk is None:
                raise ValueError(
                    "serving.prefix_caching requires prefill_chunk: the "
                    "suffix-only prefill of a prefix hit IS the chunked-"
                    "prefill machinery (attach replaces the matched "
                    "chunks), so without it every admission would pay "
                    "the full prefill and the trie would be a silent "
                    "no-op"
                )
            if dp > 1:
                raise ValueError(
                    "serving.prefix_caching requires dp=1: the prefix "
                    "attach copies donor-slot blocks into the admitted "
                    "slot, and that copy must stay shard-local — the "
                    f"slot dim is sharded over dp={dp} (same constraint "
                    "as compact_threshold)"
                )
            if self.speculation != "off":
                raise ValueError(
                    "serving.prefix_caching cannot combine with "
                    f"speculation={self.speculation!r}: prefix attach "
                    "rides the chunked prefill, which the speculative "
                    "modes exclude (and generated tokens are never "
                    "indexed in the trie, so drafting gains nothing)"
                )
        if self.kv_quantization == "int8":
            if self.speculation != "off":
                raise ValueError(
                    "serving.kv_quantization='int8' cannot combine with "
                    f"speculation={self.speculation!r}: the token/"
                    "verify programs read and write the fp cache layout "
                    "only"
                )
            if self.compact_threshold is not None:
                raise ValueError(
                    "serving.kv_quantization='int8' cannot combine with "
                    "compact_threshold: the slot gather/scatter programs "
                    "repack the fp cache layout only, so compaction "
                    "would silently run on stale scale planes"
                )
        # -- sampled decode (same no-op-trap contract) --
        if self.temperature < 0:
            raise ValueError(
                f"serving.temperature must be >= 0, got "
                f"{self.temperature}"
            )
        if self.temperature > 0:
            if not self.spec_drafting:
                raise ValueError(
                    f"serving.temperature={self.temperature} requires a "
                    "drafting speculation mode ('ngram' or "
                    "'draft-model'): the sampled path runs inside the "
                    "verify unit (residual sampling over the verify "
                    "logits), and with speculation="
                    f"{self.speculation!r} every decode program is the "
                    "greedy argmax law — the knob would silently emit "
                    "greedy tokens"
                )
            if self.decode_horizon != 1:
                raise ValueError(
                    f"serving.temperature={self.temperature} requires "
                    f"decode_horizon=1 (got {self.decode_horizon}): the "
                    "fused token scans are greedy-argmax programs, so a "
                    "fused unit mid-sampled-run would silently emit "
                    "greedy tokens (the verify window is the sampled "
                    "path's multi-token mechanism)"
                )
            if self.prefill_chunk is not None:
                raise ValueError(
                    f"serving.temperature={self.temperature} cannot "
                    "combine with prefill_chunk: the chunk interleave's "
                    "per-step decode units are greedy token programs, "
                    "so a long admission would silently emit greedy "
                    "tokens mid-sampled-run"
                )
        elif self.sample_seed:
            raise ValueError(
                f"serving.sample_seed={self.sample_seed} requires "
                "temperature > 0: the greedy path never consumes the "
                "host RNG, so the knob would be a silent no-op"
            )

    @property
    def spec_drafting(self) -> bool:
        """True when a drafter runs (verify steps exist)."""
        return self.speculation in ("ngram", "draft-model")

    @property
    def spec_gammas(self) -> tuple[int, ...]:
        """The verify-step γ ladder: powers of two 1, 2, 4, ... below
        ``spec_gamma``, plus ``spec_gamma`` itself (adaptive γ backs
        off through these buckets; empty when not drafting)."""
        if not self.spec_drafting:
            return ()
        gs = []
        g = 1
        while g < self.spec_gamma:
            gs.append(g)
            g *= 2
        gs.append(self.spec_gamma)
        return tuple(sorted(set(gs)))

    def draft_model_config(self, config: ModelConfig) -> ModelConfig:
        """The draft transformer's config: the target at
        ``spec_draft_layers`` depth (and an optional kv_heads
        override), everything else — hidden size, heads, dtype,
        attention — identical, so the draft shares the target's
        ParallelismPlan and its outputs live in the same hidden/token
        space the verify step argmaxes over."""
        kwargs: dict[str, Any] = {"num_layers": self.spec_draft_layers}
        if self.spec_draft_kv_heads is not None:
            kwargs["num_kv_heads"] = self.spec_draft_kv_heads
        return dc_replace(config, **kwargs)

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt_len={prompt_len} exceeds the largest prefill bucket "
            f"{self.prefill_buckets[-1]} (serving.max_seq={self.max_seq})"
        )

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ServingConfig":
        fields = {}
        for k in ("max_batch", "block_size", "max_seq", "queue_capacity",
                  "blocks_budget", "hbm_budget_gb", "decode_horizon",
                  "inflight_window", "prefill_chunk", "compact_threshold",
                  "reject_infeasible", "max_dispatch_retries",
                  "retry_backoff_s", "dispatch_deadline_factor",
                  "dispatch_deadline_min_s", "speculation", "spec_gamma",
                  "spec_adaptive", "spec_draft_layers",
                  "spec_draft_kv_heads", "prefix_caching",
                  "kv_quantization", "temperature", "sample_seed",
                  "hedge_factor"):
            if k in d:
                fields[k] = d[k]
        if "prefill_buckets" in d:
            fields["prefill_buckets"] = tuple(d["prefill_buckets"])
        return cls(**fields)

    def to_dict(self) -> dict[str, Any]:
        return {
            "max_batch": self.max_batch,
            "block_size": self.block_size,
            "max_seq": self.max_seq,
            "num_blocks": self.num_blocks,
            "prefill_buckets": list(self.prefill_buckets),
            "queue_capacity": self.queue_capacity,
            "blocks_budget": self.total_blocks,
            "hbm_budget_gb": self.hbm_budget_gb,
            "decode_horizon": self.decode_horizon,
            "inflight_window": self.inflight_window,
            "prefill_chunk": self.prefill_chunk,
            "compact_threshold": self.compact_threshold,
            "reject_infeasible": self.reject_infeasible,
            "max_dispatch_retries": self.max_dispatch_retries,
            "retry_backoff_s": self.retry_backoff_s,
            "dispatch_deadline_factor": self.dispatch_deadline_factor,
            "dispatch_deadline_min_s": self.dispatch_deadline_min_s,
            "speculation": self.speculation,
            "spec_gamma": self.spec_gamma,
            "spec_adaptive": self.spec_adaptive,
            "spec_draft_layers": self.spec_draft_layers,
            "spec_draft_kv_heads": self.spec_draft_kv_heads,
            "prefix_caching": self.prefix_caching,
            "kv_quantization": self.kv_quantization,
            "temperature": self.temperature,
            "sample_seed": self.sample_seed,
            "hedge_factor": self.hedge_factor,
        }

    @property
    def fused_horizons(self) -> tuple[int, ...]:
        """The power-of-two fused-scan bucket ladder: 2, 4, ... up to
        ``decode_horizon`` (empty when the fast path is off)."""
        ks = []
        k = 2
        while k <= self.decode_horizon:
            ks.append(k)
            k *= 2
        return tuple(ks)


# ---------------------------------------------------------------------------
# device programs
# ---------------------------------------------------------------------------


def _split_qkv(qkv: jax.Array, config: ModelConfig):
    """[..., qkv_width] -> q [..., H], k/v [..., kv_heads * head_dim]."""
    h, kvd = config.hidden_size, config.kv_heads * config.head_dim
    return qkv[..., :h], qkv[..., h:h + kvd], qkv[..., h + kvd:]


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


KV_UPDATE, KV_ATTEND = SERVE_PHASES


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


def _layer_of(plane: jax.Array, l: jax.Array) -> jax.Array:
    """Layer ``l`` of a carried cache plane ``[L, ...]``."""
    return jax.lax.dynamic_index_in_dim(plane, l, 0, keepdims=False)


def _layer_tokens(plane: jax.Array, l: jax.Array) -> jax.Array:
    """Layer ``l`` of a carried K/V plane as attention reads it,
    token-major ``[B, S_max, kvh, d]``.  The plane is flattened BEFORE
    the slice: then the v5e compiler takes the dynamic slice as the
    prologue of the attention reduce.  Sliced first and flattened
    after, it wrote the layer out in fp32 and read it back, per plane
    and layer (``PERF.md`` §6, PR 26)."""
    nl, b, nb, bs, kvh, d = plane.shape
    return _layer_of(plane.reshape(nl, b, nb * bs, kvh, d), l)


def _heads(t: jax.Array, nh: int, d: int) -> jax.Array:
    """[B, S, nh*d] -> [B, nh, S, d]."""
    b, s, _ = t.shape
    return t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)


@jax.named_scope(KV_ATTEND)
def _cached_attention(q: jax.Array, k_flat: jax.Array, v_flat: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Length-masked decode attention over the flattened cache.

    q: ``[B, n, 1, d]``; k_flat/v_flat: ``[B, S_max, kvh, d]``;
    valid: ``[B, S_max]`` bool.  Same math as
    ``models.attention.dense_attention`` (fp32 softmax, 1/sqrt(d),
    grouped-query einsum broadcasting) with the causal mask replaced by
    the per-slot validity mask — positions past a slot's length
    contribute exactly zero (softmax of -inf)."""
    b, n, _, d = q.shape
    kvh = k_flat.shape[2]
    q32 = q.astype(jnp.float32)
    k32 = k_flat.transpose(0, 2, 1, 3).astype(jnp.float32)  # [B, kvh, S, d]
    v32 = v_flat.transpose(0, 2, 1, 3).astype(jnp.float32)
    if kvh != n:
        q32 = q32.reshape(b, kvh, n // kvh, 1, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, None, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32)
        out = out.reshape(b, n, 1, d)
    else:
        logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v32)
    return out.astype(k_flat.dtype)


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


@jax.named_scope(KV_ATTEND)
def _chunk_attention(qh: jax.Array, k_all: jax.Array, v_all: jax.Array,
                     start: int) -> jax.Array:
    """Offset-causal fp32 attention for one prefill chunk.

    qh: ``[1, n, C, d]`` (the chunk's queries, global positions
    ``start..start+C``); k_all/v_all: ``[start+C, kvh, d]`` (prefix +
    chunk keys).  Same math as ``_cached_attention`` (fp32 softmax,
    1/sqrt(d), grouped-query broadcasting) with the per-slot validity
    mask replaced by the STATIC offset-causal mask ``j <= start + qi``
    — for real query positions this reaches only real keys, so pad
    positions in a final partial chunk never contaminate a real
    output (their own rows are discarded by the caller)."""
    b, n, c, d = qh.shape
    kvh = k_all.shape[1]
    s_tot = k_all.shape[0]
    q32 = qh.astype(jnp.float32)
    k32 = k_all.transpose(1, 0, 2).astype(jnp.float32)[None]  # [1,kvh,S,d]
    v32 = v_all.transpose(1, 0, 2).astype(jnp.float32)[None]
    mask = (jnp.arange(s_tot)[None, :]
            <= (start + jnp.arange(c))[:, None])            # [C, S]
    if kvh != n:
        q32 = q32.reshape(b, kvh, n // kvh, c, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32)
        out = out.reshape(b, n, c, d)
    else:
        logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v32)
    return out.astype(k_all.dtype)


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


def _carry_shardings(mesh: Mesh):
    """Shardings of the GPT block's decode carry ``(cache, x)``."""
    return (cache_shardings(mesh),
            NamedSharding(mesh, decode_batch_spec(mesh)))


def build_compact_gather(mesh: Mesh, carry_shardings=None):
    """Jitted ``gather(carry, idx) -> small_carry``: repack the active
    slots named by ``idx`` into a smaller decode batch bucket (slot
    compaction, dp=1 only — the gather must stay shard-local).  The big
    carry is NOT donated: it survives on device and the compacted scan's
    results are scattered back into it at scan exit.
    ``carry_shardings``: those of ``(cache, x)`` when the carry is not
    the GPT block's (a ``HybridCache`` and its token buffer)."""
    from dlbb_tpu.serve.kvcache import gather_cache_slots

    @named("serve_compact_gather")
    def gather(carry, idx):
        cache, x = carry
        return (gather_cache_slots(cache, idx), x[idx])

    return jax.jit(
        gather, out_shardings=carry_shardings or _carry_shardings(mesh))


def build_compact_scatter(mesh: Mesh, carry_shardings=None):
    """Jitted ``scatter(carry, small_carry, idx) -> carry``: write the
    compacted rows back into their big-batch slots (only the big carry
    is donated — the small rows land inside larger output buffers;
    ``idx`` rows are distinct by construction — active slots padded
    with distinct free slots, so the scatter is unambiguous)."""
    from dlbb_tpu.serve.kvcache import scatter_cache_slots

    @named("serve_compact_scatter")
    def scatter(carry, small_carry, idx):
        cache, x = carry
        s_cache, s_x = small_carry
        return (scatter_cache_slots(cache, s_cache, idx),
                x.at[idx].set(s_x))

    # only the big carry is donated: the small rows land inside larger
    # output buffers, so their donation could never be honoured
    return jax.jit(
        scatter,
        donate_argnums=(0,),
        out_shardings=carry_shardings or _carry_shardings(mesh),
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
def _inject_token(carry, slot, vec):
    """Place a freshly-prefilled request's first token into the decode
    input buffer: ``x[slot, 0] = vec``."""
    cache, x = carry
    mask = (jnp.arange(x.shape[0]) == slot)[:, None, None]
    return cache, jnp.where(mask, vec[None, None, :].astype(x.dtype), x)


# ---------------------------------------------------------------------------
# speculative decoding (docs/serving.md, "Speculative decoding")
# ---------------------------------------------------------------------------


@named("serve_inject_greedy")
def _inject_token_greedy(carry, slot, vec, table):
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
def _inject_token_sampled(carry, slot, tok, table):
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


@jax.named_scope(KV_ATTEND)
def _verify_attention(q: jax.Array, k_flat: jax.Array, v_flat: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Offset-causal length-masked attention for one verify step.

    q: ``[B, n, G, d]`` (G = gamma+1 verify positions per slot);
    k_flat/v_flat: ``[B, S_max, kvh, d]``; valid: ``[B, G, S_max]`` bool
    — query ``i`` of slot ``b`` reaches keys ``j <= lengths[b] + i``
    (the per-slot offset-causal mask, ``_chunk_attention``'s static mask
    made per-slot dynamic).  Same math as ``_cached_attention`` (fp32
    softmax, 1/sqrt(d), grouped-query broadcasting), of which it is the
    G>1 generalisation."""
    b, n, g, d = q.shape
    kvh = k_flat.shape[2]
    q32 = q.astype(jnp.float32)
    k32 = k_flat.transpose(0, 2, 1, 3).astype(jnp.float32)  # [B, kvh, S, d]
    v32 = v_flat.transpose(0, 2, 1, 3).astype(jnp.float32)
    if kvh != n:
        q32 = q32.reshape(b, kvh, n // kvh, g, d)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, None, :, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v32)
        out = out.reshape(b, n, g, d)
    else:
        logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32) / math.sqrt(d)
        logits = jnp.where(valid[:, None, :, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", probs, v32)
    return out.astype(k_flat.dtype)


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


def _ngram_propose(hist: list, gamma: int,
                   max_ngram: int = 3) -> Optional[list]:
    """Prompt-lookup / n-gram drafting (Saxena 2023): find the most
    recent earlier occurrence of the history's trailing n-gram (n from
    ``max_ngram`` down to 1) in ``hist`` (= the request's prompt token
    ids + every committed token) and propose the γ ids that followed
    it.  When the match sits d < γ positions back, the continuation
    runs off the end of the history after d tokens — but a trailing
    match at distance d means the history is locally d-periodic, so
    the proposal extends CYCLICALLY through that period rather than
    flat-padding (greedy feedback through a fixed table falls into
    short cycles, and cyclic extension is what lets a γ≫d proposal
    stay correct for the whole window).  Pure, deterministic function
    of the history — drafter determinism from trace seeds is a test
    invariant.  None = cold (no occurrence of even the last token):
    the scheduler falls back to a plain decode unit."""
    ln = len(hist)
    for n in range(min(max_ngram, ln - 1), 0, -1):
        key = hist[ln - n:]
        for start in range(ln - n - 1, -1, -1):
            if hist[start:start + n] == key:
                cont = list(hist[start + n:start + n + gamma])
                if len(cont) < gamma:
                    d = len(cont)  # == distance back to the match
                    cont += [cont[i % d] for i in range(d, gamma)]
                return cont
    return None


def softmax_np(logits: np.ndarray, temperature: float) -> np.ndarray:
    """Host-side temperature softmax (float64, max-subtracted) — the
    sampled path's target law ``p``.  The device never softmaxes: the
    verify logits come to host raw and every probability the sampler
    consumes is computed here, so the sampled law is exactly
    reproducible from the journal'd seeds."""
    z = np.asarray(logits, np.float64) / float(temperature)
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def residual_distribution(p_target: np.ndarray,
                          q_draft: np.ndarray) -> np.ndarray:
    """The rejection-correction distribution of speculative SAMPLING
    (Leviathan et al. 2023): ``norm(max(p - q, 0))``.  Degenerates to
    ``p`` when ``q`` dominates it everywhere (rejection then has zero
    probability, so the branch is never taken)."""
    resid = np.maximum(np.asarray(p_target, np.float64)
                       - np.asarray(q_draft, np.float64), 0.0)
    z = resid.sum()
    if z <= 0.0:
        return np.asarray(p_target, np.float64)
    return resid / z


def speculative_sample(p_target: np.ndarray, q_draft: np.ndarray,
                       draft_id: int,
                       rng: np.random.Generator) -> tuple[int, bool]:
    """One position of the residual-sampling correction — HOW the
    equivalence gate weakens for sampled (temperature > 0) decode:
    accept the drafted token with probability ``min(1, p/q)``; on
    rejection, sample from ``residual_distribution(p, q)``.  The
    composite law is exactly ``p`` (distribution-identity, pinned by
    ``tests/test_speculative.py``), so sampled speculative decode is
    distribution-identical — not token-identical — to the sequential
    sampler.  The engine's default serving path is greedy (argmax),
    which this correction degenerates to as temperature -> 0; with
    ``serving.temperature > 0`` the scheduler's verify units run this
    helper position-by-position over the host-side verify softmax
    (``q`` = the deterministic drafter's one-hot, so acceptance is
    ``p[draft]`` and the residual is ``p`` with the draft's mass
    removed — docs/serving.md)."""
    p = float(p_target[draft_id])
    q = float(q_draft[draft_id])
    accept_p = 1.0 if q <= 0.0 and p > 0.0 else (
        min(1.0, p / q) if q > 0.0 else 0.0)
    if rng.uniform() < accept_p:
        return int(draft_id), True
    resid = residual_distribution(p_target, q_draft)
    return int(rng.choice(len(resid), p=resid)), False


def _with_deadline(fn, deadline: Optional[float], label: str,
                   phase: str) -> Any:
    """Run ``fn()`` under the serving dispatch watchdog (the PR-5
    daemon-thread pattern, ``bench/runner._call_with_deadline``).

    With no deadline this is a direct call — zero threads, zero
    overhead.  With one, ``fn`` runs on a daemon thread joined for
    ``deadline`` seconds; an overrun ABANDONS the thread (it may be
    wedged inside the runtime and cannot be killed) and raises
    :class:`DeadlineExceeded` — the engine then fails the unit's
    requests closed and continues on a fresh carry, so the zombie's
    eventual outputs (if any) are never consumed."""
    if deadline is None:
        return fn()
    box: dict[str, Any] = {}

    def target() -> None:
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — marshalled to caller
            box["error"] = e

    t = threading.Thread(target=target, daemon=True,
                         name=f"dlbb-serve-{phase}-{label}")
    t.start()
    t.join(deadline)
    if t.is_alive():
        raise DeadlineExceeded(label, deadline, phase=phase)
    if "error" in box:
        raise box["error"]
    return box["value"]


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


@dataclass
class _SlotState:
    req: Request
    tokens_done: int = 0
    admitted_s: float = 0.0
    first_token_s: float = 0.0
    # adaptive speculation: this request's current verify γ (a ladder
    # bucket) and its acceptance-rate EMA (-1 = no verify observed yet)
    gamma_eff: int = 0
    accept_ema: float = -1.0


@dataclass
class _RunStats:
    ttft_s: list[float] = field(default_factory=list)
    per_token_s: list[float] = field(default_factory=list)
    prefill_s: list[float] = field(default_factory=list)
    decode_step_s: list[float] = field(default_factory=list)
    e2e_latency_s: list[float] = field(default_factory=list)
    completed_output_tokens: int = 0
    generated_tokens: int = 0
    decode_steps: int = 0       # decode steps executed (fused trips count)
    decode_units: int = 0       # host dispatches (a fused scan is ONE)
    fused_scans: int = 0
    fused_steps: int = 0
    single_steps: int = 0
    prefill_chunks: int = 0
    compacted_scans: int = 0
    # K/V tiles the decode units' steps fetched (ops/decode_attention.py)
    # and tiles the planes they ran over hold, times steps
    kv_tiles_live: int = 0
    kv_tiles_held: int = 0
    # resilience accounting (docs/resilience.md, serving-faults section)
    retries: int = 0
    hung_dispatches: int = 0
    failed_requests: int = 0
    preempted_requests: int = 0
    deadline_shed: int = 0
    completed_past_deadline: int = 0
    # speculative decoding (docs/serving.md, "Speculative decoding")
    spec_verify_units: int = 0      # draft-and-verify dispatches
    spec_fallback_units: int = 0    # cold-drafter plain-decode fallbacks
    spec_proposed_tokens: int = 0   # γ per resident slot per verify
    spec_accepted_tokens: int = 0   # drafts the target verify accepted
    spec_commit_tokens: int = 0     # committed incl. the bonus token
    spec_slot_verifies: int = 0     # slot-level verifies (for mean len)
    spec_draft_s: float = 0.0       # host drafting / draft-scan wall
    # shared-prefix cache (docs/serving.md, "Prefix cache & quantized KV")
    prefix_hits: int = 0            # admissions that attached to the trie
    prefix_tokens_reused: int = 0   # prompt tokens served from shared blocks
    prefix_cow_blocks: int = 0      # blocks rewritten privately (CoW)


class ServingEngine:
    """Trace-driven continuous-batching engine (see module docstring).

    One engine serves many traces: each :meth:`run_trace` starts from a
    fresh cache.  The journal (``resilience.journal.SweepJournal``) and
    metrics registry are optional — the bench harness wires both."""

    def __init__(
        self,
        config: ModelConfig,
        serving: ServingConfig,
        mesh: Mesh,
        params: Any = None,
        journal: Any = None,
        registry: Optional[MetricsRegistry] = None,
        seed: int = 0,
        verbose: bool = True,
        capture_tokens: bool = False,
    ) -> None:
        axes = mesh.axis_names
        self.dp = mesh.shape["dp"] if "dp" in axes else 1
        self.tp = mesh.shape["tp"] if "tp" in axes else 1
        serving.validate(config, dp=self.dp, tp=self.tp)
        self.config = config
        self.serving = serving
        self.mesh = mesh
        self.verbose = verbose
        # the equivalence gate: argmax "token ids" of every generated
        # output recorded per request (syncs each unit — leave off for
        # perf runs)
        self.capture_tokens = capture_tokens
        # public and reassignable: the bench wires one journal per run
        # directory; tests swap it between run_trace calls
        self.journal = journal
        # fleet-replica control plane for the CURRENT run (run_trace's
        # ``control=``); None outside a fleet
        self._control: Any = None
        self.registry = registry if registry is not None else MetricsRegistry()
        self._requests = self.registry.labeled_counter(
            "serve_requests", "outcome",
            initial=("arrived", "admitted", "rejected", "completed",
                     "failed", "preempted", "canceled"),
            help="request lifecycle outcomes",
        )
        self._rejections = self.registry.labeled_counter(
            "serve_rejections", "reason",
            initial=("queue-full", "infeasible", "deadline"),
            help="requests shed, by rejection reason",
        )
        self._retry_counter = self.registry.labeled_counter(
            "serve_request_retries", "phase",
            initial=("prefill", "decode", "bookkeeping"),
            help="transient dispatch/bookkeeping retries, by phase",
        )
        self._deadline_counter = self.registry.labeled_counter(
            "serve_deadline_exceeded", "reason",
            initial=("shed-queued", "completed-late"),
            help="per-request SLO deadline misses, by how they surfaced",
        )
        for name, hlp in (
            ("serve_decode_steps",
             "decode steps executed (each fused-scan trip counts once)"),
            ("serve_fused_scan_steps",
             "decode steps executed inside fused lax.scan dispatches"),
            ("serve_prefill_chunks", "prefill chunks processed"),
            ("serve_hung_dispatches",
             "decode units abandoned by the dispatch watchdog"),
        ):
            self.registry.inc(name, 0, help=hlp)
        self._quantized = serving.kv_quantization == "int8"
        if serving.prefix_caching:
            for name, hlp in (
                ("serve_prefix_hits",
                 "admissions that attached to shared prefix blocks"),
                ("serve_prefix_tokens_reused",
                 "prompt tokens served from shared blocks (prefill "
                 "skipped)"),
            ):
                self.registry.inc(name, 0, help=hlp)
        self._dtype = _dtype_of(config.dtype)
        self.params = (params if params is not None
                       else init_params_sharded(config, jax.random.key(seed),
                                                mesh))
        self._prefill_jits: dict[int, Any] = {}
        self._fused_ks = serving.fused_horizons
        # a layer_types model (models/hybrid.py) runs the programs of
        # serve/hybrid.py under the same names, through the same
        # scheduler: token ids in, tokens fed back on the device
        self._hybrid = None
        carry_sh = None
        if config.is_hybrid:
            from dlbb_tpu.serve import hybrid as serve_hybrid

            self._hybrid = serve_hybrid
            self._probe_rids: tuple[int, ...] = ()
            self.probed: dict[int, dict[str, Any]] = {}
            # -1 names no probed request (the programs then return the
            # last slot's logits, which nobody keeps)
            self._probe_slots = np.full((serve_hybrid.PROBES,), -1, np.int32)
            self._probe_dev = jnp.array(self._probe_slots)  # a copy
            self._decode = self._with_probe(
                serve_hybrid.build_decode_step(config, mesh))
            self._decode_fused = {
                k: self._with_probe(
                    serve_hybrid.build_decode_fused(config, mesh, k))
                for k in self._fused_ks
            }
            carry_sh = (serve_hybrid.hybrid_cache_shardings(mesh),
                        NamedSharding(mesh, serve_hybrid.token_spec(mesh)))
            self.registry.inc(
                "serve_state_resets", 0,
                help="recycled slots whose recurrent state a new "
                     "request's first prompt chunk cleared")
            self.registry.set_gauge(
                "serve_state_bytes",
                state_cache_bytes(config, serving.max_batch),
                help="bytes of slot-indexed recurrent state and "
                     "convolution inputs the cache holds")
            self.registry.set_gauge(
                "serve_kv_bytes",
                kv_cache_bytes(config, serving.max_batch, serving.max_seq,
                               tp=self.tp),
                help="bytes of paged K/V the cache holds (full-attention "
                     "layers only)")
        else:
            self._decode = build_decode_step(config, mesh,
                                             quantized=self._quantized)
            self._decode_fused = {
                k: build_decode_fused(config, mesh, k,
                                      quantized=self._quantized)
                for k in self._fused_ks
            }
        self._prefill_chunk_jits: dict[int, Any] = {}
        self._attach_jits: dict[int, Any] = {}
        self._compact_gather_fn = None
        self._compact_scatter_fn = None
        if serving.compact_threshold is not None:
            self._compact_gather_fn = build_compact_gather(mesh, carry_sh)
            self._compact_scatter_fn = build_compact_scatter(mesh, carry_sh)
        self._fast = (serving.decode_horizon > 1
                      or serving.inflight_window > 1
                      or serving.prefill_chunk is not None
                      or serving.compact_threshold is not None)
        self._inject = jax.jit(
            self._hybrid.inject_token if self._hybrid else _inject_token,
            donate_argnums=(0,))
        self._x_sharding = NamedSharding(mesh, decode_batch_spec(mesh))
        self._active_sharding = NamedSharding(mesh, P())
        # the fp layout's decode attention fetches tiles of this many
        # tokens under each slot's length: the kernel's own reckoning
        # from the K plane this engine carries (the int8 layout reads the
        # whole layer and counts nothing)
        self._kv_tile = 0
        if not self._quantized:
            k_plane = jax.eval_shape(self._fresh_carry)[0].k
            check_kernel_takes(k_plane, mesh)
            self._kv_tile = plane_tile_tokens(k_plane, mesh)
            for name, hlp in (
                ("serve_kv_tiles_live",
                 "K/V tiles of a layer the decode steps fetched (tokens "
                 "under the active slots' lengths)"),
                ("serve_kv_tiles_held",
                 "K/V tiles of a layer the planes hold, times decode steps"),
            ):
                self.registry.inc(name, 0, help=hlp)
        # -- speculative decoding (docs/serving.md) --
        # token-feedback modes quantise decode through the greedy token
        # table; the legacy jits above stay built (jax.jit is lazy, so
        # an unused ladder costs nothing) and the "off" path is
        # bit-for-bit untouched
        self._token_mode = serving.speculation != "off"
        # non-adaptive runs verify at exactly spec_gamma; adaptive runs
        # need the whole back-off ladder compiled
        self._spec_gammas: tuple[int, ...] = (
            serving.spec_gammas if serving.spec_adaptive
            else ((serving.spec_gamma,) if serving.spec_drafting else ()))
        self._table: Optional[jax.Array] = None
        self._decode_token = None
        self._decode_fused_token: dict[int, Any] = {}
        self._verify: dict[int, Any] = {}
        self._draft_config: Optional[ModelConfig] = None
        self._draft_params: Any = None
        self._draft_prefill = None
        self._draft_scan: dict[int, Any] = {}
        if self._token_mode:
            self._table = jax.device_put(
                token_embedding_table(config.hidden_size, self._dtype),
                NamedSharding(mesh, P()))
            self._decode_token = build_decode_token_step(config, mesh)
            self._decode_fused_token = {
                k: build_decode_fused_token(config, mesh, k)
                for k in self._fused_ks
            }
            self._inject_greedy = jax.jit(_inject_token_greedy,
                                          donate_argnums=(0,))
            dp_ax = decode_batch_spec(mesh)[0]
            self._ids_sharding = NamedSharding(mesh, P(dp_ax, None))
        # sampled (temperature > 0) decode: host residual sampling over
        # the verify logits — verify_probs/spec_commit replace the
        # greedy on-device verify, and the cold-drafter fallback is the
        # γ=0 probs program (one sampled token per trip), so a sampled
        # run NEVER dispatches a greedy token program after prefill
        self._sampled = serving.temperature > 0
        self._verify_probs: dict[int, Any] = {}
        self._spec_commit = None
        self._inject_sampled = None
        if self._sampled:
            probs_gammas = set(self._spec_gammas)
            if serving.speculation == "ngram":
                probs_gammas.add(0)     # the cold-drafter fallback unit
            self._verify_probs = {g: build_verify_probs(config, mesh, g)
                                  for g in sorted(probs_gammas)}
            self._spec_commit = build_spec_commit(config, mesh)
            self._inject_sampled = jax.jit(_inject_token_sampled,
                                           donate_argnums=(0,))
            self.registry.inc(
                "serve_sampled_tokens", 0,
                help="tokens committed by the sampled (temperature > 0) "
                     "residual-sampling path")
        if serving.spec_drafting:
            self._verify = {g: build_verify_step(config, mesh, g)
                            for g in self._spec_gammas}
            self._spec_proposed = self.registry.labeled_counter(
                "serve_spec_proposed_total", "drafter",
                initial=("ngram", "draft-model"),
                help="draft tokens proposed to the verify step, by drafter",
            )
            self._spec_accepted = self.registry.labeled_counter(
                "serve_spec_accepted_total", "drafter",
                initial=("ngram", "draft-model"),
                help="draft tokens the target verify accepted, by drafter",
            )
        if serving.speculation == "draft-model":
            self._draft_config = serving.draft_model_config(config)
            # the draft model is the ENGINE's (never caller-supplied):
            # derived deterministically from the seed so replays draft
            # identically; sharded by the same ParallelismPlan
            self._draft_params = init_params_sharded(
                self._draft_config, jax.random.key(seed + 1), mesh)
            self._draft_prefill = build_prefill(
                self._draft_config, mesh, name="serve_spec_draft_prefill")
            self._draft_scan = {
                g: build_draft_scan(self._draft_config, mesh, g)
                for g in self._spec_gammas
            }
        self._t0 = time.perf_counter()

    # -- clock (monotonic, run-relative) -----------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    # -- setup -------------------------------------------------------------

    def _with_probe(self, program):
        """A hybrid decode program under the GPT programs' signature
        ``(carry, params, active[, remaining]) -> (carry, ys)``: the
        probed slots go in as its last argument, and ``ys`` is the
        pair ``(tokens, logits of the probed slots)``."""
        def call(carry, params, *masks, probe=None):
            carry, toks, seen = program(
                carry, params, *masks,
                self._probe_dev if probe is None else probe)
            return carry, (toks, seen)
        return call

    def probe(self, rids) -> None:
        """Keep, for the requests ``rids`` (at most ``PROBES`` resident
        at once), the logits the serving programs themselves produced:
        the last prompt position's and every decode step's, held on the
        device with the committed tokens until :meth:`probe_results`
        fetches them.  Nothing is synced or copied to the host while a
        trace is served, and the same programs run whether or not a
        request is probed.  Each ``run_trace`` starts the record anew.
        Only ``layer_types`` models return logits."""
        if self._hybrid is None:
            raise ValueError("probe() needs a layer_types model: the GPT "
                             "block has no vocabulary and no logits")
        self._probe_rids = tuple(int(r) for r in rids)

    def probe_results(self) -> dict[int, dict[str, Any]]:
        """``rid -> {"slot", "recycled", "prompt_ids", "tokens",
        "logits", "prompt_state", "end_state"}`` of the last
        ``run_trace``: ``logits[i]`` (float32 ``[vocab]``, numpy) are the
        logits token ``tokens[i]`` was the ``argmax`` of; ``recycled``
        says whether the slot had served another request before; the two
        states are the slot's recurrent state ``[L_lin, heads, d_v,
        d_k]`` after the prompt and after the last decode step (which
        took in ``tokens[-2]``; None if the request did not finish)."""
        out = {}
        for rid, rec in self.probed.items():
            logits = [np.asarray(rec["first_logits"])]
            tokens = [int(np.argmax(logits[0]))]
            for toks, seen, row, col, steps in rec["units"]:
                toks, seen = np.asarray(toks), np.asarray(seen)
                if toks.ndim == 1:          # a per-step unit
                    toks, seen = toks[None], seen[None]
                tokens += [int(t) for t in toks[:steps, row]]
                logits += [seen[i, col] for i in range(steps)]
            out[rid] = {"slot": rec["slot"], "recycled": rec["recycled"],
                        "prompt_ids": rec["prompt_ids"], "tokens": tokens,
                        "logits": logits,
                        "prompt_state": np.asarray(rec["prompt_state"]),
                        "end_state": (None if rec["end_state"] is None
                                      else np.asarray(rec["end_state"]))}
        return out

    def _probe_slot(self, req: Request, slot: int, recycled: bool,
                    first_logits: jax.Array, cache: Any) -> None:
        """Start the record of a probed request just admitted into
        ``slot``, and name the slot to the decode programs (in the
        place of a probed request that is done, else the first)."""
        done = [i for i, s in enumerate(self._probe_slots)
                if not any(r["slot"] == s and not r["done"]
                           for r in self.probed.values())]
        self._probe_slots[done[0] if done else 0] = slot
        self._probe_dev = jnp.array(self._probe_slots)  # a copy
        self.probed[req.rid] = {
            "slot": slot, "recycled": recycled, "done": False,
            "prompt_ids": prompt_ids_from_seed(
                req.seed, req.prompt_len, self.config.vocab_size)[0],
            "first_logits": first_logits, "units": [],
            # the slot's recurrent state as the prompt's last chunk
            # left it: a copy of one slot, dispatched and not waited for
            "prompt_state": self._hybrid.slot_state(cache, np.int32(slot)),
            "end_state": None}

    def _prompt_input(self, req: Request, pad_to: int) -> jax.Array:
        """A request's prompt as the prefill programs take it: seeded
        embeddings ``[1, pad_to, hidden]``, or for a ``layer_types``
        model token ids ``[1, pad_to]`` (embedded on the device)."""
        if self._hybrid is not None:
            return jnp.asarray(prompt_ids_from_seed(
                req.seed, req.prompt_len, self.config.vocab_size,
                pad_to=pad_to))
        return request_embeddings(
            req.seed, req.prompt_len, self.config.hidden_size,
            dtype=self._dtype, pad_to=pad_to,
            prefix_len=req.prefix_len, prefix_seed=req.prefix_seed)

    def _fresh_carry(self):
        if self._hybrid is not None:
            return self._hybrid.fresh_carry(self.config, self.serving,
                                            self.mesh)
        create = (create_quant_kv_cache if self._quantized
                  else create_kv_cache)
        cache = create(
            self.config, self.serving.max_batch, self.serving.num_blocks,
            self.serving.block_size, mesh=self.mesh,
        )
        x = jax.device_put(
            jnp.zeros((self.serving.max_batch, 1, self.config.hidden_size),
                      self._dtype),
            self._x_sharding,
        )
        return (cache, x)

    def _create_prefix(self):
        """The carry a prompt's first chunk starts from."""
        if self._hybrid is not None:
            return self._hybrid.create_prefix(self.config, self.mesh)
        return create_prefix(self.config, self.mesh)

    def _fresh_draft_cache(self) -> Optional[KVCache]:
        """The draft model's own paged KV plane (same slot/block
        geometry as the target's — both planes cover max_seq tokens per
        slot — at the draft config's layer/kv-head dims).  None when no
        draft model is configured, so every carry-reset site can assign
        unconditionally."""
        if self._draft_config is None:
            return None
        return create_kv_cache(
            self._draft_config, self.serving.max_batch,
            self.serving.num_blocks, self.serving.block_size,
            mesh=self.mesh,
        )

    def capture_device_traces(self, trace_root: Any) -> list[dict]:
        """Serving capture parity with the sweep engine's gated capture
        (docs/observability.md): ONE dedicated prefill and ONE decode
        scan (fused when the fast path is configured) captured through
        ``obs/capture.py`` on FRESH state, strictly outside every timed
        region — the bench calls this after ``run_trace`` has returned,
        so no capture overhead can touch TTFT/goodput.  Each returned
        meta carries its ``phase`` so the devtrace report renders
        per-phase rows; failures are contained in the metas exactly as
        sweep captures are."""
        from dlbb_tpu.obs import capture as obs_capture

        if self._hybrid is not None:
            raise ValueError(
                "capture_device_traces is not wired for layer_types "
                "models (it replays a monolithic prefill, which they do "
                "not have); trace a run with benchmarks/run.py --trace 1")
        cfg = self.serving
        bucket = cfg.prefill_buckets[0]

        def prefill_payload():
            carry = self._fresh_carry()
            x = request_embeddings(0, bucket, self.config.hidden_size,
                                   dtype=self._dtype, pad_to=bucket)
            return (carry[0], x)

        def prefill_fn(t):
            return self._prefill_jit(bucket)(
                t[0], self.params, t[1], np.int32(0), np.int32(bucket))

        metas = [obs_capture.capture_device_trace(
            prefill_fn, prefill_payload, trace_root,
            label=f"serve_prefill_b{bucket}")]
        metas[0]["phase"] = "prefill"

        if self._fast and self._fused_ks:
            k = min(self._fused_ks)
            fused = (self._decode_fused_token[k] if self._token_mode
                     else self._decode_fused[k])

            if self._token_mode:
                def decode_fn(t):
                    return fused(t[0], self.params, self._table, t[1],
                                 t[2])
            else:
                def decode_fn(t):
                    return fused(t[0], self.params, t[1], t[2])

            def decode_payload():
                return (self._fresh_carry(), self._zero_active(),
                        self._zero_remaining())

            label = (f"serve_decode_fused_token_k{k}" if self._token_mode
                     else f"serve_decode_fused_k{k}")
        else:
            if self._token_mode:
                def decode_fn(t):
                    return self._decode_token(t[0], self.params,
                                              self._table, t[1])
            else:
                def decode_fn(t):
                    return self._decode(t[0], self.params, t[1])

            def decode_payload():
                return (self._fresh_carry(), self._zero_active())

            label = ("serve_decode_token_step" if self._token_mode
                     else "serve_decode_step")
        meta = obs_capture.capture_device_trace(
            decode_fn, decode_payload, trace_root, label=label)
        meta["phase"] = "decode"
        # token steps the captured program executes per dispatch — the
        # run's scans vary k, so downstream device-time accounting must
        # normalise per STEP, never per dispatch
        meta["decode_steps_per_scan"] = (min(self._fused_ks)
                                         if self._fast and self._fused_ks
                                         else 1)
        metas.append(meta)
        return metas

    def _zero_active(self) -> jax.Array:
        return jax.device_put(
            jnp.zeros((self.serving.max_batch,), bool),
            self._active_sharding)

    def _zero_remaining(self) -> jax.Array:
        return jax.device_put(
            jnp.zeros((self.serving.max_batch,), jnp.int32),
            self._active_sharding)

    def _infeasible_reason(self, r: Request) -> Optional[str]:
        """Why the envelope can never serve ``r`` (None = feasible)."""
        max_bucket = self.serving.prefill_buckets[-1]
        if r.output_len < 1:
            return f"output_len must be >= 1 (got {r.output_len})"
        if r.prompt_len < 1 or r.prompt_len > max_bucket:
            return (f"prompt_len={r.prompt_len} outside (0, {max_bucket}] "
                    "(largest prefill bucket)")
        if r.total_tokens > self.serving.max_seq:
            return (f"prompt+output={r.total_tokens} exceeds "
                    f"serving.max_seq={self.serving.max_seq} "
                    "(per-slot cache capacity)")
        need = max(1, math.ceil(r.total_tokens / self.serving.block_size))
        if need > self.serving.total_blocks:
            return (f"needs {need} cache blocks, budget is "
                    f"{self.serving.total_blocks} (serving.blocks_budget)")
        return None

    def _validate_trace(self, trace: TrafficTrace) -> None:
        """Fail BEFORE the run on any request the config cannot serve —
        an infeasible request rejected mid-trace would read as load.
        (``serving.reject_infeasible`` flips this into per-request
        runtime rejection, journaled with reason="infeasible".)"""
        for r in trace:
            reason = self._infeasible_reason(r)
            if reason is not None:
                raise ValueError(f"request {r.rid}: {reason}")

    def _prefill_jit(self, bucket: int):
        """The monolithic-prefill jit of one prompt bucket (one program
        per bucket either way; a jit of its own gives each its name,
        ``serve_prefill_b<bucket>``; built lazily, warmed by
        ``_compile``)."""
        jit = self._prefill_jits.get(bucket)
        if jit is None:
            jit = build_prefill(self.config, self.mesh,
                                quantized=self._quantized,
                                name=f"serve_prefill_b{bucket}")
            self._prefill_jits[bucket] = jit
        return jit

    def _chunk_jit(self, chunk_index: int):
        """The chunked-prefill jit for static chunk offset
        ``chunk_index * prefill_chunk`` (one retrace per offset — the
        bucketed chunk ladder; built lazily, warmed by ``_compile``)."""
        jit = self._prefill_chunk_jits.get(chunk_index)
        if jit is None:
            chunk = self.serving.prefill_chunk
            if self._hybrid is not None:
                jit = self._hybrid.build_prefill_chunk(
                    self.config, self.mesh, chunk, chunk_index * chunk)
            else:
                jit = build_prefill_chunk(self.config, self.mesh, chunk,
                                          chunk_index * chunk,
                                          quantized=self._quantized)
            self._prefill_chunk_jits[chunk_index] = jit
        return jit

    def _attach_jit(self, m_chunks: int):
        """The prefix-attach jit for ``m_chunks`` matched chunks (one
        retrace per matched chunk count — the same bucketing as the
        chunk-jit ladder; built lazily, warmed by ``_compile``)."""
        jit = self._attach_jits.get(m_chunks)
        if jit is None:
            chunk = self.serving.prefill_chunk
            jit = build_prefix_attach(self.config, self.mesh,
                                      m_chunks * chunk,
                                      self.serving.block_size,
                                      quantized=self._quantized)
            self._attach_jits[m_chunks] = jit
        return jit

    def _compile(self, buckets: list[int], max_chunks: int = 0) -> None:
        """Warm every jit the trace will hit (prefill per bucket or per
        chunk offset, decode + the fused-scan ladder, compaction,
        inject) on scratch state, so compile time never lands in TTFT."""
        carry = self._fresh_carry()
        cfg = self.serving
        active = jax.device_put(
            jnp.zeros((cfg.max_batch,), bool), self._active_sharding,
        )
        y_last = None
        for b in buckets:
            dummy = request_embeddings(0, b, self.config.hidden_size,
                                       dtype=self._dtype, pad_to=b)
            cache, y_last = self._prefill_jit(b)(
                carry[0], self.params, dummy, np.int32(0), np.int32(b))
            carry = (cache, carry[1])
        if max_chunks:
            chunk = cfg.prefill_chunk
            total = max_chunks * chunk
            dummy = self._prompt_input(
                Request(rid=-1, arrival_s=0.0, prompt_len=total,
                        output_len=1, seed=0), total)
            prefix = self._create_prefix()
            cache = carry[0]
            for ci in range(max_chunks):
                cache, prefix, y_last = self._chunk_jit(ci)(
                    cache, prefix, self.params,
                    dummy[:, ci * chunk:(ci + 1) * chunk],
                    np.int32(0), np.int32(total))
            if cfg.prefix_caching:
                # the attach ladder: one jit per possible matched chunk
                # count (a full prompt always keeps >= 1 unmatched
                # chunk, so the ladder stops at max_chunks - 1)
                for m in range(1, max_chunks):
                    cache, _prefix = self._attach_jit(m)(
                        cache, np.int32(0), np.int32(0))
            carry = (cache, carry[1])
        remaining = jax.device_put(
            jnp.zeros((cfg.max_batch,), jnp.int32), self._active_sharding)
        if self._token_mode:
            # token-feedback warms: the legacy inject/decode/fused jits
            # are never dispatched in a token-mode run, so warming them
            # would only burn compile time — and a SAMPLED run likewise
            # never dispatches the greedy inject/decode/verify programs
            # (its entire decode surface is verify_probs + spec_commit)
            if self._sampled:
                carry = self._inject_sampled(carry, np.int32(0),
                                             np.int32(0), self._table)
                zeros_i = jax.device_put(
                    jnp.zeros((cfg.max_batch,), jnp.int32),
                    self._active_sharding)
                for g in sorted(self._verify_probs):
                    ids = jax.device_put(
                        jnp.zeros((cfg.max_batch, g), jnp.int32),
                        self._ids_sharding)
                    carry, _y = self._verify_probs[g](
                        carry, self.params, self._table, ids, active)
                carry = self._spec_commit(carry, self._table, zeros_i,
                                          remaining, active)
            else:
                carry, _tok = self._inject_greedy(carry, np.int32(0),
                                                  y_last, self._table)
                carry, _tok = self._decode_token(carry, self.params,
                                                 self._table, active)
                for k in self._fused_ks:
                    carry, _toks = self._decode_fused_token[k](
                        carry, self.params, self._table, active,
                        remaining)
                for g in self._spec_gammas:
                    ids = jax.device_put(
                        jnp.zeros((cfg.max_batch, g), jnp.int32),
                        self._ids_sharding)
                    carry, _tok, _commits = self._verify[g](
                        carry, self.params, self._table, ids, active,
                        remaining)
            if self._draft_config is not None:
                dcache = self._fresh_draft_cache()
                for b in buckets:
                    dummy = request_embeddings(
                        0, b, self.config.hidden_size,
                        dtype=self._dtype, pad_to=b)
                    dcache, _dy = self._draft_prefill(
                        dcache, self._draft_params, dummy, np.int32(0),
                        np.int32(b))
                dlen = jax.device_put(
                    jnp.zeros((cfg.max_batch,), jnp.int32),
                    self._active_sharding)
                for g in self._spec_gammas:
                    dcache, _ids = self._draft_scan[g](
                        dcache, self._draft_params, self._table,
                        carry[1], dlen, active)
                jax.block_until_ready(dcache.lengths)
            jax.block_until_ready(carry[1])
            return
        carry = self._inject(carry, np.int32(0), y_last)
        if self._hybrid is not None:
            self._hybrid.slot_state(carry[0], np.int32(0))
        carry, _y = self._decode(carry, self.params, active)
        for k in self._fused_ks:
            carry, _ys = self._decode_fused[k](carry, self.params, active,
                                               remaining)
        if self._compact_gather_fn is not None:
            bucket = cfg.max_batch // 2
            idx = jax.device_put(jnp.arange(bucket, dtype=jnp.int32),
                                 self._active_sharding)
            s_active = jax.device_put(jnp.zeros((bucket,), bool),
                                      self._active_sharding)
            s_rem = jax.device_put(jnp.zeros((bucket,), jnp.int32),
                                   self._active_sharding)
            small = self._compact_gather_fn(carry, idx)
            for k in self._fused_ks:
                small, _ys = self._decode_fused[k](small, self.params,
                                                   s_active, s_rem)
            carry = self._compact_scatter_fn(carry, small, idx)
        # block on the live carry, not an intermediate output: earlier
        # outputs may share buffers with a carry a later warm call donated
        jax.block_until_ready(carry[1])

    def _event(self, event: str, rid: int, **extra: Any) -> None:
        # the request's identifier in the span file: every lifecycle
        # event as an instant carrying the ``rid`` the admission spans
        # carry (a global load when tracing is off)
        spans.instant(event, cat="request", rid=rid)
        if self.journal is not None:
            self.journal.event(event, config=f"request-{rid}", **extra)
        ctl = self._control
        if ctl is not None and getattr(ctl, "on_event", None) is not None:
            # live lifecycle feed to the fleet supervisor (terminal
            # accounting, hedge winner detection); a sink failure must
            # never take the replica down — the journal line above is
            # already durable
            try:
                ctl.on_event(rid, event, dict(extra))
            except Exception:  # noqa: BLE001 — contained by contract
                pass

    # -- the run -----------------------------------------------------------

    def run_trace(self, trace: TrafficTrace,
                  guard: Optional[PreemptionGuard] = None,
                  collect_raw: bool = False,
                  feed: Any = None,
                  control: Any = None) -> dict[str, Any]:
        """Serve ``trace`` to completion (or to a graceful preemption
        drain); returns the report dict (``docs/serving.md`` documents
        every field).  Pure compute + host scheduling — writing
        artifacts is ``serve/bench.py``'s job.

        ``guard``: an installed :class:`PreemptionGuard` (the bench
        harness passes its own); None installs one for the run when
        possible (main thread).  On SIGTERM the engine stops admission,
        drains the in-flight window, journals still-resident requests
        ``request-preempted``, and returns a report with
        ``preempted=True`` + ``remaining_rids`` — the snapshot
        ``cli serve --resume`` replays.  ``collect_raw`` adds the raw
        latency sample lists to the report (``raw_samples``; always
        present on a preempted report so resume can merge honestly).

        ``feed``/``control`` are the fleet-replica hooks
        (``serve/fleet.py``): ``feed`` replaces the static arrival
        deque with a supervisor-fed :class:`~dlbb_tpu.serve.fleet.
        RequestFeed` (``trace`` is still used for compile planning and
        feasibility), and ``control`` is the replica control plane —
        heartbeat, kill/hang fault sites, hedge cancels, degradation
        overrides, and the fleet-shared clock origin — checked strictly
        at the scheduler-loop boundary."""
        if guard is None:
            with PreemptionGuard() as own:
                return self._serve_trace(trace, own, collect_raw,
                                         feed, control)
        return self._serve_trace(trace, guard, collect_raw, feed, control)

    def _serve_trace(self, trace: TrafficTrace, guard: PreemptionGuard,
                     collect_raw: bool, feed: Any = None,
                     control: Any = None) -> dict[str, Any]:
        self._control = control
        if not len(trace):
            raise ValueError("cannot serve an empty trace")
        cfg = self.serving
        if cfg.reject_infeasible:
            feasible = [r for r in trace
                        if self._infeasible_reason(r) is None]
            if not feasible:
                raise ValueError(
                    "every request in the trace is infeasible for this "
                    "serving envelope — nothing to serve"
                )
        else:
            self._validate_trace(trace)
            feasible = list(trace)
        if cfg.prefill_chunk is not None:
            buckets: list[int] = []
            max_chunks = max(-(-r.prompt_len // cfg.prefill_chunk)
                             for r in feasible)
        else:
            buckets = sorted({cfg.bucket_for(r.prompt_len)
                              for r in feasible})
            max_chunks = 0
        with Timer() as t_compile:
            self._compile(buckets, max_chunks)
        compile_time = t_compile.elapsed

        ledger = BlockLedger(cfg.total_blocks, cfg.block_size,
                             prefix_caching=cfg.prefix_caching)
        # registry counters are cumulative across an engine's lifetime
        # (Prometheus semantics); the report carries THIS run's deltas
        counts_base = {k: self._requests[k] for k in self._requests}
        shed_base = self._rejections["queue-full"]
        # a fleet supervisor feeds arrivals dynamically (and re-feeds
        # failovers at queue head); a standalone run serves the static
        # trace in arrival order
        pending = (feed if feed is not None
                   else deque(sorted(trace,
                                     key=lambda r: (r.arrival_s, r.rid))))
        queue: deque[Request] = deque()
        slots: dict[int, _SlotState] = {}
        free_slots = list(range(cfg.max_batch))
        stats = _RunStats()
        series: dict[str, list] = {
            "t_s": [], "queue_depth": [], "active_slots": [],
            "blocks_in_use": [], "blocks_reserved": [],
        }
        if cfg.prefix_caching:
            series["shared_blocks"] = []
        carry = self._fresh_carry()
        active_np = np.zeros((cfg.max_batch,), bool)
        active_dev = jax.device_put(jnp.array(active_np),
                                    self._active_sharding)
        # layer_types models: slots that have served a request in this
        # run (the next one's first chunk clears their state), and the
        # record of the probed requests (``probe``)
        used_slots: set[int] = set()
        if self._hybrid is not None:
            self.probed = {}
            self._probe_slots[:] = -1
            self._probe_dev = jnp.array(self._probe_slots)  # a copy
        rejected_detail: list[dict[str, Any]] = []
        tokens_by_rid: dict[int, list[int]] = {}
        # -- speculative decoding state (docs/serving.md) --
        token_mode = self._token_mode
        spec_on = cfg.spec_drafting
        # per-rid committed token history (prompt ids + every committed
        # token): the n-gram drafter's lookup context
        hist: dict[int, list[int]] = {}
        # sampled decode's host RNG: seeded from the config knob so a
        # (trace, config) pair replays token-for-token — the journal'd
        # runs stay deterministic even though the law is a distribution
        sample_rng = (np.random.default_rng(cfg.sample_seed)
                      if self._sampled else None)
        # the draft model's KV plane rides in a one-slot holder (the
        # closures below rebind it at every dispatch / carry reset);
        # its ledger mirrors the target's accounting — the draft plane
        # has the same slot/block geometry, and its COMMITTED content
        # tracks the target's exactly (draft writes past the committed
        # length are dead by the length-mask construction)
        draft_cache: list[Optional[KVCache]] = [self._fresh_draft_cache()]
        draft_ledger = (BlockLedger(cfg.total_blocks, cfg.block_size)
                        if draft_cache[0] is not None else None)
        # run-level acceptance EMA (the metrics.prom gauge)
        accept_ema_run = [-1.0]
        # per-request final outcome map (rid -> "completed" /
        # "rejected[reason]" / "failed[reason]" / "preempted") — the
        # thing kill-mid-trace ≡ uninterrupted equivalence is pinned on
        outcomes: dict[int, str] = {}
        # permanent-failure records: full exception chains, never a
        # silent skip (the serving twin of the sweep quarantine)
        failed_detail: list[dict[str, Any]] = []
        # bounded in-flight window: decode units dispatched but not yet
        # synced (cfg.inflight_window == 1 syncs every unit — the
        # legacy cadence); last_sync anchors the per-unit interval so
        # back-to-back units never double-count queued device time
        inflight: deque[dict[str, Any]] = deque()
        last_sync = [0.0]
        # host-side active_np mutations are staged; the device mask is
        # re-uploaded lazily, and ALWAYS before a decode dispatch — a
        # decode interleaved into the admission loop (chunked prefill)
        # must see slots admitted earlier in the same loop
        active_dirty = [False]

        def refresh_active() -> None:
            # ``jnp.array``, a copy: ``active_np`` is edited in place
            # after a unit is dispatched, and on the CPU backend
            # ``jnp.asarray`` may alias an aligned numpy buffer, so that a
            # unit not yet run would see a completing slot as inactive
            nonlocal active_dev
            if active_dirty[0]:
                active_dev = jax.device_put(jnp.array(active_np),
                                            self._active_sharding)
                active_dirty[0] = False

        def release(slot: int) -> _SlotState:
            """Host scan-exit: free a completed slot's blocks + slot so
            the next admission can reuse them (device order is safe —
            the scan already masked the slot inactive)."""
            st = slots.pop(slot)
            ledger.free(slot)
            if draft_ledger is not None:
                draft_ledger.free(slot)
            active_np[slot] = False
            active_dirty[0] = True
            free_slots.append(slot)
            free_slots.sort()
            if self._hybrid is not None and st.req.rid in self.probed:
                self.probed[st.req.rid]["done"] = True
            return st

        def finish(st: _SlotState, done_at: float) -> None:
            """Completion stats + journal at the unit's SYNC point (the
            honest timestamp — the device work is provably done)."""
            lat = done_at - st.req.arrival_s
            stats.e2e_latency_s.append(lat)
            stats.completed_output_tokens += st.req.output_len
            self._requests["completed"] += 1
            outcomes[st.req.rid] = "completed"
            extra: dict[str, Any] = {}
            if st.req.deadline_s is not None and lat > st.req.deadline_s:
                # served, but past its SLO — a first-class count, not a
                # rejection (the tokens were delivered)
                stats.completed_past_deadline += 1
                self._deadline_counter["completed-late"] += 1
                extra["past_deadline"] = True
            if self.capture_tokens:
                # tokens ride the completion event so a fleet supervisor
                # keeps them even when this replica dies right after
                # (its report — the usual carrier — dies with it)
                extra["tokens"] = [int(t) for t in
                                   tokens_by_rid.get(st.req.rid, [])]
            self._event("request-completed", st.req.rid,
                        output_tokens=st.req.output_len,
                        latency_s=round(lat, 6), **extra)

        def take_snapshot() -> dict[str, Any]:
            """Pre-dispatch rollback point: the host ledger/slot/
            admission bookkeeping (tiny, host-only copies).  The device
            carry needs no snapshot because every fault site fires
            BEFORE the jit consumes it — a restored host state always
            matches the on-device state (docs/resilience.md)."""
            return {
                "ledger": ledger.snapshot(),
                "draft_ledger": (draft_ledger.snapshot()
                                 if draft_ledger is not None else None),
                "slots": {s: (st, st.tokens_done)
                          for s, st in slots.items()},
                "free_slots": list(free_slots),
                "active": active_np.copy(),
                "generated": stats.generated_tokens,
            }

        def restore_snapshot(snap: dict[str, Any]) -> None:
            ledger.restore(snap["ledger"])
            if draft_ledger is not None:
                draft_ledger.restore(snap["draft_ledger"])
            slots.clear()
            for s, (st, td) in snap["slots"].items():
                st.tokens_done = td
                slots[s] = st
            free_slots[:] = snap["free_slots"]
            active_np[:] = snap["active"]
            active_dirty[0] = True
            stats.generated_tokens = snap["generated"]

        def fail_requests(states: list[_SlotState], exc: BaseException,
                          reason: str) -> None:
            """Fail requests CLOSED: journaled ``request-failed`` with
            the full exception chain, outcome recorded, counters bumped
            — never a silent skip, and never the whole run."""
            rec = exception_chain(exc)
            rids = []
            for st in states:
                rids.append(st.req.rid)
                outcomes[st.req.rid] = f"failed[{reason}]"
                stats.failed_requests += 1
                self._requests["failed"] += 1
                self._event("request-failed", st.req.rid, reason=reason,
                            error=rec["error"],
                            tokens_done=st.tokens_done)
            failed_detail.append({"reason": reason, "rids": rids, **rec})

        def fail_resident(exc: BaseException, reason: str) -> None:
            """Fail every currently-resident request (the affected set
            of a permanently-failed or hung decode unit — decode covers
            the whole resident batch), freeing their slots + blocks."""
            fail_requests([release(s) for s in sorted(list(slots))],
                          exc, reason)

        def cancel_request(rid: int, reason: str) -> None:
            """Supervisor-requested cancel (serve/fleet.py: the losing
            hedge duplicate).  Resident: the in-flight window settles
            first so the release happens at a sync point, then the
            slot's blocks are freed.  Queued / not-yet-fed: the request
            is simply dropped.  An unknown rid is a benign race — the
            request completed between the cancel decision and this loop
            boundary — and a no-op by design (the tokens are identical
            on both replicas, so a double completion is harmless)."""
            slot = next((s for s, st in slots.items()
                         if st.req.rid == rid), None)
            if slot is not None:
                drain()
                st_now = slots.get(slot)
                if st_now is None or st_now.req.rid != rid:
                    return  # completed (or failed) at the drain sync
                st = release(slot)
                hist.pop(rid, None)
                outcomes[rid] = f"canceled[{reason}]"
                self._requests["canceled"] += 1
                self._event("request-canceled", rid, reason=reason,
                            tokens_done=st.tokens_done)
                return
            for r in list(queue):
                if r.rid == rid:
                    queue.remove(r)
                    outcomes[rid] = f"canceled[{reason}]"
                    self._requests["canceled"] += 1
                    self._event("request-canceled", rid, reason=reason,
                                tokens_done=0)
                    return
            if feed is not None and feed.discard(rid):
                outcomes[rid] = f"canceled[{reason}]"
                self._requests["canceled"] += 1
                self._event("request-canceled", rid, reason=reason,
                            tokens_done=0)

        # EMA of the observed per-step interval: the horizon policy uses
        # it to convert "next arrival in X seconds" into a step budget,
        # and the dispatch watchdog scales its deadline from it
        step_ema = [0.0]
        # bumped at every catastrophic carry replacement (hung/failed
        # dispatch, abandoned window): the chunked-prefill interleave
        # checks it — chunks already written to the OLD cache are gone
        # with it, so a mid-prefill reset must restart the prefill
        # rather than keep chunking into the fresh empty cache
        carry_resets = [0]

        def unit_deadline(k: int) -> Optional[float]:
            """Watchdog deadline for a k-step unit: EMA-scaled with a
            floor while the EMA is cold; None = watchdog off."""
            f = cfg.dispatch_deadline_factor
            if f is None:
                return None
            return max(cfg.dispatch_deadline_min_s, f * k * step_ema[0])

        def abandon_window(first_unit: dict[str, Any],
                           exc: BaseException) -> None:
            """A unit's sync blew its deadline: every un-synced unit
            chains off the same donated carry, so the whole window is
            abandoned — its requests (including completions that were
            never confirmed at a sync point) fail closed, and the
            engine continues on a fresh carry."""
            nonlocal carry
            stats.hung_dispatches += 1
            self.registry.inc("serve_hung_dispatches")
            hung = [first_unit] + list(inflight)
            inflight.clear()
            last_sync[0] = time.perf_counter()
            unconfirmed = [st for u in hung for st in u["completions"]]
            fail_requests(unconfirmed, exc, "hung-dispatch")
            fail_resident(exc, "hung-dispatch")
            carry = self._fresh_carry()
            draft_cache[0] = self._fresh_draft_cache()
            carry_resets[0] += 1

        def sync_one() -> None:
            unit = inflight.popleft()
            try:
                # ``k`` is the unit WAITED FOR — under a deeper window
                # an older one than the unit just dispatched
                with spans.span("serve-decode-sync", k=unit["k_exec"]):
                    _with_deadline(
                        lambda: jax.block_until_ready(unit["ys"]),
                        unit_deadline(unit["k_exec"]),
                        f"decode[k={unit['k_exec']}]", "serve-sync")
            except DeadlineExceeded as e:
                abandon_window(unit, e)
                return
            t_ready = time.perf_counter()
            dt = t_ready - max(unit["t0"], last_sync[0])
            last_sync[0] = t_ready
            stats.decode_step_s.append(dt)
            per_step = dt / unit["k_exec"]
            step_ema[0] = (per_step if step_ema[0] == 0.0
                           else 0.5 * step_ema[0] + 0.5 * per_step)
            for _row, _slot, _rid, steps in unit["rows"]:
                for _ in range(steps):
                    stats.per_token_s.append(dt / unit["k_exec"])
            done_at = self._now()
            if unit.get("tokens"):
                # token-feedback unit: ys are the committed token ids
                # themselves ([B] per-step, [k, B] fused) — the n-gram
                # history extends from them even when capture is off
                if cfg.speculation == "ngram" or self.capture_tokens:
                    toks_np = np.asarray(unit["ys"])
                    if toks_np.ndim == 1:   # per-step unit: [B]
                        toks_np = toks_np[None]
                    for row, _slot, rid, steps in unit["rows"]:
                        ids = [int(t) for t in toks_np[:steps, row]]
                        if cfg.speculation == "ngram" and rid in hist:
                            hist[rid].extend(ids)
                        if self.capture_tokens:
                            tokens_by_rid.setdefault(rid, []).extend(ids)
            elif self.capture_tokens:
                ys_np = np.asarray(unit["ys"], np.float32)
                if ys_np.ndim == 3:        # per-step unit: [B, 1, H]
                    ys_np = ys_np[None]
                for row, _slot, rid, steps in unit["rows"]:
                    for i in range(steps):
                        tokens_by_rid.setdefault(rid, []).append(
                            int(np.argmax(ys_np[i, row, 0])))
            # finish AFTER the unit's token capture: the completion
            # event carries the request's full committed token list
            for st in unit["completions"]:
                finish(st, done_at)

        def drain() -> None:
            while inflight:
                sync_one()

        def decode_unit(k: int, steps: dict[int, int], compact: bool,
                        snap: dict[str, Any]) -> None:
            """One decode unit, committed: the device dispatch (under
            the watchdog when armed), torn-protected host bookkeeping,
            and the in-flight window push + boundary sync.  Transient
            bookkeeping faults roll themselves back and replay (pure
            host recomputation — the device result is already in hand,
            so NEVER a re-dispatch); everything else raises out to
            ``dispatch_decode``'s recovery loop with nothing committed."""
            nonlocal carry
            rows: list[tuple[int, int, int, int]] = []
            deadline = unit_deadline(k)
            t0 = time.perf_counter()
            # ONE span per dispatched unit, covering dispatch AND the
            # boundary sync below — in the per-step/window=1 cadence
            # the span therefore spans the real step wall (as PR-9's
            # did); under a deeper window the synced device time
            # belongs to an older unit and per-unit device attribution
            # lives in decode_step_s/per_token_s instead.  Its children
            # tell the two apart: ``serve-decode-dispatch`` (the jit
            # call of THIS unit) and ``serve-decode-sync`` (the wait,
            # with the ``k`` of the unit waited for); what is left is
            # the host bookkeeping at scan exit
            span_args = dict(active=len(slots), steps=k)
            if compact:
                span_args["compacted"] = True
            with spans.span("serve-decode", **span_args):
                if inject.fire("serve-decode-fail"):
                    # fires BEFORE the jit is invoked: the donated carry
                    # was never consumed, so a retry re-dispatches from
                    # unchanged device state
                    raise TransientFault(
                        "injected serve-decode-fail at the decode "
                        "dispatch boundary")

                def dispatch(fn):
                    def run():
                        if inject.fire("serve-decode-hang"):
                            # a wedged dispatch: the sleep sits on the
                            # watchdog's daemon thread, never on the
                            # engine's scheduler thread
                            time.sleep(inject.param("hang_seconds"))
                        return fn()
                    with spans.span("serve-decode-dispatch", k=k):
                        return _with_deadline(run, deadline,
                                              f"decode[k={k}]",
                                              "serve-dispatch")

                if k == 1:
                    if token_mode:
                        carry, ys = dispatch(
                            lambda: self._decode_token(
                                carry, self.params, self._table,
                                active_dev))
                    else:
                        carry, ys = dispatch(
                            lambda: self._decode(carry, self.params,
                                                 active_dev))
                    stats.single_steps += 1
                    for s in sorted(steps):
                        rows.append((s, s, slots[s].req.rid, 1))
                elif compact:
                    bucket = cfg.max_batch // 2
                    act = sorted(slots)
                    idx_np = np.asarray(
                        act + free_slots[:bucket - len(act)], np.int32)
                    idx = jax.device_put(jnp.asarray(idx_np),
                                         self._active_sharding)
                    s_act_np = np.zeros((bucket,), bool)
                    s_act_np[:len(act)] = True
                    s_rem_np = np.zeros((bucket,), np.int32)
                    for i, s in enumerate(act):
                        s_rem_np[i] = steps[s]
                    s_act = jax.device_put(jnp.asarray(s_act_np),
                                           self._active_sharding)
                    s_rem = jax.device_put(jnp.asarray(s_rem_np),
                                           self._active_sharding)

                    extra = {}
                    if self._hybrid is not None:
                        # the probed slots by their rows in the
                        # compacted batch
                        extra["probe"] = jnp.array(
                            [act.index(s) if s in act else -1
                             for s in self._probe_slots], jnp.int32)

                    def compact_unit():
                        small = self._compact_gather_fn(carry, idx)
                        small, ys = self._decode_fused[k](
                            small, self.params, s_act, s_rem, **extra)
                        return (self._compact_scatter_fn(carry, small,
                                                         idx), ys)

                    carry, ys = dispatch(compact_unit)
                    stats.fused_scans += 1
                    stats.fused_steps += k
                    stats.compacted_scans += 1
                    self.registry.inc("serve_fused_scan_steps", k)
                    for i, s in enumerate(act):
                        rows.append((i, s, slots[s].req.rid, steps[s]))
                else:
                    rem_np = np.zeros((cfg.max_batch,), np.int32)
                    for s, m in steps.items():
                        rem_np[s] = m
                    rem_dev = jax.device_put(jnp.asarray(rem_np),
                                             self._active_sharding)
                    if token_mode:
                        carry, ys = dispatch(
                            lambda: self._decode_fused_token[k](
                                carry, self.params, self._table,
                                active_dev, rem_dev))
                    else:
                        carry, ys = dispatch(
                            lambda: self._decode_fused[k](
                                carry, self.params, active_dev, rem_dev))
                    stats.fused_scans += 1
                    stats.fused_steps += k
                    self.registry.inc("serve_fused_scan_steps", k)
                    for s in sorted(steps):
                        rows.append((s, s, slots[s].req.rid, steps[s]))
                if self._kv_tile:
                    # what the unit's steps fetch of the K/V planes: step
                    # i of a slot reads the tiles under its length + i
                    max_tiles = cfg.max_seq // self._kv_tile
                    trip = np.arange(k)[:, None]
                    start = np.array([ledger.tokens(s) for s in steps])
                    live = int(live_tile_counts(
                        start[None, :] + trip,
                        trip < np.array(list(steps.values()))[None, :],
                        self._kv_tile, max_tiles).sum())
                    held = k * max_tiles * (cfg.max_batch // 2 if compact
                                            else cfg.max_batch)
                    stats.kv_tiles_live += live
                    stats.kv_tiles_held += held
                    self.registry.inc("serve_kv_tiles_live", live)
                    self.registry.inc("serve_kv_tiles_held", held)
                # host bookkeeping at scan exit: the ledger's known
                # lengths make every step's outcome deterministic at
                # dispatch time.  A torn half-applied update
                # (serve-cache-torn) restores the pre-dispatch snapshot
                # and REPLAYS the accounting — the device result is
                # already in hand, so this is pure host recomputation,
                # never a re-dispatch
                book_attempt = 0
                while True:
                    completions: list[int] = []
                    try:
                        for s, m in sorted(steps.items()):
                            st = slots[s]
                            st.tokens_done += m
                            if inject.fire("serve-cache-torn"):
                                raise TransientFault(
                                    "injected serve-cache-torn: ledger/"
                                    "slot bookkeeping torn mid-unit")
                            ledger.append(s, m)
                            if draft_ledger is not None:
                                draft_ledger.append(s, m)
                            stats.generated_tokens += m
                            if st.tokens_done >= st.req.output_len:
                                completions.append(s)
                        break
                    except (TransientFault, CorruptStats) as e:
                        restore_snapshot(snap)
                        if book_attempt >= cfg.max_dispatch_retries:
                            raise RuntimeError(
                                "ledger/slot bookkeeping kept failing "
                                "after the decode unit completed on "
                                "device"
                            ) from e
                        book_attempt += 1
                        stats.retries += 1
                        self._retry_counter["bookkeeping"] += 1
                        if self.journal is not None:
                            self.journal.event(
                                "dispatch-retry", phase="bookkeeping",
                                attempt=book_attempt, error=str(e))
                        time.sleep(cfg.retry_backoff_s
                                   * (2 ** (book_attempt - 1)))
                stats.decode_steps += k
                stats.decode_units += 1
                self.registry.inc("serve_decode_steps", k)
                if self._hybrid is not None:
                    # ys = (tokens, logits of the probed slots): a
                    # probed request keeps both, on the device
                    toks, seen = ys
                    for row, slot_, rid, m in rows:
                        if rid in self.probed:
                            col = int(np.flatnonzero(
                                self._probe_slots == slot_)[0])
                            self.probed[rid]["units"].append(
                                (toks, seen, row, col, m))
                    ys = toks
                    for s in completions:
                        # a probed request's state as its last step
                        # left it, copied before the slot is given away
                        rec = self.probed.get(slots[s].req.rid)
                        if rec is not None:
                            rec["end_state"] = self._hybrid.slot_state(
                                carry[0], np.int32(s))
                done_states = [release(s) for s in completions]
                if completions:
                    refresh_active()
                inflight.append({"t0": t0, "ys": ys, "k_exec": k,
                                 "rows": rows,
                                 "tokens": (token_mode
                                            or self._hybrid is not None),
                                 "completions": done_states})
                # a k==1 unit's y is the SAME logical value as the
                # carry's x (decode_step returns ((cache, y), y)); on
                # donation-honoring backends the duplicate outputs may
                # alias one buffer, and the next dispatch donating the
                # carry would invalidate the held ys — so per-step
                # units never stay in flight (a fused scan's stacked
                # ys is its own buffer and may)
                window = 1 if k == 1 else cfg.inflight_window
                while len(inflight) >= window:
                    sync_one()

        def spec_unit(g: int, drafts_np: np.ndarray,
                      snap: dict[str, Any]) -> None:
            """One draft-and-verify unit, committed: draft (host match
            already in ``drafts_np`` for ngram; the draft-model scan
            dispatches here), ONE batched target verify over the whole
            resident batch, a synchronous commit read, and the
            rollback-disciplined host bookkeeping.

            A verify unit never rides the in-flight window: its host
            accounting depends on the device's acceptance result, so it
            syncs at its own boundary (the window was drained before
            drafting — history and bookkeeping must be current).
            Bookkeeping is optimistic-then-rollback: every slot is
            first accounted the full γ+1 window (the fused-scan
            discipline — outcomes known at dispatch time), and the
            synced commits roll any shortfall back to the pre-dispatch
            snapshot (PR-11's ledger snapshot/restore as the
            rejection-rollback primitive) and replay the true counts.
            The rejected suffix needs NO device cleanup: appended-but-
            rejected cache positions sit past the committed lengths,
            attention is length-masked, and the next unit's writes land
            at the committed lengths — dead by construction (asserted
            by the token-identity tests, never copied or zeroed)."""
            nonlocal carry
            refresh_active()
            rows = [(s, slots[s].req.rid) for s in sorted(slots)]
            rem_map = {s: slots[s].req.output_len - slots[s].tokens_done
                       for s, _ in rows}
            deadline = unit_deadline(g + 1)
            t0 = time.perf_counter()
            with spans.span("serve-verify", active=len(slots), gamma=g,
                            drafter=cfg.speculation):
                if inject.fire("serve-decode-fail"):
                    # fires BEFORE any jit consumes the carry — a retry
                    # re-dispatches from unchanged device state (same
                    # contract as the decode unit's site)
                    raise TransientFault(
                        "injected serve-decode-fail at the verify "
                        "dispatch boundary")

                def dispatch(fn):
                    def run():
                        if inject.fire("serve-decode-hang"):
                            time.sleep(inject.param("hang_seconds"))
                        return fn()
                    return _with_deadline(run, deadline,
                                          f"verify[gamma={g}]",
                                          "serve-dispatch")

                rem_np = np.zeros((cfg.max_batch,), np.int32)
                for s, _ in rows:
                    rem_np[s] = rem_map[s]
                rem_dev = jax.device_put(jnp.asarray(rem_np),
                                         self._active_sharding)
                if cfg.speculation == "draft-model":
                    # the draft plane's rejection rollback IS this
                    # lengths vector: the host's committed lengths
                    # override the plane's own (advanced-by-γ) leaf,
                    # and entries past them are dead by the same
                    # length-mask construction as the target's
                    lengths_np = np.zeros((cfg.max_batch,), np.int32)
                    for s, _ in rows:
                        st = slots[s]
                        lengths_np[s] = (st.req.prompt_len
                                         + st.tokens_done - 1)
                    dlen = jax.device_put(jnp.asarray(lengths_np),
                                          self._active_sharding)
                    t_d = time.perf_counter()
                    dcache, ids = dispatch(
                        lambda: self._draft_scan[g](
                            draft_cache[0], self._draft_params,
                            self._table, carry[1], dlen, active_dev))
                    draft_cache[0] = dcache
                    # host dispatch wall only — the proposals stay on
                    # device and flow straight into the verify
                    stats.spec_draft_s += time.perf_counter() - t_d
                else:
                    ids = jax.device_put(jnp.asarray(drafts_np),
                                         self._ids_sharding)
                committed_ids: Optional[dict[int, list[int]]] = None
                if self._sampled:
                    # sampled verify: the device computes the γ+1
                    # verify logits WITHOUT committing (lengths/x come
                    # back unchanged — retry-idempotent); acceptance is
                    # the host's residual-sampling pass (the literal
                    # ``speculative_sample`` helper, q = the
                    # deterministic drafter's one-hot), and the tiny
                    # spec_commit program applies the decided commits
                    carry, y = dispatch(
                        lambda: self._verify_probs[g](
                            carry, self.params, self._table, ids,
                            active_dev))
                    y_np = _with_deadline(
                        lambda: np.asarray(y), deadline,
                        f"verify[gamma={g}]", "serve-sync")
                    ids_np = (np.asarray(ids)
                              if cfg.speculation == "draft-model"
                              else drafts_np)
                    vocab = y_np.shape[-1]
                    commits_np = np.zeros((cfg.max_batch,), np.int32)
                    next_np = np.zeros((cfg.max_batch,), np.int32)
                    committed_ids = {}
                    for s, _rid in rows:
                        p_rows = softmax_np(y_np[s], cfg.temperature)
                        toks: list[int] = []
                        for j in range(g):
                            d_id = int(ids_np[s, j])
                            q = np.zeros((vocab,), np.float64)
                            q[d_id] = 1.0
                            t, ok = speculative_sample(
                                p_rows[j], q, d_id, sample_rng)
                            toks.append(t)
                            if not ok:
                                break
                        else:
                            # every draft accepted: the window's +1
                            # bonus is a free draw from the last
                            # position's target distribution
                            toks.append(int(sample_rng.choice(
                                vocab, p=p_rows[g])))
                        m = min(len(toks), rem_map[s])
                        commits_np[s] = m
                        next_np[s] = toks[m - 1]
                        committed_ids[s] = toks[:m]
                    next_dev = jax.device_put(jnp.asarray(next_np),
                                              self._active_sharding)
                    com_dev = jax.device_put(jnp.asarray(commits_np),
                                             self._active_sharding)
                    carry = dispatch(
                        lambda: self._spec_commit(
                            carry, self._table, next_dev, com_dev,
                            active_dev))
                    self.registry.inc("serve_sampled_tokens",
                                      int(commits_np.sum()))
                else:
                    carry, tok, commits = dispatch(
                        lambda: self._verify[g](
                            carry, self.params, self._table, ids,
                            active_dev, rem_dev))
                    commits_np = _with_deadline(
                        lambda: np.asarray(commits), deadline,
                        f"verify[gamma={g}]", "serve-sync")
                t_ready = time.perf_counter()
                dt = t_ready - max(t0, last_sync[0])
                last_sync[0] = t_ready
                # torn-protected bookkeeping (the decode unit's replay
                # discipline): the device result is in hand, so every
                # replay is pure host recomputation, never a re-dispatch
                book_attempt = 0
                while True:
                    completions: list[int] = []
                    try:
                        for s, _rid in rows:
                            st = slots[s]
                            opt = min(g + 1, rem_map[s])
                            st.tokens_done += opt
                            ledger.append(s, opt)
                            if draft_ledger is not None:
                                draft_ledger.append(s, opt)
                            stats.generated_tokens += opt
                        if inject.fire("serve-cache-torn"):
                            raise TransientFault(
                                "injected serve-cache-torn: ledger/slot "
                                "bookkeeping torn mid-verify")
                        if any(int(commits_np[s]) != min(g + 1, rem_map[s])
                               for s, _ in rows):
                            # rejection rollback: restore the
                            # pre-dispatch snapshot, replay TRUE commits
                            restore_snapshot(snap)
                            for s, _rid in rows:
                                st = slots[s]
                                m = int(commits_np[s])
                                st.tokens_done += m
                                ledger.append(s, m)
                                if draft_ledger is not None:
                                    draft_ledger.append(s, m)
                                stats.generated_tokens += m
                        for s, _rid in rows:
                            if (slots[s].tokens_done
                                    >= slots[s].req.output_len):
                                completions.append(s)
                        break
                    except (TransientFault, CorruptStats) as e:
                        restore_snapshot(snap)
                        if book_attempt >= cfg.max_dispatch_retries:
                            raise RuntimeError(
                                "ledger/slot bookkeeping kept failing "
                                "after the verify unit completed on "
                                "device"
                            ) from e
                        book_attempt += 1
                        stats.retries += 1
                        self._retry_counter["bookkeeping"] += 1
                        if self.journal is not None:
                            self.journal.event(
                                "dispatch-retry", phase="bookkeeping",
                                attempt=book_attempt, error=str(e))
                        time.sleep(cfg.retry_backoff_s
                                   * (2 ** (book_attempt - 1)))
                # committed: per-slot acceptance stats, adaptive γ,
                # history/capture, then completions at THIS sync point
                stats.decode_steps += 1
                stats.decode_units += 1
                stats.spec_verify_units += 1
                self.registry.inc("serve_decode_steps", 1)
                stats.decode_step_s.append(dt)
                step_ema[0] = (dt if step_ema[0] == 0.0
                               else 0.5 * step_ema[0] + 0.5 * dt)
                drafter = cfg.speculation
                ladder = self._spec_gammas
                unit_acc = 0
                tok_np = (np.asarray(tok)
                          if (committed_ids is None
                              and (drafter == "ngram"
                                   or self.capture_tokens))
                          else None)
                for s, rid in rows:
                    m = int(commits_np[s])
                    acc = max(m - 1, 0)
                    unit_acc += acc
                    stats.spec_slot_verifies += 1
                    stats.spec_proposed_tokens += g
                    stats.spec_accepted_tokens += acc
                    stats.spec_commit_tokens += m
                    self._spec_proposed[drafter] += g
                    self._spec_accepted[drafter] += acc
                    for _ in range(m):
                        stats.per_token_s.append(dt / m)
                    self._event("spec-verify", rid, gamma=g,
                                accepted=acc, committed=m)
                    st = slots[s]
                    if cfg.spec_adaptive:
                        rate = acc / g if g else 0.0
                        st.accept_ema = (rate if st.accept_ema < 0
                                         else 0.5 * st.accept_ema
                                         + 0.5 * rate)
                        pos = (ladder.index(st.gamma_eff)
                               if st.gamma_eff in ladder
                               else len(ladder) - 1)
                        if st.accept_ema < 0.25 and pos > 0:
                            st.gamma_eff = ladder[pos - 1]
                        elif (st.accept_ema > 0.75
                              and pos < len(ladder) - 1):
                            st.gamma_eff = ladder[pos + 1]
                    if tok_np is not None or committed_ids is not None:
                        ids_host = (committed_ids[s]
                                    if committed_ids is not None
                                    else [int(t) for t in tok_np[s, :m]])
                        if drafter == "ngram" and rid in hist:
                            hist[rid].extend(ids_host)
                        if self.capture_tokens:
                            tokens_by_rid.setdefault(rid, []).extend(
                                ids_host)
                unit_rate = (unit_acc / (g * len(rows))
                             if (rows and g) else 0.0)
                accept_ema_run[0] = (
                    unit_rate if accept_ema_run[0] < 0
                    else 0.5 * accept_ema_run[0] + 0.5 * unit_rate)
                self.registry.set_gauge(
                    "serve_spec_acceptance_ema", accept_ema_run[0],
                    help="EMA of per-verify-unit draft acceptance rate")
                done_states = [release(s) for s in completions]
                if completions:
                    refresh_active()
                done_at = self._now()
                for st in done_states:
                    finish(st, done_at)

        def dispatch_spec() -> bool:
            """One draft-and-verify unit over the resident batch, with
            the decode path's full recovery ladder.  Returns False when
            the drafter is cold (no n-gram hit for ANY resident slot) —
            the caller falls back to the plain token decode unit, so
            speculation COMPOSES with decode_horizon/inflight_window
            instead of replacing them."""
            nonlocal carry
            # history and host bookkeeping must be current before
            # drafting (fallback token units may still be in flight)
            drain()
            if not slots:
                return True     # the drain's completions emptied the batch
            ladder = self._spec_gammas
            if cfg.spec_adaptive:
                g_want = max(st.gamma_eff for st in slots.values())
            else:
                g_want = cfg.spec_gamma
            g = ladder[0]
            for cand in ladder:
                if cand <= g_want:
                    g = cand
            drafts_np = np.zeros((cfg.max_batch, g), np.int32)
            if cfg.speculation == "ngram":
                t_d = time.perf_counter()
                any_hit = False
                for s in sorted(slots):
                    prop = _ngram_propose(hist.get(slots[s].req.rid, []),
                                          g)
                    if prop is not None:
                        drafts_np[s] = prop
                        any_hit = True
                stats.spec_draft_s += time.perf_counter() - t_d
                if not any_hit:
                    stats.spec_fallback_units += 1
                    if not self._sampled:
                        return False
                    # sampled cold fallback: the plain token decode
                    # unit is a greedy program, so a cold drafter
                    # degenerates to the γ=0 verify — one host-sampled
                    # token per trip, never a silent greedy token
                    g = 0
                    drafts_np = np.zeros((cfg.max_batch, 0), np.int32)
            snap = take_snapshot()
            attempt = 0
            while True:
                try:
                    spec_unit(g, drafts_np, snap)
                    return True
                except (TransientFault, CorruptStats) as e:
                    restore_snapshot(snap)
                    if attempt >= cfg.max_dispatch_retries:
                        fail_resident(e, "dispatch-failed")
                        return True
                    attempt += 1
                    stats.retries += 1
                    self._retry_counter["decode"] += 1
                    if self.journal is not None:
                        self.journal.event("dispatch-retry",
                                           phase="decode",
                                           attempt=attempt,
                                           error=str(e))
                    time.sleep(cfg.retry_backoff_s * (2 ** (attempt - 1)))
                except DeadlineExceeded as e:
                    restore_snapshot(snap)
                    stats.hung_dispatches += 1
                    self.registry.inc("serve_hung_dispatches")
                    drain()
                    fail_resident(e, "hung-dispatch")
                    carry = self._fresh_carry()
                    draft_cache[0] = self._fresh_draft_cache()
                    carry_resets[0] += 1
                    return True
                except Exception as e:  # noqa: BLE001 — fail closed
                    restore_snapshot(snap)
                    try:
                        drain()
                    except Exception:  # noqa: BLE001
                        inflight.clear()
                    fail_resident(e, "dispatch-failed")
                    carry = self._fresh_carry()
                    draft_cache[0] = self._fresh_draft_cache()
                    carry_resets[0] += 1
                    return True

        def dispatch_decode(max_k: Optional[int] = None) -> None:
            """One decode unit over the resident batch: a single step,
            or — when no scheduling event needs an earlier boundary — a
            fused K-step scan (largest power-of-two bucket <= the
            event horizon), optionally on a compacted half batch.
            ``max_k`` caps the horizon (the chunked-prefill interleave
            passes 1: the mid-admission request is itself a waiter, and
            a full fused scan between chunks would re-create the
            head-of-line blocking the interleave exists to remove).

            Hardened (docs/resilience.md, serving faults): a
            transiently-failed dispatch rolls the host ledger/slot
            state back to the pre-dispatch snapshot and re-issues with
            exponential backoff; exhaustion — or a real dispatch error
            — fails only the resident requests (full exception chains,
            journaled ``request-failed``), never the run; a dispatch
            exceeding the EMA-scaled watchdog deadline is abandoned on
            its daemon thread and the engine continues on a fresh
            carry."""
            nonlocal carry
            refresh_active()
            if (spec_on and max_k is None
                    and (control is None or control.spec_enabled)):
                # draft-and-verify first; a cold n-gram drafter falls
                # through to a plain token decode unit below (the
                # chunked-prefill interleave's max_k=1 also bypasses
                # drafting — a verify's γ+1 commit window would re-create
                # the head-of-line blocking the interleave removes)
                if dispatch_spec():
                    return
                refresh_active()
            with spans.span("serve-decode-plan"):
                rem = {s: slots[s].req.output_len - slots[s].tokens_done
                       for s in sorted(slots)}
                # next event: the earliest completion while anything is (or
                # may soon be) waiting for a slot; a quiescent batch fuses
                # through its full drain
                horizon = (min(rem.values()) if (queue or pending)
                           else max(rem.values()))
                horizon = min(cfg.decode_horizon, horizon)
                if control is not None and control.horizon_cap is not None:
                    # degradation ladder (serve/fleet.py): a shrunk horizon
                    # trades fused-scan throughput for scheduling latency
                    # under overload — never silently (each transition is
                    # journaled ``degrade-transition``)
                    horizon = min(horizon, max(1, control.horizon_cap))
                if pending:
                    # a known arrival is a scheduling event too: bound the
                    # scan so admission happens near the arrival instead of
                    # up to decode_horizon steps late (steps estimated from
                    # the observed per-step interval; before the first
                    # sample exists, stay per-step — one unit bootstraps
                    # the EMA)
                    if step_ema[0] > 0.0:
                        gap = pending[0].arrival_s - self._now()
                        steps_to_arrival = (max(1, int(gap / step_ema[0]))
                                            if gap > 0 else 1)
                        horizon = min(horizon, steps_to_arrival)
                    else:
                        horizon = 1
                if max_k is not None:
                    horizon = min(horizon, max_k)
                k = 1
                for cand in self._fused_ks:
                    if cand <= horizon:
                        k = cand
                steps = {s: min(k, r) for s, r in rem.items()}
                compact = (
                    self._compact_gather_fn is not None and k > 1
                    and len(slots) <= cfg.compact_threshold * cfg.max_batch
                    and len(slots) <= cfg.max_batch // 2
                )
                snap = take_snapshot()
            attempt = 0
            while True:
                try:
                    decode_unit(k, steps, compact, snap)
                    return
                except (TransientFault, CorruptStats) as e:
                    # fired BEFORE the jit consumed the carry (the
                    # injection contract): restore the host snapshot
                    # and re-issue the same unit
                    restore_snapshot(snap)
                    if attempt >= cfg.max_dispatch_retries:
                        fail_resident(e, "dispatch-failed")
                        return
                    attempt += 1
                    stats.retries += 1
                    self._retry_counter["decode"] += 1
                    if self.journal is not None:
                        self.journal.event("dispatch-retry",
                                           phase="decode",
                                           attempt=attempt,
                                           error=str(e))
                    time.sleep(cfg.retry_backoff_s * (2 ** (attempt - 1)))
                except DeadlineExceeded as e:
                    # hung dispatch: the zombie daemon thread still
                    # holds the donated carry — settle the valid
                    # in-flight tail, fail the resident batch closed,
                    # continue on a fresh carry
                    restore_snapshot(snap)
                    stats.hung_dispatches += 1
                    self.registry.inc("serve_hung_dispatches")
                    drain()
                    fail_resident(e, "hung-dispatch")
                    carry = self._fresh_carry()
                    draft_cache[0] = self._fresh_draft_cache()
                    carry_resets[0] += 1
                    return
                except Exception as e:  # noqa: BLE001 — fail closed
                    # a real (non-injected) dispatch failure: the
                    # donated carry must be presumed consumed — fail
                    # the resident batch closed with the exception
                    # chain and continue on a fresh carry
                    restore_snapshot(snap)
                    try:
                        drain()
                    except Exception:  # noqa: BLE001
                        inflight.clear()
                    fail_resident(e, "dispatch-failed")
                    carry = self._fresh_carry()
                    draft_cache[0] = self._fresh_draft_cache()
                    carry_resets[0] += 1
                    return

        def attach_plan(req: Request) -> Optional[dict[str, Any]]:
            """Host-side prefix match for one admission: the prompt's
            full-block token-id chain (pure numpy, the same
            admission-time id view the n-gram drafter uses — the trie
            never touches the device), the trie's longest indexed
            match, and the chunk-floored attach point.  The attach is
            capped at whole CHUNKS (the suffix prefill resumes at a
            static chunk-jit offset) and always leaves >= 1 chunk to
            compute (the final chunk owns ``y_last`` and the slot
            length); blocks the trie matched past that cap are
            recomputed privately — the copy-on-write tail, counted via
            ``note_cow``.  ``resets`` pins the carry generation: an
            attach copies DEVICE blocks, so a plan from before a carry
            reset degrades to a full prefill (the slot then physically
            holds every block it refs, keeping the trie true)."""
            bs = cfg.block_size
            chunk = cfg.prefill_chunk
            full_blocks = req.prompt_len // bs
            plan = {"chain": [], "attach_blocks": 0, "attach_tokens": 0,
                    "donor": None, "cow_blocks": 0,
                    "resets": carry_resets[0], "attached_tokens": 0}
            if full_blocks == 0:
                return plan
            ids = prompt_token_ids(
                req.seed, req.prompt_len, self.config.hidden_size,
                prefix_len=req.prefix_len, prefix_seed=req.prefix_seed)
            chain = [tuple(ids[i * bs:(i + 1) * bs])
                     for i in range(full_blocks)]
            plan["chain"] = chain
            depth, donor = ledger.match_prefix(chain)
            cap = ((req.prompt_len - 1) // chunk) * chunk
            attach_tokens = min(depth * bs, cap) // chunk * chunk
            if donor is None or attach_tokens <= 0:
                return plan
            plan.update(attach_blocks=attach_tokens // bs,
                        attach_tokens=attach_tokens, donor=donor,
                        cow_blocks=depth - attach_tokens // bs)
            return plan

        def prefill_once(req: Request, slot: int,
                         plan: Optional[dict[str, Any]] = None):
            """The prefill dispatch for one admitted request (chunked or
            monolithic) — returns ``(bucket, y_last, dt)``.  Raised
            through by the retry wrapper below; idempotent on retry:
            chunk writes are deterministic block writes of identical
            values, and interleaved decode units commit independently.
            With a prefix-attach ``plan``, the matched chunks' prefills
            are replaced by ONE donor-block copy (``build_prefix_attach``)
            and only the suffix chunks run; a carry reset since planning
            degrades to the full prefill (a retry after a reset finds
            zeroed donor blocks, so copying would serve garbage)."""
            nonlocal carry
            if inject.fire("serve-prefill-fail"):
                # fires BEFORE any jit is invoked — see serve-decode-fail
                raise TransientFault(
                    "injected serve-prefill-fail at the prefill "
                    "dispatch boundary")
            if cfg.prefill_chunk is not None:
                chunk = cfg.prefill_chunk
                n_chunks = -(-req.prompt_len // chunk)
                bucket = n_chunks * chunk
                m_chunks = 0
                if plan is not None and plan["attach_blocks"]:
                    plan["attached_tokens"] = 0
                    if carry_resets[0] == plan["resets"]:
                        m_chunks = plan["attach_tokens"] // chunk
                with spans.span("serve-admit-embed", rid=req.rid,
                                slot=slot):
                    x_prompt = self._prompt_input(req, bucket)
                with spans.span("serve-prefill", rid=req.rid,
                                bucket=bucket, slot=slot,
                                chunks=n_chunks - m_chunks):
                    t0 = time.perf_counter()
                    decode_spent = 0.0
                    cache = carry[0]
                    if m_chunks:
                        # copy-on-attach: one in-place block copy of the
                        # donor's matched blocks stands in for the
                        # matched chunks' prefill dispatches (the TTFT
                        # win), and its returned fp prefix carry is
                        # exactly what those chunks would have produced
                        with spans.span("serve-prefix-attach",
                                        rid=req.rid, slot=slot,
                                        donor=plan["donor"],
                                        blocks=plan["attach_blocks"]):
                            cache, prefix = self._attach_jit(m_chunks)(
                                cache, np.int32(plan["donor"]),
                                np.int32(slot))
                        plan["attached_tokens"] = m_chunks * chunk
                    else:
                        prefix = self._create_prefix()
                    for ci in range(m_chunks, n_chunks):
                        with spans.span("serve-prefill-chunk",
                                        rid=req.rid, chunk=ci):
                            cache, prefix, y_last = \
                                self._chunk_jit(ci)(
                                    cache, prefix,
                                    self.params,
                                    x_prompt[:, ci * chunk:
                                             (ci + 1) * chunk],
                                    np.int32(slot),
                                    np.int32(req.prompt_len))
                        stats.prefill_chunks += 1
                        self.registry.inc("serve_prefill_chunks")
                        if ci < n_chunks - 1 and slots:
                            # interleave: the resident batch decodes
                            # between chunks instead of head-of-line
                            # blocking
                            carry = (cache, carry[1])
                            td = time.perf_counter()
                            resets = carry_resets[0]
                            dispatch_decode(max_k=1)
                            decode_spent += time.perf_counter() - td
                            if carry_resets[0] != resets:
                                # the resident batch failed and took the
                                # carry with it — this request's chunks
                                # 0..ci died in the old cache; restart
                                # the prefill on the fresh carry (chunk
                                # writes are deterministic, so a replay
                                # is exact) via the retry wrapper
                                raise TransientFault(
                                    "carry reset during the chunked-"
                                    "prefill interleave (resident batch "
                                    "failed closed)")
                            cache = carry[0]
                    carry = (cache, carry[1])
                    jax.block_until_ready(y_last)
                    # the interleaved units' dispatch+sync time is
                    # already billed to decode_step_s/per_token_s —
                    # keep prefill_s a PREFILL cost
                    dt = time.perf_counter() - t0 - decode_spent
            else:
                bucket = cfg.bucket_for(req.prompt_len)
                with spans.span("serve-admit-embed", rid=req.rid,
                                slot=slot):
                    x_prompt = request_embeddings(
                        req.seed, req.prompt_len,
                        self.config.hidden_size,
                        dtype=self._dtype, pad_to=bucket,
                    )
                with spans.span("serve-prefill", rid=req.rid,
                                bucket=bucket, slot=slot):
                    t0 = time.perf_counter()
                    cache, y_last = self._prefill_jit(bucket)(
                        carry[0], self.params, x_prompt,
                        np.int32(slot), np.int32(req.prompt_len))
                    if self._draft_prefill is not None:
                        # the draft plane is prefilled at admission from
                        # the SAME prompt embeddings (idempotent masked
                        # writes, so the retry wrapper covers it); its
                        # cost is billed as prefill — the admission
                        # price of the draft model
                        dcache, _dy = self._draft_prefill(
                            draft_cache[0], self._draft_params, x_prompt,
                            np.int32(slot), np.int32(req.prompt_len))
                        draft_cache[0] = dcache
                    jax.block_until_ready(y_last)
                    dt = time.perf_counter() - t0
                carry = (cache, carry[1])
            return bucket, y_last, dt

        def prefill_dispatch(req: Request, slot: int,
                             plan: Optional[dict[str, Any]] = None):
            """Bounded-retry wrapper around :func:`prefill_once` —
            transient dispatch failures back off and re-issue (chunk
            counters rolled back so a retried prefill never
            double-counts); exhaustion raises to the admission loop's
            fail-closed path.  The prefix-attach ``plan`` rides through
            unchanged: each attempt re-checks the carry generation
            itself, so a retry after a mid-prefill carry reset degrades
            to the full prefill instead of copying zeroed donor blocks."""
            attempt = 0
            while True:
                chunks_base = stats.prefill_chunks
                try:
                    return prefill_once(req, slot, plan)
                except (TransientFault, CorruptStats) as e:
                    stats.prefill_chunks = chunks_base
                    if attempt >= cfg.max_dispatch_retries:
                        raise
                    attempt += 1
                    stats.retries += 1
                    self._retry_counter["prefill"] += 1
                    if self.journal is not None:
                        self.journal.event("dispatch-retry",
                                           phase="prefill", rid=req.rid,
                                           attempt=attempt,
                                           error=str(e))
                    time.sleep(cfg.retry_backoff_s * (2 ** (attempt - 1)))

        def fail_admission(req: Request, slot: int,
                           exc: BaseException) -> None:
            """A permanently-failed prefill fails ONLY the admitting
            request: reservation undone, journaled with the chain.  A
            real (non-injected) failure also consumed the donated
            cache, so the resident batch fails closed too and the
            engine continues on a fresh carry."""
            nonlocal carry
            ledger.free(slot)
            if draft_ledger is not None:
                draft_ledger.free(slot)
            free_slots.append(slot)
            free_slots.sort()
            fail_requests([_SlotState(req=req, tokens_done=0)], exc,
                          "dispatch-failed")
            if not isinstance(exc, InjectedFault):
                fail_resident(exc, "dispatch-failed")
                carry = self._fresh_carry()
                draft_cache[0] = self._fresh_draft_cache()

        # a fleet run shares one clock origin across every replica (the
        # supervisor's barrier sets it after ALL replicas have compiled,
        # so per-replica compile skew never distorts arrival/deadline
        # accounting); a standalone run starts its own
        self._t0 = (control.sync_start() if control is not None
                    else time.perf_counter())
        last_sync[0] = self._t0
        preempted = False
        while pending or queue or slots:
            if control is not None:
                # replica control plane (serve/fleet.py), strictly at
                # the loop boundary so a fence can never tear a
                # half-applied dispatch: heartbeat, injected replica
                # kill/hang, supervisor cancels (losing hedges)
                control.beat()
                control.check()
                for c_rid, c_reason in control.take_cancels():
                    cancel_request(c_rid, c_reason)
            if inject.fire("serve-preempt"):
                # chaos harness: deliver a real SIGTERM to ourselves —
                # the PreemptionGuard turns it into the drain flag below
                # (inert-flag fallback off the main thread)
                if guard.installed:
                    os.kill(os.getpid(), signal.SIGTERM)
                else:
                    guard.request()
            if guard.requested:
                # graceful drain: stop admission at this boundary; the
                # in-flight window settles below and still-resident
                # requests are journaled ``request-preempted``
                preempted = True
                break
            now = self._now()
            # 1. arrivals -> admission control (bounded queue)
            while pending and pending[0].arrival_s <= now:
                req = pending.popleft()
                self._requests["arrived"] += 1
                self._event("request-arrived", req.rid,
                            prompt=req.prompt_len, output=req.output_len)
                reason = (self._infeasible_reason(req)
                          if cfg.reject_infeasible else None)
                if reason is not None:
                    self._requests["rejected"] += 1
                    self._rejections["infeasible"] += 1
                    outcomes[req.rid] = "rejected[infeasible]"
                    rejected_detail.append({
                        "rid": req.rid, "reason": "infeasible",
                        "queue_depth": len(queue), "queue_wait_s": 0.0,
                        "detail": reason,
                    })
                    # distinct journal event from the load-shed path:
                    # infeasible is a config/trace mismatch, never load
                    self._event("request-infeasible", req.rid,
                                reason="infeasible", detail=reason)
                elif len(queue) >= cfg.queue_capacity:
                    head_wait = (now - queue[0].arrival_s if queue
                                 else 0.0)
                    self._requests["rejected"] += 1
                    self._rejections["queue-full"] += 1
                    outcomes[req.rid] = "rejected[queue-full]"
                    rejected_detail.append({
                        "rid": req.rid, "reason": "queue-full",
                        "queue_depth": len(queue),
                        "queue_wait_s": round(head_wait, 6),
                    })
                    self._event("request-rejected", req.rid,
                                reason="queue-full",
                                queue_depth=len(queue),
                                queue_wait_s=round(head_wait, 6))
                else:
                    queue.append(req)
                    self._requests["admitted"] += 1
                    self._event("request-admitted", req.rid,
                                queue_depth=len(queue))
            # 2. step-boundary scheduling: grant slots + block
            #    reservations, prefill each granted request.  First,
            #    per-request SLO shedding: a queue head whose wait has
            #    already blown its deadline is shed
            #    (``request-rejected[reason=deadline]`` — DISTINCT from
            #    queue-full: this is latency, not capacity) rather than
            #    served into a guaranteed SLO miss
            while (queue and queue[0].deadline_s is not None
                    and now - queue[0].arrival_s > queue[0].deadline_s):
                req = queue.popleft()
                wait = now - req.arrival_s
                self._requests["rejected"] += 1
                self._rejections["deadline"] += 1
                self._deadline_counter["shed-queued"] += 1
                stats.deadline_shed += 1
                outcomes[req.rid] = "rejected[deadline]"
                rejected_detail.append({
                    "rid": req.rid, "reason": "deadline",
                    "queue_depth": len(queue),
                    "queue_wait_s": round(wait, 6),
                    "deadline_s": req.deadline_s,
                })
                self._event("request-rejected", req.rid,
                            reason="deadline",
                            queue_wait_s=round(wait, 6),
                            deadline_s=req.deadline_s)
            scheduled = False
            if queue and free_slots:
                # scan boundary: settle in-flight decode before the
                # prefill blocks, so its sync cost lands in decode
                # timing and TTFT stays honest
                with spans.span("serve-drain", inflight=len(inflight)):
                    drain()
                # one child span per step of an admission, each with
                # the request's ``rid`` and its ``slot``: plan, embed
                # and prefill (inside ``prefill_once``), inject, book —
                # what is left of ``serve-admission`` is this loop
                with spans.span("serve-admission", queue=len(queue),
                                free_slots=len(free_slots)):
                    while queue and free_slots:
                        req, slot = queue[0], free_slots[0]
                        with spans.span("serve-admit-plan", rid=req.rid,
                                        slot=slot):
                            # prefix admission: blocks the trie already
                            # holds are counted ONCE fleet-wide, so a
                            # request whose private suffix fits is
                            # admittable even when its full footprint
                            # would not be — the int8/prefix capacity
                            # win
                            plan = (attach_plan(req)
                                    if cfg.prefix_caching else None)
                            attach_blocks = (plan["attach_blocks"]
                                             if plan else 0)
                            fits = ledger.can_reserve(
                                req.total_tokens,
                                shared_blocks=attach_blocks)
                            if fits:
                                queue.popleft()
                                free_slots.pop(0)
                                ledger.reserve(
                                    slot, req.total_tokens,
                                    chain=(plan["chain"] if plan
                                           else None),
                                    attach_blocks=attach_blocks)
                                if draft_ledger is not None:
                                    draft_ledger.reserve(
                                        slot, req.total_tokens)
                        if not fits:
                            break
                        try:
                            bucket, y_last, dt = prefill_dispatch(
                                req, slot, plan)
                        except Exception as e:  # noqa: BLE001 — closed
                            fail_admission(req, slot, e)
                            continue
                        with spans.span("serve-admit-inject", rid=req.rid,
                                        slot=slot):
                            first_id = -1
                            if token_mode and self._sampled:
                                # sampled inject: position 0 obeys the same
                                # temperature law as every later token —
                                # the prefill's last logits come to host
                                # (one [H] vector per admission), the first
                                # token is drawn from their softmax, and
                                # the device only embeds the committed id
                                # (once per ADMISSION, not per token)
                                # comm-lint: disable=host-transfer-in-loop
                                p0 = softmax_np(np.asarray(y_last),
                                                cfg.temperature)
                                first_id = int(sample_rng.choice(
                                    p0.shape[-1], p=p0))
                                carry = self._inject_sampled(
                                    carry, np.int32(slot),
                                    np.int32(first_id), self._table)
                            elif token_mode:
                                # greedy token inject: argmax on device, a
                                # 4-byte id to host — the history seed AND
                                # the equivalence capture in one transfer
                                carry, first_tok = self._inject_greedy(
                                    carry, np.int32(slot), y_last,
                                    self._table)
                                first_id = int(first_tok)
                            else:
                                carry = self._inject(carry, np.int32(slot),
                                                     y_last)
                            if self._hybrid is not None:
                                recycled = slot in used_slots
                                used_slots.add(slot)
                                if recycled:
                                    # the prompt's first chunk started
                                    # from a zero state and overwrote
                                    # what the slot's last request left
                                    spans.instant("state-reset",
                                                  cat="request",
                                                  rid=req.rid, slot=slot)
                                    self.registry.inc("serve_state_resets")
                                if req.rid in self._probe_rids:
                                    self._probe_slot(req, slot, recycled,
                                                     y_last, carry[0])
                        with spans.span("serve-admit-book", rid=req.rid,
                                        slot=slot):
                            ledger.append(slot, req.prompt_len)
                            if draft_ledger is not None:
                                draft_ledger.append(slot, req.prompt_len)
                            if cfg.prefix_caching and plan is not None:
                                reused = plan["attached_tokens"]
                                if reused:
                                    stats.prefix_hits += 1
                                    stats.prefix_tokens_reused += reused
                                    self.registry.inc("serve_prefix_hits")
                                    self.registry.inc(
                                        "serve_prefix_tokens_reused", reused)
                                    self._event(
                                        "prefix-attach", req.rid, slot=slot,
                                        donor=plan["donor"], tokens=reused,
                                        blocks=reused // cfg.block_size)
                                    if plan["cow_blocks"]:
                                        # matched deeper than the attach cap:
                                        # the tail blocks were recomputed
                                        # privately — the copy-on-write edge
                                        ledger.note_cow(plan["cow_blocks"])
                                        stats.prefix_cow_blocks += (
                                            plan["cow_blocks"])
                                        self._event(
                                            "prefix-cow", req.rid, slot=slot,
                                            blocks=plan["cow_blocks"])
                                # index this slot's full-block chain: the
                                # prefill (attached or full) made the slot
                                # a physical holder of every block it refs,
                                # and dedup against already-shared blocks
                                # refunds the private reservation
                                ledger.register(slot, plan["chain"])
                            t_first = self._now()
                            st = _SlotState(req=req, tokens_done=1,
                                            admitted_s=now,
                                            first_token_s=t_first,
                                            gamma_eff=cfg.spec_gamma)
                            if cfg.speculation == "ngram":
                                # prompt-lookup context: the prompt's own
                                # token-id view (pure numpy, admission-time)
                                # plus the prefill's first committed token
                                hist[req.rid] = prompt_token_ids(
                                    req.seed, req.prompt_len,
                                    self.config.hidden_size,
                                    period=req.prompt_period,
                                    prefix_len=req.prefix_len,
                                    prefix_seed=req.prefix_seed) + [first_id]
                            slots[slot] = st
                            active_np[slot] = True
                            active_dirty[0] = True
                            stats.ttft_s.append(t_first - req.arrival_s)
                            stats.prefill_s.append(dt)
                            stats.generated_tokens += 1
                            scheduled = True
                            if self.capture_tokens:
                                # device-side argmax: a 4-byte scalar comes
                                # to host per admission, never the whole
                                # hidden state (host-transfer-in-loop)
                                tokens_by_rid.setdefault(req.rid, []).append(
                                    first_id if token_mode
                                    else int(jnp.argmax(y_last)))
                            self._event(
                                "request-prefill", req.rid, slot=slot,
                                bucket=bucket,
                                ttft_s=round(t_first - req.arrival_s, 6))
                            if st.tokens_done >= req.output_len:
                                finish(release(slot), self._now())
                if scheduled:
                    refresh_active()
            # 3. a decode unit over every resident request: one step, or
            #    a fused K-step scan on the fast path
            if slots:
                dispatch_decode()
            elif pending and not queue:
                # idle until the next arrival (nothing resident, nothing
                # admittable); settle any in-flight tail first
                drain()
                wait = pending[0].arrival_s - self._now()
                if wait > 0:
                    time.sleep(min(wait, 0.05))
            # 4. timeseries sample at the step boundary
            series["t_s"].append(round(self._now(), 6))
            series["queue_depth"].append(len(queue))
            series["active_slots"].append(len(slots))
            series["blocks_in_use"].append(ledger.blocks_in_use)
            series["blocks_reserved"].append(ledger.blocks_reserved)
            self.registry.set_gauge("serve_queue_depth", len(queue),
                                    help="bounded admission queue depth")
            self.registry.set_gauge("serve_active_slots", len(slots),
                                    help="decode slots in use")
            self.registry.set_gauge("serve_cache_blocks_in_use",
                                    ledger.blocks_in_use,
                                    help="cache blocks holding tokens")
            if cfg.prefix_caching:
                series["shared_blocks"].append(ledger.shared_blocks)
                self.registry.set_gauge(
                    "serve_cache_shared_blocks", ledger.shared_blocks,
                    help="trie-indexed blocks counted once fleet-wide")
                self.registry.set_gauge(
                    "serve_cache_prefix_refs", ledger.trie.total_refs(),
                    help="slot references across all shared blocks")
        drain()
        remaining_rids: list[int] = []
        if preempted:
            # graceful drain: the in-flight window settled above;
            # still-resident requests are preempted — journaled, freed,
            # and replayed by ``cli serve --resume`` (serve/bench.py
            # writes the ledger/queue/trace-cursor snapshot)
            for s in sorted(list(slots)):
                st = release(s)
                outcomes[st.req.rid] = "preempted"
                stats.preempted_requests += 1
                self._requests["preempted"] += 1
                remaining_rids.append(st.req.rid)
                self._event("request-preempted", st.req.rid,
                            tokens_done=st.tokens_done,
                            output_len=st.req.output_len)
            remaining_rids += [r.rid for r in queue]
            remaining_rids += [r.rid for r in pending]
            if self.journal is not None:
                self.journal.event("preempted",
                                   signal=guard.signal_received,
                                   remaining=len(remaining_rids))
            if self.verbose:
                print(f"[serve] SIGTERM received — drained the in-flight "
                      f"window, {len(remaining_rids)} request(s) remain "
                      "for --resume")
        wall = self._now()

        self.registry.set_gauge("serve_queue_depth_peak",
                                max(series["queue_depth"], default=0))
        self.registry.set_gauge("serve_cache_blocks_peak",
                                ledger.peak_in_use)
        goodput = (stats.completed_output_tokens / wall) if wall > 0 else 0.0
        arrived = self._requests["arrived"] - counts_base["arrived"]
        # shed rate counts LOAD shedding only (queue-full) — an
        # infeasible rejection is a config/trace mismatch, and folding
        # it in would misread as pressure and prompt a pointless
        # queue_capacity tune
        shed = self._rejections["queue-full"] - shed_base
        report = {
            "schema": SERVING_REPORT_SCHEMA,
            "model": {
                "hidden_size": self.config.hidden_size,
                "num_layers": self.config.num_layers,
                "num_heads": self.config.num_heads,
                "kv_heads": self.config.kv_heads,
                "attention": self.config.attention,
                "dtype": self.config.dtype,
            },
            "mesh": {"dp": self.dp, "tp": self.tp},
            "serving": cfg.to_dict(),
            "trace": {
                "kind": trace.kind,
                "seed": trace.seed,
                "num_requests": len(trace),
                "params": dict(trace.params),
                "horizon_s": trace.horizon_s,
            },
            "requests": {
                **{k: self._requests[k] - counts_base[k]
                   for k in ("arrived", "admitted", "rejected",
                             "completed", "failed", "preempted",
                             "canceled")},
                "rejected_rids": [d["rid"] for d in rejected_detail],
                "rejected_detail": rejected_detail,
                "shed_rate": (shed / arrived) if arrived else 0.0,
                "deadline_shed": stats.deadline_shed,
                "completed_past_deadline": stats.completed_past_deadline,
                # rid -> final outcome: the per-request ground truth the
                # kill-mid-trace ≡ uninterrupted chaos gate compares
                "outcomes": {str(rid): o
                             for rid, o in sorted(outcomes.items())},
            },
            "goodput_tokens_per_s": goodput,
            "throughput_tokens_per_s": (
                stats.generated_tokens / wall if wall > 0 else 0.0
            ),
            "completed_output_tokens": stats.completed_output_tokens,
            "generated_tokens": stats.generated_tokens,
            "decode_steps": stats.decode_steps,
            "decode_units": stats.decode_units,
            # share of the K/V planes' tiles the decode steps fetched
            "kv_live_share": (stats.kv_tiles_live / stats.kv_tiles_held
                              if stats.kv_tiles_held else 0.0),
            "fast_path": {
                "enabled": self._fast,
                "decode_horizon": cfg.decode_horizon,
                "inflight_window": cfg.inflight_window,
                "prefill_chunk": cfg.prefill_chunk,
                "compact_threshold": cfg.compact_threshold,
                "fused_scans": stats.fused_scans,
                "fused_steps": stats.fused_steps,
                "single_steps": stats.single_steps,
                "prefill_chunks": stats.prefill_chunks,
                "compacted_scans": stats.compacted_scans,
                "kv_tiles_live": stats.kv_tiles_live,
                "kv_tiles_held": stats.kv_tiles_held,
            },
            "speculation": {
                "mode": cfg.speculation,
                "gamma": cfg.spec_gamma,
                "adaptive": cfg.spec_adaptive,
                "temperature": cfg.temperature,
                "sampled": self._sampled,
                "sample_seed": cfg.sample_seed,
                "verify_units": stats.spec_verify_units,
                "fallback_units": stats.spec_fallback_units,
                "proposed_tokens": stats.spec_proposed_tokens,
                "accepted_tokens": stats.spec_accepted_tokens,
                "acceptance_rate": (
                    stats.spec_accepted_tokens
                    / stats.spec_proposed_tokens
                    if stats.spec_proposed_tokens else 0.0),
                "mean_accepted_len": (
                    stats.spec_commit_tokens / stats.spec_slot_verifies
                    if stats.spec_slot_verifies else 0.0),
                "draft_overhead_s": stats.spec_draft_s,
            },
            "resilience": {
                "retries": stats.retries,
                "hung_dispatches": stats.hung_dispatches,
                "failed_requests": stats.failed_requests,
                "failed": failed_detail,
            },
            "preempted": preempted,
            "remaining_rids": sorted(remaining_rids),
            "prefix": {
                "enabled": cfg.prefix_caching,
                "kv_quantization": cfg.kv_quantization,
                "hits": stats.prefix_hits,
                "tokens_reused": stats.prefix_tokens_reused,
                "cow_blocks": stats.prefix_cow_blocks,
                "hit_rate": (stats.prefix_hits / len(stats.prefill_s)
                             if stats.prefill_s else 0.0),
            },
            "ttft": summarize(stats.ttft_s),
            "per_token_latency": summarize(stats.per_token_s),
            "e2e_latency": summarize(stats.e2e_latency_s),
            "prefill_time": summarize(stats.prefill_s),
            "decode_step_time": summarize(stats.decode_step_s),
            "cache": ledger.stats(),
            "timeseries": series,
            "compile_time_s": compile_time,
            "wall_seconds": wall,
        }
        if collect_raw or preempted:
            # the raw sample lists: a preempted session's checkpoint
            # carries them so the --resume merge can re-summarize over
            # BOTH sessions instead of faking a merged percentile
            report["raw_samples"] = {
                "ttft_s": list(stats.ttft_s),
                "per_token_s": list(stats.per_token_s),
                "prefill_s": list(stats.prefill_s),
                "decode_step_s": list(stats.decode_step_s),
                "e2e_latency_s": list(stats.e2e_latency_s),
            }
        if self.capture_tokens:
            report["completed_tokens"] = {
                str(rid): toks for rid, toks in sorted(tokens_by_rid.items())
            }
        if self.verbose:
            ttft = report["ttft"]
            ptl = report["per_token_latency"]
            print(
                f"[serve] {trace.kind} x{len(trace)}: "
                f"{report['requests']['completed']} completed / "
                f"{report['requests']['rejected']} rejected, "
                f"goodput {goodput:.0f} tok/s, "
                f"ttft p50 {ttft['median'] * 1e3:.1f} ms "
                f"p99 {ttft['p99'] * 1e3:.1f} ms, "
                f"per-token p50 {ptl['median'] * 1e3:.2f} ms"
            )
        return report
