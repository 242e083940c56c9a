"""The serving envelope: :class:`ServingConfig` (the YAML ``serving:``
section), its validation against a model and a mesh, and what is derived
from it (the prefill-bucket ladder, the fused-scan ladder, the verify
ladder, the draft model's configuration).

It knows the model's configuration and nothing of the programs or the
scheduler: ``serve/engine.py`` validates it when an engine is built, and
what one block family's serving path does not have is refused by that
family (``check_serving`` of ``serve/gpt.py`` / ``serve/hybrid.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace as dc_replace
from typing import Any, Optional

from dlbb_tpu.models.configs import ModelConfig, validate_serving

# decode feedback / drafting modes (ServingConfig.speculation):
# "off" = legacy continuous hidden-state feedback; "greedy" = token
# feedback without drafting (the speculative modes' pinned oracle);
# "ngram" / "draft-model" = draft-and-verify speculative decoding
SPECULATION_MODES = ("off", "greedy", "ngram", "draft-model")


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
                     shard-local), and speculation="off".
    kv_quantization: "none" (fp cache, bit-identical legacy layout) or
                     "int8": K/V planes stored as int8 blocks with a
                     per-(block, kv-head) fp32 scale side-channel
                     plane, dequantised inside the length-masked
                     attention — ~3.9x smaller cache, so
                     ``hbm_budget_gb`` admits proportionally more
                     resident requests (``kv_cache_bytes_per_device``
                     prices the quantized layout statically).
                     Requires speculation="off" (fp-cache-only
                     programs).
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
        # speculation with a forced tp_overlap ring or non-dense attention is
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
        self._validate_token_paths(dp)

    def _validate_token_paths(self, dp: int) -> None:
        """The speculation, prefix-cache, int8 and sampling knobs (the
        tail of :meth:`validate`)."""
        # -- speculation ladder (same no-op-trap contract as
        #    inflight_window: a knob that would silently do nothing is a
        #    config error) --
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
                    f"slot dim is sharded over dp={dp}"
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
                  "inflight_window", "prefill_chunk", "reject_infeasible",
                  "max_dispatch_retries", "retry_backoff_s",
                  "dispatch_deadline_factor",
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
