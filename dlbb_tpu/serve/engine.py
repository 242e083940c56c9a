"""The serving engine: continuous batching over the paged cache, for
whichever block family the model is of.

Three boxes, arrows one way: the scheduler (this file and
``serve/run.py``) -> a family's device programs (``serve/gpt.py``,
``serve/hybrid.py``) -> the cache and its attention helpers
(``serve/kvcache.py``, ``serve/attend.py``), with the envelope
(``serve/config.py``) beside them.  :func:`family_for` is the one place
that looks at which family a model is of; :class:`ServingEngine` holds
the module it returns and asks it for everything that differs (the
seam: ``docs/serving.md``, "Adding a block family").

The engine is what outlives a trace: the family's programs and their
compilation, the probes, the metrics registry and the journal.  One run
of one trace is ``serve/run.py``'s :class:`~dlbb_tpu.serve.run.TraceRun`,
which calls those programs; :meth:`ServingEngine.run_trace` builds one
and returns its report.
"""

from __future__ import annotations

import contextlib
import math
from types import ModuleType
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlbb_tpu.data.synthetic import request_embeddings, token_embedding_table
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.models.transformer import _dtype_of, init_params_sharded
from dlbb_tpu.obs.export import MetricsRegistry
from dlbb_tpu.resilience.preempt import PreemptionGuard
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.kvcache import KVCache, create_kv_cache
from dlbb_tpu.serve.lookahead import InputLookahead
from dlbb_tpu.serve.run import TraceRun
from dlbb_tpu.serve.traffic import Request, TrafficTrace


def family_for(config: ModelConfig) -> ModuleType:
    """The module that holds ``config``'s block family's serving
    programs and answers the scheduler's questions about it (the seam:
    ``docs/serving.md``, "Adding a block family")."""
    if config.is_hybrid:
        from dlbb_tpu.serve import hybrid

        return hybrid
    from dlbb_tpu.serve import gpt

    return gpt


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


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
        # the block family's programs, looked up once (never per
        # dispatch); what it cannot serve is refused before the envelope
        # is held to the model
        self._family = family = family_for(config)
        family.check_serving(config, serving)
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
        self._gmm_products = self.registry.labeled_counter(
            "serve_gmm_products", "k_tiles", initial=("whole", "split"),
            help="grouped expert products of the decode steps and prompt "
                 "chunks dispatched, by contraction tiles",
        )
        for name, hlp in (
            ("serve_decode_steps",
             "decode steps executed (each fused-scan trip counts once)"),
            ("serve_fused_scan_steps",
             "decode steps executed inside fused lax.scan dispatches"),
            ("serve_prefill_chunks", "prefill chunks processed"),
            ("serve_chunk_attn_kernel",
             "layer attentions of the prompt chunks dispatched that ran "
             "as the chunk_attend kernel"),
            ("serve_chunk_attn_dense",
             "layer attentions of the prompt chunks dispatched that ran "
             "as the dense float32 attention"),
            ("serve_decode_units_overlapped",
             "single-step decode units waited for only after a later "
             "program (a prompt chunk, a prefix attach) was launched "
             "behind them"),
            ("serve_hung_dispatches",
             "decode units abandoned by the dispatch watchdog"),
            ("serve_input_ready",
             "admissions whose prompt input the look-ahead worker had "
             "ready"),
            ("serve_input_waited",
             "admissions that waited for the worker or prepared their "
             "input inline"),
            ("serve_input_wait_seconds",
             "time admissions spent waiting for, or preparing, their "
             "input"),
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
        # probed requests (``probe``), for a family whose decode programs
        # return logits: -1 names no probed request (the programs then
        # return the last slot's logits, which nobody keeps)
        self._probes = family.PROBES
        self._probe_rids: tuple[int, ...] = ()
        self.probed: dict[int, dict[str, Any]] = {}
        self._probe_slots = np.full((self._probes,), -1, np.int32)
        self._probe_dev = jnp.asarray(self._probe_slots.copy())
        self._decode, self._decode_fused = family.decode_programs(
            config, mesh, self._fused_ks, quantized=self._quantized,
            probe=lambda: self._probe_dev)
        family.register_metrics(self.registry, config, serving, self.tp)
        self._prefill_chunk_jits: dict[int, Any] = {}
        self._attach_jits: dict[int, Any] = {}
        self._fast = (serving.decode_horizon > 1
                      or serving.inflight_window > 1
                      or serving.prefill_chunk is not None)
        self._inject = jax.jit(family.inject_token, donate_argnums=(0,))
        self._active_sharding = NamedSharding(mesh, P())
        # the fp layout's decode attention fetches tiles of this many
        # tokens under each slot's length: the kernel's own reckoning
        # from the paged plane this engine carries, K/V or latent rows
        # (``family.attend_tiles``; the int8 layout reads the whole layer
        # and counts nothing)
        self._kv_tile, self._tiles_of = 0, "kv"
        if not self._quantized:
            self._tiles_of, self._kv_tile = family.attend_tiles(
                config, jax.eval_shape(self._fresh_carry)[0], mesh)
            for name, hlp in (
                (f"serve_{self._tiles_of}_tiles_live",
                 f"{self._tiles_of} tiles of a layer the decode steps "
                 "fetched (tokens under the active slots' lengths)"),
                (f"serve_{self._tiles_of}_tiles_held",
                 f"{self._tiles_of} tiles of a layer the planes hold, "
                 "times decode steps"),
            ):
                self.registry.inc(name, 0, help=hlp)
        self._build_token_programs(seed)

    def _build_token_programs(self, seed: int) -> None:
        """The programs of the token-fed paths (docs/serving.md,
        speculative decoding), left empty where ``speculation`` is off."""
        config, serving, mesh = self.config, self.serving, self.mesh
        family = self._family
        # token-feedback modes quantise decode through the greedy token
        # table; the programs above stay built (jax.jit is lazy, so an
        # unused ladder costs nothing)
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
            self._decode_token = family.build_decode_token_step(config, mesh)
            self._decode_fused_token = {
                k: family.build_decode_fused_token(config, mesh, k)
                for k in self._fused_ks
            }
            self._inject_greedy = jax.jit(family.inject_token_greedy,
                                          donate_argnums=(0,))
            dp_ax = family.decode_batch_spec(mesh)[0]
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
            self._verify_probs = {
                g: family.build_verify_probs(config, mesh, g)
                for g in sorted(probs_gammas)}
            self._spec_commit = family.build_spec_commit(config, mesh)
            self._inject_sampled = jax.jit(family.inject_token_sampled,
                                           donate_argnums=(0,))
        if serving.spec_drafting:
            self._verify = {g: family.build_verify_step(config, mesh, g)
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
            self._draft_prefill = family.build_prefill(
                self._draft_config, mesh, name="serve_spec_draft_prefill")
            self._draft_scan = {
                g: family.build_draft_scan(self._draft_config, mesh, g)
                for g in self._spec_gammas
            }

    # -- setup -------------------------------------------------------------

    def probe(self, rids) -> None:
        """Keep, for the requests ``rids`` (at most ``PROBES`` resident
        at once), the logits the serving programs themselves produced:
        the last prompt position's and every decode step's, held on the
        device with the committed tokens until :meth:`probe_results`
        fetches them.  Nothing is synced or copied to the host while a
        trace is served, and the same programs run whether or not a
        request is probed.  Each ``run_trace`` starts the record anew.
        Only a family whose decode programs return logits has it."""
        if not self._probes:
            raise ValueError(self._family.LACKS["probe"])
        self._probe_rids = tuple(int(r) for r in rids)

    def probe_results(self) -> dict[int, dict[str, Any]]:
        """``rid -> {"slot", "recycled", "prompt_ids", "tokens",
        "logits", "experts", "gates", "exit_gates", "prompt_state",
        "end_state"}`` of the last
        ``run_trace``: ``logits[i]`` (float32 ``[vocab]``, numpy) are the
        logits token ``tokens[i]`` was the ``argmax`` of, ``experts[i]``
        and ``gates[i]`` the experts chosen at that position in every
        expert layer and the weights they got (``[expert layers, k]``;
        None for a model without), ``exit_gates[i]`` a looped stack's
        exit gate of every pass there (``[passes]``; None for a plain
        stack); ``recycled``
        says whether the slot had served another request before; the two
        states are the slot's recurrent state ``[L_lin, heads, d_v,
        d_k]`` (of a model without one its rows of the first K plane,
        ``[num_blocks, block_size, kvh, d]``: the family's
        ``slot_state``) after the prompt and after the last decode step
        (which took in ``tokens[-2]``; None if the request did not
        finish)."""
        out = {}
        for rid, rec in self.probed.items():
            # ``seen`` is the logits, or (logits, experts, gates): a
            # chunk's ``last`` gives the same parts for one position
            parts = [[np.asarray(part)] for part in
                     self._family.probe_parts(rec["first_logits"])]
            tokens = [int(np.argmax(parts[0][0]))]
            for toks, seen, row, col, steps in rec["units"]:
                toks = np.asarray(toks)
                seen = [np.asarray(part) for part in
                        (seen if isinstance(seen, tuple) else (seen,))]
                if toks.ndim == 1:          # a per-step unit
                    toks, seen = toks[None], [part[None] for part in seen]
                tokens += [int(t) for t in toks[:steps, row]]
                for kept, part in zip(parts, seen):
                    kept += [part[i, col] for i in range(steps)]
            out[rid] = {"slot": rec["slot"], "recycled": rec["recycled"],
                        "prompt_ids": rec["prompt_ids"], "tokens": tokens,
                        "logits": parts[0],
                        "experts": None, "gates": None, "exit_gates": None,
                        **dict(zip(self._family.probe_names(self.config),
                                   parts[1:])),
                        "prompt_state": np.asarray(rec["prompt_state"]),
                        "end_state": (None if rec["end_state"] is None
                                      else np.asarray(rec["end_state"]))}
        return out

    def _prompt_input(self, req: Request, pad_to: int) -> jax.Array:
        """A request's prompt as the family's chunk programs take it
        (seeded embeddings, or token ids embedded on the device)."""
        return self._family.prompt_input(self.config, req, pad_to,
                                         self._dtype)

    def _padded_len(self, req: Request) -> int:
        """The length a prompt is padded to: whole prefill chunks, or the
        monolithic prefill's bucket."""
        chunk = self.serving.prefill_chunk
        if chunk is None:
            return self.serving.bucket_for(req.prompt_len)
        return -(-req.prompt_len // chunk) * chunk

    def _request_input(self, req: Request) -> jax.Array:
        """What the look-ahead prepares, on its worker or inline."""
        return self._prompt_input(req, self._padded_len(req))

    def _fresh_carry(self):
        return self._family.fresh_carry(self.config, self.serving,
                                        self.mesh)

    def _create_prefix(self):
        """The carry a prompt's first chunk starts from (looked up in
        the family at each call: a checker may replace it)."""
        return self._family.create_prefix(self.config, self.mesh)

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

        if "monolithic_prefill" in self._family.LACKS:
            raise ValueError(self._family.LACKS["monolithic_prefill"])
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
            jit = self._family.build_prefill(
                self.config, self.mesh, quantized=self._quantized,
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
            jit = self._family.build_prefill_chunk(
                self.config, self.mesh, chunk, chunk_index * chunk,
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
            jit = self._family.build_prefix_attach(
                self.config, self.mesh, m_chunks * chunk,
                self.serving.block_size, quantized=self._quantized)
            self._attach_jits[m_chunks] = jit
        return jit

    def _compile(self, buckets: list[int], max_chunks: int = 0) -> None:
        """Warm every jit the trace will hit (prefill per bucket or per
        chunk offset, decode + the fused-scan ladder, inject) on scratch
        state, so compile time never lands in TTFT."""
        carry = self._fresh_carry()
        cfg = self.serving
        active = self._zero_active()
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
        remaining = self._zero_remaining()
        if self._token_mode:
            # token-feedback warms: the plain inject/decode/fused jits
            # are never dispatched in a token-mode run, so warming them
            # would only burn compile time — and a SAMPLED run likewise
            # never dispatches the greedy inject/decode/verify programs
            # (its entire decode surface is verify_probs + spec_commit)
            if self._sampled:
                carry = self._inject_sampled(carry, np.int32(0),
                                             np.int32(0), self._table)
                zeros_i = self._zero_remaining()
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
                dlen = self._zero_remaining()
                for g in self._spec_gammas:
                    dcache, _ids = self._draft_scan[g](
                        dcache, self._draft_params, self._table,
                        carry[1], dlen, active)
                jax.block_until_ready(dcache.lengths)
            jax.block_until_ready(carry[1])
            return
        carry = self._inject(carry, np.int32(0), y_last)
        if self._probes:
            self._family.slot_state(carry[0], np.int32(0))
        carry, _y = self._decode(carry, self.params, active)
        for k in self._fused_ks:
            carry, _ys = self._decode_fused[k](carry, self.params, active,
                                               remaining)
        # block on the live carry, not an intermediate output: earlier
        # outputs may share buffers with a carry a later warm call donated
        jax.block_until_ready(carry[1])

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
        with contextlib.ExitStack() as stack:
            if guard is None:
                guard = stack.enter_context(PreemptionGuard())
            # joined on every way out, with what it still holds dropped
            lookahead = stack.enter_context(
                InputLookahead(self._request_input))
            try:
                return TraceRun(self, trace, guard, lookahead, collect_raw,
                                feed, control).serve()
            finally:
                self.registry.inc("serve_input_ready", lookahead.ready)
                self.registry.inc("serve_input_waited", lookahead.waited)
                self.registry.inc("serve_input_wait_seconds",
                                  lookahead.wait_s)
