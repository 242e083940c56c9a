"""The scheduler of the serving engine: continuous batching over the
paged cache, for whichever block family the model is of.

Three boxes, arrows one way: this file -> a family's device programs
(``serve/gpt.py``, ``serve/hybrid.py``) -> the cache and its attention
helpers (``serve/kvcache.py``, ``serve/attend.py``), with the envelope
(``serve/config.py``) beside them.  :func:`family_for` is the one place
that looks at which family a model is of; :class:`ServingEngine` holds
the module it returns and asks it for everything that differs (the
seam: ``docs/serving.md``, "Adding a block family").

What is here is host code (Orca-style iteration-level scheduling):
arrivals from a ``TrafficTrace`` pass admission control (bounded queue:
overflow is a *rejected* request), waiting requests are granted slots
and worst-case block reservations at step boundaries, a prompt is
prefilled in chunks interleaved with the resident batch's decode steps
(or at once, per bucket, without ``prefill_chunk``), completed requests
free slot and blocks at once (the inputs of the queue's head are
prepared on a worker meanwhile: ``serve/lookahead.py``), and each decode
unit runs over whatever mix of old and new requests is resident: one
step, or, when the ledger knows no scheduling event is nearer, K steps
fused into one scan (``decode_horizon``), up to ``inflight_window``
units dispatched before the oldest is waited for.  Around that: prefix-cache attach, the
draft-and-verify units of speculative decoding, per-phase spans
(``serve-admission`` / ``serve-prefill`` / ``serve-decode``),
request-lifecycle events into the resilience journal, and live
MetricsRegistry counters and gauges.

Resilience (``docs/resilience.md``, serving faults): every fault site
fires strictly on the HOST side of a dispatch boundary; the families'
jitted programs are byte-identical with or without an active plan
(statically pinned).  A transiently-failed prefill/decode dispatch
rolls the host ledger/slot bookkeeping back to a pre-dispatch snapshot
and re-issues with exponential backoff; exhausted retries fail only
the affected requests, journaled ``request-failed`` with full
exception chains, never the run.  ``dispatch_deadline_factor`` arms
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

import contextlib
import math
import os
import signal
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from types import ModuleType
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
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.models.transformer import _dtype_of, init_params_sharded
from dlbb_tpu.obs import spans
from dlbb_tpu.obs.export import MetricsRegistry
from dlbb_tpu.ops.decode_attention import live_tile_counts
from dlbb_tpu.resilience import inject
from dlbb_tpu.resilience.errors import (
    CorruptStats,
    DeadlineExceeded,
    InjectedFault,
    TransientFault,
    exception_chain,
)
from dlbb_tpu.resilience.preempt import PreemptionGuard
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.kvcache import BlockLedger, KVCache, create_kv_cache
from dlbb_tpu.serve.lookahead import InputLookahead
from dlbb_tpu.serve.speculative import (
    _ngram_propose,
    softmax_np,
    speculative_sample,
)
from dlbb_tpu.serve.traffic import Request, TrafficTrace
from dlbb_tpu.utils.metrics import Timer, summarize

SERVING_REPORT_SCHEMA = "dlbb_serving_report_v1"


def family_for(config: ModelConfig) -> ModuleType:
    """The module that holds ``config``'s block family's serving
    programs and answers the scheduler's questions about it (the seam:
    ``docs/serving.md``, "Adding a block family")."""
    if config.is_hybrid:
        from dlbb_tpu.serve import hybrid

        return hybrid
    from dlbb_tpu.serve import gpt

    return gpt


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


def _launch_span(name: str, launch: int, fields=None, program=None):
    """A span that names a launch (:meth:`ServingEngine._launch`): the
    call itself, with the ``program`` called, or a wait of the scheduler
    thread for a device value (``serve-decode-sync`` /
    ``serve-prefill-sync`` / ``serve-inject-sync``), with the ``launch``
    waited for.  ``fields()`` gives the span's own arguments (a decode
    unit's ``k``, an admission's ``rid``).  With no tracer the shared
    null context: no argument built, no string formatted."""
    if spans.active() is None:
        return spans.span(name)
    args = fields() if fields else {}
    args["launch"] = launch
    if program is not None:
        # the name the profile's "XLA Modules" line prints
        args["program"] = f"jit_{program.__name__}"
    return spans.span(name, **args)


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
    # single-step units settled after a later launch went out: the
    # device found its next program queued when the step ended
    decode_units_overlapped: int = 0
    # calls of a jitted serving program on the scheduler's path: the
    # next launch's number (``ServingEngine._launch``)
    launches: int = 0
    fused_scans: int = 0
    fused_steps: int = 0
    single_steps: int = 0
    prefill_chunks: int = 0
    # K/V tiles the decode units' steps fetched (ops/decode_attention.py)
    # and tiles the planes they ran over hold, times steps
    kv_tiles_live: int = 0
    kv_tiles_held: int = 0
    # per decode unit: the slot-steps it ran and the cached tokens those
    # steps attended over (each step's own token included), reckoned at
    # the dispatch from the ledger's lengths
    unit_slot_steps: list[int] = field(default_factory=list)
    unit_live_tokens: list[int] = field(default_factory=list)
    # the block family's own samples (``family.unit_counted`` /
    # ``chunk_counted``); keys that start with "_" are its scratch
    family: dict[str, Any] = field(default_factory=dict)
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
        # fleet-replica control plane for the CURRENT run (run_trace's
        # ``control=``); None outside a fleet
        self._control: Any = None
        # the CURRENT run's counts (``_launch`` numbers its calls there)
        self._stats = _RunStats()
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
        # -- speculative decoding (docs/serving.md) --
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
        self._t0 = time.perf_counter()

    # -- clock (monotonic, run-relative) -----------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    # -- the launch sequence -------------------------------------------------

    def _launch(self, program, *args, span: str = "serve-launch",
                fields=None, via=None):
        """THE call of a jitted serving program on the scheduler's path:
        ``program(*args)``, numbered.  Every one goes through here, so
        the run's launches are ONE sequence 0..n-1 (``stats.launches``,
        the report's ``launches``) and the n-th launch is the n-th
        ``jit_serve_*`` event of a profile's "XLA Modules" line.

        With a tracer active the call runs inside ``span`` — the span
        that wraps exactly this call already (``serve-decode-dispatch``,
        ``serve-prefill-chunk``, ``serve-prefix-attach``) or the child
        ``serve-launch`` — which carries ``fields()`` (the span's own
        arguments, built only then), ``launch`` (the number) and
        ``program`` (the name the profile prints).  With none it costs
        one integer add and the shared null context.  ``via(call)`` runs
        the call under a caller's guard (the dispatch watchdog).  The
        number never reaches a traced value: the programs and their
        cache keys are what they were."""
        stats = self._stats
        number = stats.launches
        stats.launches = number + 1
        with _launch_span(span, number, fields, program):
            if via is None:
                return program(*args)
            return via(lambda: program(*args))

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

    def _probe_slot(self, req: Request, slot: int, recycled: bool,
                    first_logits: jax.Array, cache: Any) -> None:
        """Start the record of a probed request just admitted into
        ``slot``, and name the slot to the decode programs (in the
        place of a probed request that is done, else the first)."""
        done = [i for i, s in enumerate(self._probe_slots)
                if not any(r["slot"] == s and not r["done"]
                           for r in self.probed.values())]
        self._probe_slots[done[0] if done else 0] = slot
        self._probe_dev = jnp.asarray(self._probe_slots.copy())
        self.probed[req.rid] = {
            "slot": slot, "recycled": recycled, "done": False,
            "prompt_ids": prompt_ids_from_seed(
                req.seed, req.prompt_len, self.config.vocab_size)[0],
            "first_logits": first_logits, "units": [],
            # the slot's recurrent state as the prompt's last chunk
            # left it: a copy of one slot, dispatched and not waited for
            "prompt_state": self._launch(self._family.slot_state, cache,
                                         np.int32(slot)),
            "end_state": None}

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
            # token-feedback warms: the plain inject/decode/fused jits
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
        if self._probes:
            self._family.slot_state(carry[0], np.int32(0))
        carry, _y = self._decode(carry, self.params, active)
        for k in self._fused_ks:
            carry, _ys = self._decode_fused[k](carry, self.params, active,
                                               remaining)
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
        with contextlib.ExitStack() as stack:
            if guard is None:
                guard = stack.enter_context(PreemptionGuard())
            # joined on every way out, with what it still holds dropped
            lookahead = stack.enter_context(
                InputLookahead(self._request_input))
            try:
                return self._serve_trace(trace, guard, collect_raw, feed,
                                         control, lookahead)
            finally:
                self.registry.inc("serve_input_ready", lookahead.ready)
                self.registry.inc("serve_input_waited", lookahead.waited)
                self.registry.inc("serve_input_wait_seconds",
                                  lookahead.wait_s)

    def _serve_trace(self, trace: TrafficTrace, guard: PreemptionGuard,
                     collect_raw: bool, feed: Any, control: Any,
                     lookahead: InputLookahead) -> dict[str, Any]:
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
        self._stats = stats = _RunStats()
        series: dict[str, list] = {
            "t_s": [], "queue_depth": [], "active_slots": [],
            "blocks_in_use": [], "blocks_reserved": [],
        }
        if cfg.prefix_caching:
            series["shared_blocks"] = []
        carry = self._fresh_carry()
        active_np = np.zeros((cfg.max_batch,), bool)
        active_dev = jax.device_put(jnp.asarray(active_np.copy()),
                                    self._active_sharding)
        # slots that have served a request in this run (what the next
        # one finds there is the family's: ``slot_recycled``), and the
        # record of the probed requests (``probe``)
        used_slots: set[int] = set()
        self.probed = {}
        self._probe_slots[:] = -1
        self._probe_dev = jnp.asarray(self._probe_slots.copy())
        rejected_detail: list[dict[str, Any]] = []
        tokens_by_rid: dict[int, list[int]] = {}
        # -- speculative decoding state (docs/serving.md) --
        token_mode = self._token_mode
        ys_are_tokens = token_mode or self._family.TOKENS_FED_BACK
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
        # synced.  A fused scan waits there for the window's boundary
        # (``cfg.inflight_window``); a single step waits for the next
        # program that donates the whole carry (``settle_aliased``) or
        # for a boundary that drains, so that what donates the cache
        # alone (a prompt chunk, a prefix attach) goes out behind it;
        # last_sync anchors the per-unit interval so
        # back-to-back units never double-count queued device time
        inflight: deque[dict[str, Any]] = deque()
        last_sync = [0.0]
        # host-side active_np mutations are staged; the device mask is
        # re-uploaded lazily, and ALWAYS before a decode dispatch — a
        # decode interleaved into the admission loop (chunked prefill)
        # must see slots admitted earlier in the same loop
        active_dirty = [False]

        def refresh_active() -> None:
            # a HOST copy: ``active_np`` is edited in place after a unit
            # is dispatched, and on the CPU backend an upload may alias
            # the numpy buffer (``jnp.array`` copies it on the DEVICE, in
            # a program that runs when it runs): under load a unit not
            # yet run saw a completing slot as inactive
            nonlocal active_dev
            if active_dirty[0]:
                active_dev = jax.device_put(jnp.asarray(active_np.copy()),
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
            if st.req.rid in self.probed:
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
                    lookahead.drop(rid)
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
                # ``k`` and ``launch`` are the unit's WAITED FOR — under
                # a deeper window an older one than the unit just
                # dispatched
                with _launch_span("serve-decode-sync", unit["launch"],
                                  lambda: {"k": unit["k_exec"]}):
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
            if unit["k_exec"] == 1 and stats.launches > unit["next_launch"]:
                # the device found a program queued behind this step
                stats.decode_units_overlapped += 1
                self.registry.inc("serve_decode_units_overlapped")
            per_step = dt / unit["k_exec"]
            step_ema[0] = (per_step if step_ema[0] == 0.0
                           else 0.5 * step_ema[0] + 0.5 * per_step)
            for _row, _slot, _rid, steps in unit["rows"]:
                for _ in range(steps):
                    stats.per_token_s.append(dt / unit["k_exec"])
            done_at = self._now()
            if self._probes:
                # the family's small integers of this unit (None where
                # it has none): ready with the tokens, no sync of their own
                self._family.unit_counted(self.registry, self.config,
                                          stats.family, unit.get("counts"))
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

        def settle_aliased() -> None:
            """Before a launch that donates the WHOLE carry (a decode or
            verify unit, an inject).  A single step's ``ys`` is the same
            logical value as the carry's ``x`` (``decode_step`` returns
            ``((cache, y), y)``); where donation is honoured the two
            outputs may alias one buffer, and the launch would delete
            the held ``ys``.  So a single step still in flight (it can
            only be the window's newest unit) is settled first, with
            whatever was dispatched before it.  A fused scan's stacked
            ``ys`` is a buffer of its own and stays."""
            if inflight and inflight[-1]["k_exec"] == 1:
                drain()

        def decode_unit(k: int, steps: dict[int, int],
                        snap: dict[str, Any]) -> None:
            """One decode unit, committed: the device dispatch (under
            the watchdog when armed), torn-protected host bookkeeping,
            and the in-flight window push.  A fused scan then syncs the
            window down to its bound; a single step is left in flight
            and settled by whoever next donates the whole carry
            (``settle_aliased``: ``dispatch_decode``, an inject) or
            drains (``prefill_once`` behind each chunk, a fault path,
            the loop's exit).  Transient
            bookkeeping faults roll themselves back and replay (pure
            host recomputation — the device result is already in hand,
            so NEVER a re-dispatch); everything else raises out to
            ``dispatch_decode``'s recovery loop with nothing committed."""
            nonlocal carry
            rows: list[tuple[int, int, int, int]] = []
            deadline = unit_deadline(k)
            t0 = time.perf_counter()
            # ONE span per dispatched unit, covering the dispatch and,
            # after a fused scan, the boundary sync below, whose device
            # time belongs to an older unit; a single step's wait comes
            # later and outside it.  A unit's own device time is
            # its paired execution's: ``serve-decode-dispatch`` (the jit
            # call of THIS unit) carries the unit's ``launch`` number,
            # and the n-th launch is the n-th ``jit_serve_*`` event of
            # the profile's "XLA Modules" line
            # (``docs/observability.md`` §1; ``decode_step_s`` /
            # ``per_token_s`` are host intervals that also hold
            # whatever was queued before the unit).
            # ``serve-decode-sync`` is the wait, with the ``k`` and the
            # ``launch`` of the unit waited for; what is left is the
            # host bookkeeping at scan exit
            with spans.span("serve-decode", active=len(slots), steps=k,
                            unit=stats.decode_units):
                if inject.fire("serve-decode-fail"):
                    # fires BEFORE the jit is invoked: the donated carry
                    # was never consumed, so a retry re-dispatches from
                    # unchanged device state
                    raise TransientFault(
                        "injected serve-decode-fail at the decode "
                        "dispatch boundary")

                def guarded(call):
                    def run():
                        if inject.fire("serve-decode-hang"):
                            # a wedged dispatch: the sleep sits on the
                            # watchdog's daemon thread, never on the
                            # engine's scheduler thread
                            time.sleep(inject.param("hang_seconds"))
                        return call()
                    return _with_deadline(run, deadline, f"decode[k={k}]",
                                          "serve-dispatch")

                def dispatch(program, *args):
                    return self._launch(program, *args,
                                        span="serve-decode-dispatch",
                                        fields=lambda: {"k": k},
                                        via=guarded)

                if k == 1:
                    if token_mode:
                        carry, ys = dispatch(
                            self._decode_token, carry, self.params,
                            self._table, active_dev)
                    else:
                        carry, ys = dispatch(self._decode, carry,
                                             self.params, active_dev)
                    stats.single_steps += 1
                    for s in sorted(steps):
                        rows.append((s, s, slots[s].req.rid, 1))
                else:
                    rem_np = np.zeros((cfg.max_batch,), np.int32)
                    for s, m in steps.items():
                        rem_np[s] = m
                    rem_dev = jax.device_put(jnp.asarray(rem_np),
                                             self._active_sharding)
                    if token_mode:
                        carry, ys = dispatch(
                            self._decode_fused_token[k], carry,
                            self.params, self._table, active_dev, rem_dev)
                    else:
                        carry, ys = dispatch(
                            self._decode_fused[k], carry, self.params,
                            active_dev, rem_dev)
                    stats.fused_scans += 1
                    stats.fused_steps += k
                    self.registry.inc("serve_fused_scan_steps", k)
                    for s in sorted(steps):
                        rows.append((s, s, slots[s].req.rid, steps[s]))
                # this unit's launch: its wait names it (``sync_one``)
                launched = stats.launches - 1
                # what the unit's steps attend over: step i of a slot
                # reads the tokens (and the tiles) under its length + i
                trip = np.arange(k)[:, None]
                start = np.array([ledger.tokens(s) for s in steps])
                stepping = trip < np.array(list(steps.values()))[None, :]
                stats.unit_slot_steps.append(int(stepping.sum()))
                if self._probes:
                    self._family.unit_dispatched(
                        self.registry, self.config, stats.family,
                        stats.unit_slot_steps[-1], k, cfg.max_batch)
                stats.unit_live_tokens.append(
                    int(((start[None, :] + trip + 1) * stepping).sum()))
                if self._kv_tile:
                    max_tiles = cfg.max_seq // self._kv_tile
                    live = int(live_tile_counts(
                        start[None, :] + trip, stepping,
                        self._kv_tile, max_tiles).sum())
                    # a looped stack's step fetches them once a pass
                    live *= self.config.total_ut_steps
                    held = (k * max_tiles * cfg.max_batch
                            * self.config.total_ut_steps)
                    stats.kv_tiles_live += live
                    stats.kv_tiles_held += held
                    self.registry.inc(
                        f"serve_{self._tiles_of}_tiles_live", live)
                    self.registry.inc(
                        f"serve_{self._tiles_of}_tiles_held", held)
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
                counts = None
                if self._probes:
                    # ys = (tokens, seen of the probed slots, counts): a
                    # probed request keeps the first two, on the device
                    toks, seen, counts = ys
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
                            rec["end_state"] = self._launch(
                                self._family.slot_state, carry[0],
                                np.int32(s))
                done_states = [release(s) for s in completions]
                if completions:
                    refresh_active()
                inflight.append({"t0": t0, "ys": ys, "k_exec": k,
                                 "launch": launched,
                                 "next_launch": stats.launches,
                                 "rows": rows, "counts": counts,
                                 "tokens": ys_are_tokens,
                                 "completions": done_states})
                # who donates what decides where a unit is waited for.
                # The decode programs, the verify units and the injects
                # donate the whole carry; a prompt chunk and the prefix
                # attach donate the cache alone and never touch
                # ``carry[1]``.  So a single step, whose ``ys`` may
                # alias that ``x``, is settled before the next program
                # of the first kind (``settle_aliased``) and not here:
                # a chunk sliced and called meanwhile is queued behind
                # it and starts the moment the step ends.  A fused scan
                # rides the window
                if k > 1:
                    while len(inflight) >= cfg.inflight_window:
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

                def guarded(call):
                    def run():
                        if inject.fire("serve-decode-hang"):
                            time.sleep(inject.param("hang_seconds"))
                        return call()
                    return _with_deadline(run, deadline,
                                          f"verify[gamma={g}]",
                                          "serve-dispatch")

                def dispatch(program, *args):
                    return self._launch(program, *args, via=guarded)

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
                        self._draft_scan[g], draft_cache[0],
                        self._draft_params, self._table, carry[1], dlen,
                        active_dev)
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
                        self._verify_probs[g], carry, self.params,
                        self._table, ids, active_dev)
                    with _launch_span("serve-decode-sync",
                                      stats.launches - 1,
                                      lambda: {"k": g + 1}):
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
                        self._spec_commit, carry, self._table, next_dev,
                        com_dev, active_dev)
                else:
                    carry, tok, commits = dispatch(
                        self._verify[g], carry, self.params, self._table,
                        ids, active_dev, rem_dev)
                    with _launch_span("serve-decode-sync",
                                      stats.launches - 1,
                                      lambda: {"k": g + 1}):
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
            event horizon).
            ``max_k`` caps the horizon (the chunked-prefill interleave
            passes 1: the mid-admission request is itself a waiter, and
            a full fused scan between chunks would re-create the
            head-of-line blocking the interleave exists to remove).

            Every decode program donates the whole carry, so a single
            step still in flight is settled first (``settle_aliased``);
            its watchdog may fail the resident batch closed, and then
            there is nothing to dispatch.  The unit dispatched here is
            waited for by ``decode_unit`` (a fused scan, at the
            window's bound) or by a later settle (a single step).

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
            settle_aliased()
            if not slots:
                return
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
                snap = take_snapshot()
            attempt = 0
            while True:
                try:
                    decode_unit(k, steps, snap)
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

        def prefill_once(req: Request, slot: int, x_prompt: jax.Array,
                         plan: Optional[dict[str, Any]] = None):
            """The prefill dispatch for one admitted request (chunked or
            monolithic) over its prepared input ``x_prompt`` — returns
            ``(bucket, y_last, dt)``.  Raised through by the retry
            wrapper below; idempotent on retry:
            chunk writes are deterministic block writes of identical
            values, and interleaved decode units commit independently.
            A chunk (and the prefix attach) donates the cache alone, so
            it is called BEHIND whatever decode unit is in flight (the
            loop's own step at the admission's head, the interleaved
            step of the chunk before) and the window is drained only
            then: the device finds the chunk queued when the step ends.
            The next interleaved step, which donates the whole carry,
            goes out after that drain; after the last chunk the drain
            comes before ``serve-prefill-sync``, so the window is empty
            when the first token is injected.  A drain whose watchdog
            abandons the window resets the carry under a chunk already
            sent on the old cache: the prefill restarts, as after a
            failed interleaved dispatch.
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
            bucket = self._padded_len(req)
            if cfg.prefill_chunk is not None:
                chunk = cfg.prefill_chunk
                n_chunks = bucket // chunk
                m_chunks = 0
                if plan is not None and plan["attach_blocks"]:
                    plan["attached_tokens"] = 0
                    if carry_resets[0] == plan["resets"]:
                        m_chunks = plan["attach_tokens"] // chunk
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
                        cache, prefix = self._launch(
                            self._attach_jit(m_chunks), cache,
                            np.int32(plan["donor"]), np.int32(slot),
                            span="serve-prefix-attach",
                            fields=lambda: {
                                "rid": req.rid, "slot": slot,
                                "donor": plan["donor"],
                                "blocks": plan["attach_blocks"]})
                        plan["attached_tokens"] = m_chunks * chunk
                    else:
                        prefix = self._create_prefix()
                    lasts = []
                    resets = carry_resets[0]
                    for ci in range(m_chunks, n_chunks):
                        cache, prefix, y_last = self._launch(
                            self._chunk_jit(ci), cache, prefix,
                            self.params,
                            x_prompt[:, ci * chunk:(ci + 1) * chunk],
                            np.int32(slot), np.int32(req.prompt_len),
                            span="serve-prefill-chunk",
                            fields=lambda: {
                                "rid": req.rid, "chunk": ci,
                                "seq": stats.prefill_chunks})
                        launched = stats.launches - 1
                        carry = (cache, carry[1])
                        lasts.append(y_last)
                        stats.prefill_chunks += 1
                        self.registry.inc("serve_prefill_chunks")
                        # the chunk is queued: now wait for the step
                        # dispatched before it, then interleave the next
                        # one (the resident batch decodes between chunks
                        # instead of head-of-line blocking); both are
                        # decode time, not this prefill's
                        td = time.perf_counter()
                        if inflight:
                            with spans.span("serve-drain",
                                            inflight=len(inflight)):
                                drain()
                        if ci < n_chunks - 1 and slots:
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
                    with _launch_span("serve-prefill-sync", launched,
                                      lambda: {"rid": req.rid}):
                        jax.block_until_ready(y_last)
                    # the interleaved units' dispatch+sync time is
                    # already billed to decode_step_s/per_token_s —
                    # keep prefill_s a PREFILL cost
                    dt = time.perf_counter() - t0 - decode_spent
                    if self._probes:
                        # the family's small integers of each chunk:
                        # ready with the last chunk's logits
                        for last in lasts:
                            self._family.chunk_counted(
                                self.registry, self.config, stats.family,
                                last)
            else:
                with spans.span("serve-prefill", rid=req.rid,
                                bucket=bucket, slot=slot):
                    t0 = time.perf_counter()
                    cache, y_last = self._launch(
                        self._prefill_jit(bucket), carry[0], self.params,
                        x_prompt, np.int32(slot),
                        np.int32(req.prompt_len))
                    launched = stats.launches - 1
                    if self._draft_prefill is not None:
                        # the draft plane is prefilled at admission from
                        # the SAME prompt embeddings (idempotent masked
                        # writes, so the retry wrapper covers it); its
                        # cost is billed as prefill — the admission
                        # price of the draft model
                        dcache, _dy = self._launch(
                            self._draft_prefill, draft_cache[0],
                            self._draft_params, x_prompt, np.int32(slot),
                            np.int32(req.prompt_len))
                        draft_cache[0] = dcache
                    with _launch_span("serve-prefill-sync", launched,
                                      lambda: {"rid": req.rid}):
                        jax.block_until_ready(y_last)
                    dt = time.perf_counter() - t0
                carry = (cache, carry[1])
            return bucket, y_last, dt

        def prefill_dispatch(req: Request, slot: int, x_prompt: jax.Array,
                             plan: Optional[dict[str, Any]] = None):
            """Bounded-retry wrapper around :func:`prefill_once` —
            transient dispatch failures back off and re-issue over the
            same prepared input (chunk counters rolled back so a retried
            prefill never double-counts); exhaustion raises to the
            admission loop's
            fail-closed path.  The prefix-attach ``plan`` rides through
            unchanged: each attempt re-checks the carry generation
            itself, so a retry after a mid-prefill carry reset degrades
            to the full prefill instead of copying zeroed donor blocks."""
            attempt = 0
            while True:
                chunks_base = stats.prefill_chunks
                try:
                    return prefill_once(req, slot, x_prompt, plan)
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

        def fail_admission(req: Request, slot: int, exc: BaseException,
                           dispatched: bool = True) -> None:
            """A permanently-failed prefill fails ONLY the admitting
            request: reservation undone, journaled with the chain.  A
            real (non-injected) failure of a ``dispatched`` prefill also
            consumed the donated cache, so the resident batch fails
            closed too and the engine continues on a fresh carry; an
            input that could not be prepared touched no cache."""
            nonlocal carry
            ledger.free(slot)
            if draft_ledger is not None:
                draft_ledger.free(slot)
            free_slots.append(slot)
            free_slots.sort()
            fail_requests([_SlotState(req=req, tokens_done=0)], exc,
                          "dispatch-failed")
            if dispatched and not isinstance(exc, InjectedFault):
                # units dispatched before the failed program are sound:
                # settle them, then fail what is resident
                try:
                    drain()
                except Exception:  # noqa: BLE001
                    inflight.clear()
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
                lookahead.drop(req.rid)
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
                # scan boundary: in-flight decode is settled before the
                # prefill blocks, so its sync cost lands in decode
                # timing and TTFT stays honest.  A bucketed prefill
                # settles it here; a chunked one behind its first chunk
                # (``prefill_once``), so that the device has that chunk
                # queued while the host takes the input, builds the
                # prefix and calls it
                if cfg.prefill_chunk is None:
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
                        # the input: taken ready from the look-ahead,
                        # waited for, or prepared inline; the worker
                        # goes on with the queue's new head meanwhile
                        try:
                            with spans.span("serve-admit-embed",
                                            rid=req.rid, slot=slot):
                                x_prompt = lookahead.take(req)
                        except Exception as e:  # noqa: BLE001 — closed
                            fail_admission(req, slot, e, dispatched=False)
                            continue
                        lookahead.top_up(queue)
                        try:
                            bucket, y_last, dt = prefill_dispatch(
                                req, slot, x_prompt, plan)
                        except Exception as e:  # noqa: BLE001 — closed
                            fail_admission(req, slot, e)
                            continue
                        with spans.span("serve-admit-inject", rid=req.rid,
                                        slot=slot):
                            first_id = -1
                            # every inject donates the whole carry: no
                            # single step may be in flight behind it
                            # (``prefill_once`` left the window empty)
                            settle_aliased()
                            if token_mode and self._sampled:
                                # sampled inject: position 0 obeys the same
                                # temperature law as every later token —
                                # the prefill's last logits come to host
                                # (one [H] vector per admission), the first
                                # token is drawn from their softmax, and
                                # the device only embeds the committed id
                                # (once per ADMISSION, not per token)
                                with _launch_span("serve-inject-sync",
                                                  stats.launches - 1,
                                                  lambda: {"rid": req.rid}):
                                    # comm-lint: disable=host-transfer-in-loop
                                    y_np = np.asarray(y_last)
                                p0 = softmax_np(y_np, cfg.temperature)
                                first_id = int(sample_rng.choice(
                                    p0.shape[-1], p=p0))
                                carry = self._launch(
                                    self._inject_sampled, carry,
                                    np.int32(slot), np.int32(first_id),
                                    self._table)
                            elif token_mode:
                                # greedy token inject: argmax on device, a
                                # 4-byte id to host — the history seed AND
                                # the equivalence capture in one transfer
                                carry, first_tok = self._launch(
                                    self._inject_greedy, carry,
                                    np.int32(slot), y_last, self._table)
                                with _launch_span("serve-inject-sync",
                                                  stats.launches - 1,
                                                  lambda: {"rid": req.rid}):
                                    first_id = int(first_tok)
                            else:
                                carry = self._launch(
                                    self._inject, carry, np.int32(slot),
                                    y_last)
                            recycled = slot in used_slots
                            used_slots.add(slot)
                            if recycled:
                                self._family.slot_recycled(
                                    self.registry, req.rid, slot)
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
                                if not token_mode:
                                    # device-side argmax: a 4-byte scalar
                                    # comes to host per admission, never
                                    # the whole hidden state
                                    # (host-transfer-in-loop); it runs
                                    # behind the newest launch, the inject
                                    # (a probing family's ``last`` may
                                    # carry more behind the logits)
                                    with _launch_span(
                                            "serve-inject-sync",
                                            stats.launches - 1,
                                            lambda: {"rid": req.rid}):
                                        first_id = int(jnp.argmax(
                                            self._family.probe_parts(
                                                y_last)[0]
                                            if self._probes else y_last))
                                tokens_by_rid.setdefault(
                                    req.rid, []).append(first_id)
                            self._event(
                                "request-prefill", req.rid, slot=slot,
                                bucket=bucket,
                                ttft_s=round(t_first - req.arrival_s, 6))
                            if st.tokens_done >= req.output_len:
                                finish(release(slot), self._now())
                if scheduled:
                    refresh_active()
            # what still waits for a slot has its input prepared on the
            # worker while the device decodes (a request that found a
            # slot as it arrived never passes through it)
            lookahead.top_up(queue)
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
            # single steps waited for after a later launch went out (a
            # prompt chunk queued behind them): of ``decode_units``
            "decode_units_overlapped": stats.decode_units_overlapped,
            # calls of a jitted serving program (``_launch``): the span
            # file's ``launch`` arguments run 0..launches-1
            "launches": stats.launches,
            # share of the K/V planes' tiles the decode steps fetched
            f"{self._tiles_of}_live_share": (
                stats.kv_tiles_live / stats.kv_tiles_held
                if stats.kv_tiles_held else 0.0),
            # the block family's own (experts touched, the fullest
            # expert's load)
            **self._family.report_shares(self.config, stats.family),
            # share of admissions whose input the look-ahead had ready
            "input_ready_share": lookahead.ready_share,
            "fast_path": {
                "enabled": self._fast,
                "decode_horizon": cfg.decode_horizon,
                "inflight_window": cfg.inflight_window,
                "prefill_chunk": cfg.prefill_chunk,
                "fused_scans": stats.fused_scans,
                "fused_steps": stats.fused_steps,
                "single_steps": stats.single_steps,
                "prefill_chunks": stats.prefill_chunks,
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
                # per decode unit, in dispatch order (a ``serve-decode``
                # span's ``unit``): slot-steps and cached tokens attended
                "unit_slot_steps": list(stats.unit_slot_steps),
                "unit_live_tokens": list(stats.unit_live_tokens),
                # the family's, per decode unit (``unit``) and prompt
                # chunk (a ``serve-prefill-chunk`` span's ``seq``)
                **{key: list(values)
                   for key, values in stats.family.items()
                   if not key.startswith("_")},
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
