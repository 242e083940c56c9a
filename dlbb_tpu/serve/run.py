"""One run of one trace through the serving engine: :class:`TraceRun`,
the scheduler.  ``ServingEngine.run_trace`` builds a run and returns
``run.serve()``; the run holds what lives for one trace and calls the
engine's programs, and the engine knows nothing of a run.

Orca-style iteration-level scheduling, four phases at each step
boundary: arrivals from a ``TrafficTrace`` pass admission control
(bounded queue: overflow is a *rejected* request); queue heads past
their SLO deadline are shed and waiting requests are granted slots and
worst-case block reservations, each prompt prefilled in chunks
interleaved with the resident batch's decode steps (or at once, per
bucket, without ``prefill_chunk``; the inputs of the queue's head are
prepared on a worker meanwhile: ``serve/lookahead.py``); a decode unit
runs over whatever mix of old and new requests is resident: one step,
or, when the ledger knows no scheduling event is nearer, K steps fused
into one scan (``decode_horizon``), up to ``inflight_window`` units
dispatched before the oldest is waited for, and completed requests free
slot and blocks at once; the time series is sampled.  Around that:
prefix-cache attach, the draft-and-verify units of speculative
decoding, per-phase spans (``serve-admission`` / ``serve-prefill`` /
``serve-decode``), request-lifecycle events into the resilience
journal, and live MetricsRegistry counters and gauges.

Resilience (``docs/resilience.md``, serving faults): every fault site
fires strictly on the HOST side of a dispatch boundary; the families'
jitted programs are byte-identical with or without an active plan
(statically pinned).  A transiently-failed prefill/decode dispatch
rolls the host ledger/slot bookkeeping back to a pre-dispatch snapshot
and re-issues with exponential backoff; exhausted retries fail only
the affected requests, journaled ``request-failed`` with full
exception chains, never the run.  ``dispatch_deadline_factor`` arms
an EMA-scaled watchdog (the sweep runner's daemon-thread pattern) that
abandons a hung dispatch or window sync and continues on a fresh carry.
SIGTERM under the run's ``PreemptionGuard`` drains gracefully:
admission stops, the in-flight window settles, resident requests are
journaled ``request-preempted``, and the report carries the
remaining-rid cursor ``serve/bench.py`` checkpoints for
``cli serve --resume``.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dlbb_tpu.data.synthetic import prompt_ids_from_seed, prompt_token_ids
from dlbb_tpu.obs import spans
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
from dlbb_tpu.serve.kvcache import BlockLedger, KVCache
from dlbb_tpu.serve.lookahead import InputLookahead
from dlbb_tpu.serve.speculative import (
    _ngram_propose,
    softmax_np,
    speculative_sample,
)
from dlbb_tpu.serve.traffic import Request, TrafficTrace
from dlbb_tpu.utils.metrics import Timer, summarize

SERVING_REPORT_SCHEMA = "dlbb_serving_report_v1"


def _with_deadline(fn, deadline: Optional[float], label: str,
                   phase: str) -> Any:
    """Run ``fn()`` under the serving dispatch watchdog (the
    daemon-thread pattern of ``bench/runner._call_with_deadline``).

    With no deadline this is a direct call — zero threads, zero
    overhead.  With one, ``fn`` runs on a daemon thread joined for
    ``deadline`` seconds; an overrun ABANDONS the thread (it may be
    wedged inside the runtime and cannot be killed) and raises
    :class:`DeadlineExceeded` — the run then fails the unit's
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
    """A span that names a launch (:meth:`TraceRun._launch`): the call
    itself, with the ``program`` called, or a wait of the scheduler
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


@dataclass
class _SlotState:
    req: Request
    tokens_done: int = 0
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
    # next launch's number (``TraceRun._launch``)
    launches: int = 0
    fused_scans: int = 0
    fused_steps: int = 0
    single_steps: int = 0
    prefill_chunks: int = 0
    # layer attentions of the prompt chunks dispatched that ran as the
    # ``chunk_attend`` kernel, and those that ran dense
    # (``family.chunk_attn_counts``)
    chunk_attn_kernel: int = 0
    chunk_attn_dense: int = 0
    # grouped expert products of the decode steps and prompt chunks
    # dispatched whose contraction was one tile, and those whose
    # contraction was split (``family.gmm_counts``)
    gmm_whole_k: int = 0
    gmm_split_k: int = 0
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


class _TokenFed:
    """What only the token-fed decode paths read (``speculation`` other
    than "off"): a run that feeds no tokens back builds none of it."""

    def __init__(self, engine: Any) -> None:
        cfg = engine.serving
        # per-rid committed token history (prompt ids + every committed
        # token): the n-gram drafter's lookup context
        self.hist: dict[int, list[int]] = {}
        # sampled decode's host RNG: seeded from the config knob so a
        # (trace, config) pair replays token-for-token — the journal'd
        # runs stay deterministic even though the law is a distribution
        self.rng = (np.random.default_rng(cfg.sample_seed)
                    if engine._sampled else None)
        # the draft model's KV plane (``serve`` builds it, every carry
        # reset replaces it); its ledger mirrors the target's accounting
        # — the draft plane has the same slot/block geometry, and its
        # COMMITTED content tracks the target's exactly (draft writes
        # past the committed length are dead by the length-mask
        # construction)
        self.draft: Optional[KVCache] = None
        self.draft_ledger = (BlockLedger(cfg.total_blocks, cfg.block_size)
                             if engine._draft_config is not None else None)
        # run-level acceptance EMA (the metrics.prom gauge)
        self.accept_ema = -1.0


class TraceRun:
    """One run of one trace (see the module docstring).  Building it
    compiles nothing and starts no clock: :meth:`serve` does both first,
    so a test can build a run and drive one phase without a program."""

    def __init__(self, engine: Any, trace: TrafficTrace,
                 guard: PreemptionGuard, lookahead: InputLookahead,
                 collect_raw: bool = False, feed: Any = None,
                 control: Any = None) -> None:
        self.engine = engine
        self.cfg = cfg = engine.serving
        self.config = engine.config
        self.family = engine._family
        self.registry = engine.registry
        self._requests = engine._requests
        self._rejections = engine._rejections
        self.trace, self.guard, self.lookahead = trace, guard, lookahead
        self.collect_raw, self.feed = collect_raw, feed
        # the fleet-replica control plane (``run_trace``'s ``control=``)
        self.control = control
        self.stats = _RunStats()
        # the clock's origin: :meth:`serve` sets it again once compiled
        self.t0 = time.perf_counter()
        self.compile_time = 0.0
        self.ledger = BlockLedger(cfg.total_blocks, cfg.block_size,
                                  prefix_caching=cfg.prefix_caching)
        # registry counters are cumulative across an engine's lifetime
        # (Prometheus semantics); the report carries THIS run's deltas
        self.counts_base = {k: self._requests[k] for k in self._requests}
        self.shed_base = self._rejections["queue-full"]
        # a fleet supervisor feeds arrivals dynamically (and re-feeds
        # failovers at queue head); a standalone run serves the static
        # trace in arrival order
        self.pending = (feed if feed is not None
                        else deque(sorted(trace,
                                          key=lambda r: (r.arrival_s, r.rid))))
        self.queue: deque[Request] = deque()
        self.slots: dict[int, _SlotState] = {}
        self.free_slots = list(range(cfg.max_batch))
        self.series: dict[str, list] = {
            "t_s": [], "queue_depth": [], "active_slots": [],
            "blocks_in_use": [], "blocks_reserved": [],
        }
        if cfg.prefix_caching:
            self.series["shared_blocks"] = []
        # the device's state: :meth:`serve` builds it once compiled
        self.carry: Any = None
        self.active_np = np.zeros((cfg.max_batch,), bool)
        self.active_dev: Any = None
        # host-side active_np mutations are staged; the device mask is
        # re-uploaded lazily, and ALWAYS before a decode dispatch — a
        # decode interleaved into the admission loop (chunked prefill)
        # must see slots admitted earlier in the same loop
        self.active_dirty = False
        # slots that have served a request in this run (what the next
        # one finds there is the family's: ``slot_recycled``)
        self.used_slots: set[int] = set()
        self.rejected_detail: list[dict[str, Any]] = []
        self.tokens_by_rid: dict[int, list[int]] = {}
        self.ys_are_tokens = (engine._token_mode
                              or self.family.TOKENS_FED_BACK)
        self.spec = _TokenFed(engine) if engine._token_mode else None
        # the ledgers a slot's blocks and tokens are booked in
        self.ledgers = [self.ledger]
        if self.spec is not None and self.spec.draft_ledger is not None:
            self.ledgers.append(self.spec.draft_ledger)
        # per-request final outcome map (rid -> "completed" /
        # "rejected[reason]" / "failed[reason]" / "preempted") — the
        # thing kill-mid-trace ≡ uninterrupted equivalence is pinned on
        self.outcomes: dict[int, str] = {}
        # permanent-failure records: full exception chains, never a
        # silent skip (the serving twin of the sweep quarantine)
        self.failed_detail: list[dict[str, Any]] = []
        # bounded in-flight window: decode units dispatched but not yet
        # synced.  A fused scan waits there for the window's boundary
        # (``cfg.inflight_window``); a single step waits for the next
        # program that donates the whole carry (``settle_aliased``) or
        # for a boundary that drains, so that what donates the cache
        # alone (a prompt chunk, a prefix attach) goes out behind it;
        # last_sync anchors the per-unit interval so back-to-back units
        # never double-count queued device time
        self.inflight: deque[dict[str, Any]] = deque()
        self.last_sync = 0.0
        # EMA of the observed per-step interval: the horizon policy uses
        # it to convert "next arrival in X seconds" into a step budget,
        # and the dispatch watchdog scales its deadline from it
        self.step_ema = 0.0
        # bumped at every catastrophic carry replacement (hung/failed
        # dispatch or prefill, abandoned window): the chunked-prefill
        # interleave checks it — chunks already written to the OLD cache
        # are gone with it, so a mid-prefill reset must restart the
        # prefill rather than keep chunking into the fresh empty cache
        self.carry_resets = 0
        self.preempted = False

    # -- the loop -------------------------------------------------------------

    def serve(self) -> dict[str, Any]:
        """Compile, start the clock, serve the trace to completion (or to
        a graceful preemption drain) and return the report."""
        eng = self.engine
        self.compile_time = self._warm()
        self.carry = eng._fresh_carry()
        self.active_dev = jax.device_put(jnp.asarray(self.active_np.copy()),
                                         eng._active_sharding)
        # the record of the probed requests (``probe``) starts anew
        eng.probed = {}
        eng._probe_slots[:] = -1
        eng._probe_dev = jnp.asarray(eng._probe_slots.copy())
        if self.spec is not None:
            self.spec.draft = eng._fresh_draft_cache()
        # a fleet run shares one clock origin across every replica (the
        # supervisor's barrier sets it after ALL replicas have compiled,
        # so per-replica compile skew never distorts arrival/deadline
        # accounting); a standalone run starts its own
        self.t0 = (self.control.sync_start() if self.control is not None
                   else time.perf_counter())
        self.last_sync = self.t0
        while self.pending or self.queue or self.slots:
            if self.at_boundary():
                break
            now = self._now()
            # 1. arrivals -> admission control (bounded queue)
            self.arrive(now)
            # 2. step-boundary scheduling: shed the queue heads whose SLO
            #    is already blown, then grant slots + block reservations
            #    and prefill each granted request
            self.shed_expired(now)
            if self.queue and self.free_slots:
                self.admit()
            # what still waits for a slot has its input prepared on the
            # worker while the device decodes (a request that found a
            # slot as it arrived never passes through it)
            self.lookahead.top_up(self.queue)
            # 3. a decode unit over every resident request: one step, or
            #    a fused K-step scan on the fast path; else idle until
            #    the next arrival (nothing resident, nothing admittable)
            if self.slots:
                self.dispatch_decode()
            elif self.pending and not self.queue:
                self.idle()
            # 4. timeseries sample at the step boundary
            self.sample()
        self.drain()
        remaining = self.preempt() if self.preempted else []
        return self.report(remaining)

    def _warm(self) -> float:
        """Hold the trace to the envelope, then compile every program it
        will hit; returns the compile time."""
        cfg, eng, trace = self.cfg, self.engine, self.trace
        if not len(trace):
            raise ValueError("cannot serve an empty trace")
        if cfg.reject_infeasible:
            feasible = [r for r in trace
                        if eng._infeasible_reason(r) is None]
            if not feasible:
                raise ValueError(
                    "every request in the trace is infeasible for this "
                    "serving envelope — nothing to serve"
                )
        else:
            eng._validate_trace(trace)
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
            eng._compile(buckets, max_chunks)
        return t_compile.elapsed

    def at_boundary(self) -> bool:
        """The loop boundary: the fleet's control plane, the injected
        preemption, and whether a SIGTERM asks for the drain."""
        control = self.control
        if control is not None:
            # replica control plane (serve/fleet.py), strictly at the
            # loop boundary so a fence can never tear a half-applied
            # dispatch: heartbeat, injected replica kill/hang,
            # supervisor cancels (losing hedges)
            control.beat()
            control.check()
            for c_rid, c_reason in control.take_cancels():
                self.cancel_request(c_rid, c_reason)
        if inject.fire("serve-preempt"):
            # chaos harness: deliver a real SIGTERM to ourselves — the
            # PreemptionGuard turns it into the drain flag below (inert-
            # flag fallback off the main thread)
            if self.guard.installed:
                os.kill(os.getpid(), signal.SIGTERM)
            else:
                self.guard.request()
        # graceful drain: admission stops at this boundary; the
        # in-flight window settles after the loop and still-resident
        # requests are journaled ``request-preempted``
        self.preempted = self.guard.requested
        return self.preempted

    def arrive(self, now: float) -> None:
        """Phase 1: the requests arrived by ``now`` pass admission
        control — rejected as infeasible (``reject_infeasible``), shed
        by a full queue, or queued."""
        cfg, queue = self.cfg, self.queue
        while self.pending and self.pending[0].arrival_s <= now:
            req = self.pending.popleft()
            self._requests["arrived"] += 1
            self._event("request-arrived", req.rid,
                        prompt=req.prompt_len, output=req.output_len)
            reason = (self.engine._infeasible_reason(req)
                      if cfg.reject_infeasible else None)
            if reason is not None:
                # distinct journal event from the load-shed path:
                # infeasible is a config/trace mismatch, never load
                self._reject(req, "infeasible", "request-infeasible",
                             {"queue_depth": len(queue), "queue_wait_s": 0.0,
                              "detail": reason}, detail=reason)
            elif len(queue) >= cfg.queue_capacity:
                head_wait = round(now - queue[0].arrival_s if queue
                                  else 0.0, 6)
                self._reject(req, "queue-full", "request-rejected",
                             {"queue_depth": len(queue),
                              "queue_wait_s": head_wait},
                             queue_depth=len(queue), queue_wait_s=head_wait)
            else:
                queue.append(req)
                self._requests["admitted"] += 1
                self._event("request-admitted", req.rid,
                            queue_depth=len(queue))

    def shed_expired(self, now: float) -> None:
        """Phase 2's first step, per-request SLO shedding: a queue head
        whose wait has already blown its deadline is shed
        (``request-rejected[reason=deadline]`` — DISTINCT from
        queue-full: this is latency, not capacity) rather than served
        into a guaranteed SLO miss."""
        queue = self.queue
        while (queue and queue[0].deadline_s is not None
                and now - queue[0].arrival_s > queue[0].deadline_s):
            req = queue.popleft()
            self.lookahead.drop(req.rid)
            wait = round(now - req.arrival_s, 6)
            self.engine._deadline_counter["shed-queued"] += 1
            self.stats.deadline_shed += 1
            self._reject(req, "deadline", "request-rejected",
                         {"queue_depth": len(queue), "queue_wait_s": wait,
                          "deadline_s": req.deadline_s},
                         queue_wait_s=wait, deadline_s=req.deadline_s)

    def _reject(self, req: Request, reason: str, event: str,
                record: dict[str, Any], **args: Any) -> None:
        """Reject ``req`` for ``reason``: its outcome, the counters, the
        report's ``record`` and the journal's ``event``."""
        self._requests["rejected"] += 1
        self._rejections[reason] += 1
        self.outcomes[req.rid] = f"rejected[{reason}]"
        self.rejected_detail.append({"rid": req.rid, "reason": reason,
                                     **record})
        self._event(event, req.rid, reason=reason, **args)

    def admit(self) -> None:
        """Phase 2: grant the queue's head slots and block reservations
        while both last, and prefill each granted request."""
        # scan boundary: in-flight decode is settled before the prefill
        # blocks, so its sync cost lands in decode timing and TTFT stays
        # honest.  A bucketed prefill settles it here; a chunked one
        # behind its first chunk (``prefill_once``), so that the device
        # has that chunk queued while the host takes the input, builds
        # the prefix and calls it
        if self.cfg.prefill_chunk is None:
            with spans.span("serve-drain", inflight=len(self.inflight)):
                self.drain()
        # one child span per step of an admission, each with the
        # request's ``rid`` and its ``slot``: plan, embed and prefill
        # (inside ``prefill_once``), inject, book — what is left of
        # ``serve-admission`` is this loop
        scheduled = False
        with spans.span("serve-admission", queue=len(self.queue),
                        free_slots=len(self.free_slots)):
            while self.queue and self.free_slots:
                admitted = self.admit_one()
                if admitted is None:
                    break
                scheduled = scheduled or admitted
        if scheduled:
            self.refresh_active()

    def admit_one(self) -> Optional[bool]:
        """One admission of the queue's head into the first free slot:
        None when its blocks do not fit (the admission loop stops), False
        when it failed closed, True when it is resident."""
        cfg, req, slot = self.cfg, self.queue[0], self.free_slots[0]
        with spans.span("serve-admit-plan", rid=req.rid, slot=slot):
            # prefix admission: blocks the trie already holds are
            # counted ONCE fleet-wide, so a request whose private suffix
            # fits is admittable even when its full footprint would not
            # be — the int8/prefix capacity win
            plan = self.attach_plan(req) if cfg.prefix_caching else None
            attach_blocks = plan["attach_blocks"] if plan else 0
            fits = self.ledger.can_reserve(req.total_tokens,
                                           shared_blocks=attach_blocks)
            if fits:
                self.queue.popleft()
                self.free_slots.pop(0)
                self.ledger.reserve(
                    slot, req.total_tokens,
                    chain=plan["chain"] if plan else None,
                    attach_blocks=attach_blocks)
                for ledger in self.ledgers[1:]:
                    ledger.reserve(slot, req.total_tokens)
        if not fits:
            return None
        # the input: taken ready from the look-ahead, waited for, or
        # prepared inline; the worker goes on with the queue's new head
        # meanwhile
        try:
            with spans.span("serve-admit-embed", rid=req.rid, slot=slot):
                x_prompt = self.lookahead.take(req)
        except Exception as e:  # noqa: BLE001 — fail closed
            self.fail_admission(req, slot, e, dispatched=False)
            return False
        self.lookahead.top_up(self.queue)
        try:
            bucket, y_last, dt = self.prefill_dispatch(req, slot, x_prompt,
                                                       plan)
        except Exception as e:  # noqa: BLE001 — fail closed
            self.fail_admission(req, slot, e)
            return False
        with spans.span("serve-admit-inject", rid=req.rid, slot=slot):
            first_id = self.inject_first(req, slot, y_last)
        with spans.span("serve-admit-book", rid=req.rid, slot=slot):
            t_first = self.book_admission(req, slot, plan, first_id)
            self.stats.ttft_s.append(t_first - req.arrival_s)
            self.stats.prefill_s.append(dt)
            self.stats.generated_tokens += 1
            if self.engine.capture_tokens:
                if not self.engine._token_mode:
                    # device-side argmax: a 4-byte scalar comes to host
                    # per admission, never the whole hidden state
                    # (host-transfer-in-loop); it runs behind the newest
                    # launch, the inject (a probing family's ``last`` may
                    # carry more behind the logits)
                    with _launch_span("serve-inject-sync",
                                      self.stats.launches - 1,
                                      lambda: {"rid": req.rid}):
                        first_id = int(jnp.argmax(
                            self.family.probe_parts(y_last)[0]
                            if self.engine._probes else y_last))
                self.tokens_by_rid.setdefault(req.rid, []).append(first_id)
            self._event("request-prefill", req.rid, slot=slot,
                        bucket=bucket,
                        ttft_s=round(t_first - req.arrival_s, 6))
            if self.slots[slot].tokens_done >= req.output_len:
                self.finish(self.release(slot), self._now())
        return True

    def inject_first(self, req: Request, slot: int, y_last: Any) -> int:
        """The prefill's first token into the carry; returns its id
        where the host learns it (token-fed paths), else -1."""
        eng, stats = self.engine, self.stats
        first_id = -1
        # every inject donates the whole carry: no single step may be in
        # flight behind it (``prefill_once`` left the window empty)
        self.settle_aliased()
        if eng._token_mode and eng._sampled:
            # sampled inject: position 0 obeys the same temperature law
            # as every later token — the prefill's last logits come to
            # host (one [H] vector per admission), the first token is
            # drawn from their softmax, and the device only embeds the
            # committed id (once per ADMISSION, not per token)
            with _launch_span("serve-inject-sync", stats.launches - 1,
                              lambda: {"rid": req.rid}):
                # comm-lint: disable=host-transfer-in-loop
                y_np = np.asarray(y_last)
            p0 = softmax_np(y_np, self.cfg.temperature)
            first_id = int(self.spec.rng.choice(p0.shape[-1], p=p0))
            self.carry = self._launch(
                eng._inject_sampled, self.carry, np.int32(slot),
                np.int32(first_id), eng._table)
        elif eng._token_mode:
            # greedy token inject: argmax on device, a 4-byte id to host
            # — the history seed AND the equivalence capture in one
            # transfer
            self.carry, first_tok = self._launch(
                eng._inject_greedy, self.carry, np.int32(slot), y_last,
                eng._table)
            with _launch_span("serve-inject-sync", stats.launches - 1,
                              lambda: {"rid": req.rid}):
                first_id = int(first_tok)
        else:
            self.carry = self._launch(eng._inject, self.carry,
                                      np.int32(slot), y_last)
        recycled = slot in self.used_slots
        self.used_slots.add(slot)
        if recycled:
            self.family.slot_recycled(self.registry, req.rid, slot)
        if req.rid in eng._probe_rids:
            self._probe_slot(req, slot, recycled, y_last, self.carry[0])
        return first_id

    def book_admission(self, req: Request, slot: int,
                       plan: Optional[dict[str, Any]], first_id: int
                       ) -> float:
        """Book an admitted request: its prompt in the ledgers, its
        prefix attach, and its slot; returns its first token's time."""
        cfg, stats, ledger = self.cfg, self.stats, self.ledger
        for led in self.ledgers:
            led.append(slot, req.prompt_len)
        if cfg.prefix_caching and plan is not None:
            reused = plan["attached_tokens"]
            if reused:
                stats.prefix_hits += 1
                stats.prefix_tokens_reused += reused
                self.registry.inc("serve_prefix_hits")
                self.registry.inc("serve_prefix_tokens_reused", reused)
                self._event("prefix-attach", req.rid, slot=slot,
                            donor=plan["donor"], tokens=reused,
                            blocks=reused // cfg.block_size)
                if plan["cow_blocks"]:
                    # matched deeper than the attach cap: the tail blocks
                    # were recomputed privately — the copy-on-write edge
                    ledger.note_cow(plan["cow_blocks"])
                    stats.prefix_cow_blocks += plan["cow_blocks"]
                    self._event("prefix-cow", req.rid, slot=slot,
                                blocks=plan["cow_blocks"])
            # index this slot's full-block chain: the prefill (attached
            # or full) made the slot a physical holder of every block it
            # refs, and dedup against already-shared blocks refunds the
            # private reservation
            ledger.register(slot, plan["chain"])
        t_first = self._now()
        st = _SlotState(req=req, tokens_done=1, gamma_eff=cfg.spec_gamma)
        if cfg.speculation == "ngram":
            # prompt-lookup context: the prompt's own token-id view (pure
            # numpy, admission-time) plus the prefill's first committed
            # token
            self.spec.hist[req.rid] = prompt_token_ids(
                req.seed, req.prompt_len, self.config.hidden_size,
                period=req.prompt_period, prefix_len=req.prefix_len,
                prefix_seed=req.prefix_seed) + [first_id]
        self.slots[slot] = st
        self.active_np[slot] = True
        self.active_dirty = True
        return t_first

    def idle(self) -> None:
        """Phase 3 with nothing resident and nothing admittable: settle
        any in-flight tail, then sleep toward the next arrival."""
        self.drain()
        wait = self.pending[0].arrival_s - self._now()
        if wait > 0:
            time.sleep(min(wait, 0.05))

    def sample(self) -> None:
        """Phase 4: the time-series sample and the gauges."""
        series, ledger, reg = self.series, self.ledger, self.registry
        series["t_s"].append(round(self._now(), 6))
        series["queue_depth"].append(len(self.queue))
        series["active_slots"].append(len(self.slots))
        series["blocks_in_use"].append(ledger.blocks_in_use)
        series["blocks_reserved"].append(ledger.blocks_reserved)
        reg.set_gauge("serve_queue_depth", len(self.queue),
                      help="bounded admission queue depth")
        reg.set_gauge("serve_active_slots", len(self.slots),
                      help="decode slots in use")
        reg.set_gauge("serve_cache_blocks_in_use", ledger.blocks_in_use,
                      help="cache blocks holding tokens")
        if self.cfg.prefix_caching:
            series["shared_blocks"].append(ledger.shared_blocks)
            reg.set_gauge(
                "serve_cache_shared_blocks", ledger.shared_blocks,
                help="trie-indexed blocks counted once fleet-wide")
            reg.set_gauge(
                "serve_cache_prefix_refs", ledger.trie.total_refs(),
                help="slot references across all shared blocks")

    def preempt(self) -> list[int]:
        """Graceful drain after a SIGTERM (the in-flight window already
        settled): still-resident requests are preempted — journaled,
        freed, and replayed by ``cli serve --resume`` (serve/bench.py
        writes the ledger/queue/trace-cursor snapshot).  Returns the rids
        that remain."""
        remaining: list[int] = []
        for s in sorted(list(self.slots)):
            st = self.release(s)
            self.outcomes[st.req.rid] = "preempted"
            self._requests["preempted"] += 1
            remaining.append(st.req.rid)
            self._event("request-preempted", st.req.rid,
                        tokens_done=st.tokens_done,
                        output_len=st.req.output_len)
        remaining += [r.rid for r in self.queue]
        remaining += [r.rid for r in self.pending]
        if self.engine.journal is not None:
            self.engine.journal.event(
                "preempted", signal=self.guard.signal_received,
                remaining=len(remaining))
        if self.engine.verbose:
            print(f"[serve] SIGTERM received — drained the in-flight "
                  f"window, {len(remaining)} request(s) remain "
                  "for --resume")
        return remaining

    # -- the clock, the launch sequence, the events ---------------------------

    def _now(self) -> float:
        """Seconds since the run's clock origin (monotonic)."""
        return time.perf_counter() - self.t0

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
        stats = self.stats
        number = stats.launches
        stats.launches = number + 1
        with _launch_span(span, number, fields, program):
            if via is None:
                return program(*args)
            return via(lambda: program(*args))

    def _book_gmm(self, tokens: int, steps: int = 1) -> None:
        """Book the grouped expert products of a program dispatched over
        ``tokens`` tokens ``steps`` times, by contraction tiles."""
        whole, split = self.family.gmm_counts(self.config, tokens)
        self.stats.gmm_whole_k += whole * steps
        self.stats.gmm_split_k += split * steps
        self.engine._gmm_products["whole"] += whole * steps
        self.engine._gmm_products["split"] += split * steps

    def _probe_slot(self, req: Request, slot: int, recycled: bool,
                    first_logits: jax.Array, cache: Any) -> None:
        """Start the record of a probed request just admitted into
        ``slot``, and name the slot to the decode programs (in the
        place of a probed request that is done, else the first)."""
        eng = self.engine
        done = [i for i, s in enumerate(eng._probe_slots)
                if not any(r["slot"] == s and not r["done"]
                           for r in eng.probed.values())]
        eng._probe_slots[done[0] if done else 0] = slot
        eng._probe_dev = jnp.asarray(eng._probe_slots.copy())
        eng.probed[req.rid] = {
            "slot": slot, "recycled": recycled, "done": False,
            "prompt_ids": prompt_ids_from_seed(
                req.seed, req.prompt_len, self.config.vocab_size)[0],
            "first_logits": first_logits, "units": [],
            # the slot's recurrent state as the prompt's last chunk
            # left it: a copy of one slot, dispatched and not waited for
            "prompt_state": self._launch(self.family.slot_state, cache,
                                         np.int32(slot)),
            "end_state": None}

    def _event(self, event: str, rid: int, **extra: Any) -> None:
        # the request's identifier in the span file: every lifecycle
        # event as an instant carrying the ``rid`` the admission spans
        # carry (a global load when tracing is off)
        spans.instant(event, cat="request", rid=rid)
        journal = self.engine.journal
        if journal is not None:
            journal.event(event, config=f"request-{rid}", **extra)
        ctl = self.control
        if ctl is not None and getattr(ctl, "on_event", None) is not None:
            # live lifecycle feed to the fleet supervisor (terminal
            # accounting, hedge winner detection); a sink failure must
            # never take the replica down — the journal line above is
            # already durable
            try:
                ctl.on_event(rid, event, dict(extra))
            except Exception:  # noqa: BLE001 — contained by contract
                pass

    def _retry(self, phase: str, attempt: int, exc: BaseException,
               **where: Any) -> None:
        """Count, journal and back off before re-issuing ``phase``."""
        self.stats.retries += 1
        self.engine._retry_counter[phase] += 1
        journal = self.engine.journal
        if journal is not None:
            journal.event("dispatch-retry", phase=phase, **where,
                          attempt=attempt, error=str(exc))
        time.sleep(self.cfg.retry_backoff_s * (2 ** (attempt - 1)))

    # -- slots, snapshots and failure -----------------------------------------

    def refresh_active(self) -> None:
        # a HOST copy: ``active_np`` is edited in place after a unit is
        # dispatched, and on the CPU backend an upload may alias the
        # numpy buffer (``jnp.array`` copies it on the DEVICE, in a
        # program that runs when it runs): under load a unit not yet run
        # saw a completing slot as inactive
        if self.active_dirty:
            self.active_dev = jax.device_put(
                jnp.asarray(self.active_np.copy()),
                self.engine._active_sharding)
            self.active_dirty = False

    def release(self, slot: int) -> _SlotState:
        """Host scan-exit: free a completed slot's blocks + slot so the
        next admission can reuse them (device order is safe — the scan
        already masked the slot inactive)."""
        st = self.slots.pop(slot)
        for ledger in self.ledgers:
            ledger.free(slot)
        self.active_np[slot] = False
        self.active_dirty = True
        self.free_slots.append(slot)
        self.free_slots.sort()
        if st.req.rid in self.engine.probed:
            self.engine.probed[st.req.rid]["done"] = True
        return st

    def finish(self, st: _SlotState, done_at: float) -> None:
        """Completion stats + journal at the unit's SYNC point (the
        honest timestamp — the device work is provably done)."""
        lat = done_at - st.req.arrival_s
        self.stats.e2e_latency_s.append(lat)
        self.stats.completed_output_tokens += st.req.output_len
        self._requests["completed"] += 1
        self.outcomes[st.req.rid] = "completed"
        extra: dict[str, Any] = {}
        if st.req.deadline_s is not None and lat > st.req.deadline_s:
            # served, but past its SLO — a first-class count, not a
            # rejection (the tokens were delivered)
            self.stats.completed_past_deadline += 1
            self.engine._deadline_counter["completed-late"] += 1
            extra["past_deadline"] = True
        if self.engine.capture_tokens:
            # tokens ride the completion event so a fleet supervisor
            # keeps them even when this replica dies right after (its
            # report — the usual carrier — dies with it)
            extra["tokens"] = [int(t) for t in
                               self.tokens_by_rid.get(st.req.rid, [])]
        self._event("request-completed", st.req.rid,
                    output_tokens=st.req.output_len,
                    latency_s=round(lat, 6), **extra)

    def take_snapshot(self) -> dict[str, Any]:
        """Pre-dispatch rollback point: the host ledger/slot/admission
        bookkeeping (tiny, host-only copies).  The device carry needs no
        snapshot because every fault site fires BEFORE the jit consumes
        it — a restored host state always matches the on-device state
        (docs/resilience.md)."""
        return {
            "ledgers": [ledger.snapshot() for ledger in self.ledgers],
            "slots": {s: (st, st.tokens_done)
                      for s, st in self.slots.items()},
            "free_slots": list(self.free_slots),
            "active": self.active_np.copy(),
            "generated": self.stats.generated_tokens,
        }

    def restore_snapshot(self, snap: dict[str, Any]) -> None:
        for ledger, kept in zip(self.ledgers, snap["ledgers"]):
            ledger.restore(kept)
        self.slots.clear()
        for s, (st, td) in snap["slots"].items():
            st.tokens_done = td
            self.slots[s] = st
        self.free_slots[:] = snap["free_slots"]
        self.active_np[:] = snap["active"]
        self.active_dirty = True
        self.stats.generated_tokens = snap["generated"]

    def fail_requests(self, states: list[_SlotState], exc: BaseException,
                      reason: str) -> None:
        """Fail requests CLOSED: journaled ``request-failed`` with the
        full exception chain, outcome recorded, counters bumped — never a
        silent skip, and never the whole run."""
        rec = exception_chain(exc)
        rids = []
        for st in states:
            rids.append(st.req.rid)
            self.outcomes[st.req.rid] = f"failed[{reason}]"
            self.stats.failed_requests += 1
            self._requests["failed"] += 1
            self._event("request-failed", st.req.rid, reason=reason,
                        error=rec["error"], tokens_done=st.tokens_done)
        self.failed_detail.append({"reason": reason, "rids": rids, **rec})

    def fail_resident(self, exc: BaseException, reason: str) -> None:
        """Fail every currently-resident request (the affected set of a
        permanently-failed or hung decode unit — decode covers the whole
        resident batch), freeing their slots + blocks."""
        self.fail_requests([self.release(s) for s in sorted(list(self.slots))],
                           exc, reason)

    def _reset_carry(self) -> None:
        """A fresh carry (and draft plane) in place of one a failed or
        abandoned dispatch may have consumed, and the next generation."""
        self.carry = self.engine._fresh_carry()
        if self.spec is not None:
            self.spec.draft = self.engine._fresh_draft_cache()
        self.carry_resets += 1

    def _cancel(self, rid: int, reason: str, tokens_done: int) -> None:
        self.outcomes[rid] = f"canceled[{reason}]"
        self._requests["canceled"] += 1
        self._event("request-canceled", rid, reason=reason,
                    tokens_done=tokens_done)

    def cancel_request(self, rid: int, reason: str) -> None:
        """Supervisor-requested cancel (serve/fleet.py: the losing hedge
        duplicate).  Resident: the in-flight window settles first so the
        release happens at a sync point, then the slot's blocks are
        freed.  Queued / not-yet-fed: the request is simply dropped.  An
        unknown rid is a benign race — the request completed between the
        cancel decision and this loop boundary — and a no-op by design
        (the tokens are identical on both replicas, so a double
        completion is harmless)."""
        slot = next((s for s, st in self.slots.items()
                     if st.req.rid == rid), None)
        if slot is not None:
            self.drain()
            st_now = self.slots.get(slot)
            if st_now is None or st_now.req.rid != rid:
                return  # completed (or failed) at the drain sync
            st = self.release(slot)
            if self.spec is not None:
                self.spec.hist.pop(rid, None)
            self._cancel(rid, reason, st.tokens_done)
            return
        for r in list(self.queue):
            if r.rid == rid:
                self.queue.remove(r)
                self.lookahead.drop(rid)
                self._cancel(rid, reason, 0)
                return
        if self.feed is not None and self.feed.discard(rid):
            self._cancel(rid, reason, 0)

    def fail_admission(self, req: Request, slot: int, exc: BaseException,
                       dispatched: bool = True) -> None:
        """A permanently-failed prefill fails ONLY the admitting request:
        reservation undone, journaled with the chain.  A real
        (non-injected) failure of a ``dispatched`` prefill also consumed
        the donated cache, so the resident batch fails closed too and the
        run continues on a fresh carry; an input that could not be
        prepared touched no cache."""
        for ledger in self.ledgers:
            ledger.free(slot)
        self.free_slots.append(slot)
        self.free_slots.sort()
        self.fail_requests([_SlotState(req=req, tokens_done=0)], exc,
                           "dispatch-failed")
        if dispatched and not isinstance(exc, InjectedFault):
            # units dispatched before the failed program are sound:
            # settle them, then fail what is resident
            try:
                self.drain()
            except Exception:  # noqa: BLE001
                self.inflight.clear()
            self.fail_resident(exc, "dispatch-failed")
            self._reset_carry()

    # -- the in-flight window -------------------------------------------------

    def unit_deadline(self, k: int) -> Optional[float]:
        """Watchdog deadline for a k-step unit: EMA-scaled with a floor
        while the EMA is cold; None = watchdog off."""
        f = self.cfg.dispatch_deadline_factor
        if f is None:
            return None
        return max(self.cfg.dispatch_deadline_min_s, f * k * self.step_ema)

    def abandon_window(self, first_unit: dict[str, Any],
                       exc: BaseException) -> None:
        """A unit's sync blew its deadline: every un-synced unit chains
        off the same donated carry, so the whole window is abandoned —
        its requests (including completions that were never confirmed at
        a sync point) fail closed, and the run continues on a fresh
        carry."""
        self.stats.hung_dispatches += 1
        self.registry.inc("serve_hung_dispatches")
        hung = [first_unit] + list(self.inflight)
        self.inflight.clear()
        self.last_sync = time.perf_counter()
        unconfirmed = [st for u in hung for st in u["completions"]]
        self.fail_requests(unconfirmed, exc, "hung-dispatch")
        self.fail_resident(exc, "hung-dispatch")
        self._reset_carry()

    def sync_one(self) -> None:
        unit = self.inflight.popleft()
        stats, eng = self.stats, self.engine
        try:
            # ``k`` and ``launch`` are the unit's WAITED FOR — under a
            # deeper window an older one than the unit just dispatched
            with _launch_span("serve-decode-sync", unit["launch"],
                              lambda: {"k": unit["k_exec"]}):
                _with_deadline(
                    lambda: jax.block_until_ready(unit["ys"]),
                    self.unit_deadline(unit["k_exec"]),
                    f"decode[k={unit['k_exec']}]", "serve-sync")
        except DeadlineExceeded as e:
            self.abandon_window(unit, e)
            return
        t_ready = time.perf_counter()
        dt = t_ready - max(unit["t0"], self.last_sync)
        self.last_sync = t_ready
        stats.decode_step_s.append(dt)
        if unit["k_exec"] == 1 and stats.launches > unit["next_launch"]:
            # the device found a program queued behind this step
            stats.decode_units_overlapped += 1
            self.registry.inc("serve_decode_units_overlapped")
        per_step = dt / unit["k_exec"]
        self.step_ema = (per_step if self.step_ema == 0.0
                         else 0.5 * self.step_ema + 0.5 * per_step)
        for _row, _slot, _rid, steps in unit["rows"]:
            for _ in range(steps):
                stats.per_token_s.append(dt / unit["k_exec"])
        done_at = self._now()
        if eng._probes:
            # the family's small integers of this unit (None where it has
            # none): ready with the tokens, no sync of their own
            self.family.unit_counted(self.registry, self.config,
                                     stats.family, unit.get("counts"))
        if unit.get("tokens"):
            # token-feedback unit: ys are the committed token ids
            # themselves ([B] per-step, [k, B] fused) — the n-gram
            # history extends from them even when capture is off
            ngram = self.cfg.speculation == "ngram"
            if ngram or eng.capture_tokens:
                toks_np = np.asarray(unit["ys"])
                if toks_np.ndim == 1:   # per-step unit: [B]
                    toks_np = toks_np[None]
                for row, _slot, rid, steps in unit["rows"]:
                    ids = [int(t) for t in toks_np[:steps, row]]
                    if ngram and rid in self.spec.hist:
                        self.spec.hist[rid].extend(ids)
                    if eng.capture_tokens:
                        self.tokens_by_rid.setdefault(rid, []).extend(ids)
        elif eng.capture_tokens:
            ys_np = np.asarray(unit["ys"], np.float32)
            if ys_np.ndim == 3:        # per-step unit: [B, 1, H]
                ys_np = ys_np[None]
            for row, _slot, rid, steps in unit["rows"]:
                for i in range(steps):
                    self.tokens_by_rid.setdefault(rid, []).append(
                        int(np.argmax(ys_np[i, row, 0])))
        # finish AFTER the unit's token capture: the completion event
        # carries the request's full committed token list
        for st in unit["completions"]:
            self.finish(st, done_at)

    def drain(self) -> None:
        while self.inflight:
            self.sync_one()

    def settle_aliased(self) -> None:
        """Before a launch that donates the WHOLE carry (a decode or
        verify unit, an inject).  A single step's ``ys`` is the same
        logical value as the carry's ``x`` (``decode_step`` returns
        ``((cache, y), y)``); where donation is honoured the two outputs
        may alias one buffer, and the launch would delete the held
        ``ys``.  So a single step still in flight (it can only be the
        window's newest unit) is settled first, with whatever was
        dispatched before it.  A fused scan's stacked ``ys`` is a buffer
        of its own and stays."""
        if self.inflight and self.inflight[-1]["k_exec"] == 1:
            self.drain()

    # -- decode units ---------------------------------------------------------

    def dispatch_decode(self, max_k: Optional[int] = None) -> None:
        """One decode unit over the resident batch: a single step, or —
        when no scheduling event needs an earlier boundary — a fused
        K-step scan (largest power-of-two bucket <= the event horizon).
        ``max_k`` caps the horizon (the chunked-prefill interleave passes
        1: the mid-admission request is itself a waiter, and a full fused
        scan between chunks would re-create the head-of-line blocking the
        interleave exists to remove).

        Every decode program donates the whole carry, so a single step
        still in flight is settled first (``settle_aliased``); its
        watchdog may fail the resident batch closed, and then there is
        nothing to dispatch.  The unit dispatched here is waited for by
        ``decode_unit`` (a fused scan, at the window's bound) or by a
        later settle (a single step); it runs under the recovery ladder
        of :meth:`_committed`."""
        cfg, control, slots = self.cfg, self.control, self.slots
        self.settle_aliased()
        if not slots:
            return
        self.refresh_active()
        if (cfg.spec_drafting and max_k is None
                and (control is None or control.spec_enabled)):
            # draft-and-verify first; a cold n-gram drafter falls through
            # to a plain token decode unit below (the chunked-prefill
            # interleave's max_k=1 also bypasses drafting — a verify's
            # γ+1 commit window would re-create the head-of-line blocking
            # the interleave removes)
            if self.dispatch_spec():
                return
            self.refresh_active()
        with spans.span("serve-decode-plan"):
            rem = {s: slots[s].req.output_len - slots[s].tokens_done
                   for s in sorted(slots)}
            # next event: the earliest completion while anything is (or
            # may soon be) waiting for a slot; a quiescent batch fuses
            # through its full drain
            horizon = (min(rem.values()) if (self.queue or self.pending)
                       else max(rem.values()))
            horizon = min(cfg.decode_horizon, horizon)
            if control is not None and control.horizon_cap is not None:
                # degradation ladder (serve/fleet.py): a shrunk horizon
                # trades fused-scan throughput for scheduling latency
                # under overload — never silently (each transition is
                # journaled ``degrade-transition``)
                horizon = min(horizon, max(1, control.horizon_cap))
            if self.pending:
                # a known arrival is a scheduling event too: bound the
                # scan so admission happens near the arrival instead of
                # up to decode_horizon steps late (steps estimated from
                # the observed per-step interval; before the first sample
                # exists, stay per-step — one unit bootstraps the EMA)
                if self.step_ema > 0.0:
                    gap = self.pending[0].arrival_s - self._now()
                    steps_to_arrival = (max(1, int(gap / self.step_ema))
                                        if gap > 0 else 1)
                    horizon = min(horizon, steps_to_arrival)
                else:
                    horizon = 1
            if max_k is not None:
                horizon = min(horizon, max_k)
            k = 1
            for cand in self.engine._fused_ks:
                if cand <= horizon:
                    k = cand
            steps = {s: min(k, r) for s, r in rem.items()}
            snap = self.take_snapshot()
        self._committed(self.decode_unit, snap, k, steps)

    def _committed(self, unit, snap: dict[str, Any], *args: Any) -> None:
        """``unit(*args, snap)`` under the decode path's recovery ladder
        (docs/resilience.md, serving faults): a transiently-failed
        dispatch rolls the host ledger/slot state back to the
        pre-dispatch snapshot and re-issues with exponential backoff;
        exhaustion — or a real dispatch error — fails only the resident
        requests (full exception chains, journaled ``request-failed``),
        never the run; a dispatch exceeding the EMA-scaled watchdog
        deadline is abandoned on its daemon thread and the run continues
        on a fresh carry."""
        attempt = 0
        while True:
            try:
                unit(*args, snap)
                return
            except (TransientFault, CorruptStats) as e:
                # fired BEFORE the jit consumed the carry (the injection
                # contract): restore the host snapshot and re-issue the
                # same unit
                self.restore_snapshot(snap)
                if attempt >= self.cfg.max_dispatch_retries:
                    self.fail_resident(e, "dispatch-failed")
                    return
                attempt += 1
                self._retry("decode", attempt, e)
            except DeadlineExceeded as e:
                # hung dispatch: the zombie daemon thread still holds the
                # donated carry — settle the valid in-flight tail, fail
                # the resident batch closed, continue on a fresh carry
                self.restore_snapshot(snap)
                self.stats.hung_dispatches += 1
                self.registry.inc("serve_hung_dispatches")
                self.drain()
                self.fail_resident(e, "hung-dispatch")
                self._reset_carry()
                return
            except Exception as e:  # noqa: BLE001 — fail closed
                # a real (non-injected) dispatch failure: the donated
                # carry must be presumed consumed — fail the resident
                # batch closed with the exception chain and continue on
                # a fresh carry
                self.restore_snapshot(snap)
                try:
                    self.drain()
                except Exception:  # noqa: BLE001
                    self.inflight.clear()
                self.fail_resident(e, "dispatch-failed")
                self._reset_carry()
                return

    def _guarded(self, deadline: Optional[float], label: str):
        """The dispatch watchdog's guard for one unit's launches (the
        injected hang sits on the watchdog's daemon thread, never on the
        scheduler's)."""
        def guarded(call):
            def run():
                if inject.fire("serve-decode-hang"):
                    time.sleep(inject.param("hang_seconds"))
                return call()
            return _with_deadline(run, deadline, label, "serve-dispatch")
        return guarded

    def _book_failed(self, exc: BaseException, attempt: int,
                     snap: dict[str, Any], unit: str) -> int:
        """A torn bookkeeping pass of a ``unit`` whose device result is
        in hand: roll back, and replay (pure host recomputation, never a
        re-dispatch) until the retries run out.  Returns the attempt."""
        self.restore_snapshot(snap)
        if attempt >= self.cfg.max_dispatch_retries:
            raise RuntimeError(
                "ledger/slot bookkeeping kept failing after the "
                f"{unit} unit completed on device") from exc
        self._retry("bookkeeping", attempt + 1, exc)
        return attempt + 1

    def decode_unit(self, k: int, steps: dict[int, int],
                    snap: dict[str, Any]) -> None:
        """One decode unit, committed: the device dispatch (under the
        watchdog when armed), torn-protected host bookkeeping, and the
        in-flight window push.  A fused scan then syncs the window down
        to its bound; a single step is left in flight and settled by
        whoever next donates the whole carry (``settle_aliased``:
        ``dispatch_decode``, an inject) or drains (``prefill_once``
        behind each chunk, a fault path, the loop's exit).  Transient
        bookkeeping faults roll themselves back and replay (pure host
        recomputation — the device result is already in hand, so NEVER
        a re-dispatch); everything else raises out to
        ``dispatch_decode``'s recovery ladder with nothing committed."""
        cfg, stats, eng, slots = self.cfg, self.stats, self.engine, self.slots
        rows: list[tuple[int, int, int, int]] = []
        guarded = self._guarded(self.unit_deadline(k), f"decode[k={k}]")
        t0 = time.perf_counter()
        # ONE span per dispatched unit, covering the dispatch and, after
        # a fused scan, the boundary sync below, whose device time
        # belongs to an older unit; a single step's wait comes later and
        # outside it.  A unit's own device time is its paired
        # execution's: ``serve-decode-dispatch`` (the jit call of THIS
        # unit) carries the unit's ``launch`` number, and the n-th launch
        # is the n-th ``jit_serve_*`` event of the profile's "XLA
        # Modules" line (``docs/observability.md`` §1; ``decode_step_s``
        # / ``per_token_s`` are host intervals that also hold whatever
        # was queued before the unit).  ``serve-decode-sync`` is the
        # wait, with the ``k`` and the ``launch`` of the unit waited
        # for; what is left is the host bookkeeping at scan exit
        with spans.span("serve-decode", active=len(slots), steps=k,
                        unit=stats.decode_units):
            if inject.fire("serve-decode-fail"):
                # fires BEFORE the jit is invoked: the donated carry was
                # never consumed, so a retry re-dispatches from unchanged
                # device state
                raise TransientFault(
                    "injected serve-decode-fail at the decode dispatch "
                    "boundary")

            def dispatch(program, *args):
                return self._launch(program, *args,
                                    span="serve-decode-dispatch",
                                    fields=lambda: {"k": k}, via=guarded)

            # the token-fed programs take the token table after params
            table = (eng._table,) if eng._token_mode else ()
            if k == 1:
                self.carry, ys = dispatch(
                    eng._decode_token if eng._token_mode else eng._decode,
                    self.carry, eng.params, *table, self.active_dev)
                stats.single_steps += 1
                for s in sorted(steps):
                    rows.append((s, s, slots[s].req.rid, 1))
            else:
                rem_np = np.zeros((cfg.max_batch,), np.int32)
                for s, m in steps.items():
                    rem_np[s] = m
                rem_dev = jax.device_put(jnp.asarray(rem_np),
                                         eng._active_sharding)
                fused = (eng._decode_fused_token if eng._token_mode
                         else eng._decode_fused)
                self.carry, ys = dispatch(fused[k], self.carry, eng.params,
                                          *table, self.active_dev, rem_dev)
                stats.fused_scans += 1
                stats.fused_steps += k
                self.registry.inc("serve_fused_scan_steps", k)
                for s in sorted(steps):
                    rows.append((s, s, slots[s].req.rid, steps[s]))
            self._book_gmm(cfg.max_batch, k)
            # this unit's launch: its wait names it (``sync_one``)
            launched = stats.launches - 1
            # what the unit's steps attend over: step i of a slot reads
            # the tokens (and the tiles) under its length + i
            trip = np.arange(k)[:, None]
            start = np.array([self.ledger.tokens(s) for s in steps])
            stepping = trip < np.array(list(steps.values()))[None, :]
            stats.unit_slot_steps.append(int(stepping.sum()))
            if eng._probes:
                self.family.unit_dispatched(
                    self.registry, self.config, stats.family,
                    stats.unit_slot_steps[-1], k, cfg.max_batch,
                    lengths=start[None, :] + trip, stepping=stepping,
                    block_size=cfg.block_size)
            stats.unit_live_tokens.append(
                int(((start[None, :] + trip + 1) * stepping).sum()))
            if eng._kv_tile:
                max_tiles = cfg.max_seq // eng._kv_tile
                live = int(live_tile_counts(start[None, :] + trip, stepping,
                                            eng._kv_tile, max_tiles).sum())
                # a looped stack's step fetches them once a pass
                live *= self.config.total_ut_steps
                held = (k * max_tiles * cfg.max_batch
                        * self.config.total_ut_steps)
                stats.kv_tiles_live += live
                stats.kv_tiles_held += held
                self.registry.inc(f"serve_{eng._tiles_of}_tiles_live", live)
                self.registry.inc(f"serve_{eng._tiles_of}_tiles_held", held)
            completions = self._book_decode(steps, snap)
            stats.decode_steps += k
            stats.decode_units += 1
            self.registry.inc("serve_decode_steps", k)
            counts = None
            if eng._probes:
                # ys = (tokens, seen of the probed slots, counts): a
                # probed request keeps the first two, on the device
                toks, seen, counts = ys
                for row, slot_, rid, m in rows:
                    if rid in eng.probed:
                        col = int(np.flatnonzero(
                            eng._probe_slots == slot_)[0])
                        eng.probed[rid]["units"].append(
                            (toks, seen, row, col, m))
                ys = toks
                for s in completions:
                    # a probed request's state as its last step left it,
                    # copied before the slot is given away
                    rec = eng.probed.get(slots[s].req.rid)
                    if rec is not None:
                        rec["end_state"] = self._launch(
                            self.family.slot_state, self.carry[0],
                            np.int32(s))
            done_states = [self.release(s) for s in completions]
            if completions:
                self.refresh_active()
            self.inflight.append({
                "t0": t0, "ys": ys, "k_exec": k, "launch": launched,
                "next_launch": stats.launches, "rows": rows,
                "counts": counts, "tokens": self.ys_are_tokens,
                "completions": done_states})
            # who donates what decides where a unit is waited for.  The
            # decode programs, the verify units and the injects donate
            # the whole carry; a prompt chunk and the prefix attach
            # donate the cache alone and never touch ``carry[1]``.  So a
            # single step, whose ``ys`` may alias that ``x``, is settled
            # before the next program of the first kind
            # (``settle_aliased``) and not here: a chunk sliced and
            # called meanwhile is queued behind it and starts the moment
            # the step ends.  A fused scan rides the window
            if k > 1:
                while len(self.inflight) >= cfg.inflight_window:
                    self.sync_one()

    def _book_decode(self, steps: dict[int, int],
                     snap: dict[str, Any]) -> list[int]:
        """Host bookkeeping at scan exit: the ledger's known lengths make
        every step's outcome deterministic at dispatch time.  A torn
        half-applied update (serve-cache-torn) restores the pre-dispatch
        snapshot and REPLAYS the accounting.  Returns the slots whose
        requests completed."""
        attempt = 0
        while True:
            completions: list[int] = []
            try:
                for s, m in sorted(steps.items()):
                    st = self.slots[s]
                    st.tokens_done += m
                    if inject.fire("serve-cache-torn"):
                        raise TransientFault(
                            "injected serve-cache-torn: ledger/slot "
                            "bookkeeping torn mid-unit")
                    for ledger in self.ledgers:
                        ledger.append(s, m)
                    self.stats.generated_tokens += m
                    if st.tokens_done >= st.req.output_len:
                        completions.append(s)
                return completions
            except (TransientFault, CorruptStats) as e:
                attempt = self._book_failed(e, attempt, snap, "decode")

    # -- speculative decoding (docs/serving.md, "Speculative decoding") -------

    def dispatch_spec(self) -> bool:
        """One draft-and-verify unit over the resident batch, with the
        decode path's full recovery ladder.  Returns False when the
        drafter is cold (no n-gram hit for ANY resident slot) — the
        caller falls back to the plain token decode unit, so speculation
        COMPOSES with decode_horizon/inflight_window instead of replacing
        them."""
        cfg, stats, slots = self.cfg, self.stats, self.slots
        # history and host bookkeeping must be current before drafting
        # (fallback token units may still be in flight)
        self.drain()
        if not slots:
            return True     # the drain's completions emptied the batch
        ladder = self.engine._spec_gammas
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
                prop = _ngram_propose(
                    self.spec.hist.get(slots[s].req.rid, []), g)
                if prop is not None:
                    drafts_np[s] = prop
                    any_hit = True
            stats.spec_draft_s += time.perf_counter() - t_d
            if not any_hit:
                stats.spec_fallback_units += 1
                if not self.engine._sampled:
                    return False
                # sampled cold fallback: the plain token decode unit is a
                # greedy program, so a cold drafter degenerates to the
                # γ=0 verify — one host-sampled token per trip, never a
                # silent greedy token
                g = 0
                drafts_np = np.zeros((cfg.max_batch, 0), np.int32)
        self._committed(self.spec_unit, self.take_snapshot(), g, drafts_np)
        return True

    def spec_unit(self, g: int, drafts_np: np.ndarray,
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
        Bookkeeping is optimistic-then-rollback: every slot is first
        accounted the full γ+1 window (the fused-scan discipline —
        outcomes known at dispatch time), and the synced commits roll
        any shortfall back to the pre-dispatch snapshot (the fault path's
        snapshot/restore as the rejection-rollback primitive) and replay
        the true counts.  The rejected suffix needs NO device cleanup:
        appended-but-rejected cache positions sit past the committed
        lengths, attention is length-masked, and the next unit's writes
        land at the committed lengths — dead by construction (asserted
        by the token-identity tests, never copied or zeroed)."""
        cfg, eng, slots = self.cfg, self.engine, self.slots
        self.refresh_active()
        rows = [(s, slots[s].req.rid) for s in sorted(slots)]
        rem_map = {s: slots[s].req.output_len - slots[s].tokens_done
                   for s, _ in rows}
        deadline = self.unit_deadline(g + 1)
        guarded = self._guarded(deadline, f"verify[gamma={g}]")
        t0 = time.perf_counter()
        with spans.span("serve-verify", active=len(slots), gamma=g,
                        drafter=cfg.speculation):
            if inject.fire("serve-decode-fail"):
                # fires BEFORE any jit consumes the carry — a retry
                # re-dispatches from unchanged device state (same
                # contract as the decode unit's site)
                raise TransientFault(
                    "injected serve-decode-fail at the verify dispatch "
                    "boundary")

            def dispatch(program, *args):
                return self._launch(program, *args, via=guarded)

            rem_np = np.zeros((cfg.max_batch,), np.int32)
            for s, _ in rows:
                rem_np[s] = rem_map[s]
            rem_dev = jax.device_put(jnp.asarray(rem_np),
                                     eng._active_sharding)
            ids = self._draft_ids(g, drafts_np, rows, dispatch)
            committed_ids: Optional[dict[int, list[int]]] = None
            tok = None
            if eng._sampled:
                commits_np, committed_ids = self._verify_sampled(
                    g, ids, drafts_np, rows, rem_map, deadline, dispatch)
            else:
                self.carry, tok, commits = dispatch(
                    eng._verify[g], self.carry, eng.params, eng._table,
                    ids, self.active_dev, rem_dev)
                with _launch_span("serve-decode-sync",
                                  self.stats.launches - 1,
                                  lambda: {"k": g + 1}):
                    commits_np = _with_deadline(
                        lambda: np.asarray(commits), deadline,
                        f"verify[gamma={g}]", "serve-sync")
            t_ready = time.perf_counter()
            dt = t_ready - max(t0, self.last_sync)
            self.last_sync = t_ready
            completions = self._book_verify(g, rows, rem_map, commits_np,
                                            snap)
            self._verified(g, rows, commits_np, tok, committed_ids, dt)
            done_states = [self.release(s) for s in completions]
            if completions:
                self.refresh_active()
            done_at = self._now()
            for st in done_states:
                self.finish(st, done_at)

    def _draft_ids(self, g: int, drafts_np: np.ndarray,
                   rows: list[tuple[int, int]], dispatch) -> Any:
        """The γ draft ids a verify checks, on the device: the n-gram
        drafter's host match uploaded, or the draft model's scan."""
        cfg, eng, slots = self.cfg, self.engine, self.slots
        if cfg.speculation != "draft-model":
            return jax.device_put(jnp.asarray(drafts_np),
                                  eng._ids_sharding)
        # the draft plane's rejection rollback IS this lengths vector:
        # the host's committed lengths override the plane's own
        # (advanced-by-γ) leaf, and entries past them are dead by the
        # same length-mask construction as the target's
        lengths_np = np.zeros((cfg.max_batch,), np.int32)
        for s, _ in rows:
            lengths_np[s] = (slots[s].req.prompt_len
                             + slots[s].tokens_done - 1)
        dlen = jax.device_put(jnp.asarray(lengths_np),
                              eng._active_sharding)
        t_d = time.perf_counter()
        self.spec.draft, ids = dispatch(
            eng._draft_scan[g], self.spec.draft, eng._draft_params,
            eng._table, self.carry[1], dlen, self.active_dev)
        # host dispatch wall only — the proposals stay on device and flow
        # straight into the verify
        self.stats.spec_draft_s += time.perf_counter() - t_d
        return ids

    def _verify_sampled(self, g: int, ids: Any, drafts_np: np.ndarray,
                        rows: list[tuple[int, int]], rem_map: dict[int, int],
                        deadline: Optional[float], dispatch):
        """Sampled verify: the device computes the γ+1 verify logits
        WITHOUT committing (lengths/x come back unchanged —
        retry-idempotent); acceptance is the host's residual-sampling
        pass (the literal ``speculative_sample`` helper, q = the
        deterministic drafter's one-hot), and the tiny spec_commit
        program applies the decided commits.  Returns the commits and
        the committed ids by slot."""
        cfg, eng, rng = self.cfg, self.engine, self.spec.rng
        self.carry, y = dispatch(eng._verify_probs[g], self.carry,
                                 eng.params, eng._table, ids,
                                 self.active_dev)
        with _launch_span("serve-decode-sync", self.stats.launches - 1,
                          lambda: {"k": g + 1}):
            y_np = _with_deadline(lambda: np.asarray(y), deadline,
                                  f"verify[gamma={g}]", "serve-sync")
        ids_np = (np.asarray(ids) if cfg.speculation == "draft-model"
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
                t, ok = speculative_sample(p_rows[j], q, d_id, rng)
                toks.append(t)
                if not ok:
                    break
            else:
                # every draft accepted: the window's +1 bonus is a free
                # draw from the last position's target distribution
                toks.append(int(rng.choice(vocab, p=p_rows[g])))
            m = min(len(toks), rem_map[s])
            commits_np[s] = m
            next_np[s] = toks[m - 1]
            committed_ids[s] = toks[:m]
        next_dev = jax.device_put(jnp.asarray(next_np),
                                  eng._active_sharding)
        com_dev = jax.device_put(jnp.asarray(commits_np),
                                 eng._active_sharding)
        self.carry = dispatch(eng._spec_commit, self.carry, eng._table,
                              next_dev, com_dev, self.active_dev)
        return commits_np, committed_ids

    def _book_verify(self, g: int, rows: list[tuple[int, int]],
                     rem_map: dict[int, int], commits_np: np.ndarray,
                     snap: dict[str, Any]) -> list[int]:
        """Torn-protected bookkeeping of a verify (the decode unit's
        replay discipline), optimistic-then-rollback.  Returns the slots
        whose requests completed."""
        slots, stats = self.slots, self.stats
        attempt = 0
        while True:
            completions: list[int] = []
            try:
                for s, _rid in rows:
                    opt = min(g + 1, rem_map[s])
                    slots[s].tokens_done += opt
                    for ledger in self.ledgers:
                        ledger.append(s, opt)
                    stats.generated_tokens += opt
                if inject.fire("serve-cache-torn"):
                    raise TransientFault(
                        "injected serve-cache-torn: ledger/slot "
                        "bookkeeping torn mid-verify")
                if any(int(commits_np[s]) != min(g + 1, rem_map[s])
                       for s, _ in rows):
                    # rejection rollback: restore the pre-dispatch
                    # snapshot, replay TRUE commits
                    self.restore_snapshot(snap)
                    for s, _rid in rows:
                        m = int(commits_np[s])
                        slots[s].tokens_done += m
                        for ledger in self.ledgers:
                            ledger.append(s, m)
                        stats.generated_tokens += m
                for s, _rid in rows:
                    if slots[s].tokens_done >= slots[s].req.output_len:
                        completions.append(s)
                return completions
            except (TransientFault, CorruptStats) as e:
                attempt = self._book_failed(e, attempt, snap, "verify")

    def _verified(self, g: int, rows: list[tuple[int, int]],
                  commits_np: np.ndarray, tok: Any,
                  committed_ids: Optional[dict[int, list[int]]],
                  dt: float) -> None:
        """A committed verify: per-slot acceptance stats, adaptive γ,
        history and capture (the completions follow at THIS sync)."""
        cfg, stats, eng = self.cfg, self.stats, self.engine
        stats.decode_steps += 1
        stats.decode_units += 1
        stats.spec_verify_units += 1
        self.registry.inc("serve_decode_steps", 1)
        stats.decode_step_s.append(dt)
        self.step_ema = (dt if self.step_ema == 0.0
                         else 0.5 * self.step_ema + 0.5 * dt)
        drafter = cfg.speculation
        ladder = eng._spec_gammas
        unit_acc = 0
        tok_np = (np.asarray(tok)
                  if (committed_ids is None
                      and (drafter == "ngram" or eng.capture_tokens))
                  else None)
        for s, rid in rows:
            m = int(commits_np[s])
            acc = max(m - 1, 0)
            unit_acc += acc
            stats.spec_slot_verifies += 1
            stats.spec_proposed_tokens += g
            stats.spec_accepted_tokens += acc
            stats.spec_commit_tokens += m
            eng._spec_proposed[drafter] += g
            eng._spec_accepted[drafter] += acc
            for _ in range(m):
                stats.per_token_s.append(dt / m)
            self._event("spec-verify", rid, gamma=g, accepted=acc,
                        committed=m)
            st = self.slots[s]
            if cfg.spec_adaptive:
                rate = acc / g if g else 0.0
                st.accept_ema = (rate if st.accept_ema < 0
                                 else 0.5 * st.accept_ema + 0.5 * rate)
                pos = (ladder.index(st.gamma_eff)
                       if st.gamma_eff in ladder else len(ladder) - 1)
                if st.accept_ema < 0.25 and pos > 0:
                    st.gamma_eff = ladder[pos - 1]
                elif st.accept_ema > 0.75 and pos < len(ladder) - 1:
                    st.gamma_eff = ladder[pos + 1]
            if tok_np is not None or committed_ids is not None:
                ids_host = (committed_ids[s] if committed_ids is not None
                            else [int(t) for t in tok_np[s, :m]])
                if drafter == "ngram" and rid in self.spec.hist:
                    self.spec.hist[rid].extend(ids_host)
                if eng.capture_tokens:
                    self.tokens_by_rid.setdefault(rid, []).extend(ids_host)
        unit_rate = unit_acc / (g * len(rows)) if (rows and g) else 0.0
        spec = self.spec
        spec.accept_ema = (unit_rate if spec.accept_ema < 0
                           else 0.5 * spec.accept_ema + 0.5 * unit_rate)
        self.registry.set_gauge(
            "serve_spec_acceptance_ema", spec.accept_ema,
            help="EMA of per-verify-unit draft acceptance rate")

    # -- prefill --------------------------------------------------------------

    def attach_plan(self, req: Request) -> Optional[dict[str, Any]]:
        """Host-side prefix match for one admission: the prompt's
        full-block token-id chain (pure numpy, the same admission-time id
        view the n-gram drafter uses — the trie never touches the
        device), the trie's longest indexed match, and the chunk-floored
        attach point.  The attach is capped at whole CHUNKS (the suffix
        prefill resumes at a static chunk-jit offset) and always leaves
        >= 1 chunk to compute (the final chunk owns ``y_last`` and the
        slot length); blocks the trie matched past that cap are
        recomputed privately — the copy-on-write tail, counted via
        ``note_cow``.  ``resets`` pins the carry generation: an attach
        copies DEVICE blocks, so a plan from before a carry reset
        degrades to a full prefill (the slot then physically holds every
        block it refs, keeping the trie true)."""
        bs = self.cfg.block_size
        chunk = self.cfg.prefill_chunk
        full_blocks = req.prompt_len // bs
        plan = {"chain": [], "attach_blocks": 0, "attach_tokens": 0,
                "donor": None, "cow_blocks": 0,
                "resets": self.carry_resets, "attached_tokens": 0}
        if full_blocks == 0:
            return plan
        ids = prompt_token_ids(
            req.seed, req.prompt_len, self.config.hidden_size,
            prefix_len=req.prefix_len, prefix_seed=req.prefix_seed)
        chain = [tuple(ids[i * bs:(i + 1) * bs]) for i in range(full_blocks)]
        plan["chain"] = chain
        depth, donor = self.ledger.match_prefix(chain)
        cap = ((req.prompt_len - 1) // chunk) * chunk
        attach_tokens = min(depth * bs, cap) // chunk * chunk
        if donor is None or attach_tokens <= 0:
            return plan
        plan.update(attach_blocks=attach_tokens // bs,
                    attach_tokens=attach_tokens, donor=donor,
                    cow_blocks=depth - attach_tokens // bs)
        return plan

    def prefill_once(self, req: Request, slot: int, x_prompt: jax.Array,
                     plan: Optional[dict[str, Any]] = None):
        """The prefill dispatch for one admitted request (chunked or
        monolithic) over its prepared input ``x_prompt`` — returns
        ``(bucket, y_last, dt)``.  Raised through by the retry wrapper
        (``prefill_dispatch``); idempotent on retry: chunk writes are
        deterministic block writes of identical values, and interleaved
        decode units commit independently.  A chunk (and the prefix
        attach) donates the cache alone, so it is called BEHIND whatever
        decode unit is in flight (the loop's own step at the admission's
        head, the interleaved step of the chunk before) and the window is
        drained only then: the device finds the chunk queued when the
        step ends.  The next interleaved step, which donates the whole
        carry, goes out after that drain; after the last chunk the drain
        comes before ``serve-prefill-sync``, so the window is empty when
        the first token is injected.  A drain whose watchdog abandons the
        window resets the carry under a chunk already sent on the old
        cache: the prefill restarts, as after a failed interleaved
        dispatch.  With a prefix-attach ``plan``, the matched chunks'
        prefills are replaced by ONE donor-block copy
        (``build_prefix_attach``) and only the suffix chunks run; a carry
        reset since planning degrades to the full prefill (a retry after
        a reset finds zeroed donor blocks, so copying would serve
        garbage)."""
        cfg, stats, eng = self.cfg, self.stats, self.engine
        if inject.fire("serve-prefill-fail"):
            # fires BEFORE any jit is invoked — see serve-decode-fail
            raise TransientFault(
                "injected serve-prefill-fail at the prefill dispatch "
                "boundary")
        bucket = eng._padded_len(req)
        if cfg.prefill_chunk is None:
            return self._prefill_bucket(req, slot, x_prompt, bucket)
        chunk = cfg.prefill_chunk
        n_chunks = bucket // chunk
        m_chunks = 0
        if plan is not None and plan["attach_blocks"]:
            plan["attached_tokens"] = 0
            if self.carry_resets == plan["resets"]:
                m_chunks = plan["attach_tokens"] // chunk
        with spans.span("serve-prefill", rid=req.rid, bucket=bucket,
                        slot=slot, chunks=n_chunks - m_chunks):
            t0 = time.perf_counter()
            decode_spent = 0.0
            cache = self.carry[0]
            if m_chunks:
                # copy-on-attach: one in-place block copy of the donor's
                # matched blocks stands in for the matched chunks'
                # prefill dispatches (the TTFT win), and its returned fp
                # prefix carry is exactly what those chunks would have
                # produced
                cache, prefix = self._launch(
                    eng._attach_jit(m_chunks), cache,
                    np.int32(plan["donor"]), np.int32(slot),
                    span="serve-prefix-attach",
                    fields=lambda: {
                        "rid": req.rid, "slot": slot,
                        "donor": plan["donor"],
                        "blocks": plan["attach_blocks"]})
                plan["attached_tokens"] = m_chunks * chunk
            else:
                prefix = eng._create_prefix()
            lasts = []
            resets = self.carry_resets
            for ci in range(m_chunks, n_chunks):
                cache, prefix, y_last = self._launch(
                    eng._chunk_jit(ci), cache, prefix, eng.params,
                    x_prompt[:, ci * chunk:(ci + 1) * chunk],
                    np.int32(slot), np.int32(req.prompt_len),
                    span="serve-prefill-chunk",
                    fields=lambda: {"rid": req.rid, "chunk": ci,
                                    "seq": stats.prefill_chunks})
                launched = stats.launches - 1
                self.carry = (cache, self.carry[1])
                lasts.append(y_last)
                stats.prefill_chunks += 1
                self.registry.inc("serve_prefill_chunks")
                on_kernel, dense = self.family.chunk_attn_counts(
                    self.config, ci * chunk, chunk)
                stats.chunk_attn_kernel += on_kernel
                stats.chunk_attn_dense += dense
                self.registry.inc("serve_chunk_attn_kernel", on_kernel)
                self.registry.inc("serve_chunk_attn_dense", dense)
                self._book_gmm(chunk)
                # the chunk is queued: now wait for the step dispatched
                # before it, then interleave the next one (the resident
                # batch decodes between chunks instead of head-of-line
                # blocking); both are decode time, not this prefill's
                td = time.perf_counter()
                if self.inflight:
                    with spans.span("serve-drain",
                                    inflight=len(self.inflight)):
                        self.drain()
                if ci < n_chunks - 1 and self.slots:
                    self.dispatch_decode(max_k=1)
                decode_spent += time.perf_counter() - td
                if self.carry_resets != resets:
                    # the resident batch failed and took the carry with
                    # it — this request's chunks 0..ci died in the old
                    # cache; restart the prefill on the fresh carry
                    # (chunk writes are deterministic, so a replay is
                    # exact) via the retry wrapper
                    raise TransientFault(
                        "carry reset during the chunked-prefill "
                        "interleave (resident batch failed closed)")
                cache = self.carry[0]
            with _launch_span("serve-prefill-sync", launched,
                              lambda: {"rid": req.rid}):
                jax.block_until_ready(y_last)
            # the interleaved units' dispatch+sync time is already
            # billed to decode_step_s/per_token_s — keep prefill_s a
            # PREFILL cost
            dt = time.perf_counter() - t0 - decode_spent
            if eng._probes:
                # the family's small integers of each chunk: ready with
                # the last chunk's logits
                for last in lasts:
                    self.family.chunk_counted(self.registry, self.config,
                                              stats.family, last)
        return bucket, y_last, dt

    def _prefill_bucket(self, req: Request, slot: int, x_prompt: jax.Array,
                        bucket: int):
        """The monolithic prefill of one prompt bucket (no
        ``prefill_chunk``), and the draft plane's beside it."""
        eng = self.engine
        with spans.span("serve-prefill", rid=req.rid, bucket=bucket,
                        slot=slot):
            t0 = time.perf_counter()
            cache, y_last = self._launch(
                eng._prefill_jit(bucket), self.carry[0], eng.params,
                x_prompt, np.int32(slot), np.int32(req.prompt_len))
            launched = self.stats.launches - 1
            if eng._draft_prefill is not None:
                # the draft plane is prefilled at admission from the SAME
                # prompt embeddings (idempotent masked writes, so the
                # retry wrapper covers it); its cost is billed as prefill
                # — the admission price of the draft model
                self.spec.draft, _dy = self._launch(
                    eng._draft_prefill, self.spec.draft,
                    eng._draft_params, x_prompt, np.int32(slot),
                    np.int32(req.prompt_len))
            with _launch_span("serve-prefill-sync", launched,
                              lambda: {"rid": req.rid}):
                jax.block_until_ready(y_last)
            dt = time.perf_counter() - t0
        self.carry = (cache, self.carry[1])
        return bucket, y_last, dt

    def prefill_dispatch(self, req: Request, slot: int, x_prompt: jax.Array,
                         plan: Optional[dict[str, Any]] = None):
        """Bounded-retry wrapper around :meth:`prefill_once` — transient
        dispatch failures back off and re-issue over the same prepared
        input (chunk counters rolled back so a retried prefill never
        double-counts); exhaustion raises to the admission's fail-closed
        path.  The prefix-attach ``plan`` rides through unchanged: each
        attempt re-checks the carry generation itself, so a retry after a
        mid-prefill carry reset degrades to the full prefill instead of
        copying zeroed donor blocks."""
        attempt = 0
        while True:
            chunks_base = self.stats.prefill_chunks
            try:
                return self.prefill_once(req, slot, x_prompt, plan)
            except (TransientFault, CorruptStats) as e:
                self.stats.prefill_chunks = chunks_base
                if attempt >= self.cfg.max_dispatch_retries:
                    raise
                attempt += 1
                self._retry("prefill", attempt, e, rid=req.rid)

    # -- the report -----------------------------------------------------------

    def report(self, remaining_rids: list[int]) -> dict[str, Any]:
        """The run's report (``docs/serving.md`` documents every field)."""
        cfg, stats, eng, trace = self.cfg, self.stats, self.engine, self.trace
        wall = self._now()
        series, ledger = self.series, self.ledger
        self.registry.set_gauge("serve_queue_depth_peak",
                                max(series["queue_depth"], default=0))
        self.registry.set_gauge("serve_cache_blocks_peak",
                                ledger.peak_in_use)
        goodput = (stats.completed_output_tokens / wall) if wall > 0 else 0.0
        base = self.counts_base
        arrived = self._requests["arrived"] - base["arrived"]
        # shed rate counts LOAD shedding only (queue-full) — an
        # infeasible rejection is a config/trace mismatch, and folding it
        # in would misread as pressure and prompt a pointless
        # queue_capacity tune
        shed = self._rejections["queue-full"] - self.shed_base
        rejected_detail = self.rejected_detail
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
            "mesh": {"dp": eng.dp, "tp": eng.tp},
            "serving": cfg.to_dict(),
            "trace": {
                "kind": trace.kind,
                "seed": trace.seed,
                "num_requests": len(trace),
                "params": dict(trace.params),
                "horizon_s": trace.horizon_s,
            },
            "requests": {
                **{k: self._requests[k] - base[k]
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
                             for rid, o in sorted(self.outcomes.items())},
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
            # of the prompt chunks' layer attentions, the share that ran
            # as the ``chunk_attend`` kernel
            "chunk_attn_kernel_share": (
                stats.chunk_attn_kernel
                / (stats.chunk_attn_kernel + stats.chunk_attn_dense)
                if stats.chunk_attn_kernel + stats.chunk_attn_dense
                else 0.0),
            # of the grouped expert products dispatched, the share whose
            # contraction was one tile (``ops/routed_experts.py``)
            "gmm_whole_k_share": (
                stats.gmm_whole_k / (stats.gmm_whole_k + stats.gmm_split_k)
                if stats.gmm_whole_k + stats.gmm_split_k else 0.0),
            # calls of a jitted serving program (``_launch``): the span
            # file's ``launch`` arguments run 0..launches-1
            "launches": stats.launches,
            # share of the K/V planes' tiles the decode steps fetched
            f"{eng._tiles_of}_live_share": (
                stats.kv_tiles_live / stats.kv_tiles_held
                if stats.kv_tiles_held else 0.0),
            # the block family's own (experts touched, the fullest
            # expert's load)
            **self.family.report_shares(self.config, stats.family, cfg),
            # share of admissions whose input the look-ahead had ready
            "input_ready_share": self.lookahead.ready_share,
            "fast_path": {
                "enabled": eng._fast,
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
                "sampled": eng._sampled,
                "sample_seed": cfg.sample_seed,
                "verify_units": stats.spec_verify_units,
                "fallback_units": stats.spec_fallback_units,
                "proposed_tokens": stats.spec_proposed_tokens,
                "accepted_tokens": stats.spec_accepted_tokens,
                "acceptance_rate": (
                    stats.spec_accepted_tokens / stats.spec_proposed_tokens
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
                "failed": self.failed_detail,
            },
            "preempted": self.preempted,
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
            "compile_time_s": self.compile_time,
            "wall_seconds": wall,
        }
        if self.collect_raw or self.preempted:
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
        if eng.capture_tokens:
            report["completed_tokens"] = {
                str(rid): toks
                for rid, toks in sorted(self.tokens_by_rid.items())
            }
        if eng.verbose:
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
