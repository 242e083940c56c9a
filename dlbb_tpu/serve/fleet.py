"""Replica-level fault tolerance: the serving fleet supervisor.

PR-11 made ONE engine survive its own faults (transient dispatches,
torn bookkeeping, graceful preemption).  This module makes the engine
itself a replaceable unit: the device mesh is partitioned into N
independent replica sub-meshes (``comm/mesh.partition_devices`` —
contiguous, disjoint *failure domains*), each running its own
:class:`~dlbb_tpu.serve.engine.ServingEngine` (own ``BlockLedger``, own
KV planes, own journal track), under a host-side supervisor that:

- **routes** admissions least-loaded with prefix affinity: a request
  carrying a ``prefix_seed`` goes back to the replica whose
  ``PrefixTrie`` already holds that prefix (the re-prefill there is a
  cheap attach), falling back to the replica with the fewest resident
  blocks;
- **health-checks** replicas through a per-replica heartbeat — the
  PR-11 dispatch-EMA watchdog generalised one level up.  A replica that
  dies (``serve-replica-kill``), hangs past its heartbeat deadline
  (``serve-replica-hang``), or crashes is **fenced**: no new
  admissions, its kill flag set (a hung replica that later wakes raises
  :class:`ReplicaKilled` at its next loop boundary — it can never
  double-serve), and every resident request **failed over**: re-enqueued
  at the head of a survivor's feed and re-prefilled there, original
  ``arrival_s`` (and therefore ``deadline_s`` accounting) preserved;
- **hedges** stragglers when ``serving.hedge_factor`` is set: a request
  resident past p99 x factor is duplicated onto a second replica,
  first completion wins, the loser is cancelled and its blocks freed —
  greedy decode depends only on (params, request), and every replica
  initialises from the same seed, so the tokens are pinned identical
  either way;
- **degrades** explicitly under overload or shrinking capacity through
  a monotonic ladder (:data:`DEGRADE_LEVELS`): full service -> disable
  speculation -> cap the decode horizon at 1 -> shed best-effort (no
  ``deadline_s``) arrivals.  Every transition is journaled and counted
  (``serve_degrade_transitions_total``); nothing degrades silently.

Failover is transactional: the routing mutation runs against a
snapshot, the ``serve-failover-torn`` site fires after the mutation and
BEFORE any feed push, and a torn attempt restores the snapshot and
retries — a request is never double-routed and a shared prefix block is
never double-freed (the chaos class ``cli chaos --plan fleet`` pins
this, plus token-identity vs an unfaulted single-replica run).

Everything here is strictly host-side: threads, deques and dicts.  No
function in this module is ever traced or jitted, and the static
zero-injection AST pin from PR-11 extends over this file
(``tests/test_fleet.py``) — the jitted prefill/decode programs are
byte-identical with or without a fleet or a fault plan.

See ``docs/fleet.md`` for the supervisor state machine, the failover
contract, hedging semantics and the degradation-ladder table.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Callable, Optional, Sequence

import numpy as np

from dlbb_tpu.comm.mesh import (available_devices, fault_domain_record,
                                partition_devices)
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.obs.export import MetricsRegistry
from dlbb_tpu.resilience import inject
from dlbb_tpu.resilience.errors import (DeadlineExceeded, InjectedFault,
                                        TornWrite, exception_chain)
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine, family_for
from dlbb_tpu.serve.traffic import Request, TrafficTrace

FLEET_REPORT_SCHEMA = "dlbb_fleet_report_v1"

# The degradation ladder, in escalation order.  Transitions are
# monotonic within a run: the supervisor only ever climbs (recovering
# capacity mid-trace would un-shed nobody and make the journal
# ambiguous about which requests saw which service level).
DEGRADE_LEVELS = ("full", "no-speculation", "short-horizon",
                  "shed-best-effort")

# Feed-empty sentinel arrival.  Deliberately NOT float("inf"): the
# engine's admission planner computes ``int(gap / step_ema)`` on the
# next arrival gap, and int(inf) raises.  1e12 seconds is ~31k years —
# far enough.
_FAR_FUTURE_S = 1.0e12

_FENCE_REASONS = ("replica-killed", "replica-hung", "replica-crashed")


class ReplicaKilled(InjectedFault):
    """A replica was killed (the ``serve-replica-kill`` site, or the
    supervisor's kill flag after fencing).  Simulated SIGKILL: it
    propagates straight out of the engine — no cleanup, no report —
    and the supervisor fails the residents over."""


class _FeedHorizon:
    """What an open-but-empty feed shows at index 0: a pseudo-arrival in
    the far future, so the engine's arrival-gap planner keeps decoding
    at full horizon instead of seeing IndexError or int(inf)."""

    __slots__ = ()
    arrival_s = _FAR_FUTURE_S
    rid = -1


_HORIZON = _FeedHorizon()


class RequestFeed:
    """Thread-safe arrival feed a fleet supervisor pushes into and one
    engine drains (``run_trace(..., feed=)``).

    Mimics the deque the engine otherwise builds from the static trace:
    truthiness means "more work may come" (items present OR still
    open), ``[0]`` peeks the next arrival (a far-future sentinel while
    empty-but-open, so the engine idles instead of exiting), and
    ``popleft``/``discard`` mutate from the engine side only.  The
    supervisor closes the feed once every request is fleet-terminal —
    only then does the engine's main loop condition go false."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._items: deque[Request] = deque()
        self._closed = False

    def push(self, req: Request) -> None:
        with self._lock:
            if self._closed:
                raise RuntimeError("push into a closed feed")
            self._items.append(req)

    def push_front(self, req: Request) -> None:
        """Failover re-admission: the moved request jumps the line (it
        already waited its queue time on the dead replica)."""
        with self._lock:
            if self._closed:
                raise RuntimeError("push into a closed feed")
            self._items.appendleft(req)

    def popleft(self) -> Request:
        with self._lock:
            return self._items.popleft()

    def discard(self, rid: int) -> bool:
        """Drop a not-yet-admitted request (hedge-loser cancel)."""
        with self._lock:
            for i, req in enumerate(self._items):
                if req.rid == rid:
                    del self._items[i]
                    return True
        return False

    def close(self) -> None:
        with self._lock:
            self._closed = True

    @property
    def closed(self) -> bool:
        return self._closed

    def __bool__(self) -> bool:
        with self._lock:
            return bool(self._items) or not self._closed

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)

    def __iter__(self):
        with self._lock:
            return iter(list(self._items))

    def __getitem__(self, idx: int) -> Any:
        if idx != 0:
            raise IndexError("feeds only expose the head")
        with self._lock:
            if self._items:
                return self._items[0]
            if not self._closed:
                return _HORIZON
            raise IndexError("feed drained and closed")


class _StartGate:
    """Fleet-shared clock origin.  Every replica compiles, then parks in
    :meth:`arrive`; the supervisor releases the gate once all live
    replicas arrived (or gave up on the dead ones) and the SAME
    ``t0`` becomes every engine's clock origin — arrival offsets and
    ``deadline_s`` accounting agree across the fleet, un-skewed by
    per-replica compile time."""

    def __init__(self, timeout_s: float) -> None:
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._timeout_s = timeout_s
        self.arrived: set[int] = set()
        self.t0: Optional[float] = None

    def arrive(self, replica: int) -> float:
        with self._lock:
            self.arrived.add(replica)
        self._event.wait(self._timeout_s)
        with self._lock:
            if self.t0 is None:
                # gate timed out (supervisor gone?) — fail open with a
                # local origin rather than hanging the replica forever
                self.t0 = time.perf_counter()
            return self.t0

    def release(self) -> float:
        with self._lock:
            if self.t0 is None:
                self.t0 = time.perf_counter()
        self._event.set()
        return self.t0


class ReplicaControl:
    """Per-replica control plane the engine consults strictly at its
    scheduler-loop boundary (``run_trace(..., control=)``): heartbeat
    out, kill/cancel/degradation in.  Everything here is host-side; the
    fault sites fire in :meth:`check`, never inside a jit."""

    def __init__(self, replica: int, gate: _StartGate) -> None:
        self.replica = replica
        self._gate = gate
        self._lock = threading.Lock()
        self._cancels: deque[tuple[int, str]] = deque()
        self._kill_reason: Optional[str] = None
        # degradation knobs the engine reads per loop iteration
        self.spec_enabled = True
        self.horizon_cap: Optional[int] = None
        # lifecycle sink the supervisor installs (engine._event feeds it)
        self.on_event: Optional[Callable[[int, str, dict], None]] = None
        # heartbeat state (supervisor-read)
        self.started = False
        self.last_beat = time.monotonic()
        self.beat_ema: Optional[float] = None
        self.beats = 0

    # -- engine side -------------------------------------------------------

    def sync_start(self) -> float:
        return self._gate.arrive(self.replica)

    def beat(self) -> None:
        now = time.monotonic()
        if self.started:
            dt = now - self.last_beat
            self.beat_ema = (dt if self.beat_ema is None
                             else 0.9 * self.beat_ema + 0.1 * dt)
        self.last_beat = now
        self.started = True
        self.beats += 1

    def check(self) -> None:
        """Loop-boundary fault + kill-flag check.  The hang site sleeps
        (the heartbeat watchdog must fence us meanwhile); the kill site
        — or a fence that already set the flag — raises, so a fenced
        replica can never dispatch again, even one waking from a hang
        after its residents were failed over."""
        if inject.fire("serve-replica-hang"):
            time.sleep(inject.param("hang_seconds"))
        if self._kill_reason is None and inject.fire("serve-replica-kill"):
            with self._lock:
                if self._kill_reason is None:
                    self._kill_reason = "serve-replica-kill"
        if self._kill_reason is not None:
            raise ReplicaKilled(
                f"replica {self.replica} killed ({self._kill_reason})"
            )

    def take_cancels(self) -> list[tuple[int, str]]:
        with self._lock:
            if not self._cancels:
                return []
            out = list(self._cancels)
            self._cancels.clear()
            return out

    # -- supervisor side ---------------------------------------------------

    def request_kill(self, reason: str) -> None:
        with self._lock:
            if self._kill_reason is None:
                self._kill_reason = reason

    @property
    def kill_reason(self) -> Optional[str]:
        return self._kill_reason

    def cancel(self, rid: int, reason: str) -> None:
        with self._lock:
            self._cancels.append((rid, reason))


class _ReplicaJournal:
    """A replica's view of the ONE shared fleet journal: every line
    gains ``replica=N`` (the per-replica Perfetto track key —
    ``obs/spans.journal_to_trace``) and writes serialise through a
    shared lock (``SweepJournal`` is single-writer by design)."""

    def __init__(self, journal: Any, replica: int,
                 lock: threading.Lock) -> None:
        self._journal = journal
        self._lock = lock
        self.replica = replica

    def event(self, event: str, config: Optional[str] = None,
              **extra: Any) -> None:
        if self._journal is None:
            return
        extra.setdefault("replica", self.replica)
        with self._lock:
            self._journal.event(event, config=config, **extra)


class FleetConfig:
    """Fleet-level knobs (the ``fleet:`` config section).

    replicas             independent failure domains to partition the
                         device mesh into
    heartbeat_factor     fence a replica silent for factor x its own
                         loop-period EMA ...
    heartbeat_min_s      ... but never sooner than this floor (compile
                         stalls and idle sleeps are legal silences)
    start_timeout_s      cap on waiting for every replica to compile
                         and reach the shared clock gate
    stall_timeout_s      fleet-level fail-closed: no routing/terminal
                         progress for this long ends the run with every
                         outstanding request failed, never a hang
    degrade              enable the automatic overload ladder
    degrade_high_water   escalate one level when resident requests
                         exceed this multiple of live slot capacity
    degrade_interval_s   minimum spacing between automatic escalations
    hedge_min_completions completions needed before the p99 estimate is
                         trusted enough to hedge on
    tick_s               supervisor loop period
    """

    _FIELDS = ("replicas", "heartbeat_factor", "heartbeat_min_s",
               "start_timeout_s", "stall_timeout_s", "degrade",
               "degrade_high_water", "degrade_interval_s",
               "hedge_min_completions", "tick_s")

    def __init__(self, replicas: int = 2, heartbeat_factor: float = 32.0,
                 heartbeat_min_s: float = 1.5,
                 start_timeout_s: float = 120.0,
                 stall_timeout_s: float = 120.0, degrade: bool = True,
                 degrade_high_water: float = 2.0,
                 degrade_interval_s: float = 0.25,
                 hedge_min_completions: int = 8,
                 tick_s: float = 0.005) -> None:
        self.replicas = int(replicas)
        self.heartbeat_factor = float(heartbeat_factor)
        self.heartbeat_min_s = float(heartbeat_min_s)
        self.start_timeout_s = float(start_timeout_s)
        self.stall_timeout_s = float(stall_timeout_s)
        self.degrade = bool(degrade)
        self.degrade_high_water = float(degrade_high_water)
        self.degrade_interval_s = float(degrade_interval_s)
        self.hedge_min_completions = int(hedge_min_completions)
        self.tick_s = float(tick_s)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "FleetConfig":
        unknown = set(d) - set(cls._FIELDS)
        if unknown:
            raise ValueError(
                f"unknown fleet config key(s) {sorted(unknown)} "
                f"(known: {list(cls._FIELDS)})"
            )
        return cls(**{k: d[k] for k in cls._FIELDS if k in d})

    def to_dict(self) -> dict[str, Any]:
        return {k: getattr(self, k) for k in self._FIELDS}

    def validate(self) -> None:
        if self.replicas < 1:
            raise ValueError(f"fleet.replicas={self.replicas} must be >= 1")
        if self.heartbeat_factor < 1.0:
            raise ValueError(
                f"fleet.heartbeat_factor={self.heartbeat_factor} must be "
                ">= 1 (a sub-EMA deadline fences healthy replicas)"
            )
        for knob in ("heartbeat_min_s", "start_timeout_s",
                     "stall_timeout_s", "degrade_high_water",
                     "degrade_interval_s", "tick_s"):
            if getattr(self, knob) <= 0:
                raise ValueError(f"fleet.{knob} must be > 0")
        if self.hedge_min_completions < 1:
            raise ValueError("fleet.hedge_min_completions must be >= 1")


def validate_fleet(config: dict[str, Any], model_cfg: ModelConfig,
                   serving_cfg: ServingConfig, fleet_cfg: FleetConfig,
                   n_devices: int) -> tuple[int, int]:
    """The fleet admission ladder — every rung rejects BEFORE any
    replica builds, with the reason, never as a mid-run OOM or a
    lopsided fleet:

    1. the fleet knobs themselves are sane;
    2. the device count partitions into ``replicas`` equal failure
       domains;
    3. the per-replica (dp, tp) plan fits inside one domain;
    4. the per-replica serving envelope (incl. the HBM budget — each
       replica carries its OWN full KV planes) passes the engine's own
       ``ServingConfig.validate`` and its block family's
       ``check_serving``.

    Returns the per-replica ``(dp, tp)``."""
    fleet_cfg.validate()
    par = dict(config.get("parallelism", {}))
    tp = int(par.get("world_size", 1))
    dp = int(par.get("data_parallel", 1))
    for axis in ("sequence_parallel", "pipeline_parallel",
                 "expert_parallel"):
        if int(par.get(axis, 1)) > 1:
            raise ValueError(
                f"serving fleets support (dp, tp) replicas only "
                f"(got {axis}={par[axis]})"
            )
    if n_devices % fleet_cfg.replicas != 0:
        raise ValueError(
            f"{n_devices} device(s) do not partition into "
            f"{fleet_cfg.replicas} equal failure domains"
        )
    per_domain = n_devices // fleet_cfg.replicas
    if dp * tp > per_domain:
        raise ValueError(
            f"per-replica plan dp={dp} x tp={tp} needs {dp * tp} "
            f"devices but each of the {fleet_cfg.replicas} failure "
            f"domains has only {per_domain} "
            f"({n_devices} devices total)"
        )
    family_for(model_cfg).check_serving(model_cfg, serving_cfg)
    serving_cfg.validate(model_cfg, dp=dp, tp=tp)
    return dp, tp


# engine terminal lifecycle events -> fleet outcome kind
_TERMINAL_EVENTS = {
    "request-completed": "completed",
    "request-failed": "failed",
    "request-rejected": "rejected",
    "request-infeasible": "rejected",
    "request-canceled": "canceled",
}


class FleetSupervisor:
    """Host-side control plane over N replica engines (module
    docstring).  One instance serves one trace; all shared state is
    owned by the supervisor thread — replica threads communicate only
    through the event deque (lifecycle sink), their control objects,
    and their feeds."""

    def __init__(self, model_cfg: ModelConfig, serving_cfg: ServingConfig,
                 fleet_cfg: FleetConfig, meshes: Sequence,
                 fault_domains: Optional[dict[str, list[int]]] = None,
                 seed: int = 0, journal: Any = None,
                 registry: Optional[MetricsRegistry] = None,
                 verbose: bool = False,
                 capture_tokens: bool = True) -> None:
        if not meshes:
            raise ValueError("a fleet needs at least one replica mesh")
        self.model = model_cfg
        self.serving = serving_cfg
        self.fleet = fleet_cfg
        self.meshes = list(meshes)
        self.fault_domains = dict(fault_domains or {})
        self.seed = seed
        self.journal = journal
        self.verbose = verbose
        self.capture_tokens = capture_tokens
        self.registry = registry if registry is not None else MetricsRegistry()
        self._failover_counter = self.registry.labeled_counter(
            "serve_failovers", "reason", initial=_FENCE_REASONS,
            help="requests failed over off a fenced replica, by fence "
                 "reason")
        self._hedge_counter = self.registry.labeled_counter(
            "serve_hedges", "outcome", initial=("issued", "won", "lost"),
            help="hedged requests: issued duplicates, and whether the "
                 "hedge (won) or the primary (lost) completed first")
        self._degrade_counter = self.registry.labeled_counter(
            "serve_degrade_transitions", "level",
            initial=DEGRADE_LEVELS[1:],
            help="degradation-ladder escalations, by level entered")

        R = len(self.meshes)
        self._gate = _StartGate(fleet_cfg.start_timeout_s)
        self._jlock = threading.Lock()
        self.controls = [ReplicaControl(i, self._gate) for i in range(R)]
        self.feeds = [RequestFeed() for _ in range(R)]
        self.engines: list[Optional[ServingEngine]] = [None] * R
        self.reports: list[Optional[dict]] = [None] * R
        self.death: list[Optional[dict]] = [None] * R
        self._threads: list[Optional[threading.Thread]] = [None] * R
        self._done = [False] * R
        self._fenced = [False] * R
        self._fence_reason: list[Optional[str]] = [None] * R

        # routing state (supervisor thread only)
        self._events: deque[tuple[int, int, str, dict]] = deque()
        self._elock = threading.Lock()
        self._req_by_rid: dict[int, Request] = {}
        self._assign: dict[int, int] = {}      # rid -> primary replica
        self._hedged: dict[int, int] = {}      # rid -> hedge replica
        self._hedge_resolved: set[int] = set()
        self._terminal: dict[int, str] = {}    # rid -> fleet outcome
        self._routed_at: dict[int, float] = {}
        self._copy_blocks: dict[tuple[int, int], int] = {}
        self._blocks = [0] * R                 # resident-block estimate
        self._routed_count = [0] * R
        self._affinity: dict[tuple, int] = {}
        self._affinity_hits = 0
        self._affinity_misses = 0
        self._shed = 0
        self._e2e: list[float] = []
        self._ttft: dict[int, float] = {}
        self._tokens: dict[int, list[int]] = {}
        self._completed_by: dict[int, int] = {}
        self._failover_rids: set[int] = set()
        self._failover_log: list[dict[str, Any]] = []
        self._level = 0
        self._degrade_log: list[dict[str, Any]] = []
        self._last_degrade = -1.0e9
        self._t0: Optional[float] = None

    # -- journal -----------------------------------------------------------

    def _jevent(self, event: str, config: Optional[str] = None,
                **extra: Any) -> None:
        if self.journal is None:
            return
        with self._jlock:
            self.journal.event(event, config=config, **extra)

    # -- replica workers ---------------------------------------------------

    def _sink(self, replica: int) -> Callable[[int, str, dict], None]:
        def on_event(rid: int, event: str, extra: dict) -> None:
            with self._elock:
                self._events.append((replica, rid, event, extra))
        return on_event

    def _worker(self, idx: int, trace: TrafficTrace) -> None:
        ctl = self.controls[idx]
        try:
            engine = ServingEngine(
                self.model, self.serving, self.meshes[idx],
                journal=_ReplicaJournal(self.journal, idx, self._jlock),
                seed=self.seed, verbose=False,
                capture_tokens=self.capture_tokens,
            )
            self.engines[idx] = engine
            ctl.on_event = self._sink(idx)
            self._jevent("replica-up", replica=idx,
                         devices=self.fault_domains.get(str(idx)))
            self.reports[idx] = engine.run_trace(
                trace, feed=self.feeds[idx], control=ctl)
        except ReplicaKilled as e:
            self.death[idx] = {"reason": "replica-killed",
                               **exception_chain(e)}
            self._jevent("replica-failed", replica=idx,
                         reason="replica-killed", **exception_chain(e))
        except BaseException as e:  # noqa: BLE001 — fail closed, never hang
            self.death[idx] = {"reason": "replica-crashed",
                               **exception_chain(e)}
            self._jevent("replica-failed", replica=idx,
                         reason="replica-crashed", **exception_chain(e))
        finally:
            self._done[idx] = True

    # -- clock -------------------------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - (self._t0 or time.perf_counter())

    # -- routing -----------------------------------------------------------

    def _blocks_for(self, req: Request) -> int:
        total = req.prompt_len + req.output_len
        return -(-total // self.serving.block_size)

    def _admittable(self) -> list[int]:
        return [i for i in range(len(self.meshes))
                if not self._fenced[i] and not self._done[i]]

    def _pick(self, req: Request,
              exclude: frozenset = frozenset()) -> Optional[int]:
        alive = [i for i in self._admittable() if i not in exclude]
        if not alive:
            return None
        key = None
        if req.prefix_seed is not None:
            key = (req.prefix_seed, req.prefix_len)
            aff = self._affinity.get(key)
            if aff is not None and aff in alive:
                self._affinity_hits += 1
                return aff
        tgt = min(alive, key=lambda i: (self._blocks[i], i))
        if key is not None:
            self._affinity[key] = tgt
            self._affinity_misses += 1
        return tgt

    def _push(self, rid: int, req: Request, tgt: int,
              front: bool = False) -> None:
        self._assign[rid] = tgt
        nb = self._blocks_for(req)
        self._copy_blocks[(rid, tgt)] = nb
        self._blocks[tgt] += nb
        (self.feeds[tgt].push_front if front
         else self.feeds[tgt].push)(req)
        self._routed_count[tgt] += 1

    def _route(self, req: Request) -> None:
        rid = req.rid
        self._req_by_rid.setdefault(rid, req)
        if self._level >= 3 and req.deadline_s is None:
            # shed-best-effort: requests without an SLO class are
            # rejected at the door while the fleet is at ladder level 3
            self._terminal[rid] = "rejected[degraded-shed]"
            self._shed += 1
            self._jevent("request-rejected", config=f"request-{rid}",
                         reason="degraded-shed", level=self._level)
            return
        tgt = self._pick(req)
        if tgt is None:
            self._terminal[rid] = "failed[no-replica]"
            self._jevent("request-failed", config=f"request-{rid}",
                         reason="no-replica")
            return
        self._routed_at[rid] = self._now()
        self._push(rid, req, tgt)

    # -- lifecycle events --------------------------------------------------

    def _drain_events(self) -> int:
        with self._elock:
            batch = list(self._events)
            self._events.clear()
        for replica, rid, event, extra in batch:
            self._handle_event(replica, rid, event, extra)
        return len(batch)

    def _handle_event(self, rep: int, rid: int, event: str,
                      extra: dict) -> None:
        if event == "request-prefill":
            ttft = extra.get("ttft_s")
            if ttft is not None:
                # last write wins: a failed-over request's re-prefill
                # overwrites the dead replica's number — THAT is the
                # TTFT the client observed
                self._ttft[rid] = float(ttft)
            return
        kind = _TERMINAL_EVENTS.get(event)
        if kind is None:
            return
        nb = self._copy_blocks.pop((rid, rep), None)
        if nb:
            self._blocks[rep] = max(0, self._blocks[rep] - nb)
        reason = extra.get("reason")
        out = ("completed" if kind == "completed"
               else f"{kind}[{reason}]" if reason else kind)
        prev = self._terminal.get(rid)
        # precedence: a completion anywhere beats any other copy's fate
        # (hedge loser cancels, fence-time failures); first-terminal
        # wins otherwise
        if prev is None or (kind == "completed"
                            and not prev.startswith("completed")):
            self._terminal[rid] = out
        if kind == "completed":
            lat = extra.get("latency_s")
            if prev is None or not prev.startswith("completed"):
                if lat is not None:
                    self._e2e.append(float(lat))
                self._completed_by[rid] = rep
                toks = extra.get("tokens")
                if toks is not None:
                    self._tokens[rid] = [int(t) for t in toks]
            hedge = self._hedged.get(rid)
            if hedge is not None and rid not in self._hedge_resolved:
                self._hedge_resolved.add(rid)
                won = rep == hedge
                self._hedge_counter["won" if won else "lost"] += 1
                loser = self._assign.get(rid) if won else hedge
                if (loser is not None and loser != rep
                        and not self._fenced[loser]
                        and not self._done[loser]):
                    self.controls[loser].cancel(rid, "hedge-lost")

    # -- fencing & failover ------------------------------------------------

    def _routing_snapshot(self) -> dict[str, Any]:
        return {
            "assign": dict(self._assign),
            "blocks": list(self._blocks),
            "copy_blocks": dict(self._copy_blocks),
            "affinity": dict(self._affinity),
            "hedged": dict(self._hedged),
            "routed_count": list(self._routed_count),
        }

    def _restore_routing(self, snap: dict[str, Any]) -> None:
        self._assign = dict(snap["assign"])
        self._blocks = list(snap["blocks"])
        self._copy_blocks = dict(snap["copy_blocks"])
        self._affinity = dict(snap["affinity"])
        self._hedged = dict(snap["hedged"])
        self._routed_count = list(snap["routed_count"])

    def _fence(self, idx: int, reason: str,
               chain: Optional[dict] = None) -> None:
        """Fence ``idx`` (kill flag + closed feed + purged affinity) and
        fail its residents over.  The routing mutation is transactional:
        built against a snapshot, ``serve-failover-torn`` fires after
        the mutation and before any feed push, and a torn attempt rolls
        back and retries — never a double-routed request or a leaked
        block estimate."""
        if self._fenced[idx]:
            return
        self._fenced[idx] = True
        self._fence_reason[idx] = reason
        self.controls[idx].request_kill(reason)
        self.feeds[idx].close()
        self._jevent("replica-fenced", replica=idx, reason=reason,
                     **(chain or {}))
        if self.verbose:
            print(f"[fleet] replica {idx} FENCED ({reason})")
        # the dead replica's block estimates and prefix homes are moot
        self._blocks[idx] = 0
        for key in [k for k in self._copy_blocks if k[1] == idx]:
            del self._copy_blocks[key]
        self._affinity = {k: v for k, v in self._affinity.items()
                          if v != idx}
        # hedge copies touching the dead replica resolve to the survivor
        for rid, hedge in list(self._hedged.items()):
            if hedge == idx:
                del self._hedged[rid]
            elif self._assign.get(rid) == idx:
                self._assign[rid] = hedge
                del self._hedged[rid]
        residents = [rid for rid, rep in self._assign.items()
                     if rep == idx and rid not in self._terminal]
        pushes: list[tuple[int, Request, int]] = []
        orphans: list[int] = []
        for attempt in (1, 2):
            snap = self._routing_snapshot()
            pushes, orphans = [], []
            try:
                for rid in residents:
                    req = self._req_by_rid[rid]
                    tgt = self._pick(req, exclude=frozenset({idx}))
                    if tgt is None:
                        orphans.append(rid)
                        continue
                    self._assign[rid] = tgt
                    nb = self._blocks_for(req)
                    self._copy_blocks[(rid, tgt)] = nb
                    self._blocks[tgt] += nb
                    self._routed_count[tgt] += 1
                    pushes.append((rid, req, tgt))
                if pushes and inject.fire("serve-failover-torn"):
                    raise TornWrite(
                        "fleet routing table torn mid-failover")
                break
            except TornWrite as e:
                self._restore_routing(snap)
                self._jevent("failover-torn", replica=idx,
                             attempt=attempt, **exception_chain(e))
                if attempt == 2:
                    raise
        # COMMIT — only a committed routing table touches the feeds,
        # so a torn attempt above never half-delivered a request
        for rid, req, tgt in pushes:
            self.feeds[tgt].push_front(req)
            self._failover_counter[reason] += 1
            self._failover_rids.add(rid)
            rec = {"rid": rid, "from": idx, "to": tgt, "reason": reason}
            self._failover_log.append(rec)
            self._jevent("request-failover", config=f"request-{rid}",
                         from_replica=idx, to_replica=tgt, reason=reason,
                         **(chain or {}))
        for rid in orphans:
            self._terminal[rid] = "failed[replica-lost]"
            self._jevent("request-failed", config=f"request-{rid}",
                         reason="replica-lost", replica=idx,
                         **(chain or {}))

    def _health(self) -> None:
        for idx in range(len(self.meshes)):
            if self._fenced[idx]:
                continue
            if self._done[idx]:
                if self.death[idx] is not None:
                    self._fence(idx, self.death[idx]["reason"],
                                chain={k: v
                                       for k, v in self.death[idx].items()
                                       if k != "reason"})
                continue
            ctl = self.controls[idx]
            if not ctl.started:
                continue  # still compiling — the start gate owns this
            ema = ctl.beat_ema if ctl.beat_ema else 0.05
            deadline = max(self.fleet.heartbeat_min_s,
                           self.fleet.heartbeat_factor * ema)
            if time.monotonic() - ctl.last_beat > deadline:
                exc = DeadlineExceeded(f"replica-{idx} heartbeat",
                                       deadline, phase="heartbeat")
                self._fence(idx, "replica-hung",
                            chain=exception_chain(exc))

    # -- hedging -----------------------------------------------------------

    def _maybe_hedge(self, now: float) -> None:
        factor = self.serving.hedge_factor
        if factor is None:
            return
        if len(self._e2e) < self.fleet.hedge_min_completions:
            return
        threshold = factor * float(np.quantile(self._e2e, 0.99))
        for rid, routed_at in list(self._routed_at.items()):
            if (rid in self._terminal or rid in self._hedged
                    or now - routed_at <= threshold):
                continue
            primary = self._assign.get(rid)
            if primary is None:
                continue
            req = self._req_by_rid[rid]
            alt = self._pick(req, exclude=frozenset({primary}))
            if alt is None:
                continue
            self._hedged[rid] = alt
            nb = self._blocks_for(req)
            self._copy_blocks[(rid, alt)] = nb
            self._blocks[alt] += nb
            self._routed_count[alt] += 1
            self.feeds[alt].push_front(req)
            self._hedge_counter["issued"] += 1
            self._jevent("request-hedged", config=f"request-{rid}",
                         primary=primary, hedge=alt,
                         threshold_s=round(threshold, 6))

    # -- degradation ladder ------------------------------------------------

    def degrade_to(self, level: int, reason: str) -> None:
        """Climb the ladder to ``level`` (monotonic: requests to a
        level at or below the current one are no-ops — the fleet never
        silently recovers service classes mid-run).  Each level entered
        is applied to every live replica, journaled, and counted."""
        level = int(level)
        if level <= self._level:
            return
        if level >= len(DEGRADE_LEVELS):
            raise ValueError(
                f"degrade level {level} out of range "
                f"(max {len(DEGRADE_LEVELS) - 1})"
            )
        while self._level < level:
            self._level += 1
            name = DEGRADE_LEVELS[self._level]
            if self._level == 1:
                for ctl in self.controls:
                    ctl.spec_enabled = False
            elif self._level == 2:
                for ctl in self.controls:
                    ctl.horizon_cap = 1
            # level 3 (shed-best-effort) acts at routing time
            self._degrade_counter[name] += 1
            rec = {"level": self._level, "name": name, "reason": reason,
                   "t_s": round(self._now(), 6)}
            self._degrade_log.append(rec)
            self._jevent("degrade-transition", level=self._level,
                         name=name, reason=reason)
            if self.verbose:
                print(f"[fleet] DEGRADE -> {name} ({reason})")

    def _maybe_degrade(self, now: float) -> None:
        if (not self.fleet.degrade or self._level >= 3
                or now - self._last_degrade
                < self.fleet.degrade_interval_s):
            return
        alive = self._admittable()
        if not alive:
            return
        capacity = len(alive) * self.serving.max_batch
        resident = sum(1 for rid in self._assign
                       if rid not in self._terminal)
        pressure = resident / max(1, capacity)
        if pressure > self.fleet.degrade_high_water:
            self._last_degrade = now
            self.degrade_to(
                self._level + 1,
                f"overload: {resident} resident requests over "
                f"{capacity} live slots (pressure {pressure:.2f})")

    # -- gauges ------------------------------------------------------------

    def _export_gauges(self) -> None:
        resident: dict[int, int] = {i: 0 for i in range(len(self.meshes))}
        for rid, rep in self._assign.items():
            if rid not in self._terminal:
                resident[rep] += 1
        for rid, rep in self._hedged.items():
            if rid not in self._terminal:
                resident[rep] += 1
        for i, n in resident.items():
            self.registry.set_gauge(
                "serve_replica_resident_requests", n, replica=str(i),
                help="requests resident (routed, not terminal) per "
                     "replica")
        self.registry.set_gauge(
            "serve_fleet_degrade_level", self._level,
            help="current degradation-ladder level (0 = full service)")
        self.registry.set_gauge(
            "serve_fleet_live_replicas", len(self._admittable()),
            help="replicas admitting new requests")

    # -- the run -----------------------------------------------------------

    def serve(self, trace: TrafficTrace) -> dict[str, Any]:
        """Serve ``trace`` across the fleet; returns the aggregated
        fleet report (schema :data:`FLEET_REPORT_SCHEMA`)."""
        R = len(self.meshes)
        reqs = sorted(trace, key=lambda r: (r.arrival_s, r.rid))
        if not reqs:
            raise ValueError("cannot serve an empty trace")
        self._req_by_rid = {r.rid: r for r in reqs}
        for i in range(R):
            t = threading.Thread(target=self._worker, args=(i, trace),
                                 name=f"fleet-replica-{i}", daemon=True)
            self._threads[i] = t
            t.start()
        # hold the gate until every replica that is still alive has
        # compiled and parked — the shared t0 keeps arrival offsets and
        # deadline_s accounting identical across the fleet
        gate_deadline = time.monotonic() + self.fleet.start_timeout_s
        while time.monotonic() < gate_deadline:
            with self._gate._lock:
                arrived = set(self._gate.arrived)
            if all(self._done[i] or i in arrived for i in range(R)):
                break
            time.sleep(0.01)
        self._t0 = self._gate.release()
        wall_start = time.perf_counter()

        i = 0
        last_progress = time.monotonic()
        while True:
            now = self._now()
            progressed = 0
            while i < len(reqs) and reqs[i].arrival_s <= now:
                self._route(reqs[i])
                i += 1
                progressed += 1
            progressed += self._drain_events()
            self._health()
            self._maybe_hedge(now)
            self._maybe_degrade(now)
            self._export_gauges()
            outstanding = [rid for rid in self._assign
                           if rid not in self._terminal]
            if progressed:
                last_progress = time.monotonic()
            if i >= len(reqs) and not outstanding:
                break
            if not self._admittable():
                # the whole fleet is gone: fail closed, loudly — every
                # unserved request gets a terminal outcome and the run
                # ends instead of hanging
                for j in range(i, len(reqs)):
                    rid = reqs[j].rid
                    self._terminal[rid] = "failed[no-replica]"
                    self._jevent("request-failed",
                                 config=f"request-{rid}",
                                 reason="no-replica")
                i = len(reqs)
                for rid in outstanding:
                    if rid not in self._terminal:
                        self._terminal[rid] = "failed[replica-lost]"
                        self._jevent("request-failed",
                                     config=f"request-{rid}",
                                     reason="replica-lost")
                break
            if (time.monotonic() - last_progress
                    > self.fleet.stall_timeout_s):
                self._jevent("fleet-stall",
                             outstanding=sorted(outstanding),
                             timeout_s=self.fleet.stall_timeout_s)
                for rid in outstanding:
                    self._terminal[rid] = "failed[fleet-stall]"
                    self._jevent("request-failed",
                                 config=f"request-{rid}",
                                 reason="fleet-stall")
                for idx in self._admittable():
                    self._fence(idx, "replica-hung",
                                chain={"error": "fleet stall timeout"})
                break
            time.sleep(self.fleet.tick_s)

        for feed in self.feeds:
            feed.close()
        for i, t in enumerate(self._threads):
            if t is None:
                continue
            # a fenced replica may still be inside an injected hang; its
            # thread is a daemon and will observe the kill flag on wake —
            # don't let shutdown block on it
            t.join(timeout=2.0 if self._fenced[i] else 60.0)
        self._drain_events()
        self._export_gauges()
        wall = time.perf_counter() - wall_start
        return self._build_report(trace, wall)

    # -- the report --------------------------------------------------------

    def _build_report(self, trace: TrafficTrace,
                      wall: float) -> dict[str, Any]:
        from dlbb_tpu.utils.metrics import summarize

        R = len(self.meshes)
        outcomes = {rid: self._terminal.get(rid, "failed[unresolved]")
                    for rid in self._req_by_rid}
        counts = {"completed": 0, "failed": 0, "rejected": 0,
                  "canceled": 0, "preempted": 0}
        for out in outcomes.values():
            for k in counts:
                if out.startswith(k):
                    counts[k] += 1
                    break
        replicas = []
        for i in range(R):
            rep = self.reports[i]
            if rep is not None:
                # the fleet artifact carries the aggregate; strip the
                # per-replica bulk (fleet-level tokens/series are the
                # authoritative copies)
                rep = {k: v for k, v in rep.items()
                       if k not in ("timeseries", "completed_tokens")}
            status = ("fenced" if self._fenced[i]
                      else "failed" if self.death[i] is not None
                      else "ok")
            replicas.append({
                "replica": i,
                "devices": self.fault_domains.get(str(i)),
                "status": status,
                "fence_reason": self._fence_reason[i],
                "routed": self._routed_count[i],
                "death": self.death[i],
                "report": rep,
            })
        clean_ttft = [v for rid, v in self._ttft.items()
                      if rid not in self._failover_rids]
        fo_ttft = [v for rid, v in self._ttft.items()
                   if rid in self._failover_rids]
        penalty = (float(np.mean(fo_ttft) - np.mean(clean_ttft))
                   if fo_ttft and clean_ttft else None)
        completed_tokens = sum(
            self._req_by_rid[rid].output_len
            for rid, out in outcomes.items() if out == "completed")
        report: dict[str, Any] = {
            "schema": FLEET_REPORT_SCHEMA,
            "model": {
                "hidden_size": self.model.hidden_size,
                "num_layers": self.model.num_layers,
                "num_heads": self.model.num_heads,
                "kv_heads": self.model.kv_heads,
                "attention": self.model.attention,
                "dtype": self.model.dtype,
            },
            "serving": self.serving.to_dict(),
            "fleet": {**self.fleet.to_dict(),
                      "fault_domains": self.fault_domains},
            "trace": {"kind": trace.kind, "seed": trace.seed,
                      "num_requests": len(trace)},
            "requests": {
                "arrived": len(trace),
                "shed": self._shed,
                "outcomes": {str(r): o
                             for r, o in sorted(outcomes.items())},
                **counts,
            },
            "routing": {
                "per_replica": {str(i): self._routed_count[i]
                                for i in range(R)},
                "prefix_affinity_hits": self._affinity_hits,
                "prefix_affinity_misses": self._affinity_misses,
            },
            "replicas": replicas,
            "failovers": {
                "total": len(self._failover_log),
                "by_reason": {r: int(self._failover_counter[r])
                              for r in _FENCE_REASONS},
                "requests": self._failover_log,
            },
            "hedges": {k: int(self._hedge_counter[k])
                       for k in ("issued", "won", "lost")},
            "degrade": {"level": self._level,
                        "name": DEGRADE_LEVELS[self._level],
                        "transitions": self._degrade_log},
            "ttft": summarize(sorted(self._ttft.values())),
            "ttft_failover": summarize(sorted(fo_ttft)),
            "failover_ttft_penalty_s": penalty,
            "e2e_latency": summarize(sorted(self._e2e)),
            "goodput_tokens_per_s": (completed_tokens / wall
                                     if wall > 0 else 0.0),
            "wall_seconds": wall,
        }
        if self.capture_tokens:
            report["completed_tokens"] = {
                str(rid): toks
                for rid, toks in sorted(self._tokens.items())
            }
        return report


def run_fleet(
    config: dict[str, Any],
    trace: TrafficTrace,
    output_dir: Optional[str] = None,
    devices: Optional[Sequence] = None,
    journal: bool = True,
    verbose: bool = True,
    fault_plan: Optional[str] = None,
    capture_tokens: bool = True,
) -> dict[str, Any]:
    """Run one trace across a replica fleet (the ``cli serve
    --replicas N`` entry point).

    ``config`` follows the experiment-YAML schema with ``fleet:`` next
    to ``serving:``/``model:``/``parallelism:`` (the parallelism plan is
    PER REPLICA).  Writes the serving artifact family under
    ``output_dir``: ``fleet_<name>.json`` (schema
    ``dlbb_fleet_report_v1``), the shared journal with per-replica
    tracks, ``metrics.prom``, and ``serving_manifest.json`` whose
    ``fault_domains`` field marks the run as a fleet so report overlays
    never aggregate it with single-replica numbers."""
    import os

    from dlbb_tpu.obs import spans
    from dlbb_tpu.obs.export import fleet_metrics
    from dlbb_tpu.parallel.plan import ParallelismPlan
    from dlbb_tpu.resilience.journal import SweepJournal
    from dlbb_tpu.serve.bench import (DEFAULT_SERVE_MODEL,
                                      SERVING_MANIFEST_SCHEMA, _hbm_record)
    from dlbb_tpu.utils.config import save_json
    from dlbb_tpu.utils.simulate import topology_record
    from dlbb_tpu.utils.sysinfo import collect_system_info

    model_cfg = ModelConfig.from_dict(config.get("model",
                                                 DEFAULT_SERVE_MODEL))
    serving_cfg = ServingConfig.from_dict(config.get("serving", {}))
    fleet_cfg = FleetConfig.from_dict(config.get("fleet", {}))
    devs = list(devices) if devices is not None else available_devices()
    validate_fleet(config, model_cfg, serving_cfg, fleet_cfg, len(devs))
    groups = partition_devices(devs, fleet_cfg.replicas)
    plans = [ParallelismPlan.from_config(config, model_cfg, devices=g)
             for g in groups]
    meshes = [p.mesh for p in plans]
    domains = fault_domain_record(groups)

    fault_spec = fault_plan
    if fault_spec is None and inject.active() is None:
        fault_spec = os.environ.get(inject.ENV_VAR, "").strip() or None

    name = config.get("experiment", {}).get("name") or (
        f"fleet{fleet_cfg.replicas}_{trace.kind}_{len(trace)}req_"
        f"seed{trace.seed}"
    )
    out = Path(output_dir) if output_dir is not None else None
    jrn = None
    if out is not None and journal:
        jrn = SweepJournal(
            out,
            meta={"mode": "fleet", "name": name,
                  "replicas": fleet_cfg.replicas,
                  "trace_kind": trace.kind, "num_requests": len(trace),
                  "fault_plan": fault_spec},
            sink=spans.journal_sink,
        )
    topology = topology_record(fault_domains=domains)
    try:
        with inject.plan_scope(fault_spec):
            sup = FleetSupervisor(
                model_cfg, serving_cfg, fleet_cfg, meshes,
                fault_domains=domains, journal=jrn,
                seed=config.get("input", {}).get("seed", 0),
                verbose=verbose, capture_tokens=capture_tokens,
            )
            if jrn is not None:
                jrn.event("topology", **topology)
            report = sup.serve(trace)
    finally:
        if jrn is not None:
            jrn.close()

    report["experiment"] = config.get("experiment", {})
    report["backend"] = "xla_tpu"
    report["mesh"] = plans[0].mesh_dict()  # ONE replica's mesh
    report["topology"] = topology
    report["hbm"] = _hbm_record(model_cfg, serving_cfg, plans[0])
    report["system_info"] = collect_system_info()
    report["timestamp"] = time.time()

    if out is not None:
        trace_path = trace.save(out / f"trace_{name}.json")
        result_path = save_json(report, out / f"fleet_{name}.json")
        registry = fleet_metrics(report, registry=sup.registry)
        prom_path = registry.write_textfile(out / "metrics.prom")
        manifest = {
            "schema": SERVING_MANIFEST_SCHEMA,
            "name": name,
            "kind": "fleet",
            "result": result_path.name,
            "trace_file": trace_path.name,
            "metrics": prom_path.name,
            "requests": report["requests"],
            "goodput_tokens_per_s": report["goodput_tokens_per_s"],
            "wall_seconds": report["wall_seconds"],
            "mesh": plans[0].mesh_dict(),
            "hbm": report["hbm"],
            "topology": topology,
            "fault_domains": domains,
            "failovers": report["failovers"]["total"],
            "hedges": report["hedges"],
            "degrade_level": report["degrade"]["level"],
            "journal": (None if jrn is None else jrn.path.name),
        }
        save_json(manifest, out / "serving_manifest.json")
        if verbose:
            print(f"[fleet] report written to {result_path}")
    return report
