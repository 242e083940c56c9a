"""Unified collective-benchmark driver.

Replaces the duplicated skeleton of the reference's benchmark scripts
(constants → init → per-(op,size) loop of {warmup, timed measurement, gather,
JSON dump}; e.g. ``collectives/1d/openmpi.py:204-300``,
``collectives/3d/dsccl.py:120-241``) with one driver over declarative sweep
configs.  "Which backend executes the collective" — the reference's
MPI/Gloo/oneCCL axis — becomes a named :class:`~dlbb_tpu.comm.variants.Variant`
(mesh topology / reduction strategy / combiner flags), recorded in the result
JSON's implementation field so stats curves stay comparable.

Timing semantics (SURVEY §7 "hard parts"): each op is a jitted shard_map
micro-program; warmup absorbs XLA compilation; each timed iteration is
``perf_counter``-bracketed ``fn(x).block_until_ready()`` — the async-dispatch
analogue of ``comm.Barrier(); MPI.Wtime(); op; Wtime()``
(``collectives/1d/openmpi.py:60-66``).

Result JSON schema is reference-compatible: the 1D stats reader accepts
``implementation`` (``collectives/1d/stats.py:167``), and field names /
filenames match ``collectives/1d/openmpi.py:273-295`` and
``collectives/3d/openmpi.py:205-233``.
"""

from __future__ import annotations

import contextlib
import os
import signal
import threading
import time
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dlbb_tpu.analysis.costmodel import COST_MODEL_VERSION
from dlbb_tpu.bench import schedule
from dlbb_tpu.comm.mesh import get_mesh
from dlbb_tpu.comm.ops import (
    COMPRESSED_OPS,
    MATMUL_OPS,
    build_allreduce_hierarchical,
    get_op,
    make_payload,
    payload_cache_key,
)
from dlbb_tpu.comm.variants import Variant, get_variant
from dlbb_tpu.obs import capture as obs_capture
from dlbb_tpu.obs import spans
from dlbb_tpu.obs.export import MetricsRegistry, sweep_metrics
from dlbb_tpu.resilience import inject
from dlbb_tpu.resilience.errors import (
    CorruptStats,
    DeadlineExceeded,
    exception_chain,
    is_transient,
)
from dlbb_tpu.resilience.journal import SweepJournal
from dlbb_tpu.resilience.preempt import PreemptionGuard
from dlbb_tpu.resilience.validate import (
    validate_result_json,
    validate_timings,
)
from dlbb_tpu.utils.compile_cache import sweep_scope
from dlbb_tpu.utils.config import save_json
from dlbb_tpu.utils.sysinfo import collect_system_info
from dlbb_tpu.utils.timing import resolve_timing_mode, time_collective

# Reference 1D sweep constants (``collectives/1d/openmpi.py:14-49``).
# NOTE the reference's size labels are 2x the actual fp16 payload
# ("16MB" = 4,194,304 elements x 2 B = 8 MiB — BASELINE.md); labels are kept
# verbatim for curve comparability, with honest byte counts in the JSON.
DATA_SIZES_1D: dict[str, int] = {
    "1KB": 256,
    "64KB": 16384,
    "1MB": 262144,
    "16MB": 4194304,
}

# Extension to the north-star 1 KB–1 GB curve (BASELINE.json metric).
EXTENDED_DATA_SIZES_1D: dict[str, int] = {
    **DATA_SIZES_1D,
    "64MB": 16777216,
    "256MB": 67108864,
    "1GB": 268435456,
}

OPERATIONS_1D: tuple[str, ...] = (
    "allreduce",
    "allgather",
    "broadcast",
    "gather",
    "scatter",
    "reduce",
    "alltoall",
    "sendrecv",
)

# Reference 3D sweep grid (``collectives/3d/openmpi.py:19-31``).
OPERATIONS_3D: tuple[str, ...] = (
    "allreduce",
    "allgather",
    "broadcast",
    "gather",
    "reduce",
)
GRID_3D: dict[str, Sequence[int]] = {
    "batch_sizes": (1, 8, 16, 32),
    "seq_lengths": (1, 2048, 4096, 8192),
    "hidden_dims": (2048, 4096),
}


@dataclass(frozen=True)
class Sweep1D:
    """1D collective microbenchmark sweep (flat element-count payloads)."""

    implementation: str = "xla_tpu"
    variant: str = "default"
    operations: tuple[str, ...] = OPERATIONS_1D
    data_sizes: tuple[tuple[str, int], ...] = tuple(DATA_SIZES_1D.items())
    rank_counts: tuple[int, ...] = (2, 4, 8)
    dtype: str = "bfloat16"
    warmup_iterations: int = 10
    measurement_iterations: int = 100
    output_dir: str = "results/1d"
    root: int = 0
    # "auto" | "per_iter" | "chained" — see dlbb_tpu.utils.timing
    timing_mode: str = "auto"
    # wall-time cap per config; iteration counts scale down to fit (actual
    # counts recorded in the result JSON) — for slow hosts / huge payloads
    max_config_seconds: Optional[float] = None
    # skip configs whose estimated global input+output footprint exceeds
    # this (host-simulated meshes hold every shard in one RAM pool)
    max_global_bytes: Optional[int] = None
    # skip configs whose result JSON already exists AND validates (parse +
    # finite stats, dlbb_tpu.resilience.validate) in output_dir — lets an
    # interrupted sweep (time-budgeted publisher runs, preemptions) pick up
    # where it left off instead of re-measuring the whole grid; an invalid
    # existing artifact (torn write) is re-measured with a warning
    resume: bool = False
    # pipelined execution engine (dlbb_tpu.bench.schedule): compile config
    # N+1..N+prefetch on a background thread between measurements.
    # None = auto (schedule.default_pipeline: only on hosts with spare
    # cores); False = serial debug mode (--no-pipeline), identical
    # schema/semantics; True forces the thread on
    pipeline: Optional[bool] = None
    prefetch: int = 2
    # persistent XLA compilation cache for this sweep: "auto" = on, in the
    # one directory utils/compile_cache.py resolves; None/"off" = off
    compile_cache: Optional[str] = "auto"
    # --- resilience knobs (docs/resilience.md) ---------------------------
    # fault-injection plan spec (dlbb_tpu.resilience.inject grammar);
    # None = DLBB_FAULT_PLAN env (itself usually unset -> no injection)
    fault_plan: Optional[str] = None
    # wall-clock watchdog per work unit, covering both the background
    # compile and the measurement: an overrun is abandoned + quarantined,
    # never blocks the pipeline drain (DLBB_UNIT_DEADLINE env default)
    unit_deadline_seconds: Optional[float] = None
    # bounded retry with exponential backoff for transient failures;
    # retried configs recompute from scratch and carry `retries: N`
    max_retries: int = 2
    retry_backoff_seconds: float = 0.05
    # append-only crash-safe sweep_journal.jsonl next to the artifacts
    journal: bool = True
    # --- observability knobs (docs/observability.md) ---------------------
    # host-side span trace (Chrome trace-event JSON, Perfetto-loadable):
    # a file path, or None = DLBB_SPANS env (usually unset -> disabled)
    span_trace: Optional[str] = None
    # per-config jax.profiler device captures on DEDICATED profile reps
    # excluded from the stats series and run outside the measurement
    # gate; a directory, or None = DLBB_DEVICE_TRACE env
    device_trace_dir: Optional[str] = None

    kind: str = "1d"


@dataclass(frozen=True)
class Sweep3D:
    """3D LLM-shaped tensor collective sweep over (batch, seq, hidden)."""

    implementation: str = "xla_tpu"
    variant: str = "default"
    operations: tuple[str, ...] = OPERATIONS_3D
    batch_sizes: tuple[int, ...] = tuple(GRID_3D["batch_sizes"])
    seq_lengths: tuple[int, ...] = tuple(GRID_3D["seq_lengths"])
    hidden_dims: tuple[int, ...] = tuple(GRID_3D["hidden_dims"])
    rank_counts: tuple[int, ...] = (4, 8)
    dtype: str = "bfloat16"
    warmup_iterations: int = 10
    measurement_iterations: int = 100
    output_dir: str = "results/3d"
    root: int = 0
    timing_mode: str = "auto"
    max_config_seconds: Optional[float] = None
    max_global_bytes: Optional[int] = None
    resume: bool = False
    # pipelined execution engine — see Sweep1D (None = host-auto)
    pipeline: Optional[bool] = None
    prefetch: int = 2
    compile_cache: Optional[str] = "auto"
    # resilience knobs — see Sweep1D / docs/resilience.md
    fault_plan: Optional[str] = None
    unit_deadline_seconds: Optional[float] = None
    max_retries: int = 2
    retry_backoff_seconds: float = 0.05
    journal: bool = True
    # observability knobs — see Sweep1D / docs/observability.md
    span_trace: Optional[str] = None
    device_trace_dir: Optional[str] = None

    kind: str = "3d"


def _dtype_of(name: str):
    return {
        "bfloat16": jnp.bfloat16,
        "float16": jnp.float16,
        "float32": jnp.float32,
    }[name]


def _impl_name(sweep) -> str:
    if sweep.variant and sweep.variant != "default":
        return f"{sweep.implementation}_{sweep.variant}"
    return sweep.implementation


def _gather_timings(local: list[float]) -> list[list[float]]:
    """Per-host × per-iteration timings, shaped like the reference's
    ``[rank][iteration]`` gather (``collectives/1d/openmpi.py:270``).

    Single-process (incl. the CPU-simulated mesh): one timing stream for the
    whole SPMD program — the schema keeps the 2D shape with one row.
    Multi-host: each host contributes its own dispatch timings via a host-side
    allgather, so load-imbalance across hosts is still computable.
    """
    if jax.process_count() == 1:
        return [local]
    from jax.experimental import multihost_utils

    arr = multihost_utils.process_allgather(np.asarray(local, dtype=np.float64))
    return np.asarray(arr).reshape(jax.process_count(), -1).tolist()


def _check_variant_flags(variant: Variant) -> None:
    """XLA flags (combiner thresholds etc.) are process-start options: they
    must already be in ``XLA_FLAGS`` before backend init.  Refuse to run —
    rather than silently mislabel results — if a flag variant was requested
    without its flags set (they are the launcher's job, see
    ``launch/launch_tpu_pod.sh``)."""
    import os

    missing = [f for f in variant.xla_flags if f not in os.environ.get("XLA_FLAGS", "")]
    if missing:
        raise RuntimeError(
            f"variant {variant.name!r} requires XLA_FLAGS to contain "
            f"{missing}; relaunch the process with them set (process-start "
            "option; cannot be applied after backend init)"
        )


_NULL_GATE = contextlib.nullcontext()


def _build_fn(op_name: str, variant: Variant, mesh, axes, root: int):
    if op_name == "allreduce" and variant.hierarchical:
        return build_allreduce_hierarchical(mesh, axes, root)
    if op_name in MATMUL_OPS and variant.overlap_schedule is not None:
        # decomposed collective-matmul schedule (docs/overlap.md) — same
        # dispatch convention as `hierarchical` above
        return get_op(op_name).build(
            mesh, axes, root, schedule=variant.overlap_schedule
        )
    if op_name in COMPRESSED_OPS and (
            variant.compression is not None
            or variant.accum_dtype is not None):
        # quantised-wire knobs (docs/compression.md) — dispatch like the
        # overlap schedule above; unset fields keep the op defaults
        kwargs: dict[str, Any] = {}
        if variant.compression is not None:
            kwargs["compression"] = variant.compression
        if variant.accum_dtype is not None:
            kwargs["accum_dtype"] = _dtype_of(variant.accum_dtype)
        return get_op(op_name).build(mesh, axes, root, **kwargs)
    return get_op(op_name).build(mesh, axes, root)


@dataclass
class _Planned:
    """One measurable sweep config, resolved at plan time."""

    num_ranks: int
    mesh: Any
    axes: tuple[str, ...]
    config: dict[str, Any]
    unit: schedule.WorkUnit
    payload_key: tuple
    # derived once here; _run_one must build the payload the unit's
    # executable was AOT-compiled against, never re-derive it
    num_elements: int
    payload_shape: Optional[tuple[int, ...]]


def _payload_geometry(
    sweep, config,
) -> tuple[int, Optional[tuple[int, ...]]]:
    """(num_elements, per-rank payload shape) of one config."""
    if sweep.kind == "1d":
        return config["num_elements"], None
    shape = (config["batch"], config["seq_len"], config["hidden_dim"])
    return int(np.prod(shape)), shape


def _plan_config(
    sweep, variant, mesh, axes, num_ranks, config,
    units, mode,
) -> _Planned:
    """Resolve one config's payload identity and compile work unit."""
    op = get_op(config["operation"])
    dtype = _dtype_of(sweep.dtype)
    num_elements, payload_shape = _payload_geometry(sweep, config)
    unit = schedule.plan_collective_unit(
        units,
        op=op,
        build_fn=lambda: _build_fn(
            config["operation"], variant, mesh, axes, sweep.root
        ),
        variant_name=variant.name,
        mesh=mesh,
        axes=axes,
        root=sweep.root,
        num_ranks=num_ranks,
        num_elements=num_elements,
        dtype=dtype,
        payload_shape=payload_shape,
        mode=mode,
        iterations=sweep.measurement_iterations,
        compiler_options=(
            dict(variant.compiler_options) if variant.compiler_options
            else None
        ),
    )
    pkey = payload_cache_key(
        op, mesh, axes, num_elements, dtype=dtype, shape=payload_shape
    )
    return _Planned(num_ranks, mesh, axes, config, unit, pkey,
                    num_elements, payload_shape)


def run_sweep(
    sweep: Sweep1D | Sweep3D,
    devices: Optional[Sequence] = None,
    verbose: bool = True,
) -> list[Path]:
    """Run a full sweep, writing one reference-schema JSON per config.

    The grid is walked twice: a *planning* pass resolves skips
    (rank gates, memory caps, ``resume``) and interns each measurable
    config's compile work unit — deduplicated by
    :func:`dlbb_tpu.bench.schedule.work_unit_key` — then the *measurement*
    pass consumes configs in plan order while a background thread compiles
    up to ``sweep.prefetch`` units ahead (``sweep.pipeline=False`` compiles
    inline through the same path).  Payloads and meshes are reused across
    configs that share them; a ``sweep_manifest.json`` with wall/compile
    totals lands next to the artifacts.

    Per-config failures — compile failures included — are contained:
    transient ones retry with exponential backoff (recomputing from
    scratch; the artifact records ``retries``), permanent ones are
    QUARANTINED — journaled ``failed`` with the exception chain in
    ``sweep_manifest.json`` — never silently skipped (hardened version of
    reference ``collectives/1d/openmpi.py:253-267``).  A per-unit
    wall-clock deadline (``unit_deadline_seconds``) watchdogs both the
    background compile and the measurement; SIGTERM lands as a graceful
    journaled stop a ``--resume`` run completes exactly
    (docs/resilience.md).
    """
    variant = get_variant(sweep.variant)
    _check_variant_flags(variant)
    impl = _impl_name(sweep)
    out_dir = Path(sweep.output_dir)
    written: list[Path] = []
    sysinfo = collect_system_info()
    n_avail = len(devices) if devices is not None else len(jax.devices())
    t_sweep0 = time.perf_counter()
    mode = resolve_timing_mode(sweep.timing_mode)

    # chaos-harness activation: an explicit sweep.fault_plan wins; else an
    # already-active plan (embedding harness) is left alone; else the env
    fault_spec = sweep.fault_plan
    if fault_spec is None and inject.active() is None:
        fault_spec = os.environ.get(inject.ENV_VAR, "").strip() or None

    # span tracing (docs/observability.md): scoped to the sweep when a
    # path is configured; a tracer an embedding harness (the CLI
    # --span-trace wrapper, a test) already opened WINS and collects this
    # sweep's spans — the tracing() scope is then a pure pass-through
    span_path = sweep.span_trace or spans.default_span_path()
    # everything from here — planning included — runs inside the sweep's
    # compilation-cache scope (on the simulated mesh the cache is on only
    # here; utils/compile_cache.py says why)
    with sweep_scope(sweep.compile_cache) as cache_dir, \
            spans.tracing(span_path,
                          meta={"kind": sweep.kind,
                                "implementation": impl,
                                "variant": variant.name}), \
            inject.plan_scope(fault_spec), PreemptionGuard() as guard:
        return _run_sweep_configured(
            sweep, variant, impl, out_dir, written, sysinfo, n_avail,
            devices, mode, cache_dir, t_sweep0, verbose, guard,
        )


def _collective_stop(requested: bool) -> bool:
    """Pod-uniform preemption decision: ANY host's SIGTERM stops every
    host at the same config boundary.  Called by every process for every
    config in the same order (like ``_resume_ok``), so the allgather
    schedule stays uniform — a per-host stop would send the surviving
    hosts into the next config's SPMD collective alone and hang the pod."""
    if jax.process_count() == 1:
        return requested
    from jax.experimental import multihost_utils

    bits = multihost_utils.process_allgather(
        np.asarray([requested], dtype=np.int32)
    )
    return bool(np.asarray(bits).any())


def _resolve_deadline(sweep) -> Optional[float]:
    """Per-work-unit wall-clock deadline: sweep field, else
    ``DLBB_UNIT_DEADLINE`` env, else off."""
    if sweep.unit_deadline_seconds is not None:
        return float(sweep.unit_deadline_seconds)
    env = os.environ.get("DLBB_UNIT_DEADLINE", "").strip()
    return float(env) if env else None


def _call_with_deadline(fn, deadline: Optional[float], label: str,
                        gate) -> Any:
    """Run ``fn`` under the measurement watchdog.

    With no deadline this is a direct call (zero threads, zero overhead).
    With one, ``fn(cancel)`` runs on a daemon thread joined for
    ``deadline`` seconds; an overrun ABANDONS the thread (it cannot be
    killed — it may be wedged inside a C extension), sets the ``cancel``
    event so the zombie — if it ever wakes — suppresses its artifact
    write (``_run_one`` checks it immediately before ``save_json``: a
    quarantined config must never be resurrected on disk by a thread the
    manifest says failed), degrades the measurement gate so the zombie
    can never block later configs or the compile worker, and raises
    :class:`DeadlineExceeded` for the quarantine path."""
    if deadline is None:
        return fn(None)
    box: dict[str, Any] = {}
    cancel = threading.Event()

    def target() -> None:
        try:
            box["value"] = fn(cancel)
        except BaseException as e:  # noqa: BLE001 — marshalled to caller
            box["error"] = e

    t = threading.Thread(target=target, daemon=True,
                         name=f"dlbb-measure-{label}")
    t.start()
    t.join(deadline)
    if t.is_alive():
        cancel.set()
        if gate is not None and hasattr(gate, "degrade"):
            gate.degrade()
        raise DeadlineExceeded(label, deadline, phase="measure")
    if "error" in box:
        raise box["error"]
    return box["value"]


def _run_sweep_configured(
    sweep, variant, impl, out_dir, written, sysinfo, n_avail, devices,
    mode, cache_dir, t_sweep0, verbose, guard: Optional[PreemptionGuard],
) -> list[Path]:
    journal = SweepJournal(
        out_dir,
        meta={"kind": sweep.kind, "implementation": impl,
              "variant": variant.name, "resume": sweep.resume,
              "fault_plan": getattr(inject.active(), "spec", None)},
        # multi-host: every process walks the same grid in the same order
        # (collective resume decisions), so one journal — the
        # coordinator's — records the run; per-host journals on a shared
        # filesystem would interleave duplicate lines
        enabled=sweep.journal and jax.process_index() == 0,
        # every journal event doubles as a span-trace instant (no-op
        # with no tracer active), so the trace and the fsync'd journal
        # tell the same story — docs/observability.md
        sink=spans.journal_sink,
    )
    # topology fingerprint: which fabric this sweep measured, journaled +
    # manifested (raises on a CPU backend nobody asked for — the no-chip
    # rule, utils/simulate.require_accelerator)
    from dlbb_tpu.utils.simulate import topology_record

    topology = topology_record()
    journal.event("topology", **topology)
    # ---- planning pass -------------------------------------------------
    plan: list[_Planned] = []
    units: "dict[tuple, schedule.WorkUnit]" = {}
    # per-sweep metrics registry (dlbb_tpu.obs.export): the config-outcome
    # counters below are registry-backed, so the manifest's `configs`
    # section and the metrics.prom textfile export come from one source
    metrics = MetricsRegistry()
    # every counter counts CONFIGS (a skipped rank count skips one whole
    # grid of them), so planned+skipped+resumed+failed adds up
    # (resume_invalid configs re-run, so they also land in
    # measured/failed — the counter is informational)
    grid_size = sum(1 for _ in _iter_configs(sweep))
    counts = metrics.labeled_counter(
        "sweep_configs", "outcome",
        initial=("resumed", "resume_invalid", "skipped_mem",
                 "skipped_ranks", "measured", "failed"),
        help="sweep configs by lifecycle outcome",
    )
    quarantined: list[dict[str, Any]] = []
    retries_total = 0
    abandoned_measurements = 0
    preempted = False
    with spans.span("plan", cat="sweep", grid_configs=grid_size,
                    rank_counts=str(tuple(sweep.rank_counts))):
        for num_ranks in sweep.rank_counts:
            if num_ranks > n_avail:
                counts["skipped_ranks"] += grid_size
                journal.event("rank-skip", num_ranks=num_ranks,
                              reason=f"{num_ranks} ranks > {n_avail} devices")
                if verbose:
                    print(
                        f"[skip] {num_ranks} ranks > {n_avail} devices "
                        "available"
                    )
                continue
            try:
                spec = variant.mesh_spec(num_ranks)
                mesh = get_mesh(spec, devices=devices)
            except ValueError as e:
                # e.g. fixed-shape variant (2x2x2) asked for an incompatible
                # rank count — skip this rank count, keep sweeping (parity
                # with the reference's per-config error-skip,
                # collectives/1d/openmpi.py:253)
                counts["skipped_ranks"] += grid_size
                journal.event("rank-skip", num_ranks=num_ranks,
                              reason=str(e))
                if verbose:
                    print(f"[skip] ranks={num_ranks}: {e}")
                continue
            axes = spec.axis_names
            for config in _iter_configs(sweep):
                fname = _result_filename(sweep, impl, num_ranks, config)
                # per-config containment covers the WHOLE planning of a
                # config (mem estimate included — it resolves the op name
                # too): e.g. an unknown op skips that config and keeps
                # sweeping, exactly like a measurement-time failure
                try:
                    if sweep.max_global_bytes is not None:
                        est = _estimate_global_bytes(sweep, config,
                                                     num_ranks)
                        if est > sweep.max_global_bytes:
                            counts["skipped_mem"] += 1
                            journal.event("skipped", config=fname,
                                          reason="memory-cap",
                                          estimated_bytes=est)
                            if verbose:
                                print(
                                    f"[skip-mem] {config['operation']} "
                                    f"ranks={num_ranks} {config}: "
                                    f"~{est / 2**30:.1f} GiB > cap "
                                    f"{sweep.max_global_bytes / 2**30:.1f}"
                                    " GiB"
                                )
                            continue
                    if sweep.resume:
                        existing = out_dir / fname
                        ok, why = _resume_ok(existing)
                        if ok:
                            counts["resumed"] += 1
                            journal.event("resume-valid", config=fname)
                            if verbose:
                                print(f"  [resume-skip] {existing.name}")
                            written.append(existing)
                            continue
                        if why != "missing":
                            # died-mid-write / corrupt artifact: NEVER
                            # trust it — re-measure (atomic overwrite)
                            # with a durable record of why
                            counts["resume_invalid"] += 1
                            journal.event("resume-invalid", config=fname,
                                          reason=why)
                            if verbose:
                                print(f"  [resume-INVALID] "
                                      f"{existing.name}: {why} — "
                                      "re-measuring")
                    plan.append(_plan_config(
                        sweep, variant, mesh, axes, num_ranks, config,
                        units, mode,
                    ))
                    journal.event("planned", config=fname)
                except Exception as e:  # noqa: BLE001 — containment
                    counts["failed"] += 1
                    quarantined.append({"config": fname,
                                        "phase": "planning",
                                        "retries": 0,
                                        **exception_chain(e)})
                    journal.event("failed", config=fname, phase="planning",
                                  error=str(e))
                    if verbose:
                        print(f"[error] {impl} {config}: planning "
                              f"failed: {e}")
                    continue

    # ---- measurement pass, compile-ahead overlapped --------------------
    # the gate keeps background compiles out of timed regions (see
    # CompileAheadScheduler); DLBB_COMPILE_OVERLAP=1 lifts it on hosts
    # with cores to spare
    measure_gate = (
        None if os.environ.get("DLBB_COMPILE_OVERLAP") == "1"
        else schedule.MeasureGate()
    )
    pipeline = (sweep.pipeline if sweep.pipeline is not None
                else schedule.default_pipeline())
    scheduler = schedule.CompileAheadScheduler(
        units.values(), prefetch=sweep.prefetch, pipeline=pipeline,
        measure_gate=measure_gate,
    )
    payloads = schedule.PayloadCache()
    # gated device-trace capture (docs/observability.md): when a capture
    # directory is configured, every measured config runs ONE dedicated
    # profile rep after its timed region, outside the measurement gate —
    # the rep never joins the stats series
    capture_dir = (sweep.device_trace_dir
                   or obs_capture.default_capture_dir())
    deadline = _resolve_deadline(sweep)
    if deadline is not None and jax.process_count() > 1:
        # a per-host abandon cannot be coordinated through a hung SPMD
        # collective (the other hosts are stuck inside it), and letting
        # one host quarantine + move on desynchronizes the pod's
        # collective schedule — the exact hang _resume_ok's allgather
        # exists to prevent.  The watchdog is single-process semantics;
        # disable it loudly on pods.
        journal.event("watchdog-disabled",
                      reason="multi-host run: per-host abandonment would "
                             "desynchronize the SPMD schedule")
        if verbose:
            print("[watchdog] unit deadline disabled: multi-host run "
                  "(per-host abandonment would desynchronize the pod)")
        deadline = None
    attempts = max(0, int(sweep.max_retries)) + 1
    scheduler.start()
    try:
        for entry in plan:
            fname = _result_filename(sweep, impl, entry.num_ranks,
                                     entry.config)
            if inject.fire("preempt"):
                # chaos harness: deliver a real SIGTERM to ourselves —
                # the PreemptionGuard turns it into the flag below
                os.kill(os.getpid(), signal.SIGTERM)
            if _collective_stop(guard is not None and guard.requested):
                preempted = True
                journal.event("preempted", config=fname,
                              signal=guard.signal_received)
                if verbose:
                    print(f"[preempt] SIGTERM received — stopping before "
                          f"{fname}; journal flushed, resume completes "
                          "the grid")
                break
            try:
                with spans.span("compile-wait", cat="sweep", config=fname):
                    unit = scheduler.get(entry.unit, deadline=deadline)
            except DeadlineExceeded as e:
                counts["failed"] += 1
                quarantined.append({
                    "config": fname, "label": entry.unit.label,
                    "phase": "compile", "retries": 0,
                    **exception_chain(e),
                })
                journal.event("failed", config=fname, phase="compile",
                              error=str(e))
                if verbose:
                    print(f"[watchdog] {impl} {fname}: {e}")
                continue
            if unit.error is not None:
                counts["failed"] += 1
                quarantined.append({
                    "config": fname, "label": unit.label,
                    "phase": "compile", "retries": 0,
                    **exception_chain(unit.error),
                })
                journal.event("failed", config=fname, phase="compile",
                              error=str(unit.error))
                if verbose:
                    print(f"[error] {impl} {entry.config}: compile failed "
                          f"for {unit.label}: {unit.error}")
                continue
            journal.event("started", config=fname)
            last_exc: Optional[BaseException] = None
            attempt = 0
            for attempt in range(attempts):
                try:
                    with spans.span(fname, cat="config",
                                    unit=unit.label, attempt=attempt):
                        path = _call_with_deadline(
                            lambda cancel: _run_one(
                                sweep, variant, impl, entry, out_dir,
                                sysinfo, verbose, mode=mode,
                                payloads=payloads,
                                measure_gate=measure_gate, retries=attempt,
                                unit=unit, cancel=cancel,
                                capture_dir=capture_dir, metrics=metrics,
                            ),
                            deadline, unit.label, measure_gate,
                        )
                    written.append(path)
                    counts["measured"] += 1
                    retries_total += attempt
                    journal.event("completed", config=fname,
                                  retries=attempt)
                    last_exc = None
                    break
                except DeadlineExceeded as e:
                    # a hang is not transient: the zombie thread still
                    # owns the payload cache (and possibly the gate) —
                    # hand later configs a fresh cache and quarantine
                    abandoned_measurements += 1
                    payloads = schedule.PayloadCache()
                    last_exc = e
                    break
                except Exception as e:  # noqa: BLE001 — sweep resilience
                    payloads.invalidate(entry.payload_key)
                    last_exc = e
                    if is_transient(e) and attempt < attempts - 1:
                        delay = (sweep.retry_backoff_seconds
                                 * (2 ** attempt))
                        journal.event("retry", config=fname,
                                      attempt=attempt + 1, error=str(e),
                                      backoff_seconds=delay)
                        if verbose:
                            print(f"[retry] {impl} {fname}: transient "
                                  f"{type(e).__name__}: {e} — backing off "
                                  f"{delay:.3f}s (attempt "
                                  f"{attempt + 1}/{attempts - 1})")
                        time.sleep(delay)
                        continue
                    break
            if last_exc is not None:
                counts["failed"] += 1
                quarantined.append({
                    "config": fname, "label": unit.label,
                    "phase": "measure", "retries": attempt,
                    **exception_chain(last_exc),
                })
                journal.event("failed", config=fname, phase="measure",
                              retries=attempt, error=str(last_exc))
                if verbose:
                    print(f"[error] {impl} {entry.config}: {last_exc}")
                    traceback.print_exception(
                        type(last_exc), last_exc, last_exc.__traceback__
                    )
                continue
    finally:
        scheduler.close()

    if plan or counts["resumed"]:
        unit_list = list(units.values())
        compiled = [u for u in unit_list if u.ready.is_set() and not u.error]
        tracer = spans.active()
        manifest_payload = {
            "kind": sweep.kind,
            "implementation": impl,
            "variant": variant.name,
            "topology": topology,
            # the α–β table version (analysis/costmodel.py) current when
            # this sweep ran: artifacts feed the fitted cost model
            # (ROADMAP item 2), and a fit must know which analytic seed
            # its residuals are priced against
            "cost_model_version": COST_MODEL_VERSION,
            "timing_mode": mode,
            "pipeline": scheduler.pipelined,
            "prefetch": sweep.prefetch,
            "wall_seconds": time.perf_counter() - t_sweep0,
            "compile_seconds_total": sum(
                u.compile_seconds for u in unit_list
            ),
            "compile_cache": {
                "dir": cache_dir,
                "enabled": cache_dir is not None,
                "persistent_hits": sum(
                    1 for u in compiled if u.persistent_cache_hit
                ),
                "persistent_misses": sum(
                    1 for u in compiled if not u.persistent_cache_hit
                ),
            },
            "work_units": {
                "planned_configs": len(plan),
                "unique": len(unit_list),
                "compile_failed": sum(
                    1 for u in unit_list if u.error is not None
                ),
            },
            "configs": dict(counts),
            "payload_cache": payloads.stats(),
            # where this sweep's wall clock went (docs/observability.md):
            # the span-trace path when tracing was on, and how many
            # dedicated profile reps were captured (all outside the
            # stats series by construction)
            "observability": {
                "span_trace": str(tracer.path) if tracer else None,
                "device_trace_dir": capture_dir,
                "device_captures": int(metrics.get("sweep_device_captures")),
            },
            "resilience": {
                "fault_plan": getattr(inject.active(), "spec", None),
                "unit_deadline_seconds": deadline,
                "max_retries": sweep.max_retries,
                "retries_total": retries_total,
                "quarantined": quarantined,
                "preempted": preempted,
                "watchdog": {
                    "abandoned_measurements": abandoned_measurements,
                    "abandoned_compiles": scheduler.abandoned,
                    "scheduler_wedged": scheduler.wedged,
                    "gate_degraded": bool(
                        getattr(measure_gate, "degraded", False)
                    ),
                },
            },
            "timestamp": time.time(),
        }
        schedule.write_sweep_manifest(out_dir, manifest_payload)
        # the Prometheus textfile export next to the manifest: the same
        # registry that backed the config counters, plus the manifest's
        # aggregate gauges (obs/export.sweep_metrics)
        sweep_metrics(manifest_payload, metrics).write_textfile(
            out_dir / "metrics.prom"
        )
        if tracer is not None:
            # checkpoint the trace now (stop() rewrites it at scope exit):
            # a crash after this point still leaves a loadable timeline
            tracer.finish()
    journal.event("sweep-end", preempted=preempted,
                  measured=counts["measured"], failed=counts["failed"])
    journal.close()
    return written


def _estimate_global_bytes(sweep, config, num_ranks: int) -> int:
    """Rough global input+output footprint of one config.

    Both multipliers come from the op registry's declared buffer kinds
    (``per_peer`` scales with P^2 x payload, ``per_rank`` with P) — not
    from a hard-coded op-name list, so a newly registered collective is
    estimated by its declaration instead of silently defaulting to the
    per-rank multiplier.  ``tests/test_bench.py`` pins every registry op's
    estimate."""
    op = get_op(config["operation"])
    n = _payload_geometry(sweep, config)[0]
    itemsize = jnp.dtype(_dtype_of(sweep.dtype)).itemsize
    p = num_ranks

    def mult(kind):
        return p * p if kind == "per_peer" else p

    transient = mult(op.transient_kind) if op.transient_kind else 0
    if (transient and op.name in MATMUL_OPS
            and get_variant(sweep.variant).overlap_schedule is not None):
        # the declared transient models the FUSED schedule (the gathered
        # activation / full partial product); the decomposed ring never
        # materialises it — one travelling chunk rides inside the in+out
        # estimate, so charging the fused footprint would skip exactly
        # the configs whose memory behavior the overlap variant exists
        # to demonstrate
        transient = 0
    return (mult(op.input_kind) + mult(op.output_kind) + transient) \
        * n * itemsize


def _iter_configs(sweep):
    if sweep.kind == "1d":
        for op in sweep.operations:
            for label, n in sweep.data_sizes:
                yield {"operation": op, "size_label": label, "num_elements": n}
    else:
        for op in sweep.operations:
            for b in sweep.batch_sizes:
                for s in sweep.seq_lengths:
                    for h in sweep.hidden_dims:
                        yield {
                            "operation": op,
                            "batch": b,
                            "seq_len": s,
                            "hidden_dim": h,
                        }


def _resume_ok(path: Path) -> tuple[bool, str]:
    """Whether a resume-mode sweep may skip this config, and why not.

    Existence is NOT enough: a process killed mid-write (or a torn legacy
    artifact) must be re-measured, so the existing JSON is validated —
    parses, carries the result schema, all timings finite
    (``dlbb_tpu.resilience.validate``) — before resume trusts it.

    Multi-host runs decide collectively: hosts have non-shared disks, and a
    run killed between one host's ``save_json`` and another's would leave
    them disagreeing — a per-host decision would send some hosts into the
    config's SPMD collective while others skip it, hanging the pod.  Every
    process calls this for every candidate config in the same order, so the
    allgather schedule stays uniform; the config re-runs everywhere unless
    ALL hosts already hold a VALID artifact (re-measuring on the hosts that
    had it just atomically overwrites)."""
    ok, why = validate_result_json(path)
    if jax.process_count() == 1:
        return ok, why
    from jax.experimental import multihost_utils

    bits = multihost_utils.process_allgather(
        np.asarray([ok], dtype=np.int32)
    )
    all_ok = bool(np.asarray(bits).all())
    if ok and not all_ok:
        why = "valid here but invalid/missing on another host"
    return all_ok, why


# filename tags for non-default dtypes: the bf16 corpus keeps the original
# (un-suffixed) names so the committed corpus stays stable; other dtypes of
# the same config coexist in the same directory (north-star curve is
# "fp32+bf16", BASELINE.json configs[1])
_DTYPE_FILE_TAG = {"float32": "fp32", "float16": "fp16"}


def _result_filename(sweep, impl: str, num_ranks: int, config) -> str:
    op_name = config["operation"]
    tag = _DTYPE_FILE_TAG.get(sweep.dtype)
    suffix = f"_{tag}" if tag else ""
    if sweep.kind == "1d":
        return (f"{impl}_{op_name}_ranks{num_ranks}_"
                f"{config['size_label']}{suffix}.json")
    b, s, h = config["batch"], config["seq_len"], config["hidden_dim"]
    return f"{impl}_{op_name}_ranks{num_ranks}_b{b}_s{s}_h{h}{suffix}.json"


def _run_one(
    sweep, variant, impl, planned: _Planned, out_dir, sysinfo, verbose,
    *, mode: str, payloads: schedule.PayloadCache,
    measure_gate=None, retries: int = 0,
    unit: Optional[schedule.WorkUnit] = None,
    cancel: Optional[threading.Event] = None,
    capture_dir: Optional[str] = None,
    metrics: Optional[MetricsRegistry] = None,
) -> Path:
    mesh, axes = planned.mesh, planned.axes
    num_ranks, config = planned.num_ranks, planned.config
    # the unit the SCHEDULER resolved: normally planned.unit itself, but
    # after a wedged compile worker it is a fresh inline-compiled clone
    # (schedule.CompileAheadScheduler.get) — never read planned.unit here
    if unit is None:
        unit = planned.unit
    op_name = config["operation"]
    op = get_op(op_name)
    dtype = _dtype_of(sweep.dtype)
    elem_bytes = jnp.dtype(dtype).itemsize
    # the plan-time geometry: what the unit's executable was compiled for
    num_elements = planned.num_elements
    payload_shape = planned.payload_shape

    def build_payload():
        return make_payload(
            op, mesh, axes, num_elements, dtype=dtype, shape=payload_shape
        )

    # chained timing DONATES its carry, so a cached payload would come back
    # deleted — only per-iter configs share payloads
    with spans.span("payload", cat="payload", label=unit.label):
        x = (build_payload() if mode == "chained"
             else payloads.get(planned.payload_key, build_payload))
    fn = unit.fn
    chain = op.make_chain(num_ranks) if op.make_chain is not None else None

    # chaos-harness sites, strictly BEFORE the timed region (zero
    # instructions inside it; see dlbb_tpu/resilience/inject.py)
    if inject.fire("exec-transient"):
        payloads.invalidate(planned.payload_key)
        raise inject.TransientFault(
            f"injected transient runtime failure for {unit.label}"
        )
    if inject.fire("exec-hang"):
        time.sleep(inject.param("hang_seconds"))

    # holding the gate keeps the compile-ahead worker out of the timed
    # region — background compilation contends for the host cores the
    # measured program runs on (measurement-honesty invariant; see
    # schedule.CompileAheadScheduler).  The span brackets the region from
    # the OUTSIDE (its clock reads happen before the gate is taken and
    # after it is released).
    try:
        with spans.span("measure", cat="measure", label=unit.label,
                        mode=mode), \
                (measure_gate if measure_gate is not None else _NULL_GATE):
            local, timing_meta = time_collective(
                fn, x,
                chain=chain,
                warmup=sweep.warmup_iterations,
                iterations=sweep.measurement_iterations,
                mode=mode,
                max_seconds=sweep.max_config_seconds,
                compiler_options=(
                    dict(variant.compiler_options)
                    if variant.compiler_options else None
                ),
                executable=None if unit.chained else unit.executable,
                chained_loop=unit.executable if unit.chained else None,
            )
    except BaseException:
        # a failure mid-measurement may have already donated the cached
        # payload (the per-iter plausibility fallback) — drop the entry
        # so no later config is handed a deleted array
        payloads.invalidate(planned.payload_key)
        raise
    if timing_meta.get("timing_mode") == "chained" and mode != "chained":
        # the per-iter plausibility fallback donated the (cached) payload
        payloads.invalidate(planned.payload_key)
    if inject.fire("stats-nan"):
        # chaos harness: poison the timing vector AFTER the timed region —
        # the pre-write validation below must refuse to publish it
        local = list(local)
        local[0] = float("nan")
        if len(local) > 1:
            local[-1] = float("inf")
    timings = _gather_timings(local)
    ok, why = validate_timings(timings)
    if not ok:
        # NaN/Inf must never reach an artifact; CorruptStats is transient
        # so the retry loop re-measures from scratch
        payloads.invalidate(planned.payload_key)
        raise CorruptStats(
            f"{unit.label}: {why} — refusing to write the artifact"
        )

    # gated device-trace capture (docs/observability.md): one DEDICATED
    # profile rep on a FRESH payload, after the timed region and outside
    # the measurement gate — its timing never joins `timings`, and a
    # capture failure never fails the config (error lands in the
    # metadata instead)
    capture_meta = None
    if capture_dir:
        fname_cap = _result_filename(sweep, impl, num_ranks, config)
        with spans.span("device-capture", cat="capture", label=unit.label):
            capture_meta = obs_capture.capture_device_trace(
                fn, build_payload, capture_dir,
                label=fname_cap.rsplit(".", 1)[0],
            )
        # only SUCCESSFUL captures count — a contained failure (profiler
        # held elsewhere) left no trace on disk and must not inflate the
        # manifest's device_captures
        if metrics is not None and "error" not in capture_meta:
            metrics.inc("sweep_device_captures",
                        help="dedicated profile reps captured "
                             "(excluded from stats)")
        elif metrics is not None:
            # a contained failure is invisible in the stats series by
            # design — the labelled counter (folded into metrics.prom)
            # is where a fleet notices its captures silently dying
            metrics.inc("obs_device_capture_failures",
                        reason=capture_meta.get("error_kind", "unknown"),
                        help="contained device-capture failures "
                             "(error recorded in the result JSON)")

    # the first config that WRITES an artifact reports the compile its
    # work unit paid for (see WorkUnit.compile_reported); later sharers
    # paid nothing (in-process dedup) and report a cache hit
    first_consumer = not unit.compile_reported
    compile_seconds = unit.compile_seconds if first_consumer else 0.0
    compile_cache_hit = (unit.persistent_cache_hit if first_consumer
                         else True)

    result: dict[str, Any] = {
        "implementation": impl,
        "mpi_implementation": impl,  # legacy key the 1D stats reader prefers
        "operation": op_name,
        "num_ranks": num_ranks,
        "num_elements": num_elements,
        "dtype": sweep.dtype,
        "warmup_iterations": sweep.warmup_iterations,
        "measurement_iterations": sweep.measurement_iterations,
        # compile accounting (dlbb_tpu.bench.schedule): what THIS config
        # paid — 0.0 with a hit when its program was already compiled
        # (in-process work-unit dedup or the persistent XLA cache)
        "compile_seconds": compile_seconds,
        "compile_cache_hit": compile_cache_hit,
        # transient-failure retries this config burned before succeeding
        # (0 = first attempt measured clean); retried attempts recompute
        # from scratch, so nothing of a failed attempt is in `timings`
        "retries": retries,
        **timing_meta,
        "timings": timings,
        "variant": variant.name,
        # wire compression of the quantised micro-ops (docs/compression.md)
        # — consumed by the stats pipeline's analytic bytes_on_wire column
        **({"compression": variant.compression or "int8"}
           if op_name in COMPRESSED_OPS else {}),
        **dict(variant.extra),
        "mesh_shape": list(mesh.devices.shape),
        "mesh_axis_names": list(mesh.axis_names),
        "payload_bytes_per_rank": num_elements * elem_bytes,
        "timestamp": time.time(),
        "system_info": sysinfo,
        # device-capture metadata (trace path + the excluded_from_stats
        # marker); absent on untraced runs — every stats field above is
        # identical either way (the obs_smoke equivalence gate)
        **({"device_trace": capture_meta} if capture_meta else {}),
    }

    if sweep.kind == "1d":
        result["data_size_name"] = config["size_label"]
    else:
        b, s, h = config["batch"], config["seq_len"], config["hidden_dim"]
        tensor_size_bytes = num_elements * 2  # reported as-bf16, like the
        # reference (``collectives/3d/openmpi.py:167-168``)
        result["tensor_shape"] = {"batch": b, "seq_len": s, "hidden_dim": h}
        result["tensor_size_bytes"] = tensor_size_bytes
        result["tensor_size_mb"] = tensor_size_bytes / 2**20

    if cancel is not None and cancel.is_set():
        # the watchdog abandoned this thread and QUARANTINED the config —
        # a late-waking zombie must not resurrect it on disk (resume and
        # the stats pipeline would trust an artifact measured concurrently
        # with later configs, contradicting the manifest's failed record)
        raise DeadlineExceeded(unit.label, 0.0, phase="measure (zombie "
                               "write suppressed after abandonment)")
    fname = _result_filename(sweep, impl, num_ranks, config)
    with spans.span("write", cat="io", file=fname):
        path = save_json(result, out_dir / fname)
    unit.compile_reported = True
    if verbose:
        # the same median the stats pipeline publishes
        # (stats1d.calculate_statistics: np.median over the flattened
        # per-host matrix), labeled with the mode actually used — a mean
        # over chained chunk means is not comparable to a per-iter mean
        median_ms = float(np.median(np.asarray(timings))) * 1e3
        print(f"  [{impl}] {fname}: median {median_ms:.3f} ms "
              f"({timing_meta.get('timing_mode', mode)})")
    return path
