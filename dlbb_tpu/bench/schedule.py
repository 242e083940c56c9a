"""Pipelined sweep execution engine (compile-ahead scheduler).

The sweep driver (``dlbb_tpu.bench.runner``) is the hot path of the whole
framework — every published curve in ``results/`` flows through it — and
before this module it was strictly serial: each config traced and compiled
its jitted shard_map program while the device sat idle, and every re-run
paid full recompilation again.  XLA compilation releases the GIL and JAX
ships a persistent compilation cache, so compile time can be overlapped
with measurement and amortised across runs without touching timing
semantics.  Three mechanisms, all orthogonal to *how* a config is timed:

- **Work units** — the sweep grid is walked once up front and deduplicated
  by :func:`work_unit_key` ``(op, variant, mesh, payload aval,
  compiler_options, timing fingerprint)``.  Configs that share a key share
  one traced/compiled program; configs that differ in ANY key component
  (same shape under a different variant, say) never do.
- **Compile-ahead** — :class:`CompileAheadScheduler` AOT-lowers and
  compiles work unit N+1..N+k on a background thread while unit N's
  configs are being measured on the main thread.  Lowering uses abstract
  payloads (:func:`dlbb_tpu.comm.ops.payload_aval`), so the background
  thread never materialises a (possibly GiB-scale) payload.  ``k`` is the
  sweep's ``prefetch``; ``pipeline=False`` degrades to inline
  compile-on-demand through the *same* code path (the ``--no-pipeline``
  debug mode).
- **Persistent compilation cache** — configured in one place,
  ``dlbb_tpu.utils.compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` or
  ``<checkout>/.jax_cache``; ``--compile-cache off`` disables it for one
  sweep), so publisher re-runs and ``resume`` sweeps deserialise
  executables instead of recompiling.  Hits/misses are observed through
  ``jax.monitoring`` events and recorded per work unit — each result
  artifact carries honest ``compile_seconds`` / ``compile_cache_hit``
  fields, and each sweep a ``sweep_manifest.json`` with the totals.

Payloads are cached too (:class:`PayloadCache`): ops that share
``(input_kind, shape, dtype, sharding, seed)`` at the same rank count reuse
one device array instead of regenerating it per config — except in chained
timing, which DONATES its carry (``utils/timing.py``); donated entries are
invalidated so a deleted array can never be handed to the next config.

Measurement semantics are bit-for-bit those of the serial driver: per_iter
vs chained selection, donation, and the plausibility probe all live in
``utils/timing.py`` and receive the pre-compiled executable through
explicit parameters (``executable`` / ``chained_loop``) rather than a
changed code path.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Iterable, Optional, Sequence

import jax

from dlbb_tpu.comm.ops import CollectiveOp, payload_aval
from dlbb_tpu.obs import spans
from dlbb_tpu.resilience import inject
from dlbb_tpu.resilience.errors import DeadlineExceeded, InjectedFault
from dlbb_tpu.utils.compile_cache import CACHE_EVENTS
from dlbb_tpu.utils.timing import build_chained_loop, chained_chunk_size


def default_pipeline() -> bool:
    """Whether the compile-ahead thread should run on this host.

    The measurement gate means a background compile can only overlap the
    sweep's un-timed work, and that overlap needs spare host cores to be
    a win: on the 2-core simulated-mesh box the thread is a measured net
    tax (BENCH_sweep.json: pipelined cold ~0.6x serial on compile-heavy
    grids — pure contention + scheduling overhead), while on multi-core
    TPU hosts the compile runs on otherwise-idle cores.  Auto therefore
    enables the thread only with >= 4 cores; ``DLBB_SWEEP_PIPELINE=1/0``
    forces either way, and lifting the gate (``DLBB_COMPILE_OVERLAP=1``)
    implies the host has cores to burn.  Serial mode keeps every other
    engine win (work-unit dedup, payload/mesh reuse, the persistent
    cache, compile accounting).
    """
    env = os.environ.get("DLBB_SWEEP_PIPELINE")
    if env is not None:
        return env.lower() not in ("0", "off", "false", "no")
    if os.environ.get("DLBB_COMPILE_OVERLAP") == "1":
        return True
    return (os.cpu_count() or 1) >= 4


# Serialises trace+lower+compile so persistent-cache hit events attribute
# to the unit being compiled.  XLA compilation would release the GIL, but
# correct per-unit cache accounting beats compile/compile parallelism —
# the pipeline's win is compile/*measure* overlap, which the lock never
# blocks (the measuring thread does not compile).
_COMPILE_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# work units
# ---------------------------------------------------------------------------


def work_unit_key(
    op: CollectiveOp,
    variant_name: str,
    mesh,
    axes: Sequence[str],
    root: int,
    aval: jax.ShapeDtypeStruct,
    mode: str,
    iterations: int,
    compiler_options: Optional[dict[str, str]],
) -> tuple:
    """Dedup identity of one compiled program.

    Everything that changes the traced/compiled artifact is in the key:
    the op, the variant *name* (two variants can share a mesh shape yet
    build different programs — hierarchical vs joint reduction — so the
    name itself is a component, never just its mesh spec), the mesh
    topology and device identity, the payload aval, per-computation
    compiler options, and the timing fingerprint (chained mode bakes the
    chunk size into the compiled loop).
    """
    timing_fp = (
        ("chained", chained_chunk_size(iterations))
        if mode == "chained" else ("per_iter",)
    )
    return (
        op.name,
        variant_name,
        tuple(mesh.devices.shape),
        tuple(mesh.axis_names),
        tuple(id(d) for d in mesh.devices.flat),
        tuple(axes),
        root,
        tuple(aval.shape),
        str(aval.dtype),
        tuple(sorted(compiler_options.items())) if compiler_options else (),
        timing_fp,
    )


@dataclass
class WorkUnit:
    """One deduplicated (trace, lower, compile) job and its products."""

    key: tuple
    build: Callable[[], tuple[Callable, Callable]]  # -> (traceable, compiled)
    label: str = ""
    chained: bool = False
    fn: Optional[Callable] = None          # traceable jitted program
    executable: Optional[Callable] = None  # compiled program / chained loop
    compile_seconds: float = 0.0
    persistent_cache_hit: bool = False
    error: Optional[Exception] = None
    consumers: int = 0  # configs measured against this unit (main thread)
    # set once a consumer has RECORDED the compile cost in an artifact —
    # attribution must go to the first config that actually writes one,
    # not the first that merely starts (its measurement may fail before
    # saving, which would make the compile cost vanish and later sharers
    # claim a cache hit for a program compiled fresh this process)
    compile_reported: bool = False
    ready: threading.Event = field(default_factory=threading.Event)


def _compile_unit(unit: WorkUnit, locked: bool = True) -> None:
    """Trace + lower + compile one unit; idempotent; never raises (build
    failures are contained in ``unit.error`` so one poisoned unit skips its
    configs while the pipeline drains).

    ``locked=False`` skips :data:`_COMPILE_LOCK` — only for the
    wedged-worker fallback (:meth:`CompileAheadScheduler.get`), where the
    zombie worker holds the lock inside a hung compile forever; the cost
    is per-unit persistent-cache-hit attribution for that compile, never
    correctness."""
    if unit.ready.is_set():
        return
    try:
        CACHE_EVENTS.ensure_registered()
        if inject.fire("compile-fail"):
            raise InjectedFault(f"injected compile failure for {unit.label}")
        if inject.fire("compile-hang"):
            # models a wedged XLA compile: the watchdog (deadline-aware
            # get()) must abandon + quarantine without blocking the drain
            time.sleep(inject.param("hang_seconds"))
        # the span wraps lock wait + compile (docs/observability.md) —
        # its clock reads sit OUTSIDE the compile_seconds bracket, so
        # tracing never inflates the compile accounting
        with spans.span("compile", cat="compile", label=unit.label,
                        chained=unit.chained), \
                (_COMPILE_LOCK if locked else contextlib.nullcontext()):
            hits0, misses0 = CACHE_EVENTS.snapshot()
            t0 = time.perf_counter()
            unit.fn, unit.executable = unit.build()
            unit.compile_seconds = time.perf_counter() - t0
            hits1, misses1 = CACHE_EVENTS.snapshot()
        # a hit claim requires BOTH a hit event and no miss in the window:
        # under DLBB_COMPILE_OVERLAP=1 a main-thread compile (the per-iter
        # fallback's loop jit, a first forced-completion reduction) can
        # fire events concurrently, and a fresh compile always fires its
        # own miss — requiring miss-free windows turns any such collision
        # into an under-reported hit, never a fabricated one
        unit.persistent_cache_hit = hits1 > hits0 and misses1 == misses0
    except Exception as e:  # noqa: BLE001 — containment is the contract
        unit.error = e
    finally:
        unit.ready.set()


def plan_collective_unit(
    units: "OrderedDict[tuple, WorkUnit]",
    op: CollectiveOp,
    build_fn: Callable[[], Callable],
    variant_name: str,
    mesh,
    axes: Sequence[str],
    root: int,
    num_ranks: int,
    num_elements: int,
    dtype,
    payload_shape: Optional[tuple[int, ...]],
    mode: str,
    iterations: int,
    compiler_options: Optional[dict[str, str]],
) -> WorkUnit:
    """Intern the work unit for one sweep config into ``units``.

    ``build_fn`` constructs the traceable jitted program (the runner's op
    builder); the returned unit's ``build`` wraps it with AOT lowering
    against the abstract payload and — in chained mode — the jitted timing
    loop with the chunk size :func:`chained_chunk_size` will pick for
    ``iterations``, so the compiled artifact is exactly what the
    measurement executes.
    """
    aval = payload_aval(op, mesh, axes, num_elements, dtype=dtype,
                        shape=payload_shape)
    key = work_unit_key(op, variant_name, mesh, axes, root, aval, mode,
                        iterations, compiler_options)
    unit = units.get(key)
    if unit is not None:
        return unit
    chained = mode == "chained"
    options = dict(compiler_options) if compiler_options else None

    def build() -> tuple[Callable, Callable]:
        fn = build_fn()
        if chained:
            chain = (op.make_chain(num_ranks)
                     if op.make_chain is not None else None)
            looped = build_chained_loop(
                fn, chain, chained_chunk_size(iterations)
            )
            lowered = looped.lower((), aval)
        else:
            lowered = fn.lower(aval)
        compiled = (lowered.compile(compiler_options=options)
                    if options else lowered.compile())
        return fn, compiled

    unit = WorkUnit(
        key=key,
        build=build,
        label=f"{op.name}/{variant_name}/r{num_ranks}/"
              f"{'x'.join(map(str, aval.shape))}/{aval.dtype}",
        chained=chained,
    )
    units[key] = unit
    return unit


# ---------------------------------------------------------------------------
# measurement gate
# ---------------------------------------------------------------------------


class MeasureGate:
    """The measurement-honesty mutex between timed regions and background
    compiles — a ``threading.Lock`` with two resilience affordances:

    - **timeout acquisition** (:meth:`acquire`): the compile worker polls
      instead of blocking forever, so a measurement thread abandoned by
      the watchdog while holding the gate can never wedge the pipeline
      drain;
    - **degraded mode** (:meth:`degrade`): once the watchdog has
      abandoned a hung unit, the gate may be held by a zombie thread for
      an unbounded time.  Rather than stalling every remaining config
      behind it, acquisition falls through ungated after a bounded wait.
      Degradation is one-way and recorded in the sweep manifest
      (``watchdog.gate_degraded``) — the measurement-honesty claim of
      post-hang configs is weakened (a zombie may still be doing device
      work) and the artifact trail says so.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.degraded = False
        self._held_here = threading.local()

    def degrade(self) -> None:
        self.degraded = True

    def acquire(self, timeout: float = 0.25) -> bool:
        return self._lock.acquire(timeout=timeout)

    def release(self) -> None:
        self._lock.release()

    def __enter__(self) -> "MeasureGate":
        # bounded wait once degraded; patient (but interruptible-by-
        # degradation) wait otherwise
        while True:
            if self._lock.acquire(timeout=0.25):
                self._held_here.held = True
                return self
            if self.degraded:
                self._held_here.held = False
                return self

    def __exit__(self, *exc) -> None:
        if getattr(self._held_here, "held", False):
            self._held_here.held = False
            self._lock.release()


# ---------------------------------------------------------------------------
# compile-ahead scheduler
# ---------------------------------------------------------------------------


class CompileAheadScheduler:
    """Bounded producer/consumer compiler.

    The worker thread compiles units in first-use order, at most
    ``prefetch`` ahead of consumption; :meth:`get` blocks until the
    requested unit is ready and frees a prefetch slot the first time each
    unit is consumed.  With ``pipeline=False`` no thread is started and
    :meth:`get` compiles inline — same code path, same metadata, zero
    overlap (the ``--no-pipeline`` debugging mode).
    """

    def __init__(
        self,
        units: Iterable[WorkUnit],
        prefetch: int = 2,
        pipeline: bool = True,
        measure_gate: "Optional[MeasureGate | threading.Lock]" = None,
    ) -> None:
        self._units = list(units)
        self._pipeline = bool(pipeline) and bool(self._units)
        # prefetch slots: the unit being measured + k compiled ahead
        self._slots = threading.Semaphore(max(1, int(prefetch)) + 1)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Measurement-honesty invariant: the worker never compiles while
        # the consumer holds this lock (i.e. while a config is being
        # TIMED).  A background compile contends for host cores with the
        # measured program — on the 2-core simulated-mesh host it was
        # measured to double tiny-op medians — so compiles overlap the
        # sweep's un-timed work instead: payload generation (seconds at
        # the GiB labels), result IO, resume allgathers, planning.
        # ``DLBB_COMPILE_OVERLAP=1`` disables the gate for hosts with
        # cores to spare.
        self._measure_gate = measure_gate
        # watchdog state: a deadline overrun abandoned a compile — the
        # worker thread may be permanently stuck inside it
        self.wedged = False
        self.abandoned = 0
        # unit keys whose compile already blew a deadline: NEVER re-run
        # those builds inline (a deterministically hanging build would
        # hang the consumer thread, where no watchdog applies)
        self._abandoned_keys: set[tuple] = set()

    @property
    def pipelined(self) -> bool:
        return self._pipeline

    def start(self) -> None:
        if not self._pipeline or self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._worker, name="dlbb-compile-ahead", daemon=True
        )
        self._thread.start()

    def _acquire_gate(self) -> bool:
        """Poll the gate with stop/degradation checks — an abandoned
        measurement thread holding the gate must never wedge the drain.
        Returns whether the gate is actually held (False = proceed
        ungated: stopping, or gate degraded by the watchdog)."""
        gate = self._measure_gate
        if gate is None:
            return False
        while not self._stop.is_set():
            if gate.acquire(timeout=0.25):
                return True
            if getattr(gate, "degraded", False):
                return False
        return False

    def _worker(self) -> None:
        try:
            for unit in self._units:
                if self._stop.is_set():
                    break
                self._slots.acquire()
                if self._stop.is_set():
                    break
                held = self._acquire_gate()
                try:
                    if not self._stop.is_set():
                        _compile_unit(unit)
                finally:
                    if held:
                        self._measure_gate.release()
        finally:
            # a unit left un-ready would hang get() forever — fail closed
            for unit in self._units:
                if not unit.ready.is_set():
                    unit.error = RuntimeError(
                        "compile-ahead worker exited before compiling "
                        f"unit {unit.label or unit.key}"
                    )
                    unit.ready.set()

    def get(self, unit: WorkUnit,
            deadline: Optional[float] = None) -> WorkUnit:
        """Block until ``unit`` is compiled (or failed); inline-compile in
        serial mode.  Call once per consuming config.

        ``deadline`` (pipelined mode only) is the watchdog: a compile
        still not ready after that many seconds raises
        :class:`~dlbb_tpu.resilience.errors.DeadlineExceeded`, marks the
        scheduler wedged, and degrades the measurement gate — the hung
        compile is abandoned on its daemon thread, never joined.  After a
        wedge, later units compile inline on the consumer thread (the
        zombie worker still holds :data:`_COMPILE_LOCK`, so the inline
        path skips it and forfeits cache-hit attribution, not
        correctness).  A serial (``pipeline=False``) scheduler compiles
        on the calling thread, where a hung compile cannot be abandoned —
        the deadline only covers what runs on the worker."""
        if not self._pipeline:
            _compile_unit(unit)
        elif self.wedged and not unit.ready.is_set():
            if unit.key in self._abandoned_keys:
                # this exact build already blew the deadline once —
                # re-running it inline would hang the consumer thread
                # (every config sharing the unit quarantines instead)
                raise DeadlineExceeded(
                    unit.label or str(unit.key), float(deadline or 0.0),
                    phase="compile (unit previously abandoned)",
                )
            clone = WorkUnit(
                key=unit.key, build=unit.build,
                label=f"{unit.label}/inline-after-wedge",
                chained=unit.chained,
            )
            _compile_unit(clone, locked=False)
            clone.consumers += 1
            return clone
        else:
            if not unit.ready.wait(deadline):
                self.wedged = True
                self.abandoned += 1
                self._abandoned_keys.add(unit.key)
                gate = self._measure_gate
                if gate is not None and hasattr(gate, "degrade"):
                    gate.degrade()
                raise DeadlineExceeded(
                    unit.label or str(unit.key), float(deadline or 0.0),
                    phase="compile",
                )
            if unit.consumers == 0:
                self._slots.release()
        unit.consumers += 1
        return unit

    def close(self) -> None:
        self._stop.set()
        self._slots.release()  # unblock a worker waiting for a slot
        if self._thread is not None:
            if self.wedged:
                # the worker may be stuck inside an abandoned compile
                # forever; bounded join, then leave the daemon thread
                # behind (recorded in the manifest via `wedged`).  The
                # cache-config reset that follows in run_sweep's finally
                # can race the zombie's eventual cache write — accepted:
                # the alternative is a sweep that never returns.
                self._thread.join(timeout=5.0)
            else:
                # join WITHOUT timeout: run_sweep's finally resets the
                # process-wide persistent-cache config right after
                # close(), and doing that while a compile is still in
                # flight races its cache write (serial mode would be
                # equally stuck inside the same wedged compile, so no
                # liveness is lost by waiting)
                self._thread.join()
            self._thread = None


# ---------------------------------------------------------------------------
# payload cache
# ---------------------------------------------------------------------------

_PAYLOAD_CACHE_BYTES_ENV = "DLBB_PAYLOAD_CACHE_BYTES"
DEFAULT_PAYLOAD_CACHE_BYTES = 1 << 30  # 1 GiB of device payloads


class PayloadCache:
    """Byte-budgeted LRU of device payloads keyed by
    :func:`dlbb_tpu.comm.ops.payload_cache_key`.

    Ops that share (shape, dtype, sharding, seed) reuse one array instead
    of re-running the rank-seeded host RNG + device_put per config.
    Entries a measurement DONATED (chained timing, or the per-iter
    plausibility fallback) must be :meth:`invalidate`-d — the array is
    deleted and unusable.  Oversized payloads (> budget) are passed
    through uncached so the 1 GB-label sweeps keep their
    build-measure-free memory profile.
    """

    def __init__(self, max_bytes: Optional[int] = None) -> None:
        if max_bytes is None:
            max_bytes = int(os.environ.get(
                _PAYLOAD_CACHE_BYTES_ENV, DEFAULT_PAYLOAD_CACHE_BYTES
            ))
        self.max_bytes = max_bytes
        self._entries: OrderedDict[tuple, Any] = OrderedDict()
        self._nbytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: tuple, build: Callable[[], Any]) -> Any:
        arr = self._entries.get(key)
        if arr is not None:
            self.hits += 1
            self._entries.move_to_end(key)
            return arr
        self.misses += 1
        arr = build()
        nbytes = int(getattr(arr, "nbytes", 0))
        if nbytes > self.max_bytes:
            return arr  # uncached pass-through
        self._entries[key] = arr
        self._nbytes += nbytes
        while self._nbytes > self.max_bytes and len(self._entries) > 1:
            _, old = self._entries.popitem(last=False)
            self._nbytes -= int(getattr(old, "nbytes", 0))
            self.evictions += 1
        return arr

    def invalidate(self, key: tuple) -> None:
        arr = self._entries.pop(key, None)
        if arr is not None:
            self._nbytes -= int(getattr(arr, "nbytes", 0))

    def stats(self) -> dict[str, int]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "resident_bytes": self._nbytes,
            "budget_bytes": self.max_bytes,
        }


# ---------------------------------------------------------------------------
# sweep manifest
# ---------------------------------------------------------------------------

MANIFEST_NAME = "sweep_manifest.json"
MANIFEST_SCHEMA = "dlbb_sweep_manifest_v1"


def write_sweep_manifest(out_dir, payload: dict[str, Any]):
    """Write the per-sweep engine manifest (wall/compile totals, cache and
    dedup accounting) next to the result artifacts.  Overwrites the
    previous sweep's manifest in the same directory — it documents the
    most recent run; the per-config compile fields in each result JSON are
    the durable record."""
    from dlbb_tpu.utils.config import save_json

    payload = {"schema": MANIFEST_SCHEMA, **payload}
    return save_json(payload, Path(out_dir) / MANIFEST_NAME)
