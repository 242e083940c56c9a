"""Device-honest benchmark timing.

Two modes (SURVEY §7 "timing semantics under async dispatch"):

- **per_iter** — the default.  ``jax.block_until_ready`` waits for device
  completion on the CPU and on a locally attached TPU, so each call is
  timed bracketed by it: the analogue of the reference's
  ``Barrier(); Wtime(); op; Wtime()`` (``collectives/1d/openmpi.py:60-66``).

- **chained** — for a backend whose ``block_until_ready`` returns on
  *enqueue*, or whose per-dispatch cost would swamp the op.  Forces a data
  dependency (fetch a scalar derived from the result) and amortises the
  dispatch: run M iterations of ``chain(op(x))`` inside ONE jitted
  ``lax.fori_loop``, fetch, subtract the calibrated fetch baseline, divide
  by M.  The chain glue feeds each iteration's output back as the next
  input so XLA cannot hoist the op out of the loop.

``resolve_timing_mode("auto")`` is per_iter unless ``DLBB_TIMING_MODE``
says otherwise; ``time_collective`` falls back to chained on its own when
the per_iter numbers fail the forced-completion plausibility check.

Warmup and measurement loops run under ``jax.profiler`` trace
annotations (``utils/profiling.annotate``), so a captured device trace
(``--trace`` / the obs device captures) distinguishes warmup reps from
measurement reps in the timeline.  The annotations wrap the LOOPS, never
the inside of a per-iteration ``perf_counter`` bracket — this module is
the sanctioned timing API (exempt from the timed-region lint rules) and
must never import the obs or chaos-harness packages: the zero-overhead
pins in ``tests/test_obs.py`` and the chaos suite assert, statically,
that nothing here can add instructions to a timed region.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dlbb_tpu.utils.profiling import annotate


def resolve_timing_mode(mode: str = "auto") -> str:
    if mode != "auto":
        return mode
    return os.environ.get("DLBB_TIMING_MODE") or "per_iter"


def force_completion(x: Any) -> float:
    """Force completion of ``x`` via a minimal data-dependent fetch: a
    device-side reduction to one scalar, then fetch.  The reduction depends
    on EVERY shard of a sharded result (a single-element slice would only
    force shard 0's producer), while only a scalar crosses the wire (a
    ``ravel()[0]`` fetch would all-gather the whole payload first).  The
    reduction's own device cost appears identically in
    ``calibrate_fetch_overhead`` and is subtracted by the chained-timing
    math; the value itself is irrelevant (NaN/inf are fine)."""
    leaf = jax.tree.leaves(x)[0]
    return float(jnp.sum(leaf))


_force = force_completion


def single_iteration_estimate(
    fn, x, trials: int = 3, op_args: tuple = (), agg: str = "median"
) -> float:
    """True-completion time of one ``fn(*op_args, x)`` call: wall time of a
    data-dependent scalar fetch on the result, minus the calibrated fetch
    overhead.  Works on any backend — the fetch cannot be satisfied by
    enqueue — so it cross-validates both timing modes (at one-dispatch
    granularity; see scripts/timing_crosscheck.py).

    ``agg``: "median" for a central estimate (cross-check artifacts), "min"
    for a stall-robust lower bound (the plausibility check — on a loaded
    host any single trial can absorb a multi-ms scheduler stall, and an
    inflated estimate there would falsely condemn honest per-iter
    timings)."""
    out = fn(*op_args, x)
    _force(out)  # compile + warm
    overhead = calibrate_fetch_overhead(out)
    samples = []
    for _ in range(trials):
        t0 = time.perf_counter()
        _force(fn(*op_args, x))
        samples.append(time.perf_counter() - t0)
    samples.sort()
    pick = samples[0] if agg == "min" else samples[len(samples) // 2]
    return max(pick - overhead, 0.0)


def per_iter_plausible(median_block: float, forced: float,
                       ratio: float = 0.2, floor: float = 0.02) -> bool:
    """Is a ``block_until_ready``-based median believable against the
    forced-completion time of one iteration?  Implausible = the op
    "finishes" in under ``ratio`` of its true completion time while the
    true time is above ``floor`` — the signature of a backend whose
    block_until_ready returns on enqueue, where per-iter timings would be
    dispatch latencies, not device times.

    ``floor`` is 20 ms: below that, eager-dispatch overhead on a loaded
    host is the same magnitude as the probe itself (no reliable signal)."""
    if forced < floor:
        return True  # too fast to distinguish dispatch from completion
    return median_block >= ratio * forced


def calibrate_fetch_overhead(x: Any, trials: int = 5) -> float:
    """Roundtrip cost of the forcing fetch on an already-ready value (min of
    ``trials``)."""
    _force(x)  # ensure ready
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        _force(x)
        best = min(best, time.perf_counter() - t0)
    return best


def time_fn_per_iter(
    fn, *args, warmup: int, iterations: int,
    max_seconds: Optional[float] = None,
) -> tuple[list[float], int, bool]:
    """Per-iteration block_until_ready timing (sync backends).

    ``max_seconds`` caps the *measurement* wall time: after the compile
    warmup, one probe iteration estimates the per-iteration cost and the
    warmup/iteration counts are scaled down to fit the budget (floor of 3
    measured iterations, never more than requested).  The actual counts are
    returned/recorded so result artifacts never overstate the sample size.
    Returns ``(timings, warmup_run, clamped)``.
    """
    with annotate("warmup"):
        jax.block_until_ready(fn(*args))  # compile + first warmup
        warmup_run = 1
        clamped = False
        if max_seconds is not None:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            probe = time.perf_counter() - t0
            warmup_run += 1
            # when even the 3-sample floor cannot fit the budget (huge
            # payloads on the single-core simulated host), drop the floor
            # to 1 — one honest recorded sample beats minutes of
            # over-budget re-runs
            floor = 1 if 3 * probe > max_seconds else 3
            affordable = max(floor, int(max_seconds / max(probe, 1e-9)))
            if affordable < warmup + iterations:
                clamped = True
                warmup = min(warmup, max(0, affordable // 10))
                iterations = min(iterations, max(floor, affordable - warmup))
        for _ in range(max(0, warmup - warmup_run)):
            jax.block_until_ready(fn(*args))
            warmup_run += 1
    out = []
    with annotate("measure"):
        for _ in range(iterations):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            out.append(time.perf_counter() - t0)
    return out, warmup_run, clamped


def chained_chunk_size(iterations: int, chunk_size: Optional[int] = None) -> int:
    """The chunk size ``time_fn_chained`` will use for ``iterations``.

    Factored out so AOT compilers of the chained loop (the sweep scheduler,
    ``dlbb_tpu.bench.schedule``) bake in exactly the chunk size the
    measurement will divide by — a mismatch would silently rescale every
    sample."""
    if chunk_size is not None:
        return chunk_size
    return max(1, min(10, iterations // 10 or 1))


def build_chained_loop(
    op: Callable,
    chain: Optional[Callable] = None,
    chunk_size: int = 10,
) -> Callable:
    """The jitted ``chunk_size``-iteration fori_loop around ``op`` that
    chained timing measures — exposed so it can be AOT-lowered/compiled
    ahead of the measurement (compile-ahead sweeps) with identical
    semantics, donation included.
    """

    def body(args, c):
        out = op(*args, c)
        return chain(out) if chain is not None else out

    # the carry (x0) is DONATED: chained timing feeds each chunk's output
    # back as the next chunk's input anyway, and without donation XLA must
    # keep input and output carries simultaneously resident — at train-step
    # scale (TrainState = params + Adam moments) that doubles state HBM and
    # OOMs configs whose training loop itself fits (measured: 1B/b8/s512
    # Adam-bf16m trains, then OOMed in this timing loop before the fix)
    return jax.jit(
        lambda args, x0: jax.lax.fori_loop(
            0, chunk_size, lambda i, c: body(args, c), x0
        ),
        donate_argnums=(1,),
    )


def time_fn_chained(
    op: Callable,
    x: Any,
    chain: Optional[Callable] = None,
    warmup: int = 1,
    iterations: int = 100,
    chunk_size: Optional[int] = None,
    op_args: tuple = (),
    compiler_options: Optional[dict[str, str]] = None,
    max_seconds: Optional[float] = None,
    looped: Optional[Callable] = None,
) -> tuple[list[float], dict[str, Any], Any]:
    """Chunked fori_loop timing (the chained mode of the module docstring).

    ``op`` is invoked as ``op(*op_args, carry)``.  Anything large the op
    needs (model params!) MUST go through ``op_args``, not a closure: arrays
    closed over by the jitted loop are embedded as compile-time constants,
    which at model scale stalls compilation indefinitely.

    ``looped`` short-circuits loop construction with a pre-built (possibly
    pre-compiled) executable from :func:`build_chained_loop` — it MUST have
    been built with this call's chunk size (:func:`chained_chunk_size`) and
    ``compiler_options`` already applied.

    Returns ``(samples, meta, carry)``: each sample is the estimated
    per-iteration time of one chunk, ``(chunk_wall - fetch_overhead) /
    chunk_size``; ``len(samples) == iterations // chunk_size`` (≥ 1).
    The input ``x`` is DONATED to the loop (see the comment in
    :func:`build_chained_loop`) — callers must use the returned final
    ``carry`` instead of ``x`` afterwards.
    """
    chunk_size = chained_chunk_size(iterations, chunk_size)
    chunks = max(1, iterations // chunk_size)

    if looped is None:
        looped = build_chained_loop(op, chain, chunk_size)
        if compiler_options:
            # variant-tuned compilation (e.g. combiner passes disabled) —
            # the options must go on the outer loop jit, which subsumes
            # the op
            looped = looped.lower(op_args, x).compile(
                compiler_options=dict(compiler_options)
            )

    warm_wall = float("inf")
    with annotate("warmup"):
        for _ in range(max(1, warmup)):
            t0 = time.perf_counter()
            x = looped(op_args, x)  # rebind: donated input is now invalid
            _force(x)
            warm_wall = min(warm_wall, time.perf_counter() - t0)
        overhead = calibrate_fetch_overhead(x)

    clamped = False
    if max_seconds is not None and warm_wall > 0:
        affordable = max(1, int(max_seconds / warm_wall))
        if affordable < chunks:
            chunks, clamped = affordable, True

    samples = []
    with annotate("measure"):
        for _ in range(chunks):
            t0 = time.perf_counter()
            x = looped(op_args, x)
            _force(x)
            wall = time.perf_counter() - t0
            samples.append(max(wall - overhead, 0.0) / chunk_size)
    meta = {
        "timing_mode": "chained",
        "timing_method": (
            "jitted lax.fori_loop chunks + data-dependent fetch, "
            "fetch overhead subtracted"
        ),
        "timing_granularity": f"chunked({chunk_size})",
        # each sample is a chunk MEAN: downstream p95/p99 measure the
        # spread of chunk means, not per-iteration tail latencies
        "percentile_caveat": (
            f"percentiles are over {chunk_size}-iteration chunk means, "
            "not per-iteration tails"
        ),
        "chunks": chunks,
        "chunk_size": chunk_size,
        "fetch_overhead_s": overhead,
    }
    if clamped:
        meta.update(
            measurement_iterations=chunks * chunk_size,
            time_budget_s=max_seconds,
            time_budget_clamped=True,
        )
    return samples, meta, x


def time_collective(
    op: Callable,
    x: Any,
    chain: Optional[Callable] = None,
    warmup: int = 10,
    iterations: int = 100,
    mode: str = "auto",
    max_seconds: Optional[float] = None,
    compiler_options: Optional[dict[str, str]] = None,
    executable: Optional[Callable] = None,
    chained_loop: Optional[Callable] = None,
) -> tuple[list[float], dict[str, Any]]:
    """Unified entry: returns (per-iteration timings, metadata).

    In chained mode (incl. the per-iter implausibility fallback) ``x`` is
    DONATED to the timing loop and must
    not be touched by the caller afterwards — the sweep driver builds a
    fresh payload per config, so nothing here returns the carry.

    ``max_seconds`` bounds the measurement wall time per config (slow hosts /
    huge payloads): iteration counts are scaled down to fit and the *actual*
    counts land in the metadata, overriding the sweep's nominal ones in the
    result JSON.  ``compiler_options`` compiles the op (or the chained loop
    around it) with variant-specific XLA options.

    Compile-ahead callers (``dlbb_tpu.bench.schedule``) pass what they
    already compiled: ``executable`` replaces ``op`` for per-iter timing
    (it must be the same program, ``compiler_options`` included), and
    ``chained_loop`` replaces the loop construction in chained mode (built
    via :func:`build_chained_loop` with :func:`chained_chunk_size` of this
    call's ``iterations``).  The traceable ``op`` is still required: the
    per-iter implausibility fallback below re-traces it inside a fresh
    loop, which a compiled executable cannot survive.  Timing semantics
    are unchanged either way — warmup absorbed compilation before, and
    with a pre-compiled program the same warmup calls simply find nothing
    left to absorb.
    """
    mode = resolve_timing_mode(mode)
    if mode == "per_iter":
        if executable is not None:
            op_exec = executable
        else:
            op_exec = op
            if compiler_options and hasattr(op, "lower"):
                # keep the traceable `op` around: the chained fallback below
                # jit-traces it, which a Compiled cannot survive
                op_exec = op.lower(x).compile(
                    compiler_options=dict(compiler_options)
                )
        timings, warmup_run, clamped = time_fn_per_iter(
            op_exec, x, warmup=warmup, iterations=iterations,
            max_seconds=max_seconds,
        )
        # Plausibility floor: if block_until_ready "finished" in a small
        # fraction of the true data-dependent completion time, it returned
        # on enqueue and per-iter numbers are dispatch latencies — warn and
        # fall back to chained timing.  A dispatch is ms-scale at most, so
        # a >= 50 ms median cannot be enqueue-only and the probe is skipped
        # (saves iterations on huge budgeted configs; recorded as skipped,
        # not as a fake validation).
        meta = {
            "timing_mode": "per_iter",
            "timing_method": "time.perf_counter() + jax.block_until_ready()",
            "timing_granularity": "per_iteration",
        }
        if not timings:  # iterations=0: nothing to sanity-check
            return timings, meta
        sorted_t = sorted(timings)
        median = sorted_t[len(sorted_t) // 2]
        if median >= 0.05:
            meta["forced_completion_probe_skipped"] = True
        else:
            forced = single_iteration_estimate(op_exec, x, trials=3,
                                               agg="min")
            if not per_iter_plausible(median, forced):
                import warnings

                warnings.warn(
                    f"per-iteration timing implausible (median "
                    f"{median * 1e3:.3f} ms vs forced completion "
                    f"{forced * 1e3:.3f} ms): block_until_ready appears to "
                    "return on enqueue; switching to chained timing",
                    stacklevel=2,
                )
                samples, cmeta, _ = time_fn_chained(
                    op, x, chain=chain, warmup=1, iterations=iterations,
                    compiler_options=compiler_options,
                    max_seconds=max_seconds, looped=chained_loop,
                )
                cmeta.update(
                    per_iter_sanity_failed=True,
                    per_iter_median_s=median,
                    forced_completion_s=forced,
                )
                return samples, cmeta
            meta["forced_completion_s"] = forced
        if clamped:
            meta.update(
                measurement_iterations=len(timings),
                warmup_iterations=warmup_run,
                time_budget_s=max_seconds,
                time_budget_clamped=True,
            )
        return timings, meta
    samples, cmeta, _ = time_fn_chained(
        op, x, chain=chain, warmup=max(1, warmup // 10),
        iterations=iterations, compiler_options=compiler_options,
        max_seconds=max_seconds, looped=chained_loop,
    )
    return samples, cmeta
