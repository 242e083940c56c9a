"""Tracing / profiling subsystem.

The reference has no in-repo profiler — its observability is wall-clock
timers plus *library* debug tracing switched on via env vars
(``CCL_LOG_LEVEL=debug``, ``I_MPI_DEBUG=10``, ``mpirun --report-bindings``;
reference ``collectives/3d/launch_dsccl.sh:34``,
``collectives/3d/launch_mpiccl.sh:12,17-18``).  The TPU-native equivalent is
the XLA profiler: ``jax.profiler`` emits xplane traces (per-op device
timelines, HLO cost analysis, memory viewer) viewable in TensorBoard or
Perfetto — strictly more information than the reference's text logs.

Surface, mirroring the reference's env-switched design:

- ``maybe_trace(trace_dir)`` — context manager; no-op when ``trace_dir`` is
  None/empty.  ``DLBB_TRACE_DIR`` env is the default, so any benchmark can
  be traced without changing its invocation (the CCL_LOG_LEVEL analogue).
- ``annotate(name)`` — host-side named region (``TraceAnnotation``) so
  warmup/measurement phases are distinguishable in the timeline
  (``utils/timing.py`` wraps its warmup/measure loops in these; every
  ``obs/spans.py`` span of an active tracer opens one, which is how
  ``train/loop.py`` and ``serve/engine.py`` get theirs).

This module is one of the two sanctioned profiler API homes (with
``dlbb_tpu/obs/capture.py``): the ``profiler-in-timed-region`` comm-lint
rule forbids profiler calls inside any timed region elsewhere in the
repo, and the runtime observability layer — host-side span tracing,
gated per-config device capture, the predicted-vs-measured calibration
gate — lives in ``dlbb_tpu/obs/`` (``docs/observability.md``).
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

# jax is imported inside each function: the stats subcommands are
# numpy-only by design (cli.py lazy-imports per branch) and must not pay
# the jax import just because this module is on their import path.

__all__ = ["maybe_trace", "annotate", "default_trace_dir"]


def default_trace_dir() -> Optional[str]:
    """The env-switched default (``DLBB_TRACE_DIR``), or None."""
    return os.environ.get("DLBB_TRACE_DIR") or None


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str] = None) -> Iterator[Optional[str]]:
    """Trace everything inside the block to ``trace_dir`` (xplane format).

    ``trace_dir=None`` falls back to ``DLBB_TRACE_DIR``; if that is unset
    too, the block runs untraced at zero cost.  Yields the resolved trace
    directory (or None) so callers can record it in result metadata.
    """
    trace_dir = trace_dir or default_trace_dir()
    if not trace_dir:
        yield None
        return
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        yield trace_dir


def annotate(name: str, **args):
    """Named host-side region, visible in the trace timeline; ``args``
    become the event's stats there.  ``obs/spans.py`` opens one for
    every span of an active tracer — program code calls ``spans.span``,
    not this."""
    import jax

    return jax.profiler.TraceAnnotation(name, **args)
