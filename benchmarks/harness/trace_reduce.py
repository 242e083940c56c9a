"""From the profiler's ``.xplane.pb`` to busy time, op shares and idle
gaps named by what the host was doing.

The arithmetic (:func:`reduce_timeline`) works on plain tuples and is
checked in the tests on a hand-made event list; :func:`load_xplane` is
the only part that knows the profiler's file.

- busy: the union of the intervals in which an operation ran on a
  device, clipped to the traced window, averaged over the devices;
- an op's time: its self time (its duration less the events nested in
  it on the same line), so a ``while`` that wraps a whole scan does not
  count its body twice;
- an idle gap belongs to the innermost host span open at that time, or
  to ``_no_host_span_``.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Any, Iterator, Optional, Sequence

WINDOW_SPAN = "bench-window"
SYNC_SPAN = "bench-sync"
NO_SPAN = "_no_host_span_"

Event = tuple[str, float, float]          # name, start_s, duration_s
Span = tuple[str, float, float]           # name, start_s, end_s


_HLO = re.compile(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")
_TARGET = re.compile(r'custom_call_target="([\w.\-]+)"')


def clean_name(text: str) -> str:
    """An op as printed and matched: the profiler names a TPU op by its
    whole HLO line (``%fusion.3 = bf16[8,512]{...} fusion(...)``); keep
    the instruction's name, its first result shape and, for a custom
    call, its target (``tpu_custom_call`` is a Pallas kernel), in the
    characters a metric name may have."""
    m = _HLO.match(text)
    target = _TARGET.search(text) if m else None
    if m:
        text = " ".join(filter(None, (m.group(1), m.group(2))))
    label = re.sub(r"[^A-Za-z0-9_.\-]+", "_", text).strip("_")
    if target:
        label += "." + target.group(1)
    return label[:64]


def _clip(events: Sequence[Event], t0: float, t1: float) -> list[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e - s))
    return out


def busy_union(events: Sequence[Event]) -> list[tuple[float, float]]:
    """The merged intervals in which any event ran."""
    merged: list[list[float]] = []
    for _n, s, d in sorted(events, key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return [(a, b) for a, b in merged]


def self_times(events: Sequence[Event]) -> dict[str, float]:
    """Seconds per op name, nested events taken out of their parents."""
    totals: dict[str, float] = {}
    stack: list[list[Any]] = []           # [name, end, self]

    def close() -> None:
        name, _end, own = stack.pop()
        totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    while stack:
        close()
    return totals


def _innermost(spans: Sequence[Span]) -> list[Span]:
    """Flatten nested host spans to disjoint pieces, each named by the
    innermost span open in it."""
    edges = sorted({t for _n, s, e in spans for t in (s, e)})
    pieces: list[Span] = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        open_now = [(s, n) for n, s, e in spans if s <= mid < e]
        if open_now:
            pieces.append((max(open_now)[1], a, b))
    return pieces


def attribute_gaps(gaps: Sequence[tuple[float, float]],
                   spans: Sequence[Span]) -> dict[str, float]:
    out: dict[str, float] = {}
    pieces = _innermost(spans)
    for g0, g1 in gaps:
        covered = 0.0
        for name, a, b in pieces:
            lap = min(g1, b) - max(g0, a)
            if lap > 0:
                out[name] = out.get(name, 0.0) + lap
                covered += lap
        rest = (g1 - g0) - covered
        if rest > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + rest
    return out


def reduce_timeline(device_events: dict[str, Sequence[Event]],
                    window: tuple[float, float],
                    host_spans: Sequence[Span] = ()) -> dict[str, Any]:
    """``device_events``: device name -> op events.  Returns ``busy_s``
    (mean over devices), ``window_s``, ``op_seconds`` (self time by op,
    mean over devices) and ``idle_gaps`` (the first device's gaps by host
    span: one program runs over all chips, so they idle together)."""
    t0, t1 = window
    n = len(device_events)
    if n == 0 or t1 <= t0:
        return {}
    host_spans = [sp for sp in host_spans if sp[2] > t0 and sp[1] < t1]
    busy = 0.0
    ops: dict[str, float] = {}
    gaps_named: dict[str, float] = {}
    for i, dev in enumerate(sorted(device_events)):
        events = _clip(device_events[dev], t0, t1)
        union = busy_union(events)
        busy += sum(b - a for a, b in union)
        for name, secs in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + secs / n
        if i == 0:
            edges = [t0] + [t for ab in union for t in ab] + [t1]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
            gaps_named = attribute_gaps(gaps, host_spans)
    return {"busy_s": busy / n, "window_s": t1 - t0,
            "op_seconds": ops, "idle_gaps": gaps_named}


def top(items: dict[str, float], k: int = 10) -> list[list[Any]]:
    return [[name, secs] for name, secs in
            sorted(items.items(), key=lambda kv: -kv[1])[:k]]


# -- the profiler's side ------------------------------------------------------


@contextlib.contextmanager
def profiling(log_dir: str) -> Iterator[None]:
    """Trace the block, with the traced window marked on the host line.
    No Python tracer: its events swamp the file and slow the host."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def load_xplane(log_dir: str) -> Optional[dict[str, Any]]:
    """Device op events by device, the traced window and the host's
    annotations, in seconds on the trace's clock; None if the profiler
    left no file or no device line."""
    import jax

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    data = jax.profiler.ProfileData.from_file(files[-1])
    device_events: dict[str, list[Event]] = {}
    annotations: list[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_events[plane.name] = [
                        (clean_name(ev.name), ev.start_ns * 1e-9,
                         ev.duration_ns * 1e-9) for ev in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench-", "step-")):
                        annotations.append((
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(s, e) for n, s, e in annotations if n == WINDOW_SPAN]
    if not device_events or not windows:
        return None
    return {"device_events": device_events, "window": windows[-1],
            "annotations": annotations}


def reduce_profile(log_dir: str, host_spans: Sequence[Span] = (),
                   sync_at: Optional[float] = None) -> dict[str, Any]:
    """The traced window reduced.  ``host_spans`` are on the caller's
    clock; ``sync_at`` is that clock's reading inside the ``bench-sync``
    annotation, which puts them on the trace's clock."""
    loaded = load_xplane(log_dir)
    if loaded is None:
        return {}
    spans = [s for s in loaded["annotations"]
             if s[0] not in (WINDOW_SPAN, SYNC_SPAN)]
    syncs = [s for s in loaded["annotations"] if s[0] == SYNC_SPAN]
    if host_spans and syncs and sync_at is not None:
        shift = syncs[-1][1] - sync_at
        spans += [(n, s + shift, e + shift) for n, s, e in host_spans]
    return reduce_timeline(loaded["device_events"], loaded["window"], spans)
