"""The device's idle time of the traced slice split by WHY the device
waited, and a decode unit's own device time (``part``):

- ``launch``, ``notice``, ``host``: shares of the traced window.  Every
  call of a jitted serving program is a numbered span of the program
  (``serve/engine.py::ServingEngine._launch``: ``serve-decode-dispatch``,
  ``serve-prefill-chunk``, ``serve-prefix-attach`` or ``serve-launch``,
  with the arguments ``launch`` and ``program``), and the device runs the
  programs in the order they were launched, so the ``jit_serve_*`` events
  of the first device's "XLA Modules" line are a run of consecutive
  launches.  For every idle gap ``[g0, g1]`` of that device (the gaps of
  ``trace_reduce.reduce_timeline``: the complement of the busy union in
  the window), with N the launched program that starts at ``g1`` (or
  runs across it) and ``l0`` the start of N's launch span:

  - **launch**: the part of the gap at or after ``l0``: the host had
    called the program and the device had not started it;
  - **notice**: of the part before ``l0``, what lies inside a
    ``serve-*-sync`` span: the device was free and the host still
    blocked in its wait;
  - **host**: the rest: the scheduler's own work between two programs.

  The three add up to the window's idle time.  A gap with no launched
  program after it has no ``l0``: all of it is notice or host.
- ``decode_step_ms``: the median, over the decode units launched inside
  the window (``serve-decode-dispatch`` spans), of the paired
  execution's duration over the unit's ``k`` steps: what the device took
  for one decode step, with nothing that was queued before it.

Pairing: the executions in the profile, in time order, are launches
``o, o + 1, ...`` for ONE offset ``o``: the one at which every execution
carries its launch's ``program`` name and the waits agree (a
``serve-*-sync`` span ends when the program of the ``launch`` it names
has ended).  So a launch made before the window whose execution falls
inside it pairs, and launches whose executions the profile did not catch
stay unpaired.  Executions of a ``jit_serve_*`` program ahead of the
first one that pairs (no launch in the file) are left out and counted;
programs that no launch names (a device-side slice, a convert) are never
paired and stay device busy time.  What was paired is printed on
standard error once a run.

Clocks.  The spans are placed on the profile's HOST clock by the
``bench-sync`` mark, as ``readers/lin_roofline.py::spans_in_window``
places them (its answer is also what "launched inside the window" means
here); the profile's own annotations of the same spans agree with that
to 10-40 us (my chip run, PR 35).  The profile's DEVICE line, though,
leads its host line by a lag that differs from run to run (0.3 to 1.5 ms
seen): programs start before the call that launched them begins.  The
pairs bound that lead from both sides: no program starts before its
call begins, and none ends after the wait for it has ended.  The spans
are moved onto the device's clock by the LEAST lead that the first rule
allows (``clock_skew_s``), so ``launch`` is time beyond the fastest
launch of the slice, a lower bound, and ``notice`` an upper bound; the
room between the two rules (``clock_slack_s``, 0.87 ms in both cells)
is what the fastest launch and the fastest notice take together, and up
to that much a gap may belong to ``launch`` in place of ``notice``.
``host`` and the sum of the other two move little with it.

None where the span file has no ``launch`` argument (a program older
than the spans), the profile no "XLA Modules" line, or nothing pairs: a
parent reads nothing, not zero.

By hand, for a cell whose metrics are not declared: ``python3 -m
benchmarks.readers.launch_gaps <cell>`` from the checkout's root prints
the whole split of the cell's last traced run, and the count and median
duration of every program in its window.
"""

from __future__ import annotations

import bisect
import json
import re
import statistics
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Any, NamedTuple, Optional, Sequence

from benchmarks.harness import trace_reduce
from benchmarks.readers import named_ops
from benchmarks.readers.lin_roofline import spans_in_window

SHARES = ("launch", "notice", "host")
SERVE_PROGRAM = "jit_serve_"
DECODE_LAUNCH = "serve-decode-dispatch"
# a wait ends within this of the end of the program it waited for (in
# the median over a slice's waits: the profile's two clocks may differ by
# milliseconds, a notice takes about one); an offset that pairs programs
# with other launches of the same names misses by whole programs
SETTLE_S = 0.1

_SYNC = re.compile(r"^serve-[a-z]+-sync$")
_CACHE: dict[str, Optional[dict[str, Any]]] = {}

# one span on the profile's clock: name, start_s, end_s, arguments
Placed = tuple[str, float, float, dict[str, Any]]


class Launch(NamedTuple):
    """One launch span of the program's span file."""

    number: int
    program: str
    start: float            # of the span, on the profile's host clock
    span: str
    args: dict[str, Any]


class Pair(NamedTuple):
    """A launch and the execution it caused, on the device's clock."""

    start: float
    end: float
    called: float           # the launch span's start, the lead taken out
    launch: Launch


def placed_spans(run, loaded: dict[str, Any]) -> list[Placed]:
    """Every closed span of the run's span file on the profile's clock
    (the file and its ``bench-sync`` mark are there:
    ``spans_in_window`` found spans with them)."""
    path = Path(named_ops.profile_path(run)).parents[3] / "spans.json"
    with open(path) as f:
        data = json.load(f)
    events = data["traceEvents"] if isinstance(data, dict) else data
    mark = [s for n, s, _e in loaded["host"]
            if n == trace_reduce.SYNC_SPAN][-1]
    own = [ev["ts"] * 1e-6 for ev in events
           if ev.get("name") == trace_reduce.SYNC_SPAN][-1]
    shift = mark - own
    open_: dict[Any, list[dict[str, Any]]] = {}
    out: list[Placed] = []
    for ev in events:
        if ev.get("ph") == "B":
            open_.setdefault(ev.get("tid"), []).append(ev)
        elif ev.get("ph") == "E" and open_.get(ev.get("tid")):
            begun = open_[ev.get("tid")].pop()
            out.append((begun["name"], begun["ts"] * 1e-6 + shift,
                        ev["ts"] * 1e-6 + shift, begun.get("args", {})))
    return out


def pair(launches: Sequence[tuple[int, str]],
         executions: Sequence[tuple[str, float, float]],
         waited: dict[int, float]) -> Optional[tuple[int, int]]:
    """``launches``: ``(number, program)`` by number; ``executions``:
    ``(program, start, duration)`` by start; ``waited``: launch number ->
    end of the ``serve-*-sync`` span that waited for it.  Returns
    ``(dropped, offset)``: execution ``dropped + j`` is launch
    ``offset + j`` (every execution that is left has a launch: the span
    file holds the whole run, the profile a slice of it).  An offset fits
    if every execution carries its launch's program name and the waits
    agree: a wait ends when the program it waited for has ended, give or
    take ``SETTLE_S`` in the median (one stalled wait does not count
    against it).  The fewest leading executions are dropped, and of the
    offsets that fit the one the waits agree with best is taken; None if
    nothing fits."""
    for dropped in range(len(executions)):
        rest = executions[dropped:]
        best: Optional[tuple[float, int]] = None
        for offset in range(len(launches) - len(rest), -1, -1):
            if any(program != launches[offset + j][1]
                   for j, (program, _s, _d) in enumerate(rest)):
                continue
            apart = [abs(waited[launches[offset + j][0]] - (start + dur))
                     for j, (_p, start, dur) in enumerate(rest)
                     if launches[offset + j][0] in waited]
            if apart and (best is None
                          or statistics.median(apart) < best[0]):
                best = (statistics.median(apart), offset)
        if best is not None and best[0] <= SETTLE_S:
            return dropped, best[1]
    return None


def split(modules: Sequence[tuple[str, float, float]],
          ops: Sequence[tuple[str, float, float]],
          window: tuple[float, float], spans: Sequence[Placed],
          inside: Optional[set[int]] = None) -> Optional[dict[str, Any]]:
    """The arithmetic, on plain tuples of ONE device: its "XLA Modules"
    events and its op events ``(name, start_s, duration_s)``, the traced
    window, and the program's spans on the profile's host clock.
    ``inside``: the launch numbers made inside the window (all, if not
    given)."""
    t0, t1 = window
    launches = sorted((Launch(a["launch"], a["program"], start, name, a)
                       for name, start, _end, a in spans
                       if "launch" in a and "program" in a),
                      key=lambda launch: launch.number)
    waits = [(start, end, a["launch"]) for name, start, end, a in spans
             if _SYNC.match(name) and "launch" in a]
    executions = sorted((ex for ex in modules
                         if ex[0].startswith(SERVE_PROGRAM)),
                        key=lambda ex: ex[1])
    if not launches or not executions or t1 <= t0:
        return None
    waited = {number: end for _start, end, number in waits}
    fit = pair([launch[:2] for launch in launches], executions, waited)
    if fit is None:
        return None
    dropped, offset = fit
    rows = [(s, s + d, launches[offset + j])
            for j, (_p, s, d) in enumerate(executions[dropped:])]
    # the device line's lead over the host line: no program starts before
    # its call begins (the least lead), none ends after its wait has
    # ended (the most); the spans go onto the DEVICE's clock at the least
    skew = max(0.0, max(launch.start - s for s, _e, launch in rows))
    most = min((waited[launch.number] - e for _s, e, launch in rows
                if launch.number in waited), default=skew)
    paired = [Pair(s, e, launch.start - skew, launch)    # in time order
              for s, e, launch in rows]
    ends = [p.end for p in paired]

    # the waits, disjoint and in time order (one scheduler thread)
    syncs = trace_reduce.busy_union(
        [("wait", start - skew, end - start) for start, end, _n in waits])
    sync_ends = [b for _a, b in syncs]
    busy = trace_reduce.busy_union(trace_reduce._clip(ops, t0, t1))
    edges = [t0] + [t for ab in busy for t in ab] + [t1]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]

    parts = dict.fromkeys(SHARES, 0.0)
    running = 0.0
    largest: dict[str, Any] = {"seconds": 0.0}
    for g0, g1 in gaps:
        # N: the first launched program that ends after the gap does
        i = bisect.bisect_right(ends, g1)
        nxt = paired[i] if i < len(paired) else None
        cut = min(max(nxt.called, g0), g1) if nxt else g1
        noticed = 0.0
        for a, b in syncs[bisect.bisect_right(sync_ends, g0):]:
            if a >= cut:
                break
            noticed += min(cut, b) - max(g0, a)
        mine = {"launch": g1 - cut, "notice": noticed,
                "host": (cut - g0) - noticed}
        for part, secs in mine.items():
            parts[part] += secs
        if nxt and nxt.start < g0:
            running += g1 - g0
        if g1 - g0 > largest["seconds"]:
            largest = {"seconds": g1 - g0, "at_s": g0 - t0, **mine,
                       "next_program": nxt.launch.program if nxt else None,
                       "next_launch": nxt.launch.number if nxt else None}

    steps_ms = [1e3 * (p.end - p.start) / p.launch.args["k"]
                for p in paired
                if p.launch.span == DECODE_LAUNCH and p.launch.args.get("k")
                and (inside is None or p.launch.number in inside)]
    return {
        **{f"{part}_s": secs for part, secs in parts.items()},
        "idle_s": sum(b - a for a, b in gaps), "window_s": t1 - t0,
        # of the idle, gaps between the ops of ONE running program (they
        # count as launch: their program was called long before)
        "inside_program_s": running,
        "largest_gap": largest,
        "decode_step_ms": steps_ms,
        # the device line's lead over the host line taken out, and how
        # much more it could be: what the fastest launch and the
        # fastest notice of the slice take together
        "clock_skew_s": skew, "clock_slack_s": max(0.0, most - skew),
        "launches_in_file": len(launches),
        "executions": len(executions), "paired": len(paired),
        "unpaired_executions": dropped,
        "unpaired_in_window": sum(1 for ex in executions[:dropped]
                                  if t0 <= ex[1] < t1),
    }


def analyse(run) -> Optional[dict[str, Any]]:
    """:func:`split` of the traced run's first device, once a profile."""
    loaded = named_ops.load(run)
    if loaded is None or not loaded.get("modules"):
        return None
    key = f"{named_ops.profile_path(run)}:{loaded['window']}"
    if key in _CACHE:
        return _CACHE[key]
    found = None
    inside = {ev["args"]["launch"] for ev in spans_in_window(run, loaded)
              if "launch" in ev.get("args", {})}
    device = sorted(loaded["ops"])[0]
    if inside and device in loaded["modules"]:
        found = split(loaded["modules"][device],
                      [op[:3] for op in loaded["ops"][device]],
                      loaded["window"], placed_spans(run, loaded), inside)
    if found is not None:
        t0, t1 = loaded["window"]
        rows: dict[str, list[float]] = {}
        for program, start, dur in loaded["modules"][device]:
            if t0 <= start < t1:
                rows.setdefault(program, []).append(1e3 * dur)
        found["programs"] = {p: [len(v), statistics.median(v)]
                             for p, v in sorted(rows.items())}
        big = found["largest_gap"]
        print(f"[benchmark] launch_gaps: {found['paired']} of "
              f"{found['executions']} jit_serve executions paired with "
              f"launches ({found['unpaired_in_window']} unpaired inside "
              f"the window), idle {found['idle_s']:.4f} s = launch "
              f"{found['launch_s']:.4f} + notice {found['notice_s']:.4f} "
              f"+ host {found['host_s']:.4f} (inside a running program "
              f"{found['inside_program_s']:.4f}); the device line leads "
              f"the host line by {1e3 * found['clock_skew_s']:.3f} ms at "
              f"least, {1e3 * found['clock_slack_s']:.3f} ms more at "
              f"most; largest gap {big['seconds']:.4f} s before "
              f"{big.get('next_program')}", file=sys.stderr)
    _CACHE[key] = found
    return found


def read(run, part: str) -> Optional[float]:
    if part not in SHARES + ("decode_step_ms",):
        raise ValueError(f"part={part!r}")
    if not run.profile.get("busy_s"):
        return None
    found = analyse(run)
    if found is None:
        return None
    if part == "decode_step_ms":
        steps = found["decode_step_ms"]
        return statistics.median(steps) if steps else None
    return 100.0 * found[f"{part}_s"] / found["window_s"]


if __name__ == "__main__":
    by_hand = SimpleNamespace(cell=SimpleNamespace(name=sys.argv[1]))
    print(json.dumps(analyse(by_hand)))
