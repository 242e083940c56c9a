"""Device ops of the traced window with the names the PROGRAM gave them:
the jitted program an op ran in and the ``jax.named_scope`` path it was
traced under.  Shared by ``trace_scope_share`` (no reader of its own).

``harness/trace_reduce.py`` reads the profile through
``jax.profiler.ProfileData``, which shows an event's own stats only.  The
names live one level down, in the stats of the event's *metadata*
(my chip run, PR 25, TPU v5 lite):

- ``tf_op``: the HLO instruction's ``op_name``, i.e. the scope path,
  ``jit(train_step)/jvp(mlp_up)/dot_general:``;
- ``program_id``: the number in the name of the "XLA Modules" event the
  op ran under, ``jit_train_step(16560049521687703676)``.

A fusion carries ONE ``op_name``, its root's, and the root is often
plumbing: the whole-cache ``select_dynamic-update-slice_fusion`` of a
decode step is the ``kv_update`` select fused into the layer scan's own
stacking ``dynamic_update_slice``, and only the latter names it.  The
profile also holds every program's optimised HLO (plane
``/host:metadata``, stat ``Hlo Proto``), so an op's scope here is its
own ``op_name`` followed by the distinct ``op_name``s of the
instructions fused into it: a fusion is found under every scope that
any of its instructions was traced under.

So this file decodes the ``.xplane.pb`` itself: the protobuf wire format
of ``XSpace`` (tsl/profiler/protobuf/xplane.proto) and ``HloProto``
(xla/service/hlo.proto), the few fields it needs, no dependency.  The
file is parsed once per process.  Times are on the same clock as
``trace_reduce.load_xplane`` (line timestamp plus event offset), so the
window is the same ``bench-window`` annotation.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Any, Iterator, Optional

from benchmarks.harness import trace_reduce
from benchmarks.harness.cells import ROOT

# one decoded op: (op name as trace_reduce prints it, start_s, dur_s,
# scope paths (its own, then those fused into it) or "", program name
# or "")
NamedOp = tuple[str, float, float, str, str]

_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
_CACHE: dict[str, Optional[dict[str, Any]]] = {}


# -- protobuf wire format -----------------------------------------------------


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message: a varint as
    an int, a length-delimited field as bytes, fixed widths as raw
    little-endian bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        if pos > end:
            raise ValueError("a field runs past the end of its message")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf: bytes) -> tuple[int, Any]:
    """XStat: ``metadata_id`` and the value (a ``ref_value`` as the pair
    ``("ref", id)``; doubles and bytes are not needed and come as raw)."""
    key, value = 0, None
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number in (3, 4):
            value = _signed(v) if number == 4 else v
        elif number == 5:
            value = v.decode("utf-8", "replace")
        elif number == 7:
            value = ("ref", v)
        else:
            value = v
    return key, value


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf: bytes, wanted_stats: tuple[str, ...] = ()
           ) -> dict[str, Any]:
    """XPlane: its lines as ``(name, [(metadata_id, start_s, dur_s)])``
    and per event metadata ``(name, {stat: value})`` with only
    ``wanted_stats`` kept."""
    lines_raw: list[bytes] = []
    event_md: dict[int, bytes] = {}
    stat_names: dict[int, str] = {}
    for number, _wire, v in _fields(buf):
        if number == 3:
            lines_raw.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            event_md[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            for n2, _w2, v2 in _fields(value):
                if n2 == 2:
                    stat_names[key] = v2.decode()

    def stats_of(raw_stats: list[bytes]) -> dict[str, Any]:
        out = {}
        for raw in raw_stats:
            key, value = _stat(raw)
            stat = stat_names.get(key, "")
            if stat in wanted_stats:
                if isinstance(value, tuple):
                    value = stat_names.get(value[1], "")
                out[stat] = value
        return out

    metadata: dict[int, tuple[str, dict[str, Any]]] = {}
    for key, raw in event_md.items():
        md_name, raw_stats = "", []
        for number, _wire, v in _fields(raw):
            if number == 2:
                md_name = v.decode("utf-8", "replace")
            elif number == 5:
                raw_stats.append(v)
        metadata[key] = (md_name, stats_of(raw_stats))

    lines = []
    for raw in lines_raw:
        line_name, t0_ns, events_raw = "", 0, []
        for number, _wire, v in _fields(raw):
            if number == 2:
                line_name = v.decode()
            elif number == 3:
                t0_ns = _signed(v)
            elif number == 4:
                events_raw.append(v)
        events = []
        for ev in events_raw:
            md_id = offset_ps = dur_ps = 0
            for number, _wire, v in _fields(ev):
                if number == 1:
                    md_id = v
                elif number == 2:
                    offset_ps = _signed(v)
                elif number == 3:
                    dur_ps = _signed(v)
            events.append((md_id, t0_ns * 1e-9 + offset_ps * 1e-12,
                           dur_ps * 1e-12))
        lines.append((line_name, events))
    return {"lines": lines, "metadata": metadata}


def _packed(wire: int, value: Any) -> list[int]:
    """A repeated int64 field: one varint, or several packed in bytes."""
    if wire == 0:
        return [value]
    out, pos = [], 0
    while pos < len(value):
        item, pos = _varint(value, pos)
        out.append(item)
    return out


def _fused_scopes(module: bytes) -> dict[str, str]:
    """HloModuleProto: for each fusion instruction, by name, the distinct
    ``op_name``s of the instructions of its fused computation (fusions
    nested in it included), space-joined in program order."""
    computations: dict[int, list[tuple[str, str, str, list[int]]]] = {}
    for number, _wire, comp in _fields(module):
        if number != 3:
            continue
        comp_id, instructions = 0, []
        for n2, _w2, v2 in _fields(comp):
            if n2 == 5:
                comp_id = v2
            elif n2 == 2:
                name = opcode = op_name = ""
                called: list[int] = []
                for n3, w3, v3 in _fields(v2):
                    if n3 == 1:
                        name = v3.decode()
                    elif n3 == 2:
                        opcode = v3.decode()
                    elif n3 == 7:
                        for n4, _w4, v4 in _fields(v3):
                            if n4 == 2:
                                op_name = v4.decode("utf-8", "replace")
                    elif n3 == 38:
                        called += _packed(w3, v3)
                instructions.append((name, opcode, op_name, called))
        computations[comp_id] = instructions

    def inside(comp_id: int, seen: dict[str, None]) -> None:
        for _name, opcode, op_name, called in computations.get(comp_id, ()):
            if op_name:
                seen.setdefault(op_name)
            if opcode == "fusion":
                for inner in called:
                    inside(inner, seen)

    out: dict[str, str] = {}
    for instructions in computations.values():
        for name, opcode, _op_name, called in instructions:
            if opcode == "fusion":
                seen: dict[str, None] = {}
                for inner in called:
                    inside(inner, seen)
                out[name] = " ".join(seen)
    return out


def _program_hlo(plane: bytes) -> dict[int, dict[str, str]]:
    """Plane ``/host:metadata``: program id -> :func:`_fused_scopes` of
    the program's ``Hlo Proto`` stat (HloProto.hlo_module)."""
    out: dict[int, dict[str, str]] = {}
    for number, _wire, entry in _fields(plane):
        if number != 4:
            continue
        program_id, metadata = _map_entry(entry)
        for n2, _w2, raw_stat in _fields(metadata):
            if n2 != 5:
                continue
            _key, value = _stat(raw_stat)
            if isinstance(value, bytes):
                for n3, _w3, module in _fields(value):
                    if n3 == 1:
                        out[program_id] = _fused_scopes(module)
    return out


def decode(data: bytes) -> dict[str, Any]:
    """An ``XSpace`` as ``{"ops": {device: [NamedOp]}, "modules":
    {device: [(program, start_s, dur_s)]}, "host": [(name, start_s,
    end_s)]}``, host being every event of the ``/host:CPU`` plane."""
    ops: dict[str, list[NamedOp]] = {}
    modules: dict[str, list[tuple[str, float, float]]] = {}
    host: list[tuple[str, float, float]] = []
    planes = []
    for number, _wire, raw in _fields(data):
        if number == 1:
            # the plane's name comes first in the message: the planes
            # this file does not read are never decoded
            head = next((v for n, _w, v in _fields(raw) if n == 2), b"")
            planes.append((head.decode(), raw))
    fused: dict[int, dict[str, str]] = {}
    for plane_name, raw in planes:
        if plane_name == "/host:metadata":
            fused = _program_hlo(raw)
    for plane_name, raw in planes:
        if plane_name.startswith("/device:TPU:"):
            plane = _plane(raw, ("tf_op", "program_id"))
            by_id: dict[int, str] = {}
            for line_name, events in plane["lines"]:
                if line_name == "XLA Modules":
                    found = []
                    for md_id, start, dur in events:
                        m = _MODULE.match(plane["metadata"][md_id][0])
                        if m:
                            by_id[int(m.group(2))] = m.group(1)
                            found.append((m.group(1), start, dur))
                    modules[plane_name] = found
            for line_name, events in plane["lines"]:
                if line_name != "XLA Ops":
                    continue
                named = []
                scopes: dict[int, tuple[str, str, str]] = {}
                for md_id, start, dur in events:
                    if md_id not in scopes:
                        text, stats = plane["metadata"][md_id]
                        program_id = stats.get("program_id")
                        scope = str(stats.get("tf_op", "")).rstrip(":")
                        m = _INSTRUCTION.match(text)
                        inner = fused.get(program_id, {}).get(
                            m.group(1) if m else "", "")
                        scopes[md_id] = (
                            trace_reduce.clean_name(text),
                            " ".join(filter(None, (scope, inner))),
                            by_id.get(program_id, ""))
                    name, scope, program = scopes[md_id]
                    named.append((name, start, dur, scope, program))
                ops[plane_name] = named
        elif plane_name.startswith("/host:CPU"):
            plane = _plane(raw)
            for _line_name, events in plane["lines"]:
                for md_id, start, dur in events:
                    host.append((plane["metadata"][md_id][0], start,
                                 start + dur))
    return {"ops": ops, "modules": modules, "host": host}


# -- the traced run's file ----------------------------------------------------


def profile_path(run) -> Optional[str]:
    """The traced run's ``.xplane.pb``, where ``benchmarks/run.py`` has
    the runner write it."""
    files = sorted(glob.glob(os.path.join(
        str(ROOT), ".bench_scratch", run.cell.name, "plugins", "profile",
        "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(run) -> Optional[dict[str, Any]]:
    """The decoded profile of ``run`` with its traced window (the last
    ``bench-window`` annotation, as ``trace_reduce.load_xplane`` takes
    it), or None: no file, a file that does not decode, no device ops,
    no window."""
    path = profile_path(run)
    if path is None:
        return None
    key = f"{path}:{os.path.getmtime(path)}"
    if key not in _CACHE:
        with open(path, "rb") as f:
            data = f.read()
        try:
            decoded = decode(data)
        except (ValueError, IndexError, KeyError) as e:
            # the profiler's file, not this program's: one cut short or
            # laid out otherwise reads as no names (the metrics are left
            # out of the line), never as a failed run
            print(f"[benchmark] named_ops cannot decode {path}: {e!r}",
                  file=sys.stderr)
            decoded = {"ops": {}, "host": []}
        windows = [(s, e) for n, s, e in decoded["host"]
                   if n == trace_reduce.WINDOW_SPAN]
        if decoded["ops"] and windows:
            decoded["window"] = windows[-1]
            _CACHE[key] = decoded
        else:
            _CACHE[key] = None
    return _CACHE[key]


def group_seconds(loaded: dict[str, Any], label) -> dict[str, float]:
    """Self time (``trace_reduce.self_times``: nested events taken out
    of their parents) of the window's ops grouped by ``label(op) ->
    str``, mean over the devices."""
    t0, t1 = loaded["window"]
    out: dict[str, float] = {}
    n = len(loaded["ops"])
    for events in loaded["ops"].values():
        clipped = []
        for op in events:
            start, end = max(op[1], t0), min(op[1] + op[2], t1)
            if end > start:
                clipped.append((label(op), start, end - start))
        for name, secs in trace_reduce.self_times(clipped).items():
            out[name] = out.get(name, 0.0) + secs / n
    return out
