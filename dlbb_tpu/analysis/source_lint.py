"""Pass 2 — AST source lint.

Custom rules over ``dlbb_tpu/`` and ``scripts/`` for the failure modes a
distributed benchmark repo cares about and generic linters do not:

- ``host-sync-in-timed-region``: ``block_until_ready`` / ``device_get`` /
  ``float(...)`` / ``np.asarray(...)`` inside a timed region, except
  through the ``utils/timing.py`` API or as the region's final bracketing
  sync.  A mid-region host sync serialises the device pipeline into the
  measurement and corrupts the number being published.
- ``missing-donation``: a train-step jit (``jax.jit(step)`` /
  ``jax.jit(train_step)`` — any traced function whose name contains
  "step" or "train") without ``donate_argnums``/``donate_argnames``;
  without donation XLA keeps input and output state simultaneously
  resident.
- ``jit-in-loop``: ``jax.jit`` of a lambda or in-loop ``def`` closing over
  the loop variable — every iteration creates a fresh callable and
  therefore a fresh trace + compile (the Python-scalar-capture recompile
  hazard).  Warning severity (a name-resolution heuristic); CI runs with
  ``--strict-warnings`` so it still gates.
- ``host-transfer-in-loop``: ``np.asarray(...)`` / ``jax.device_get`` /
  ``.block_until_ready`` inside a Python loop body — the host-side twin
  of ``jit-in-loop``: a per-iteration device->host transfer (or full
  pipeline sync) serialises dispatch into every trip and scales with the
  loop, exactly the round-trip the fused-decode fast path exists to
  eliminate.  Warning severity (argument size is not statically
  knowable); CI runs ``--strict-warnings`` so it still gates.  Exempt:
  the measurement API homes (``TIMING_API_FILES`` +
  ``PROFILER_API_FILES`` — bracketed syncs around measurement are their
  whole purpose), calls inside a *timed region* (the timed-region rules
  own that domain and its bracketing-sync convention), loops over a
  constant literal tuple/list (a bounded probe ladder, not a data
  loop), and calls on a loop-exit path (an ``if`` body ending in
  ``break``/``return``/``raise`` executes at most once).  Only the loop
  BODY is walked (the iter expression evaluates once, a ``for/else``
  clause runs once) and nested function/lambda definitions are skipped
  (defined inside the loop is not executed per iteration).
- ``unsorted-set-iteration``: a ``for`` statement iterating directly over
  a set literal / ``set(...)`` call — hash-order dependent, so publish
  scripts reprocess artifacts in a different order run to run (the
  round-5 ADVICE nondeterminism finding, generalised).
- ``wallclock-in-timed-region``: ``time.time()`` / ``datetime.now()`` /
  ``datetime.utcnow()`` inside a timed region.  The wall clock is
  non-monotonic — NTP can step it mid-measurement — so a benchmark
  number derived from it is unfalsifiable; timed regions must read
  ``time.perf_counter()`` only (wall-clock *timestamps* belong outside
  the region).  Unlike host syncs there is no bracketing exemption: a
  wall-clock read is wrong anywhere inside the region.
- ``profiler-in-timed-region``: a profiler/tracing call —
  ``jax.profiler.*`` (``trace``, ``start_trace``, ``TraceAnnotation``,
  ``StepTraceAnnotation``), the ``utils/profiling.py`` wrappers
  (``maybe_trace`` / ``annotate``), or the obs
  device capture (``obs.capture.capture_device_trace``) — inside a timed
  region.  Profiler instrumentation perturbs the region it observes
  (xplane capture serialises device work and burns host cycles), so
  device traces must come from DEDICATED profile reps outside every
  timed region (``docs/observability.md``); no bracketing exemption.
  The sanctioned API homes (``utils/profiling.py``, ``obs/capture.py``)
  are exempt, like ``utils/timing.py`` is for host syncs.
- ``float64-literal-in-jit``: a float64 value materialised inside a
  jitted function (decorated ``@jax.jit`` / ``@partial(jax.jit, ...)``
  or passed by name to ``jax.jit`` in the same file) or a timed region —
  ``np.float64(...)``, ``.astype(np.float64 / "float64" / float)``,
  ``dtype=float64`` keywords, or a dtype-free host-numpy constructor
  (``np.array`` of float literals, ``np.ones``/``np.zeros``/
  ``np.linspace``) whose default dtype is float64.  With x64 disabled
  JAX silently demotes these to f32 (the literal lies about the math
  that runs); with x64 enabled they double the bytes of everything they
  touch — wire, HBM, and the number being timed.  The numerics HLO pass
  (``numerics_audit``) catches f64 that survives to the lowered module;
  this rule catches it at the source, where the fix belongs.
- ``non-atomic-artifact-write``: a bare ``json.dump(...)`` (in-place
  write of the destination file) or ``*.write_text(json.dumps(...))``
  outside the sanctioned atomic helper (``utils/config.py``:
  ``save_json`` / ``atomic_write_text``, tmp + fsync + ``os.replace``).
  A process killed mid-dump leaves a truncated JSON at the final path —
  which resume-mode sweeps and the stats pipeline would then trust
  (the PR-5 robustness hazard, ``docs/resilience.md``).

Timed regions are detected syntactically: the body of ``with Timer()``
(also ``with Timer() as t``), and statements strictly between
``<var> = time.perf_counter()`` and the statement consuming
``time.perf_counter() - <var>`` in the same block.

Suppression: ``# comm-lint: disable=rule[,rule2]`` trailing on the line
(or on the line directly above), ``# comm-lint: disable-file=rule`` near
the top of the file.
"""

from __future__ import annotations

import ast
import io
import tokenize
from pathlib import Path
from typing import Iterable, Optional

from dlbb_tpu.analysis.findings import (
    SEVERITY_ERROR,
    SEVERITY_WARNING,
    AnalysisReport,
    Finding,
)

LINT_RULES = (
    "host-sync-in-timed-region",
    "wallclock-in-timed-region",
    "profiler-in-timed-region",
    "missing-donation",
    "jit-in-loop",
    "host-transfer-in-loop",
    "unsorted-set-iteration",
    "non-atomic-artifact-write",
    "float64-literal-in-jit",
)

# Files whose whole purpose is host synchronisation around measurement.
TIMING_API_FILES = ("utils/timing.py",)
# The sanctioned profiler/capture API homes: the only files allowed to
# bracket a profiler session with a wall timer (they report the capture's
# own cost, never a published benchmark number).
PROFILER_API_FILES = ("utils/profiling.py", "obs/capture.py")
# The one sanctioned in-place writer: the atomic helper itself (its
# json.dump-to-tmp is the mechanism every other writer must go through).
ATOMIC_API_FILES = ("utils/config.py",)
# Calls through the sanctioned timing API are never host-sync findings.
TIMING_API_NAMES = {
    "force_completion", "calibrate_fetch_overhead",
    "single_iteration_estimate", "time_fn_per_iter", "time_fn_chained",
    "time_collective",
}
_SYNC_CALL_NAMES = {"block_until_ready", "device_get"}
_SYNC_WRAPPERS = {"float", "int"}
_NP_SYNC_ATTRS = {"asarray", "array"}
# wall-clock reads (non-monotonic) that must never supply a timed-region
# measurement; perf_counter/monotonic are the sanctioned clocks
_WALLCLOCK_NAMES = {
    "time.time", "datetime.now", "datetime.utcnow",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "date.today", "datetime.date.today",
}
# profiler entry points that must never run inside a timed region: the
# wrapper API (utils/profiling.py + obs/capture.py) by short name, plus
# anything reached through a `...profiler...` attribute chain
# (jax.profiler.trace / start_trace / TraceAnnotation / ...)
_PROFILER_CALL_NAMES = {
    "maybe_trace", "annotate", "capture_device_trace",
}
# per-iteration device->host transfers the in-loop rule flags: the
# named trio only (float()/int() scalarisation of a device scalar moves
# 4 bytes and is the sanctioned way OUT of this finding; jnp.asarray is
# device-side and exempt by the np/numpy prefix check)
_HOST_TRANSFER_CALLS = {"block_until_ready", "device_get"}


def _is_profiler_call(name: str) -> bool:
    short = name.rsplit(".", 1)[-1]
    return short in _PROFILER_CALL_NAMES or "profiler" in name


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------


class Suppressions:
    def __init__(self, source: str):
        self.line_rules: dict[int, set[str]] = {}
        self.file_rules: set[str] = set()
        self.hits = 0
        try:
            tokens = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                text = tok.string.lstrip("# ").strip()
                if not text.startswith("comm-lint:"):
                    continue
                directive = text[len("comm-lint:"):].strip()
                if directive.startswith("disable-file="):
                    rules = directive[len("disable-file="):]
                    self.file_rules |= {r.strip() for r in rules.split(",")}
                elif directive.startswith("disable="):
                    rules = directive[len("disable="):]
                    self.line_rules.setdefault(tok.start[0], set()).update(
                        r.strip() for r in rules.split(",")
                    )
        except tokenize.TokenError:
            pass

    def suppressed(self, rule: str, line: int) -> bool:
        if rule in self.file_rules:
            self.hits += 1
            return True
        for ln in (line, line - 1):
            if rule in self.line_rules.get(ln, set()):
                self.hits += 1
                return True
        return False


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _call_name(node: ast.Call) -> str:
    """Dotted name of a call's function, e.g. "jax.jit" or "Timer"."""
    parts = []
    f = node.func
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


def _is_perf_counter_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _call_name(node).endswith("perf_counter"))


def _free_names(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def _sync_calls(stmt: ast.stmt) -> Iterable[tuple[ast.Call, str]]:
    """(call, description) for every host-sync call inside ``stmt``."""
    for node in ast.walk(stmt):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        short = name.rsplit(".", 1)[-1]
        if short in TIMING_API_NAMES:
            continue  # sanctioned timing API
        if short in _SYNC_CALL_NAMES:
            yield node, name
        elif name in _SYNC_WRAPPERS and node.args and not isinstance(
                node.args[0], ast.Constant):
            # float(x)/int(x) on a non-literal forces the value to host
            yield node, f"{name}() on a device value"
        elif short in _NP_SYNC_ATTRS and name.split(".")[0] in ("np",
                                                               "numpy"):
            yield node, name
        elif short == "item" and isinstance(node.func, ast.Attribute):
            yield node, ".item()"


def _wallclock_calls(stmt: ast.stmt) -> Iterable[tuple[ast.Call, str]]:
    """(call, description) for every wall-clock read inside ``stmt``."""
    for node in ast.walk(stmt):
        if isinstance(node, ast.Call) and _call_name(
                node) in _WALLCLOCK_NAMES:
            yield node, f"{_call_name(node)}()"


def _profiler_calls(stmt: ast.stmt) -> Iterable[tuple[ast.Call, str]]:
    """(call, description) for every profiler/tracing call inside
    ``stmt``."""
    for node in ast.walk(stmt):
        if isinstance(node, ast.Call) and _is_profiler_call(
                _call_name(node)):
            yield node, f"{_call_name(node)}()"


def _walk_skip_defs(node: ast.AST) -> Iterable[ast.AST]:
    """``ast.walk`` that does not descend into nested function/lambda
    definitions — code *defined* inside a loop body is not necessarily
    *executed* per iteration."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            stack.append(child)


def _host_transfer_calls(node: ast.AST) -> Iterable[tuple[ast.Call, str]]:
    """(call, description) for every device->host transfer/sync call
    inside ``node`` (nested defs excluded): ``*.block_until_ready`` /
    ``jax.device_get`` / ``np.asarray`` (numpy's ``asarray`` on a
    device array pulls the whole buffer to host; ``jnp.asarray`` stays
    on device and is not matched)."""
    for n in _walk_skip_defs(node):
        if not isinstance(n, ast.Call):
            continue
        name = _call_name(n)
        short = name.rsplit(".", 1)[-1]
        if short in _HOST_TRANSFER_CALLS:
            yield n, name
        elif short == "asarray" and name.split(".")[0] in ("np", "numpy"):
            yield n, name


def _timed_line_spans(tree: ast.AST) -> list[tuple[int, int]]:
    """Line spans of every syntactic timed region — Timer with-blocks
    and ``t = perf_counter()`` ... ``perf_counter() - t`` spans — so
    rules that defer to the timed-region rules (their bracketing-sync
    convention is policed there) can skip them."""
    spans: list[tuple[int, int]] = []
    for node in _timed_with_blocks(tree):
        spans.append((node.lineno, node.end_lineno or node.lineno))
    for scope in ast.walk(tree):
        body = getattr(scope, "body", None)
        if not isinstance(body, list):
            continue
        for blk in (body, getattr(scope, "orelse", None),
                    getattr(scope, "finalbody", None)):
            if not isinstance(blk, list):
                continue
            svars: dict[str, int] = {}
            for idx, stmt in enumerate(blk):
                if (isinstance(stmt, ast.Assign)
                        and len(stmt.targets) == 1
                        and isinstance(stmt.targets[0], ast.Name)
                        and _is_perf_counter_call(stmt.value)):
                    svars[stmt.targets[0].id] = idx
                    continue
                closed = set()
                for node in ast.walk(stmt):
                    if (isinstance(node, ast.BinOp)
                            and isinstance(node.op, ast.Sub)
                            and _is_perf_counter_call(node.left)
                            and isinstance(node.right, ast.Name)
                            and node.right.id in svars):
                        closed.add(node.right.id)
                for var in closed:
                    start = svars.pop(var)
                    spans.append((blk[start].lineno,
                                  stmt.end_lineno or stmt.lineno))
    return spans


# ---------------------------------------------------------------------------
# rule implementations
# ---------------------------------------------------------------------------


def _timed_with_blocks(tree: ast.AST) -> Iterable[ast.With]:
    for node in ast.walk(tree):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            ctx = item.context_expr
            if isinstance(ctx, ast.Call) and _call_name(ctx).rsplit(
                    ".", 1)[-1] == "Timer":
                yield node
                break


def _check_timed_with(node: ast.With, path: str, findings: list[Finding],
                      check_profiler: bool = True):
    last = node.body[-1]
    for stmt in node.body:
        for call, desc in _sync_calls(stmt):
            if stmt is last:
                continue  # bracketing sync closing the measurement
            findings.append(Finding(
                pass_name="lint",
                rule="host-sync-in-timed-region",
                severity=SEVERITY_ERROR,
                target=path,
                message=(
                    f"{desc} inside a Timer block (before its final "
                    "statement) serialises device work into the "
                    "measurement; use the utils/timing.py API or move the "
                    "sync to the region boundary"
                ),
                location=f"{path}:{call.lineno}",
                details={"sync": desc, "region": f"with Timer() at line "
                                                 f"{node.lineno}"},
            ))
        # no bracketing exemption: a wall-clock read is wrong anywhere
        # inside the region, last statement included
        for call, desc in _wallclock_calls(stmt):
            findings.append(Finding(
                pass_name="lint",
                rule="wallclock-in-timed-region",
                severity=SEVERITY_ERROR,
                target=path,
                message=(
                    f"{desc} inside a Timer block reads the wall clock — "
                    "non-monotonic (NTP can step it mid-measurement), so "
                    "any duration derived from it is unfalsifiable; use "
                    "time.perf_counter(), and take wall-clock timestamps "
                    "outside the timed region"
                ),
                location=f"{path}:{call.lineno}",
                details={"clock": desc, "region": f"with Timer() at line "
                                                  f"{node.lineno}"},
            ))
        if not check_profiler:
            continue
        # like the wall clock, no bracketing exemption: a profiler call
        # perturbs the region wherever it sits
        for call, desc in _profiler_calls(stmt):
            findings.append(Finding(
                pass_name="lint",
                rule="profiler-in-timed-region",
                severity=SEVERITY_ERROR,
                target=path,
                message=(
                    f"{desc} inside a Timer block starts/annotates a "
                    "profiler session in the measured region — capture "
                    "overhead lands in the published number; trace on "
                    "DEDICATED profile reps outside the timed region "
                    "(dlbb_tpu.obs.capture, docs/observability.md)"
                ),
                location=f"{path}:{call.lineno}",
                details={"call": desc, "region": f"with Timer() at line "
                                                 f"{node.lineno}"},
            ))


def _check_perf_counter_regions(tree: ast.AST, path: str,
                                findings: list[Finding],
                                check_profiler: bool = True):
    """Statements strictly between ``t = time.perf_counter()`` and the
    statement consuming ``perf_counter() - t`` are a timed region."""
    for scope in ast.walk(tree):
        body = getattr(scope, "body", None)
        if not isinstance(body, list):
            continue
        for blk in (body, getattr(scope, "orelse", None),
                    getattr(scope, "finalbody", None)):
            if not isinstance(blk, list):
                continue
            self_vars: dict[str, int] = {}  # var -> index of t0 assignment
            for idx, stmt in enumerate(blk):
                if (isinstance(stmt, ast.Assign)
                        and len(stmt.targets) == 1
                        and isinstance(stmt.targets[0], ast.Name)
                        and _is_perf_counter_call(stmt.value)):
                    self_vars[stmt.targets[0].id] = idx
                    continue
                # does this statement close a region? (perf_counter() - t)
                closed = set()
                for node in ast.walk(stmt):
                    if (isinstance(node, ast.BinOp)
                            and isinstance(node.op, ast.Sub)
                            and _is_perf_counter_call(node.left)
                            and isinstance(node.right, ast.Name)
                            and node.right.id in self_vars):
                        closed.add(node.right.id)
                for var in closed:
                    start = self_vars.pop(var)
                    # the statement directly before the delta is the
                    # bracketing sync closing the measurement (e.g.
                    # ``float(loss)`` then ``t = perf_counter() - t0``) —
                    # same exemption as a Timer block's final statement
                    for mid in blk[start + 1: idx - 1]:
                        for call, desc in _sync_calls(mid):
                            findings.append(Finding(
                                pass_name="lint",
                                rule="host-sync-in-timed-region",
                                severity=SEVERITY_ERROR,
                                target=path,
                                message=(
                                    f"{desc} between "
                                    f"{var} = time.perf_counter() and its "
                                    "delta serialises device work into "
                                    "the measurement; use the "
                                    "utils/timing.py API"
                                ),
                                location=f"{path}:{call.lineno}",
                                details={"sync": desc,
                                         "region": f"perf_counter span "
                                                   f"'{var}'"},
                            ))
                    # wall-clock reads get no bracketing exemption (the
                    # statement before the delta included)
                    for mid in blk[start + 1: idx]:
                        for call, desc in _wallclock_calls(mid):
                            findings.append(Finding(
                                pass_name="lint",
                                rule="wallclock-in-timed-region",
                                severity=SEVERITY_ERROR,
                                target=path,
                                message=(
                                    f"{desc} between "
                                    f"{var} = time.perf_counter() and its "
                                    "delta reads the non-monotonic wall "
                                    "clock; use time.perf_counter() and "
                                    "timestamp outside the region"
                                ),
                                location=f"{path}:{call.lineno}",
                                details={"clock": desc,
                                         "region": f"perf_counter span "
                                                   f"'{var}'"},
                            ))
                        if not check_profiler:
                            continue
                        for call, desc in _profiler_calls(mid):
                            findings.append(Finding(
                                pass_name="lint",
                                rule="profiler-in-timed-region",
                                severity=SEVERITY_ERROR,
                                target=path,
                                message=(
                                    f"{desc} between "
                                    f"{var} = time.perf_counter() and its "
                                    "delta runs a profiler session inside "
                                    "the measured region — capture "
                                    "overhead lands in the published "
                                    "number; move the capture to a "
                                    "dedicated profile rep outside the "
                                    "region (dlbb_tpu.obs.capture)"
                                ),
                                location=f"{path}:{call.lineno}",
                                details={"call": desc,
                                         "region": f"perf_counter span "
                                                   f"'{var}'"},
                            ))


def _check_donation(tree: ast.AST, path: str, findings: list[Finding]):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or _call_name(node) not in (
                "jax.jit", "jit"):
            continue
        if not node.args:
            continue
        fn = node.args[0]
        fn_name = fn.id if isinstance(fn, ast.Name) else None
        if fn_name is None or not ("step" in fn_name or "train" in fn_name):
            continue
        kwargs = {kw.arg for kw in node.keywords}
        if not kwargs & {"donate_argnums", "donate_argnames"}:
            findings.append(Finding(
                pass_name="lint",
                rule="missing-donation",
                severity=SEVERITY_ERROR,
                target=path,
                message=(
                    f"jax.jit({fn_name}) looks like a train-step jit but "
                    "donates no arguments — without donate_argnums the "
                    "input and output state are simultaneously resident "
                    "(2x state HBM)"
                ),
                location=f"{path}:{node.lineno}",
                details={"function": fn_name},
            ))


def _check_jit_in_loop(tree: ast.AST, path: str, findings: list[Finding]):
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        loop_vars: set[str] = set()
        if isinstance(loop, ast.For):
            loop_vars = {n.id for n in ast.walk(loop.target)
                         if isinstance(n, ast.Name)}
        in_loop_defs = {
            d.name: d for d in ast.walk(loop)
            if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call) or _call_name(node) not in (
                    "jax.jit", "jit", "jax.pmap", "pmap"):
                continue
            if not node.args:
                continue
            fn = node.args[0]
            if isinstance(fn, ast.Lambda):
                traced, what = fn.body, "lambda ..."
            elif isinstance(fn, ast.Name) and fn.id in in_loop_defs:
                # a def in the loop body is a fresh function object per
                # iteration, exactly like an inline lambda
                traced, what = in_loop_defs[fn.id], fn.id
            else:
                continue
            if not loop_vars or _free_names(traced) & loop_vars:
                findings.append(Finding(
                    pass_name="lint",
                    rule="jit-in-loop",
                    severity=SEVERITY_WARNING,
                    target=path,
                    message=(
                        f"jax.jit({what}) inside a loop creates a "
                        "fresh callable — and a fresh trace + XLA compile "
                        "— every iteration (Python-scalar capture "
                        "recompile hazard); hoist the jit and pass the "
                        "varying value as an argument"
                    ),
                    location=f"{path}:{node.lineno}",
                    details={"loop_line": loop.lineno},
                ))


def _is_constant_iterable(node: ast.AST) -> bool:
    """A literal tuple/list of constants — a bounded probe ladder
    (``for mode in ("head", "whole")``), not a data loop."""
    return (isinstance(node, (ast.Tuple, ast.List))
            and all(isinstance(e, ast.Constant) for e in node.elts))


def _check_host_transfer_in_loop(tree: ast.AST, path: str,
                                 findings: list[Finding]):
    """``host-transfer-in-loop``: a device->host transfer repeated every
    iteration of a Python loop (the host-side twin of jit-in-loop).
    Exempt spans: timed regions (the timed-region rules own those and
    their bracketing-sync convention), constant-literal probe loops, and
    loop-exit ``if`` bodies (break/return/raise — at most one
    execution)."""
    exempt = _timed_line_spans(tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.For) and _is_constant_iterable(node.iter):
            exempt.append((node.lineno, node.end_lineno or node.lineno))
        elif (isinstance(node, ast.If) and node.body
                and isinstance(node.body[-1],
                               (ast.Break, ast.Return, ast.Raise))):
            last = node.body[-1]
            exempt.append((node.body[0].lineno,
                           last.end_lineno or last.lineno))
    seen: set[tuple[int, int]] = set()
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        if isinstance(loop, ast.For) and _is_constant_iterable(loop.iter):
            continue
        # the loop BODY only: the iter expression evaluates once, and a
        # for/else clause runs once after the loop
        for stmt in loop.body:
            for call, desc in _host_transfer_calls(stmt):
                key = (call.lineno, call.col_offset)
                if key in seen:
                    continue  # nested loops re-discover the same call
                if any(lo <= call.lineno <= hi for lo, hi in exempt):
                    continue
                seen.add(key)
                findings.append(Finding(
                    pass_name="lint",
                    rule="host-transfer-in-loop",
                    severity=SEVERITY_WARNING,
                    target=path,
                    message=(
                        f"{desc}() inside a loop body forces a "
                        "device->host round trip (or full pipeline "
                        "sync) EVERY iteration — dispatch serialises "
                        "into each trip and the cost scales with the "
                        "loop; batch the transfer outside the loop, "
                        "keep the reduction on device (e.g. jnp.argmax "
                        "+ a scalar int()), or fuse the steps into one "
                        "dispatch (docs/serving.md fast path)"
                    ),
                    location=f"{path}:{call.lineno}",
                    details={"call": desc, "loop_line": loop.lineno},
                ))


def _check_atomic_writes(tree: ast.AST, path: str, findings: list[Finding]):
    """``non-atomic-artifact-write``: JSON artifacts must go through the
    atomic helper (tmp + fsync + ``os.replace``), never be written
    in-place at their final path."""

    def is_dumps(e: ast.AST) -> bool:
        if isinstance(e, ast.Call) and _call_name(e).rsplit(
                ".", 1)[-1] == "dumps" and _call_name(e).startswith("json"):
            return True
        if isinstance(e, ast.BinOp) and isinstance(e.op, ast.Add):
            # json.dumps(...) + "\n" and friends
            return is_dumps(e.left) or is_dumps(e.right)
        return False

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name == "json.dump":
            findings.append(Finding(
                pass_name="lint",
                rule="non-atomic-artifact-write",
                severity=SEVERITY_ERROR,
                target=path,
                message=(
                    "bare json.dump writes the destination in-place — a "
                    "process killed mid-dump leaves a truncated artifact "
                    "that resume-mode sweeps / the stats pipeline would "
                    "trust; use dlbb_tpu.utils.config.save_json (tmp + "
                    "fsync + os.replace)"
                ),
                location=f"{path}:{node.lineno}",
                details={"call": "json.dump"},
            ))
        elif (name.rsplit(".", 1)[-1] == "write_text" and node.args
                and is_dumps(node.args[0])):
            findings.append(Finding(
                pass_name="lint",
                rule="non-atomic-artifact-write",
                severity=SEVERITY_ERROR,
                target=path,
                message=(
                    "write_text(json.dumps(...)) truncates the "
                    "destination before writing — a kill mid-write tears "
                    "the artifact; use dlbb_tpu.utils.config.save_json / "
                    "atomic_write_text (tmp + fsync + os.replace)"
                ),
                location=f"{path}:{node.lineno}",
                details={"call": "write_text(json.dumps)"},
            ))


_JIT_NAMES = ("jax.jit", "jit", "jax.pmap", "pmap")


def _dotted(node: ast.AST) -> str:
    """Dotted name of an Attribute/Name expression ("" when neither)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_decorator(dec: ast.AST) -> bool:
    """``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)`` (functools
    spelling included)."""
    if isinstance(dec, ast.Call):
        name = _call_name(dec)
        if name in _JIT_NAMES:
            return True  # @jax.jit(donate_argnums=...)
        return (name.rsplit(".", 1)[-1] == "partial" and dec.args
                and _dotted(dec.args[0]) in _JIT_NAMES)
    return _dotted(dec) in _JIT_NAMES


def _jitted_spans(tree: ast.AST) -> list[tuple[int, int]]:
    """Line spans of every function the file jits: decorated defs plus
    defs whose NAME is passed to ``jax.jit``/``pmap`` anywhere in the
    file (the ``step_fn = jax.jit(step_fn, ...)`` idiom)."""
    defs: dict[str, ast.AST] = {}
    spans: list[tuple[int, int]] = []
    jit_arg_names: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
            if any(_is_jit_decorator(d) for d in node.decorator_list):
                spans.append((node.lineno, node.end_lineno or node.lineno))
        elif (isinstance(node, ast.Call) and _call_name(node) in _JIT_NAMES
                and node.args and isinstance(node.args[0], ast.Name)):
            jit_arg_names.add(node.args[0].id)
    for name in sorted(jit_arg_names):
        d = defs.get(name)
        if d is not None:
            spans.append((d.lineno, d.end_lineno or d.lineno))
    return spans


def _f64_dtype_desc(e: ast.AST) -> Optional[str]:
    """Description when ``e`` denotes the float64 dtype: the
    ``np.float64``/``jnp.float64`` attribute, the ``"float64"``/
    ``"double"`` string, or the Python ``float`` builtin (float64 by
    definition)."""
    name = _dotted(e)
    if name and name.rsplit(".", 1)[-1] in ("float64", "double"):
        return name
    if isinstance(e, ast.Constant) and e.value in ("float64", "double"):
        return repr(e.value)
    if isinstance(e, ast.Name) and e.id == "float":
        return "float (the Python builtin is float64)"
    return None


# dtype-free host-numpy constructors whose default result dtype is
# float64 regardless of argument dtypes
_NP_F64_DEFAULT_CTORS = {"ones", "zeros", "linspace", "full"}


def _float64_sites(tree: ast.AST) -> Iterable[tuple[ast.AST, str]]:
    """(node, description) for every expression that materialises a
    float64 value: ``np.float64(x)`` casts, ``.astype`` upcasts,
    ``dtype=float64`` keywords, and dtype-free host-numpy constructors
    (``np.array`` of float literals; ``np.ones``/``zeros``/``linspace``/
    ``full`` always)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        short = name.rsplit(".", 1)[-1]
        if short in ("float64", "double") and "." in name:
            yield node, f"{name}(...) cast"
            continue
        if short == "astype" and node.args:
            desc = _f64_dtype_desc(node.args[0])
            if desc:
                yield node, f".astype({desc})"
                continue
        for kw in node.keywords:
            if kw.arg == "dtype":
                desc = _f64_dtype_desc(kw.value)
                if desc:
                    yield node, f"{name}(dtype={desc})"
                break
        else:
            if name.split(".")[0] not in ("np", "numpy"):
                continue
            if short in _NP_F64_DEFAULT_CTORS:
                yield node, (f"{name}(...) without dtype= "
                             "(host numpy defaults to float64)")
            elif short in ("array", "asarray") and node.args and any(
                    isinstance(c, ast.Constant) and isinstance(c.value, float)
                    for c in ast.walk(node.args[0])):
                yield node, (f"{name}(...) of float literals without "
                             "dtype= (host numpy defaults to float64)")


def _check_float64(tree: ast.AST, path: str, findings: list[Finding],
                   include_timed: bool = True):
    """``float64-literal-in-jit``: float64 materialised inside a jitted
    function or a timed region.  With jax x64 disabled the value is
    silently demoted to f32 (the source lies about the math that runs);
    with x64 enabled it doubles the bytes of everything downstream."""
    spans = _jitted_spans(tree)
    if include_timed:
        spans += _timed_line_spans(tree)
    if not spans:
        return
    for node, desc in _float64_sites(tree):
        line = node.lineno
        if not any(lo <= line <= hi for lo, hi in spans):
            continue
        findings.append(Finding(
            pass_name="lint",
            rule="float64-literal-in-jit",
            severity=SEVERITY_ERROR,
            target=path,
            message=(
                f"{desc} inside a jitted function or timed region "
                "materialises float64 — silently demoted to f32 when "
                "jax x64 is off (the literal lies about the math that "
                "runs), and doubled wire/HBM bytes when it is on; pin "
                "an explicit 32-bit dtype (jnp.float32 / the model's "
                "policy dtype)"
            ),
            location=f"{path}:{line}",
            details={"expression": desc},
        ))


def _check_set_iteration(tree: ast.AST, path: str, findings: list[Finding]):
    def is_set_expr(e: ast.AST) -> bool:
        if isinstance(e, ast.Set):
            return True
        if isinstance(e, ast.Call) and _call_name(e) == "set":
            return True
        if isinstance(e, ast.BinOp) and isinstance(e.op, ast.BitOr):
            return is_set_expr(e.left) or is_set_expr(e.right)
        return False

    for node in ast.walk(tree):
        if isinstance(node, ast.For) and is_set_expr(node.iter):
            findings.append(Finding(
                pass_name="lint",
                rule="unsorted-set-iteration",
                severity=SEVERITY_ERROR,
                target=path,
                message=(
                    "iterating directly over a set is hash-order "
                    "dependent — artifact/publishing order changes run to "
                    "run; wrap the set in sorted(...)"
                ),
                location=f"{path}:{node.iter.lineno}",
                details={},
            ))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def lint_source(source: str, path: str) -> tuple[list[Finding], int]:
    """Lint one file's source text; returns (findings, suppressed_count)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(
            pass_name="lint", rule="syntax-error", severity=SEVERITY_ERROR,
            target=path, message=f"file does not parse: {e}",
            location=f"{path}:{e.lineno or 0}",
        )], 0

    findings: list[Finding] = []
    norm = path.replace("\\", "/")
    if not norm.endswith(TIMING_API_FILES):
        check_prof = not norm.endswith(PROFILER_API_FILES)
        for block in _timed_with_blocks(tree):
            _check_timed_with(block, path, findings,
                              check_profiler=check_prof)
        _check_perf_counter_regions(tree, path, findings,
                                    check_profiler=check_prof)
        if check_prof:
            # the measurement/capture API homes drive the device in
            # loops on purpose (timing reps, profile reps) — same
            # exemption set as the profiler rule
            _check_host_transfer_in_loop(tree, path, findings)
    _check_donation(tree, path, findings)
    _check_jit_in_loop(tree, path, findings)
    _check_set_iteration(tree, path, findings)
    # the timing API computes host-side stats inside its own perf_counter
    # spans by design — its timed regions are exempt (jitted fns are not)
    _check_float64(tree, path, findings,
                   include_timed=not norm.endswith(TIMING_API_FILES))
    if not norm.endswith(ATOMIC_API_FILES):
        _check_atomic_writes(tree, path, findings)

    sup = Suppressions(source)
    kept = []
    for f in findings:
        line = int(f.location.rsplit(":", 1)[1]) if f.location else 0
        if not sup.suppressed(f.rule, line):
            kept.append(f)
    return kept, sup.hits


DEFAULT_LINT_DIRS = ("dlbb_tpu", "scripts")


def run_source_lint(
    root: Optional[str] = None,
    paths: Optional[Iterable[str]] = None,
    verbose: bool = False,
) -> AnalysisReport:
    """Lint every ``*.py`` under ``root``'s default dirs (or explicit
    ``paths``)."""
    report = AnalysisReport()
    if paths is None:
        base = Path(root or ".")
        files = sorted(
            p for d in DEFAULT_LINT_DIRS
            for p in (base / d).rglob("*.py") if p.is_file()
        )
        if not files:
            # a typo'd --root (or wrong cwd) must not read as a clean gate
            report.findings.append(Finding(
                pass_name="lint", rule="no-files-linted",
                severity=SEVERITY_ERROR, target=str(base),
                message=(
                    f"no Python files under {'/'.join(DEFAULT_LINT_DIRS)} "
                    f"of {base.resolve()}; is --root the repo root?"
                ),
            ))
            return report
    else:
        files = [Path(p) for p in paths]
    for p in files:
        rel = str(p)
        try:
            source = p.read_text()
        except OSError as e:
            report.findings.append(Finding(
                pass_name="lint", rule="io-error",
                severity=SEVERITY_ERROR, target=rel,
                message=f"cannot read: {e}",
            ))
            continue
        findings, suppressed = lint_source(source, rel)
        report.findings.extend(findings)
        report.suppressed += suppressed
        report.files_linted += 1
        if verbose and findings:
            print(f"[lint] {rel}: {len(findings)} finding(s)")
    return report
