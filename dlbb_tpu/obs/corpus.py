"""Sweep-artifact corpus ingestion (the cm2 fit's sample table).

The committed ``results/`` tree holds a thousand-odd measured sweep
artifacts (1D/3D collective micro-benchmarks, tuning variants), each a
JSON with raw per-iteration timings plus enough configuration to compute
the analytic features the α–β model prices: per-device wire bytes
(``expectations.op_wire_bytes``), dense FLOPs (the collective-matmul
micro-ops), and the number of collective instructions one dispatch
posts.  This module normalises that corpus into one flat sample table —
the regression input of :mod:`dlbb_tpu.obs.fit`:

    sample = {op, variant, kind, ranks, dtype, wire_bytes, flops,
              collectives, dispatches, measured_median_us,
              measured_p90_us, measured_p99_us, iterations, tier,
              host, file, ...}

``dispatches`` is per *timed iteration*: per-iter timing dispatches the
program once per sample (1.0); chained timing amortises one dispatch
over the chunk (1/chunk) — exactly the γ-visibility difference the
dispatch-overhead fit needs.

The tier of every sample comes from the artifact's recorded backend
(``system_info.backend``): ``cpu`` → ``cpu-sim``, anything TPU →
``tpu-v5lite``.  A per-host fingerprint (platform + cpu count + device
count) rides along so a fit can be restricted to the host it will
predict (``fit.fit_tier(host_filter=...)``) — dispatch overhead is a
property of the *host runtime*, not of the collective.

Everything here is pure file processing — importable and runnable
WITHOUT jax (the fit must run on a dev box with no backend), mirroring
``analysis/costmodel.py``'s contract.
"""

from __future__ import annotations

import json
import math
import re
from pathlib import Path
from typing import Any, Optional, Sequence

from dlbb_tpu.analysis.expectations import OP_EXPECTED_KINDS, op_wire_bytes

CORPUS_SCHEMA = "dlbb_fit_corpus_v1"

# artifact files that are never measurement samples (manifests, traces,
# journals, reports) — skipped silently, not counted as unparseable
_NON_SAMPLE_NAMES = re.compile(
    r"^(sweep_manifest|serving_manifest|serving_resume|trace_|comm_lint"
    r"|calibration_|metrics|.*_trace)", re.IGNORECASE
)
# the subset that can be skipped WITHOUT reading the file — everything
# above except the two name families the walk must parse (manifests for
# corpus metadata, calibration_* for the schema probe); a multi-MB
# Perfetto trace must not be json.loads'd just to be discarded by name
_PREFILTER_NAMES = re.compile(
    r"^(serving_manifest|serving_resume|trace_|comm_lint"
    r"|metrics|.*_trace)", re.IGNORECASE
)

ELEM_BYTES = {
    "bfloat16": 2, "float16": 2, "float32": 4, "float64": 8,
    "int8": 1, "uint8": 1, "fp8": 1, "float8_e4m3fn": 1,
    "int32": 4, "int64": 8,
}

# ops whose wire model op_wire_bytes declines (schedule-dependent): the
# collective-matmul micro-ops move one activation gather / scatter per
# dispatch regardless of schedule — fused and ring carry the same total
# wire, only the instruction count differs (docs/overlap.md)
_MATMUL_OPS = ("ag_matmul", "matmul_rs")


def tier_of_result(data: dict[str, Any]) -> str:
    """Cost-model tier an artifact was measured on, from its recorded
    backend: the CPU-simulated mesh is the ``cpu-sim`` tier, a real TPU
    the ``tpu-v5lite`` tier (per-tier DCN splits land with the topology
    registry, ROADMAP item 3)."""
    backend = str(
        data.get("system_info", {}).get("backend", "cpu")
    ).lower()
    return "cpu-sim" if backend == "cpu" else "tpu-v5lite"


def host_fingerprint(data: dict[str, Any]) -> str:
    info = data.get("system_info", {})
    return (f"{info.get('platform', '?')}"
            f"/cpu{info.get('cpu_count', '?')}"
            f"/dev{info.get('num_devices', '?')}")


def collectives_per_dispatch(op: str, variant: str, ranks: int) -> float:
    """Analytic count of α-charged collective instructions one dispatch
    posts — the fit's per-collective-latency regressor.

    Fused lowerings post one instruction; the explicit hierarchical
    reductions one per mesh axis; the ring-decomposed schedules
    (overlap_* collective matmuls, the quantised rings) one permute per
    hop.  Approximate by construction — the fit's outlier rejection
    absorbs lowering-level deviations (e.g. XLA splitting a fused
    collective)."""
    p = max(int(ranks), 1)
    if variant.startswith("overlap_") or op.endswith("_q"):
        hops = max(p - 1, 1)
        if op == "allreduce_q":
            # quantised ring reduce-scatter phase + wire-dtype all-gather
            return 2.0 * hops
        return float(hops)
    if op == "allreduce_hierarchical" or variant.startswith("hier"):
        axes = variant[len("hier"):].count("x") + 1 if variant.startswith(
            "hier") else 2
        return float(max(axes, 2))
    if op == "sendrecv":
        return 1.0
    return 1.0


def op_flops(op: str, data: dict[str, Any]) -> int:
    """Dense FLOPs one dispatch executes — nonzero only for the
    collective-matmul micro-ops, whose payload ``[B, S, H]`` (per-rank
    sequence chunk) multiplies the gathered ``[B, P*S, H]`` activation by
    a ``[H, H/P]`` weight column (ag_matmul) or accumulates per-shard
    partial products of the same magnitude (matmul_rs): ≈ 2·B·S·H² FLOPs
    per device either way."""
    if op not in _MATMUL_OPS:
        return 0
    shape = data.get("tensor_shape")
    if isinstance(shape, dict):
        dims = (shape.get("batch"), shape.get("seq_len"),
                shape.get("hidden_dim"))
        if any(d is None for d in dims):
            return 0
        b, s, h = (int(d) for d in dims)
    elif shape and len(shape) == 3 and all(
            isinstance(x, (int, float)) for x in shape):
        b, s, h = (int(x) for x in shape)
    else:
        return 0
    return 2 * b * s * h * h


def sample_wire_bytes(op: str, data: dict[str, Any]) -> Optional[int]:
    """Analytic per-device wire bytes for one dispatch, or None when the
    op has no wire model."""
    n = int(data.get("num_elements", 0))
    p = int(data.get("num_ranks", 0))
    b = ELEM_BYTES.get(str(data.get("dtype", "")).lower())
    if not n or not p or b is None:
        return None
    variant = str(data.get("variant", "default"))
    compression = None
    if variant.startswith("compress_"):
        compression = "fp8" if "fp8" in variant else "int8"
    wire = op_wire_bytes(op, n, p, b, compression=compression)
    if wire is not None:
        return wire
    if op in _MATMUL_OPS:
        # one activation-sized gather (ag) / scatter (rs) per dispatch
        if p <= 1:
            return 0
        if op == "ag_matmul":
            return int((p - 1) * n * b)       # gathered sequence chunks
        return int((p - 1) / p * n * b)       # scattered partial rows
    return None


def _dispatches_per_iteration(data: dict[str, Any]) -> float:
    """Host dispatches amortised into one timed iteration: per-iter
    timing pays one dispatch per sample; chained timing pays one per
    chunk (``timing_granularity: chunked(N)``)."""
    if data.get("timing_mode") != "chained":
        return 1.0
    gran = str(data.get("timing_granularity", ""))
    m = re.search(r"chunked\((\d+)\)", gran)
    chunk = int(m.group(1)) if m else 10
    return 1.0 / max(chunk, 1)


def _flat_timings_us(data: dict[str, Any]) -> list[float]:
    out: list[float] = []
    for group in data.get("timings", ()):  # list of rep groups
        if isinstance(group, (int, float)):
            out.append(float(group) * 1e6)
            continue
        for v in group:
            if isinstance(v, (int, float)) and math.isfinite(v):
                out.append(float(v) * 1e6)
    return out


def ingest_result(path: Path,
                  data: dict[str, Any]) -> "tuple[Optional[dict], str]":
    """One artifact → one corpus sample (or ``(None, reason)``)."""
    op = data.get("operation")
    if not op or "timings" not in data:
        return None, "not a sweep artifact (no operation/timings)"
    timings = _flat_timings_us(data)
    if not timings:
        return None, "no finite timing samples"
    wire = sample_wire_bytes(op, data)
    if wire is None:
        return None, f"op {op!r} has no analytic wire model"
    ranks = int(data.get("num_ranks", 0))
    variant = str(data.get("variant", "default"))
    timings.sort()
    n = len(timings)
    kind_info = OP_EXPECTED_KINDS.get(op, {})
    kind = kind_info.get("required")
    if kind is None and kind_info.get("required_any"):
        kind = sorted(kind_info["required_any"])[0]
    if kind is None:
        # ops outside OP_EXPECTED_KINDS with a wire model: the
        # collective-matmul micro-ops (fused all-gather / psum_scatter)
        # and the quantised rings (permute chains); record the defining
        # primitive
        kind = {"ag_matmul": "all-gather",
                "matmul_rs": "reduce-scatter"}.get(
                    op, "collective-permute")
    return {
        "file": str(path),
        "op": op,
        "variant": variant,
        "kind": kind,
        "ranks": ranks,
        "dtype": data.get("dtype"),
        "num_elements": int(data.get("num_elements", 0)),
        "wire_bytes": int(wire),
        "flops": op_flops(op, data),
        "collectives": collectives_per_dispatch(op, variant, ranks),
        "dispatches": _dispatches_per_iteration(data),
        "measured_median_us": timings[n // 2],
        "measured_p90_us": timings[min(n - 1, int(n * 0.9))],
        "measured_p99_us": timings[min(n - 1, int(n * 0.99))],
        "iterations": n,
        "tier": tier_of_result(data),
        "host": host_fingerprint(data),
        "timestamp": data.get("timestamp"),
    }, ""


def ingest_calibration(path: Path, data: dict[str, Any],
                       baselines_dir: "Optional[str | Path]" = None
                       ) -> tuple[list[dict[str, Any]], list[dict]]:
    """Calibration reports are corpus rows too — the program-scale half
    of the fit.  Each measured target joins its committed schedule
    baseline (``stats/analysis/baselines/``) for analytic features that
    are **critical-path-consistent**: ``obs calibrate --model cm2``
    predicts ``critical_path(fitted tier) + γ``, so the features a
    calibration row regresses against must describe the critical path,
    not the whole program — collective count and wire bytes scaled by
    the baseline's ``comm_on_critical_path_us / comm_total_us`` ratio
    (the baselines record the cm1-priced split, not a per-instruction
    on-path inventory — all of one program's collectives are near-twins,
    so the µs ratio transfers to counts and bytes), and FLOPs as the
    critical path's compute slack (``critical_path_us −
    comm_on_critical_path_us``) re-expanded through the cm1 peak it was
    priced with.  Micro rows alone cannot separate the per-dispatch γ
    from the per-collective α (every micro dispatch posts >= 1
    collective); a calibration row with ZERO collectives (the serving
    prefix-attach program) pins γ directly, and the many-instruction train
    steps anchor the effective peak.  ``measured_us`` is
    model-independent, so reports priced with either model ingest
    identically."""
    from dlbb_tpu.analysis.costmodel import get_tier
    from dlbb_tpu.analysis.schedule_audit import (
        DEFAULT_BASELINE_DIR,
        load_baselines,
    )

    baselines_dir = Path(baselines_dir or DEFAULT_BASELINE_DIR)
    skipped: list[dict] = []
    if not baselines_dir.is_dir():
        return [], [{"file": str(path),
                     "reason": (f"no schedule baselines under "
                                f"{baselines_dir} to join features from")}]
    baselines = load_baselines(baselines_dir)
    cm1 = get_tier(data.get("tier") or None)
    samples: list[dict[str, Any]] = []
    # skip records carrying a measured_us are the zero-critical-path
    # programs cm1 could not score but measured anyway — the corpus's
    # pure per-dispatch-γ anchors
    rows = list(data.get("targets", ())) + [
        s for s in data.get("skipped", ()) if "measured_us" in s
    ]
    for row in rows:
        base = baselines.get(row.get("target"))
        m = row.get("measured_us")
        if base is None:
            skipped.append({"file": f"{path}::{row.get('target')}",
                            "reason": "no schedule baseline to join"})
            continue
        if not isinstance(m, (int, float)) or not math.isfinite(m) \
                or m <= 0:
            skipped.append({"file": f"{path}::{row.get('target')}",
                            "reason": "non-finite measured_us"})
            continue
        comm_total_us = float(base.get("comm_total_us", 0.0))
        comm_cp_us = float(
            base.get("comm_on_critical_path_us", comm_total_us))
        cp_us = float(base.get("critical_path_us", 0.0))
        on_cp = comm_cp_us / comm_total_us if comm_total_us > 0 else 0.0
        samples.append({
            "file": f"{path}::{row['target']}",
            "op": row["target"],
            "variant": "calibration",
            "kind": "program",
            "ranks": 8,
            "dtype": None,
            "num_elements": 0,
            "wire_bytes": int(base.get("total_wire_bytes", 0) * on_cp),
            "flops": int(max(cp_us - comm_cp_us, 0.0)
                         * cm1.peak_flops_per_us),
            "collectives": float(base.get("num_collectives", 0) * on_cp),
            "dispatches": 1.0,
            "measured_median_us": float(m),
            # calibration rows record p90, not p99 — no fabricated tail
            "measured_p90_us": float(row.get("measured_p90_us", m)),
            "measured_p99_us": None,
            "iterations": int(row.get("reps", 1)),
            "tier": data.get("tier", "cpu-sim"),
            "host": "calibration",
            "timestamp": data.get("timestamp"),
        })
    return samples, skipped


_DEVTRACE_SAMPLE_KEYS = ("op", "kind", "ranks", "wire_bytes",
                         "measured_median_us")


def ingest_devtrace(path: Path, data: dict[str, Any]
                    ) -> tuple[list[dict[str, Any]], list[dict]]:
    """A devtrace report's ``op_samples`` are corpus rows too — the
    op-granularity, device-timed half of the fit (``source:
    "devtrace"``).  Each row is ONE collective op's measured device
    communication time with ``dispatches: 0`` (a device-op duration
    carries no host dispatch) and ``flops: 0`` (compute events are
    bucketed separately), so the population identifies
    ``α·collectives + wire/β`` directly — the rows that un-pin β on
    the cpu-sim tier (``obs fit``)."""
    samples: list[dict[str, Any]] = []
    skipped: list[dict] = []
    for n, row in enumerate(data.get("op_samples", ())):
        if not isinstance(row, dict) or any(
                k not in row for k in _DEVTRACE_SAMPLE_KEYS):
            skipped.append({"file": f"{path}::op_samples[{n}]",
                            "reason": "malformed devtrace op sample"})
            continue
        m = row.get("measured_median_us")
        if not isinstance(m, (int, float)) or not math.isfinite(m) \
                or m <= 0:
            skipped.append({"file": f"{path}::op_samples[{n}]",
                            "reason": "non-finite measured_median_us"})
            continue
        sample = dict(row)
        sample.setdefault("source", "devtrace")
        sample.setdefault("dispatches", 0.0)
        sample.setdefault("flops", 0)
        sample.setdefault("host", "devtrace")
        samples.append(sample)
    return samples, skipped


def _manifest_summary(path: Path, data: dict[str, Any]) -> dict[str, Any]:
    """Compile/dedup aggregates of one ``sweep_manifest.json`` — corpus
    metadata (per-directory context for the samples), not samples."""
    out: dict[str, Any] = {"file": str(path)}
    for key in ("wall_seconds", "compile_seconds_total",
                "cost_model_version"):
        if key in data:
            out[key] = data[key]
    dedup = data.get("dedup") or data.get("work_units")
    if isinstance(dedup, dict):
        out["dedup"] = dedup
    cal = data.get("calibration")
    if isinstance(cal, dict):
        out["calibration"] = {
            k: cal.get(k) for k in ("tier", "cost_model_version",
                                    "geomean_error_factor")
        }
    return out


def build_corpus(roots: "Sequence[str | Path]",
                 verbose: bool = False,
                 baselines_dir: "Optional[str | Path]" = None
                 ) -> dict[str, Any]:
    """Walk one or more results trees into the normalised sample table.

    Calibration reports/baselines among the roots contribute
    program-scale rows (:func:`ingest_calibration`, features joined from
    ``baselines_dir``).  Returns ``{schema, samples, skipped, manifests,
    roots}``; raises :class:`FileNotFoundError` when no root exists (a
    typo'd path must fail loudly, not fit an empty corpus)."""
    roots = [Path(r) for r in roots]
    live = [r for r in roots if r.exists()]
    if not live:
        raise FileNotFoundError(
            f"no corpus root exists among {[str(r) for r in roots]}"
        )
    samples: list[dict[str, Any]] = []
    skipped: list[dict[str, str]] = []
    manifests: list[dict[str, Any]] = []
    for root in live:
        files = [root] if root.is_file() else sorted(root.rglob("*.json"))
        for path in files:
            if _PREFILTER_NAMES.match(path.name):
                continue
            try:
                data = json.loads(path.read_text())
            except (OSError, json.JSONDecodeError) as e:
                skipped.append({"file": str(path),
                                "reason": f"unreadable: {e}"})
                continue
            if not isinstance(data, dict):
                continue
            if path.name == "sweep_manifest.json":
                manifests.append(_manifest_summary(path, data))
                continue
            if data.get("schema") == "dlbb_calibration_v1":
                cal_samples, cal_skipped = ingest_calibration(
                    path, data, baselines_dir=baselines_dir)
                samples.extend(cal_samples)
                skipped.extend(cal_skipped)
                continue
            if data.get("schema") == "dlbb_devtrace_v1":
                dt_samples, dt_skipped = ingest_devtrace(path, data)
                samples.extend(dt_samples)
                skipped.extend(dt_skipped)
                continue
            if _NON_SAMPLE_NAMES.match(path.name):
                continue
            sample, reason = ingest_result(path, data)
            if sample is None:
                skipped.append({"file": str(path), "reason": reason})
                continue
            samples.append(sample)
    if verbose:
        tiers: dict[str, int] = {}
        for s in samples:
            tiers[s["tier"]] = tiers.get(s["tier"], 0) + 1
        print(f"[corpus] {len(samples)} sample(s) "
              f"({', '.join(f'{t}: {n}' for t, n in sorted(tiers.items()))})"
              f", {len(skipped)} skipped, {len(manifests)} manifest(s)")
    return {
        "schema": CORPUS_SCHEMA,
        "roots": [str(r) for r in roots],
        "samples": samples,
        "skipped": skipped,
        "manifests": manifests,
    }


def save_corpus(corpus: dict[str, Any], path: "str | Path") -> Path:
    from dlbb_tpu.utils.config import atomic_write_text

    return atomic_write_text(
        json.dumps(corpus, indent=1, sort_keys=True), Path(path)
    )
