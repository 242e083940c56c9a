"""Reference-vs-dlbb_tpu head-to-head comparison report.

Runs the repo's own stats pipeline over BOTH artifact corpora — the
reference's checked-in result JSONs (``/root/reference/collectives/{1d,3d}/
results/<backend>/``, its §6 published baseline) and this repo's
``results/{1d,3d}/`` — joins them per configuration, and emits one committed
CSV + markdown report stating, per (op x size x ranks) point, whether
``xla_tpu`` matches, beats, or loses to the BEST reference backend at that
point (best = lowest mean time across openmpi / intelmpi / dsgloo / dsccl
and, for 3D, every dsccl tuning variant directory).

Honesty caveats (carried into the report header):

- the reference corpus was measured on its 56-core CPU node with real
  MPI/oneCCL processes; this repo's committed corpus is the CPU-*simulated*
  8-device mesh on this image's single core (XLA collectives over host RAM,
  not ICI — there is no multi-chip TPU here to measure).  The comparison is
  therefore stack-vs-stack at equal rank counts, not fabric-vs-fabric.
- chunked-timing rows (``timing_granularity`` column) aggregate chunk
  means; mean comparisons remain valid, tail comparisons do not.
- the reference publishes no E2E number (BASELINE.md); the E2E section
  compares the committed ``results/e2e`` corpus against the re-measured
  reference-stack torch-CPU baseline (``bench_baseline_cpu.json``).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Optional

import numpy as np

from dlbb_tpu.stats.stats1d import process_file as process_1d_file
from dlbb_tpu.stats.stats3d import calculate_statistics_3d

# Above/below these speedup thresholds the verdict is beat/lose; between
# them the difference is within run-to-run noise and counts as a match.
BEAT, LOSE = 1.05, 0.95

# Rows whose own-side artifact was measured on the CPU-simulated mesh
# (system_info.backend == "cpu") are environment-vs-environment, not
# stack-vs-stack: 8-56 virtual devices serialised on one host core against
# the reference's real 56-core MPI node.  They get this verdict CLASS
# (structurally, not as prose caveat); the raw numbers and the speedup-based
# ``raw_verdict`` are kept alongside.
NOT_COMPARABLE = "not_comparable(simulated)"

COLUMNS_1D = [
    "operation", "data_size_name", "num_ranks", "xla_dtype",
    "ref_best_backend", "ref_best_mean_us", "ref_best_bandwidth_gbps",
    "xla_mean_us", "xla_bandwidth_gbps",
    # analytic per-device wire bytes of the own-side implementation
    # (stats1d carries it per row): bandwidth columns normalise by
    # LOGICAL payload, so this is where a compressed row's wire saving
    # is visible next to its uncompressed baseline (docs/compression.md)
    "xla_bytes_on_wire",
    "speedup", "verdict",
    "raw_verdict",
]

COLUMNS_3D = [
    "operation", "num_ranks", "batch", "seq_len", "hidden_dim",
    "tensor_size_mb", "ref_best_backend", "ref_best_mean_ms",
    "xla_mean_ms", "speedup", "verdict", "raw_verdict",
]


def _raw_verdict(speedup: float) -> str:
    if speedup >= BEAT:
        return "beat"
    if speedup <= LOSE:
        return "lose"
    return "match"


def _verdict_pair(speedup: float, own_backend: Optional[str]) -> dict:
    """verdict (class-aware) + raw_verdict (speedup-only) columns."""
    raw = _raw_verdict(speedup)
    verdict = NOT_COMPARABLE if own_backend == "cpu" else raw
    return {"verdict": verdict, "raw_verdict": raw}


def _rows_1d(results_dir: Path) -> list[dict[str, Any]]:
    """Stats rows for every 1D result JSON in one directory (in memory —
    same math as ``process_1d_results``, no artifacts written)."""
    rows = []
    for f in sorted(Path(results_dir).glob("*.json")):
        if f.name.endswith("_stats.json"):
            continue
        try:
            rows.append(process_1d_file(f))
        except Exception:  # noqa: BLE001 — per-file resilience
            continue
    return rows


def _rows_3d(results_dir: Path, backend: str) -> list[dict[str, Any]]:
    rows = []
    for f in sorted(Path(results_dir).glob("*.json")):
        if f.name.endswith("_stats.json"):
            continue
        try:
            data = json.loads(f.read_text())
            shape = data["tensor_shape"]
            rows.append({
                "backend": backend,
                "measured_backend": (data.get("system_info") or {}).get(
                    "backend"),
                "operation": data["operation"],
                "num_ranks": data["num_ranks"],
                "batch": shape["batch"],
                "seq_len": shape["seq_len"],
                "hidden_dim": shape["hidden_dim"],
                "tensor_size_mb": data["tensor_size_mb"],
                **calculate_statistics_3d(data["timings"]),
            })
        except Exception:  # noqa: BLE001
            continue
    return rows


def compare_1d(
    ref_results_root: Path, own_results_dir: Path
) -> list[dict[str, Any]]:
    """Join per (operation, data_size_name, num_ranks); one output row per
    config both corpora cover."""
    own = _rows_1d(own_results_dir)
    if not own or not Path(ref_results_root).is_dir():
        return []
    ref_best: dict[tuple, dict] = {}
    for backend_dir in sorted(Path(ref_results_root).iterdir()):
        if not backend_dir.is_dir():
            continue
        for r in _rows_1d(backend_dir):
            key = (r["operation"], r["data_size_name"], r["num_ranks"])
            if (key not in ref_best
                    or r["mean_time_us"] < ref_best[key]["mean_time_us"]):
                ref_best[key] = dict(r, backend=backend_dir.name)

    out = []
    for r in own:
        # own-side rows are keyed by (op, size, ranks, dtype): the corpus
        # carries bf16 (TPU-native) + fp32 (north-star companion) + fp16
        # (the reference's own dtype — parity slice), each joined against
        # the same reference best
        key = (r["operation"], r["data_size_name"], r["num_ranks"])
        ref = ref_best.get(key)
        if ref is None:
            continue
        speedup = ref["mean_time_us"] / r["mean_time_us"]
        out.append({
            "operation": key[0],
            "data_size_name": key[1],
            "num_ranks": key[2],
            "xla_dtype": r.get("dtype", ""),
            "ref_best_backend": ref["backend"],
            "ref_best_mean_us": round(ref["mean_time_us"], 3),
            "ref_best_bandwidth_gbps": (
                round(ref["bandwidth_gbps"], 4)
                if ref["bandwidth_gbps"] is not None else None
            ),
            "xla_mean_us": round(r["mean_time_us"], 3),
            "xla_bandwidth_gbps": (
                round(r["bandwidth_gbps"], 4)
                if r["bandwidth_gbps"] is not None else None
            ),
            "xla_bytes_on_wire": r.get("bytes_on_wire"),
            "speedup": round(speedup, 4),
            **_verdict_pair(speedup, r.get("backend")),
        })
    out.sort(key=lambda r: (r["operation"], r["num_ranks"],
                            r["xla_dtype"], r["xla_mean_us"]))
    return out


def compare_3d(
    ref_results_root: Path, own_results_dir: Path
) -> list[dict[str, Any]]:
    """Join per (operation, ranks, batch, seq, hidden).  Every reference
    directory — the four backends AND the dsccl tuning variants — competes
    for "best", because the tuned runs are legitimately the reference's
    best published numbers (SURVEY §2.3)."""
    own = _rows_3d(own_results_dir, "xla_tpu")
    if not own or not Path(ref_results_root).is_dir():
        return []
    ref_best: dict[tuple, dict] = {}
    for backend_dir in sorted(Path(ref_results_root).iterdir()):
        if not backend_dir.is_dir():
            continue
        for r in _rows_3d(backend_dir, backend_dir.name):
            key = (r["operation"], r["num_ranks"], r["batch"],
                   r["seq_len"], r["hidden_dim"])
            if (key not in ref_best
                    or r["mean_time_ms"] < ref_best[key]["mean_time_ms"]):
                ref_best[key] = r

    out = []
    for r in own:
        key = (r["operation"], r["num_ranks"], r["batch"],
               r["seq_len"], r["hidden_dim"])
        ref = ref_best.get(key)
        if ref is None:
            continue
        speedup = ref["mean_time_ms"] / r["mean_time_ms"]
        out.append({
            "operation": key[0], "num_ranks": key[1], "batch": key[2],
            "seq_len": key[3], "hidden_dim": key[4],
            "tensor_size_mb": r["tensor_size_mb"],
            "ref_best_backend": ref["backend"],
            "ref_best_mean_ms": round(ref["mean_time_ms"], 4),
            "xla_mean_ms": round(r["mean_time_ms"], 4),
            "speedup": round(speedup, 4),
            **_verdict_pair(speedup, r.get("measured_backend")),
        })
    out.sort(key=lambda r: (r["operation"], r["num_ranks"],
                            r["hidden_dim"], r["seq_len"], r["batch"]))
    return out


def _e2e_rows(repo_root: Path) -> list[dict[str, Any]]:
    """E2E tokens/s vs the reference-stack CPU baseline, from the committed
    per-config e2e corpus under ``results/e2e`` (attention-mode ladder,
    long-context ladder, infeasibility boundaries)."""
    rows = []
    cpu = repo_root / "bench_baseline_cpu.json"
    base_tps = (json.loads(cpu.read_text())["tokens_per_second"]
                if cpu.exists() else None)
    e2e_dir = repo_root / "results" / "e2e"
    if e2e_dir.exists():
        # dedupe by experiment name: if a measured artifact and a stale
        # *_infeasible.json coexist transiently (cleanup happens only on
        # publisher success), the measured one wins — mirrors
        # stage_baseline's setdefault logic
        by_name: dict[str, dict] = {}
        for f in sorted(e2e_dir.glob("*.json")):
            try:
                r = json.loads(f.read_text())
            except Exception:  # noqa: BLE001
                continue
            name = r.get("experiment", {}).get("name", f.stem)
            prev = by_name.get(name)
            if prev is not None:
                prev_measured = prev.get("status") != "infeasible"
                this_measured = r.get("status") != "infeasible"
                if prev_measured or not this_measured:
                    continue
            by_name[name] = r
        for name, r in by_name.items():
            sysinfo = r.get("system_info") or {}
            device = (
                f"{sysinfo.get('device_kind', '?')} x "
                f"{sysinfo.get('num_devices', '?')}"
            )
            simulated = sysinfo.get("backend") == "cpu"
            if r.get("status") == "infeasible":
                rows.append({
                    "config": f"{name} (results/e2e)",
                    "device": (device if sysinfo else "(not recorded)"),
                    "reference_cpu_stack_tokens_per_s": None,
                    "xla_tpu_tokens_per_s": None,
                    "speedup": None,
                    "verdict": "infeasible (see artifact reason)",
                })
                continue
            if "tokens_per_second" not in r:
                continue
            tps = r["tokens_per_second"]
            # the CPU-stack baseline was measured at the reference's
            # b8/s512 1B shape — speedup only claimed at that shape,
            # and never for simulated-mesh artifacts
            comparable = (base_tps is not None and not simulated
                          and name.startswith("1b_")
                          and name.endswith("_s512_world1"))
            rows.append({
                "config": f"{name} (results/e2e)",
                "device": device + (" (simulated)" if simulated else ""),
                "reference_cpu_stack_tokens_per_s": (
                    round(base_tps, 1) if comparable else None),
                "xla_tpu_tokens_per_s": round(tps, 1),
                "speedup": (round(tps / base_tps, 2) if comparable
                            else None),
                "verdict": (
                    _raw_verdict(tps / base_tps) if comparable
                    else "(simulated mesh — sharding evidence, not a "
                         "chip number)" if simulated
                    else "(no reference number)"
                ),
            })
    return rows


def _counts(rows: list[dict]) -> dict[str, Any]:
    """beat/match/lose count only COMPARABLE rows (same-environment
    measurements); simulated rows are counted (and sub-broken-down by
    raw_verdict) under ``not_comparable_simulated``."""
    c: dict[str, Any] = {"beat": 0, "match": 0, "lose": 0,
                         "not_comparable_simulated": 0}
    raw = {"beat": 0, "match": 0, "lose": 0}
    for r in rows:
        if r["verdict"] == NOT_COMPARABLE:
            c["not_comparable_simulated"] += 1
            raw[r["raw_verdict"]] += 1
        elif r["verdict"] in c:
            c[r["verdict"]] += 1
    if c["not_comparable_simulated"]:
        c["not_comparable_raw_verdicts"] = raw
    return c


def md_table(rows: list[dict], columns: list[str]) -> list[str]:
    """Markdown table lines (None cells render blank) — the one table
    emitter shared by every stats report module."""
    return _md_table(rows, columns)


def _md_table(rows: list[dict], columns: list[str]) -> list[str]:
    lines = ["| " + " | ".join(columns) + " |",
             "|" + "---|" * len(columns)]
    for r in rows:
        lines.append(
            "| "
            + " | ".join(
                "" if r.get(c) is None else str(r[c]) for c in columns
            )
            + " |"
        )
    return lines


def _write_csv(rows: list[dict], columns: list[str], path: Path) -> None:
    import csv
    import io

    from dlbb_tpu.utils.config import atomic_write_text

    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=columns)
    w.writeheader()
    for r in rows:
        w.writerow({k: r.get(k) for k in columns})
    atomic_write_text(buf.getvalue(), path, newline="")


def _distinct_configs(rows: list[dict]) -> int:
    """Distinct reference configs covered — dtype is an own-side axis, so
    a (op, size, ranks) point measured in several dtypes (bf16/fp16/fp32)
    is ONE config with one row per dtype."""
    keys = set()
    for r in rows:
        if "data_size_name" in r:
            keys.add((r["operation"], r["data_size_name"], r["num_ranks"]))
        else:
            keys.add((r["operation"], r["num_ranks"], r["batch"],
                      r["seq_len"], r["hidden_dim"]))
    return len(keys)


def _summary_line(dim: str, rows: list[dict], c: dict) -> str:
    line = (f"- **{dim}** ({_distinct_configs(rows)} configs, "
            f"{len(rows)} rows): {c['beat']} beat, "
            f"{c['match']} match, {c['lose']} lose")
    if c["not_comparable_simulated"]:
        raw = c["not_comparable_raw_verdicts"]
        line += (f", {c['not_comparable_simulated']} not_comparable"
                 f"(simulated) [raw: {raw['beat']} beat / {raw['match']} "
                 f"match / {raw['lose']} lose]")
    return line


def write_comparison(
    ref_root: Path,
    own_1d: Path,
    own_3d: Path,
    out_dir: Path,
    repo_root: Optional[Path] = None,
) -> dict[str, Any]:
    """Produce ``comparison_{1d,3d}.csv`` + ``COMPARISON.md`` in
    ``out_dir``; returns the summary dict (also saved as JSON)."""
    ref_root = Path(ref_root)
    out_dir = Path(out_dir)
    rows_1d = compare_1d(ref_root / "collectives" / "1d" / "results", own_1d)
    rows_3d = compare_3d(ref_root / "collectives" / "3d" / "results", own_3d)
    e2e = _e2e_rows(repo_root) if repo_root else []

    _write_csv(rows_1d, COLUMNS_1D, out_dir / "comparison_1d.csv")
    _write_csv(rows_3d, COLUMNS_3D, out_dir / "comparison_3d.csv")

    c1, c3 = _counts(rows_1d), _counts(rows_3d)
    summary = {
        "1d": {"configs": _distinct_configs(rows_1d),
               "rows": len(rows_1d), **c1},
        "3d": {"configs": _distinct_configs(rows_3d),
               "rows": len(rows_3d), **c3},
        "e2e": e2e,
        "thresholds": {"beat": BEAT, "lose": LOSE},
    }

    md = [
        "# Reference vs dlbb_tpu — head-to-head comparison",
        "",
        "Per-config join of the reference's checked-in baseline corpus "
        "(`/root/reference/collectives/{1d,3d}/results/`) against this "
        "repo's committed `results/{1d,3d}/` corpus, both processed by "
        "this repo's stats pipeline.  `ref_best_*` is the fastest "
        "reference backend (incl. dsccl tuning variants) at that config; "
        "`speedup` = ref_best_mean / xla_mean (>1 = xla_tpu faster); "
        f"verdict thresholds: beat >= {BEAT}x, lose <= {LOSE}x.",
        "",
        "**Caveats** (see `dlbb_tpu/stats/compare.py` docstring): the "
        "reference corpus ran real MPI/oneCCL ranks on a 56-core node; "
        "this repo's corpus runs the CPU-simulated 8-device mesh on this "
        "image's single core (host-RAM collectives, not ICI).  The join "
        "covers the rank counts both corpora measured.  `xla_dtype` "
        "float16 rows use the reference's own payload dtype (the closest "
        "apples-to-apples rows); bf16 is the TPU-native dtype and fp32 "
        "the north-star companion.  The three dtypes share per-config "
        "*element counts* with the reference labels: fp16/bf16 rows "
        "therefore byte-match the fp16-measured reference, while fp32 "
        "rows move 2x the reference's bytes at the same size label "
        "(4 B/element) — their speedup/raw_verdict values compare "
        "doubled payload volume.  E2E "
        "rows are real-TPU-chip numbers vs the re-measured "
        "reference-stack torch-CPU baseline.",
        "",
        "## Summary",
        "",
        "beat/match/lose count comparable (same-environment) rows only; "
        "rows measured on the CPU-simulated mesh carry the structural "
        "verdict `not_comparable(simulated)` (raw numbers and the "
        "speedup-only `raw_verdict` kept per row).",
        "",
        _summary_line("1D", rows_1d, c1),
        _summary_line("3D", rows_3d, c3),
        "",
    ]
    if e2e:
        md += ["## E2E forward throughput (per-row device column)", ""]
        md += _md_table(
            e2e,
            ["config", "device", "reference_cpu_stack_tokens_per_s",
             "xla_tpu_tokens_per_s", "speedup", "verdict"],
        )
        md.append("")
    md += ["## 1D collectives (full table)", ""]
    md += _md_table(rows_1d, COLUMNS_1D)
    md += ["", "## 3D collectives (per op x ranks aggregate; "
           "full detail in comparison_3d.csv)", ""]
    agg_rows = []
    for (op, ranks) in sorted({(r["operation"], r["num_ranks"])
                               for r in rows_3d}):
        sub = [r for r in rows_3d
               if r["operation"] == op and r["num_ranks"] == ranks]
        cs = _counts(sub)
        agg_rows.append({
            "operation": op, "num_ranks": ranks, "configs": len(sub),
            "beat": cs["beat"], "match": cs["match"], "lose": cs["lose"],
            "not_comparable": cs["not_comparable_simulated"],
            "median_speedup": round(
                float(np.median([r["speedup"] for r in sub])), 3),
        })
    md += _md_table(agg_rows, ["operation", "num_ranks", "configs", "beat",
                               "match", "lose", "not_comparable",
                               "median_speedup"])
    md.append("")

    from dlbb_tpu.utils.config import atomic_write_text

    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "COMPARISON.md").write_text("\n".join(md))
    atomic_write_text(json.dumps(summary, indent=2) + "\n",
                      out_dir / "comparison_summary.json")
    return summary
