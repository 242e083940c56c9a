"""Predicted-vs-measured calibration gate (the cost-model falsifier).

The α–β schedule auditor (PR 7) predicts a ``critical_path_us`` per audit
target from the versioned cost-model table and commits the predictions
under ``stats/analysis/baselines/`` — but nothing validated those numbers
against a real execution, which ROADMAP item 2 calls out: the model must
report predicted-vs-measured error as a first-class stat or it is
unfalsifiable.  This module closes the loop:

- :func:`run_calibration` rebuilds every committed baseline target's
  program through the SAME ``hlo_audit`` builder the prediction was
  lowered from (so predicted and measured are the identical compiled
  artifact by construction), measures its real median execution time on
  the current mesh (per-iteration ``block_until_ready`` timing — honest
  on the sim mesh, where the committed ``cpu-sim`` baselines live), and
  reports the **signed relative error** ``(measured - predicted) /
  predicted`` per target plus an aggregate (median signed error, geomean
  error factor).  The report lands as JSON + CSV
  (``atomic_write_text``), and the aggregate is merged into the output
  directory's ``sweep_manifest.json``.
- :func:`diff_calibration` compares a fresh report against the committed
  calibration baseline (``stats/analysis/calibration/``) and emits
  findings when the model error REGRESSES past the gate — the aggregate
  geomean error factor growing more than :data:`AGGREGATE_SLACK` over
  the committed run fails CI (``cli obs diff``, pinned
  ``findings.EXIT_*`` codes); per-target drift warns.  Aggregates are
  recomputed over the JOINED target set, so a subset run (the
  ``obs_smoke`` stage) diffs soundly against a full committed baseline.

Donating programs (train steps) are measured through a carry protocol:
when a second call on the original arguments dies on the donated buffer,
the step's own output state is fed back as the next input — the same
dataflow the real training loop executes.
"""

from __future__ import annotations

import json
import math
import time
from pathlib import Path
from typing import Any, Optional, Sequence

from dlbb_tpu.analysis.costmodel import (
    COST_MODEL_VERSION,
    resolve_tier,
)
from dlbb_tpu.analysis.findings import (
    SEVERITY_ERROR,
    SEVERITY_WARNING,
    Finding,
)
from dlbb_tpu.analysis.schedule_audit import DEFAULT_BASELINE_DIR

CALIBRATION_SCHEMA = "dlbb_calibration_v1"

# committed calibration baseline (the diff gate's reference point)
DEFAULT_CALIBRATION_DIR = Path("stats/analysis/calibration")
# where `cli obs calibrate` writes fresh reports by default
DEFAULT_REPORT_DIR = Path("results/obs")
BASELINE_NAME = "calibration_baseline.json"
REPORT_NAME = "calibration_report.json"
CSV_NAME = "calibration_report.csv"
METRICS_NAME = "metrics.prom"


def baseline_name(model: str = COST_MODEL_VERSION) -> str:
    """Each cost model gets its own committed baseline file (cm1 keeps
    the historical name): the error factors of different models are not
    comparable, so the diff gate never joins across them."""
    if model in (None, COST_MODEL_VERSION):
        return BASELINE_NAME
    return f"calibration_baseline_{model}.json"

# diff-gate slacks: measured medians on a loaded CPU host wobble by
# small factors run to run (a process-cold subset run measured ~3.5x
# hotter than the full-surface committed baseline on this 2-core box),
# so the gate is on the ERROR FACTOR (the max/min ratio of measured vs
# predicted, always >= 1) growing by a generous multiplicative margin —
# not on absolute microseconds.  The gate exists to catch ORDER-OF-
# MAGNITUDE model regressions (a cost-table typo, a backend swap, a
# contaminated measurement path); run-to-run host noise must never trip
# it (cost-model VERSION changes are caught exactly by the version pin)
AGGREGATE_SLACK = 8.0   # geomean error factor across joined targets
TARGET_SLACK = 16.0     # per-target factor (warning only)

CSV_COLUMNS = (
    "target", "tier", "cost_model_version", "predicted_us",
    "dispatch_count", "predicted_dispatch_overhead_us", "measured_us",
    "signed_rel_error", "error_factor", "reps",
)


def _error_factor(measured: float, predicted: float) -> float:
    m, p = max(measured, 1e-9), max(predicted, 1e-9)
    return max(m, p) / min(m, p)


def measure_target(target: Any, warmup: int = 5,
                   reps: int = 30) -> dict[str, Any]:
    """Median (+ spread) execution time in µs of one audit target's
    program — the same ``build()`` the schedule auditor lowered, now
    actually run.  Per-iteration ``perf_counter`` + ``block_until_ready``
    brackets (honest on sync backends, i.e. the sim mesh the committed
    baselines are priced for).

    Donation-aware: when the program consumes its first argument (train
    steps), the returned state is carried into the next call."""
    import jax

    fn, args = target.build()
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    out = jitted(*args)
    jax.block_until_ready(out)  # absorbs compile
    cur_args = tuple(args)
    # carry protocols, probed in order: "head" feeds out[0] back as the
    # next first argument (train steps returning (state, metrics)),
    # "whole" feeds the entire output back (programs whose output IS the
    # donated carry)
    carry = None
    try:
        out = jitted(*cur_args)
        jax.block_until_ready(out)
    except Exception:  # noqa: BLE001 — donated-buffer probe
        probe_err: Optional[Exception] = None
        for mode in ("head", "whole"):
            try:
                fed = out[0] if mode == "head" else out
                trial = (fed, *cur_args[1:])
                out = jitted(*trial)
                jax.block_until_ready(out)
                carry = mode
                cur_args = ((out[0] if mode == "head" else out),
                            *cur_args[1:])
                break
            except Exception as e:  # noqa: BLE001 — try the next protocol
                probe_err = e
        if carry is None:
            raise probe_err
    samples: list[float] = []
    for i in range(max(0, warmup - 2) + reps):
        t0 = time.perf_counter()
        out = jitted(*cur_args)
        jax.block_until_ready(out)
        elapsed = time.perf_counter() - t0
        if carry is not None:
            cur_args = ((out[0] if carry == "head" else out),
                        *cur_args[1:])
        if i >= max(0, warmup - 2):
            samples.append(elapsed)
    samples.sort()
    n = len(samples)
    return {
        "measured_us": samples[n // 2] * 1e6,
        "measured_min_us": samples[0] * 1e6,
        "measured_p90_us": samples[min(n - 1, int(n * 0.9))] * 1e6,
        "reps": n,
        "donated_carry": carry is not None,
        **({"carry_protocol": carry} if carry else {}),
    }


def run_calibration(
    baselines_dir: Optional[Path] = None,
    out_dir: Optional[Path] = None,
    tier: Optional[str] = None,
    reps: int = 30,
    warmup: int = 5,
    target_filter: Optional[Sequence[str]] = None,
    verbose: bool = True,
    model: str = COST_MODEL_VERSION,
    fit_dir: "Optional[str | Path]" = None,
) -> dict[str, Any]:
    """Measure every committed schedule-baseline target buildable on the
    current mesh and join against its predicted wall time.  Returns
    (and writes) the calibration report; merges the aggregate into
    ``out_dir/sweep_manifest.json``.

    ``model`` selects the pricing: cm1 reads each committed baseline's
    ``critical_path_us`` (γ = 0, the historical behaviour); cm2 resolves
    the fitted tier (``stats/analysis/costmodel_fit/``) and re-prices
    every target's schedule with the fitted α/β/peak plus the
    per-dispatch γ — falling back to cm1 with a loud ``fit-missing``
    warning when no DB is committed (the report records the model that
    actually priced it)."""
    import jax

    from dlbb_tpu.analysis.hlo_audit import default_targets, default_tier
    from dlbb_tpu.analysis.schedule_audit import load_baselines
    from dlbb_tpu.obs import spans

    baselines_dir = Path(baselines_dir or DEFAULT_BASELINE_DIR)
    out_dir = Path(out_dir or DEFAULT_REPORT_DIR)
    tier = tier or default_tier()
    cost_tier = resolve_tier(tier, model=model, fit_dir=fit_dir)
    baselines = load_baselines(baselines_dir)
    if not baselines:
        raise FileNotFoundError(
            f"no committed schedule baselines under {baselines_dir} — "
            "run `python -m dlbb_tpu.cli analyze snapshot --simulate 8` "
            "first (the calibration joins against them)"
        )
    builders = {t.name: t for t in default_targets()}
    n_devices = len(jax.devices())

    rows: list[dict[str, Any]] = []
    skipped: list[dict[str, str]] = []
    for name in sorted(baselines):
        base = baselines[name]
        if target_filter and not any(s in name for s in target_filter):
            skipped.append({"target": name, "reason": "filtered"})
            continue
        target = builders.get(name)
        if target is None:
            skipped.append({"target": name,
                            "reason": "no registry builder for target"})
            continue
        if target.min_devices > n_devices:
            skipped.append({
                "target": name,
                "reason": (f"needs {target.min_devices} devices, "
                           f"{n_devices} available"),
            })
            continue
        if base.get("tier") != tier:
            skipped.append({
                "target": name,
                "reason": (f"baseline priced for tier "
                           f"{base.get('tier')!r}, measuring on {tier!r}"),
            })
            continue
        overhead = cost_tier.gamma_dispatch_us
        if cost_tier.version == COST_MODEL_VERSION:
            cp = base.get("critical_path_us")
            if not cp:
                # cm1 prices this program at zero (no collectives, no
                # dots — e.g. the serving prefix-attach jit): nothing to
                # compare, BUT its measured time is the purest
                # per-dispatch-γ sample the fit corpus can get, so
                # measure it and carry the number on the skip record
                # (excluded from every aggregate)
                entry = {
                    "target": name,
                    "reason": ("baseline has no critical_path_us "
                               "(measured for the fit corpus only)"),
                }
                try:
                    m = measure_target(target, warmup=warmup, reps=reps)
                    entry["measured_us"] = m["measured_us"]
                    entry["reps"] = m["reps"]
                except Exception as e:  # noqa: BLE001 — containment
                    entry["reason"] += (f"; measurement crashed: "
                                        f"{type(e).__name__}: {e}")
                skipped.append(entry)
                continue
            predicted = float(cp) + overhead  # γ = 0 under cm1
        else:
            # fitted model: re-price this target's schedule with the
            # fitted tier (the committed baselines are cm1-priced, so
            # their numbers cannot serve a cm2 prediction)
            from dlbb_tpu.analysis.hlo_audit import audit_target

            try:
                _f, meta = audit_target(target, passes=("schedule",),
                                        tier=cost_tier)
                predicted = float(meta["schedule"]["predicted_wall_us"])
            except Exception as e:  # noqa: BLE001 — per-target containment
                skipped.append({
                    "target": name,
                    "reason": (f"cm2 re-pricing crashed: "
                               f"{type(e).__name__}: {e}"),
                })
                if verbose:
                    print(f"[obs] {name}: CRASH ({type(e).__name__}: {e})")
                continue
        try:
            with spans.span(f"calibrate:{name}", cat="calibration"):
                measured = measure_target(target, warmup=warmup, reps=reps)
        except Exception as e:  # noqa: BLE001 — per-target containment
            skipped.append({
                "target": name,
                "reason": f"measurement crashed: {type(e).__name__}: {e}",
            })
            if verbose:
                print(f"[obs] {name}: CRASH ({type(e).__name__}: {e})")
            continue
        m_us = measured["measured_us"]
        row = {
            "target": name,
            "tier": tier,
            "cost_model_version": cost_tier.version,
            "predicted_us": float(predicted),
            "dispatch_count": 1,
            "predicted_dispatch_overhead_us": overhead,
            "signed_rel_error": (m_us - predicted) / max(predicted, 1e-9),
            "error_factor": _error_factor(m_us, predicted),
            **measured,
        }
        rows.append(row)
        if verbose:
            print(f"[obs] {name}: predicted {predicted:.1f}us, measured "
                  f"{m_us:.1f}us (err {row['signed_rel_error']:+.1f}x, "
                  f"factor {row['error_factor']:.1f}x)")

    report = {
        "schema": CALIBRATION_SCHEMA,
        "tier": tier,
        "cost_model_version": cost_tier.version,
        "baselines_dir": str(baselines_dir),
        "aggregate": aggregate_errors(rows, skipped),
        "targets": rows,
        "skipped": skipped,
        "timestamp": time.time(),
    }
    if cost_tier.fit is not None:
        report["fit"] = {
            k: cost_tier.fit.get(k)
            for k in ("fit_version", "db_path", "samples_used",
                      "residuals")
        }
    write_report(report, out_dir)
    return report


def aggregate_errors(rows: list[dict[str, Any]],
                     skipped: Sequence[dict] = ()) -> dict[str, Any]:
    """The first-class predicted-vs-measured error stat: median signed
    relative error (bias direction), median absolute relative error, and
    the geometric-mean / max error factors (scale-free accuracy)."""
    if not rows:
        return {
            "targets_measured": 0,
            "targets_skipped": len(skipped),
            "median_signed_rel_error": None,
            "median_abs_rel_error": None,
            "geomean_error_factor": None,
            "max_error_factor": None,
        }
    signed = sorted(r["signed_rel_error"] for r in rows)
    abs_err = sorted(abs(e) for e in signed)
    factors = [r["error_factor"] for r in rows]
    return {
        "targets_measured": len(rows),
        "targets_skipped": len(skipped),
        "median_signed_rel_error": signed[len(signed) // 2],
        "median_abs_rel_error": abs_err[len(abs_err) // 2],
        "geomean_error_factor": math.exp(
            sum(math.log(f) for f in factors) / len(factors)
        ),
        "max_error_factor": max(factors),
    }


def write_report(report: dict[str, Any], out_dir: Path) -> Path:
    """JSON + CSV, atomically; the aggregate also lands in the output
    directory's ``sweep_manifest.json`` (created if absent, merged if a
    sweep already wrote one) so manifest consumers see the calibration
    state next to the compile/cache accounting."""
    import csv
    import io

    from dlbb_tpu.bench.schedule import MANIFEST_NAME, MANIFEST_SCHEMA
    from dlbb_tpu.utils.config import atomic_write_text, save_json

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = atomic_write_text(
        json.dumps(report, indent=2, sort_keys=True), out_dir / REPORT_NAME
    )
    buf = io.StringIO()
    writer = csv.DictWriter(buf, fieldnames=list(CSV_COLUMNS),
                            extrasaction="ignore")
    writer.writeheader()
    for row in report["targets"]:
        writer.writerow(row)
    atomic_write_text(buf.getvalue(), out_dir / CSV_NAME, newline="")

    manifest_path = out_dir / MANIFEST_NAME
    manifest: dict[str, Any] = {"schema": MANIFEST_SCHEMA,
                                "kind": "calibration"}
    if manifest_path.exists():
        try:
            manifest = json.loads(manifest_path.read_text())
        except (OSError, json.JSONDecodeError):
            pass  # torn/legacy manifest: rewrite with the calibration only
    manifest["calibration"] = {
        "tier": report["tier"],
        "cost_model_version": report["cost_model_version"],
        **report["aggregate"],
    }
    if "fit" in report:
        # the fitted-DB version this calibration was priced with — the
        # manifest-side record the fit_smoke CI stage pins
        manifest["calibration"]["fit_version"] = report["fit"].get(
            "fit_version")
        manifest["calibration"]["fitted_db"] = report["fit"].get("db_path")
    manifest.setdefault("timestamp", time.time())
    save_json(manifest, manifest_path)
    _fold_metrics(calibration_metrics(report), out_dir / METRICS_NAME)
    return path


def _metric_family(line: str) -> Optional[str]:
    if line.startswith("# HELP ") or line.startswith("# TYPE "):
        parts = line.split()
        return parts[2] if len(parts) > 2 else None
    if not line or line.startswith("#"):
        return None
    return line.split("{", 1)[0].split(" ", 1)[0]


def _fold_metrics(registry, path: Path) -> Path:
    """Fold the calibration gauges into an existing ``metrics.prom`` —
    calibrating into a sweep/serving output directory must not clobber
    that run's own export (every ``sweep_*``/``serve_*`` series would
    vanish from the scrape target, while the manifest path carefully
    merges).  Existing lines of families the calibration does not own
    are kept verbatim; re-runs replace only their own families."""
    from dlbb_tpu.obs.export import PROM_PREFIX
    from dlbb_tpu.utils.config import atomic_write_text

    own = {PROM_PREFIX + name for name in registry.as_dict()}
    kept: list[str] = []
    try:
        for line in Path(path).read_text().splitlines():
            fam = _metric_family(line)
            if fam is None or fam not in own:
                kept.append(line)
    except OSError:
        pass
    text = ("\n".join(kept) + "\n" if kept else "") \
        + registry.to_prometheus()
    return atomic_write_text(text, Path(path))


def calibration_metrics(report: dict[str, Any], registry=None):
    """Calibration / fit health as Prometheus gauges
    (``metrics.prom`` next to every calibration report): a drifting cost
    model shows up on a scrape dashboard, not only in ``obs diff`` CI."""
    from dlbb_tpu.obs.export import MetricsRegistry

    registry = registry or MetricsRegistry()
    labels = {"tier": report.get("tier"),
              "model": report.get("cost_model_version")}
    agg = report.get("aggregate", {})
    for key, metric, hlp in (
        ("geomean_error_factor", "obs_calibration_error_factor",
         "geomean predicted-vs-measured error factor across targets"),
        ("max_error_factor", "obs_calibration_max_error_factor",
         "worst per-target error factor"),
        ("median_signed_rel_error",
         "obs_calibration_median_signed_rel_error",
         "median signed relative error (bias direction)"),
    ):
        if agg.get(key) is not None:
            registry.set_gauge(metric, agg[key], help=hlp, **labels)
    registry.set_gauge("obs_calibration_targets",
                       agg.get("targets_measured", 0),
                       help="targets measured this calibration",
                       outcome="measured", **labels)
    registry.set_gauge("obs_calibration_targets",
                       agg.get("targets_skipped", 0),
                       outcome="skipped", **labels)
    fit = report.get("fit")
    if fit:
        registry.set_gauge("obs_fit_version", fit.get("fit_version") or 0,
                           help="fitted-DB version this run priced with",
                           **labels)
        if fit.get("samples_used") is not None:
            registry.set_gauge("obs_fit_samples", fit["samples_used"],
                               help="corpus samples the fit kept",
                               **labels)
        res = fit.get("residuals") or {}
        for key, metric, hlp in (
            ("geomean_error_factor", "obs_fit_residual_error_factor",
             "geomean fit residual factor over the corpus"),
            ("rms_log_error", "obs_fit_rms_log_error",
             "rms log-space fit residual"),
        ):
            if res.get(key) is not None:
                registry.set_gauge(metric, res[key], help=hlp, **labels)
    return registry


def save_calibration_baseline(report: dict[str, Any],
                              directory: Optional[Path] = None) -> Path:
    """Commit a calibration report as the diff gate's reference point —
    one file per cost model (``calibration_baseline.json`` for cm1,
    ``calibration_baseline_cm2.json`` for cm2)."""
    from dlbb_tpu.utils.config import atomic_write_text

    directory = Path(directory or DEFAULT_CALIBRATION_DIR)
    directory.mkdir(parents=True, exist_ok=True)
    path = directory / baseline_name(report.get("cost_model_version"))
    atomic_write_text(
        json.dumps(report, indent=2, sort_keys=True) + "\n", path
    )
    return path


def load_calibration_baseline(directory: "Path | str",
                              model: str = COST_MODEL_VERSION
                              ) -> dict[str, Any]:
    directory = Path(directory)
    path = (directory / baseline_name(model) if directory.is_dir()
            else directory)
    return json.loads(path.read_text())


def diff_calibration(report: dict[str, Any],
                     baseline_dir: "Path | str",
                     requested_model: Optional[str] = None
                     ) -> list[Finding]:
    """Findings when the fresh calibration regresses past the committed
    baseline.  The CI-gating (error) rules: no/unreadable baseline,
    cost-model version or tier skew, the report having been priced with
    a DIFFERENT model than ``requested_model`` (the cm2 fit DB fell back
    to cm1 — gating cm1 against its own baseline would silently pass the
    cm2 gate), and the joined-aggregate geomean error factor growing
    more than :data:`AGGREGATE_SLACK`.  Per-target drift and
    improvements warn."""
    findings: list[Finding] = []
    model = report.get("cost_model_version", COST_MODEL_VERSION)
    if requested_model and requested_model != model:
        findings.append(Finding(
            pass_name="obs", rule="cost-model-mismatch",
            severity=SEVERITY_ERROR, target=str(baseline_dir),
            message=(
                f"--model {requested_model} was requested but the "
                f"calibration was priced with {model} (missing fitted "
                "DB? run `python -m dlbb_tpu.cli obs fit` and commit "
                f"stats/analysis/costmodel_fit/) — refusing to gate "
                f"{model} in its place"
            ),
        ))
        return findings
    try:
        base = load_calibration_baseline(baseline_dir, model=model)
    except (OSError, json.JSONDecodeError) as e:
        findings.append(Finding(
            pass_name="obs", rule="missing-calibration-baseline",
            severity=SEVERITY_ERROR, target=str(baseline_dir),
            message=(
                f"no committed {model} calibration baseline ({e}) — run "
                f"`python -m dlbb_tpu.cli obs calibrate --model {model} "
                "--simulate 8` and commit "
                f"{Path(baseline_dir) / baseline_name(model)}"
            ),
        ))
        return findings
    if (base.get("cost_model_version") != report.get("cost_model_version")
            or base.get("tier") != report.get("tier")):
        findings.append(Finding(
            pass_name="obs", rule="cost-model-mismatch",
            severity=SEVERITY_ERROR, target=BASELINE_NAME,
            message=(
                f"calibration baseline is {base.get('cost_model_version')}"
                f"/{base.get('tier')} but this run is "
                f"{report.get('cost_model_version')}/{report.get('tier')} "
                "— errors are not comparable; re-run `obs calibrate` and "
                "commit the new baseline after a cost-model change"
            ),
        ))
        return findings

    base_rows = {r["target"]: r for r in base.get("targets", ())}
    cur_rows = {r["target"]: r for r in report.get("targets", ())}
    joined = sorted(set(base_rows) & set(cur_rows))
    if not joined:
        findings.append(Finding(
            pass_name="obs", rule="no-joined-targets",
            severity=SEVERITY_ERROR, target=BASELINE_NAME,
            message=(
                "the fresh calibration shares no measured target with the "
                "committed baseline — nothing to gate on; check the "
                "--targets filter / the baselines directory"
            ),
        ))
        return findings

    # aggregate over the JOINED set on both sides, so a subset run (the
    # obs_smoke stage) compares like with like
    base_join = [base_rows[t] for t in joined]
    cur_join = [cur_rows[t] for t in joined]
    base_geo = aggregate_errors(base_join)["geomean_error_factor"]
    cur_geo = aggregate_errors(cur_join)["geomean_error_factor"]
    if cur_geo > base_geo * AGGREGATE_SLACK:
        findings.append(Finding(
            pass_name="obs", rule="calibration-regression",
            severity=SEVERITY_ERROR, target=BASELINE_NAME,
            message=(
                f"aggregate cost-model error regressed: geomean error "
                f"factor {cur_geo:.1f}x vs committed {base_geo:.1f}x over "
                f"{len(joined)} joined target(s) (gate at "
                f"{AGGREGATE_SLACK:.1f}x growth) — the α–β model got "
                "WORSE at predicting this mesh; investigate (cost-model "
                "drift, backend change, measurement contamination), then "
                "re-commit the calibration baseline if the change is "
                "intended"
            ),
            details={"baseline_geomean": base_geo, "current_geomean": cur_geo,
                     "joined_targets": len(joined)},
        ))
    elif base_geo > cur_geo * AGGREGATE_SLACK:
        findings.append(Finding(
            pass_name="obs", rule="calibration-improved",
            severity=SEVERITY_WARNING, target=BASELINE_NAME,
            message=(
                f"aggregate error factor improved {base_geo / cur_geo:.1f}x "
                "under the committed baseline — re-run `obs calibrate` and "
                "commit to tighten the gate"
            ),
            details={"baseline_geomean": base_geo,
                     "current_geomean": cur_geo},
        ))
    for t in joined:
        b, c = base_rows[t]["error_factor"], cur_rows[t]["error_factor"]
        if c > b * TARGET_SLACK:
            findings.append(Finding(
                pass_name="obs", rule="target-calibration-drift",
                severity=SEVERITY_WARNING, target=t,
                message=(
                    f"per-target error factor {c:.1f}x vs committed "
                    f"{b:.1f}x (> {TARGET_SLACK:.0f}x growth) — this "
                    "target's prediction drifted; aggregate gate decides "
                    "CI, but check this one first"
                ),
                details={"baseline_factor": b, "current_factor": c},
            ))
    for t in sorted(set(cur_rows) - set(base_rows)):
        findings.append(Finding(
            pass_name="obs", rule="uncalibrated-target",
            severity=SEVERITY_WARNING, target=t,
            message=(
                "measured target has no entry in the committed "
                "calibration baseline — re-run `obs calibrate` over the "
                "full surface and commit, so the new target is gated too"
            ),
        ))
    return findings
