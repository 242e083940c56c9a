"""cm2 fitted cost model + attribution: corpus ingestion, the α–β–γ
regression (seeded-coefficient recovery, fail-closed degeneracies,
versioned DB), cm1-fallback warning, calibration schema growth
(dispatch columns, per-model baselines, Prometheus export), the
merged sweep+serving journal trace, and the attribution partition
contract (phases sum to the wall)."""

from __future__ import annotations

import json
import math

import pytest

from dlbb_tpu.analysis.costmodel import (
    CM2_VERSION,
    COST_MODEL_VERSION,
    CostTier,
    FitMissingError,
    dispatch_cost_us,
    fit_db_path,
    get_tier,
    load_fitted_tier,
    resolve_tier,
)
from dlbb_tpu.obs import corpus as corpus_mod
from dlbb_tpu.obs import fit as fit_mod
from dlbb_tpu.obs.attribution import (
    ATTRIBUTION_SCHEMA,
    PHASES,
    partition_journal,
    partition_trace,
    predict_iteration_us,
    run_attribution,
    validate_attribution,
)
from dlbb_tpu.obs.fit import FitError, fit_tier, run_fit, save_fit

# ---------------------------------------------------------------------------
# synthetic corpora
# ---------------------------------------------------------------------------

TRUE = {"gamma": 220.0, "alpha": 35.0, "beta": 5000.0, "peak": 2000.0}


def _sample(wire, collectives=1.0, dispatches=1.0, flops=0, op="allreduce",
            tier="cpu-sim", noise=1.0):
    measured = (TRUE["gamma"] * dispatches + TRUE["alpha"] * collectives
                + wire / TRUE["beta"] + flops / TRUE["peak"]) * noise
    return {
        "file": f"synth_{op}_{wire}_{collectives}.json", "op": op,
        "variant": "default", "kind": "all-reduce", "ranks": 8,
        "dtype": "bfloat16", "num_elements": wire // 2,
        "wire_bytes": int(wire), "flops": int(flops),
        "collectives": float(collectives), "dispatches": float(dispatches),
        "measured_median_us": measured, "measured_p99_us": measured * 1.2,
        "iterations": 20, "tier": tier, "host": "synthhost/cpu2/dev8",
        "timestamp": 0.0,
    }


def _synthetic_corpus():
    samples = []
    for wire in (1024, 65536, 1048576, 8 * 1048576):
        for coll in (1.0, 7.0):
            samples.append(_sample(wire, collectives=coll))
        samples.append(_sample(wire, collectives=1.0, dispatches=0.1))
        samples.append(_sample(wire, flops=2_000_000, op="ag_matmul"))
        samples.append(_sample(wire, flops=16_000_000, op="ag_matmul"))
    return samples


def test_fit_recovers_seeded_coefficients():
    fit = fit_tier(_synthetic_corpus(), "cpu-sim")
    c = fit["coefficients"]
    assert c["gamma_dispatch_us"]["value"] == pytest.approx(
        TRUE["gamma"], rel=0.05)
    assert c["alpha_us"]["value"] == pytest.approx(TRUE["alpha"], rel=0.1)
    assert c["beta_bytes_per_us"]["value"] == pytest.approx(
        TRUE["beta"], rel=0.05)
    assert c["peak_flops_per_us"]["value"] == pytest.approx(
        TRUE["peak"], rel=0.05)
    assert not fit["alpha_pinned"] and not fit["peak_pinned"]
    assert fit["residuals"]["geomean_error_factor"] < 1.05
    # CI bounds bracket the fitted value where reported
    ci = c["gamma_dispatch_us"].get("ci95")
    assert ci and ci[0] <= c["gamma_dispatch_us"]["value"] <= ci[1]


def test_fit_rejects_outliers():
    samples = _synthetic_corpus()
    samples.append(_sample(1024, noise=80.0))  # one wild host spike
    fit = fit_tier(samples, "cpu-sim")
    assert fit["outliers_rejected"] >= 1
    assert fit["coefficients"]["gamma_dispatch_us"]["value"] == \
        pytest.approx(TRUE["gamma"], rel=0.08)


def test_fit_pins_alpha_and_peak_when_unidentifiable():
    # every sample: one collective, one dispatch, zero flops — α and γ
    # are collinear and peak unconstrained; the fit must PIN, not guess
    samples = [_sample(w) for w in
               (1024, 4096, 65536, 262144, 1048576, 4 * 1048576)] * 4
    fit = fit_tier(samples, "cpu-sim", min_samples=8)
    assert fit["alpha_pinned"] and fit["peak_pinned"]
    cm1 = get_tier("cpu-sim")
    c = fit["coefficients"]
    assert c["alpha_us"] == {"value": cm1.alpha_us, "pinned": "cm1"}
    assert c["peak_flops_per_us"]["pinned"] == "cm1"
    # intercept lands in γ (minus the pinned cm1 α)
    assert c["gamma_dispatch_us"]["value"] == pytest.approx(
        TRUE["gamma"] + TRUE["alpha"] - cm1.alpha_us, rel=0.05)


def test_fit_fails_closed_on_degenerate_corpora():
    with pytest.raises(FitError, match="need >="):
        fit_tier(_synthetic_corpus()[:4], "cpu-sim")
    with pytest.raises(FitError, match="single message size"):
        fit_tier([_sample(1024) for _ in range(20)], "cpu-sim")
    with pytest.raises(FitError, match="no usable corpus samples"):
        fit_tier([], "cpu-sim")
    # all rows quarantined/non-finite: equally refused
    bad = [dict(_sample(1024), measured_median_us=float("nan"))
           for _ in range(20)]
    with pytest.raises(FitError, match="no usable corpus samples"):
        fit_tier(bad, "cpu-sim")
    with pytest.raises(KeyError):
        fit_tier(_synthetic_corpus(), "no-such-tier")


def test_fit_db_versioning_append_only(tmp_path):
    fit = fit_tier(_synthetic_corpus(), "cpu-sim")
    path, v1 = save_fit(fit, tmp_path)
    assert path == fit_db_path("cpu-sim", tmp_path) and v1 == 1
    _, v2 = save_fit(fit, tmp_path)
    assert v2 == 2
    db = json.loads(path.read_text())
    assert [e["fit_version"] for e in db["versions"]] == [1, 2]
    tier = load_fitted_tier("cpu-sim", tmp_path)
    assert tier.version == CM2_VERSION
    assert tier.fit["fit_version"] == 2  # latest wins
    pinned = load_fitted_tier("cpu-sim", tmp_path, fit_version=1)
    assert pinned.fit["fit_version"] == 1
    with pytest.raises(FitMissingError):
        load_fitted_tier("cpu-sim", tmp_path, fit_version=9)
    assert tier.gamma_dispatch_us == pytest.approx(TRUE["gamma"], rel=0.05)
    assert dispatch_cost_us(3, tier) == pytest.approx(
        3 * tier.gamma_dispatch_us)


def test_resolve_tier_cm2_fallback_warns(tmp_path, capsys):
    tier = resolve_tier("cpu-sim", model=CM2_VERSION, fit_dir=tmp_path)
    out = capsys.readouterr().out
    assert "fit-missing" in out and "falling back to cm1" in out
    # the fallback tier IS cm1: version records what actually priced
    assert tier.version == COST_MODEL_VERSION
    assert tier.gamma_dispatch_us == 0.0
    with pytest.raises(KeyError):
        resolve_tier("cpu-sim", model="cm99")


def test_resolve_tier_cm1_is_identity():
    assert resolve_tier("cpu-sim") == get_tier("cpu-sim")


# ---------------------------------------------------------------------------
# corpus ingestion
# ---------------------------------------------------------------------------


def _artifact(op="allreduce", ranks=8, elems=512, dtype="bfloat16",
              variant="default", timings=((0.001, 0.0012, 0.0011),),
              **extra):
    return {
        "operation": op, "num_ranks": ranks, "num_elements": elems,
        "dtype": dtype, "variant": variant,
        "timings": [list(t) for t in timings],
        "timing_mode": extra.pop("timing_mode", "per_iter"),
        "system_info": {"backend": extra.pop("backend", "cpu"),
                        "platform": "testbox", "cpu_count": 2,
                        "num_devices": ranks},
        **extra,
    }


def test_corpus_ingest_and_features(tmp_path):
    (tmp_path / "a.json").write_text(json.dumps(_artifact()))
    (tmp_path / "b.json").write_text(json.dumps(_artifact(
        op="ag_matmul", tensor_shape=[2, 64, 256], elems=2 * 64 * 256)))
    (tmp_path / "chained.json").write_text(json.dumps(_artifact(
        timing_mode="chained", timing_granularity="chunked(10)")))
    (tmp_path / "noop.json").write_text(json.dumps({"hello": 1}))
    (tmp_path / "sweep_manifest.json").write_text(json.dumps(
        {"wall_seconds": 2.0, "compile_seconds_total": 1.0}))
    corpus = corpus_mod.build_corpus([tmp_path])
    by_op = {s["op"]: s for s in corpus["samples"]}
    assert set(by_op) == {"allreduce", "ag_matmul"} and \
        len(corpus["samples"]) == 3
    by_file = {s["file"].rsplit("/", 1)[-1]: s for s in corpus["samples"]}
    ar = by_file["a.json"]
    assert ar["wire_bytes"] == int(2 * 7 / 8 * 512 * 2)
    assert ar["measured_median_us"] == pytest.approx(1100.0)
    assert ar["tier"] == "cpu-sim" and ar["dispatches"] == 1.0
    ag = by_file["b.json"]
    assert ag["flops"] == 2 * 2 * 64 * 256 * 256
    assert ag["wire_bytes"] == 7 * 2 * 64 * 256 * 2
    chained = [s for s in corpus["samples"]
               if s["dispatches"] != 1.0]
    assert chained and chained[0]["dispatches"] == pytest.approx(0.1)
    assert any("no operation/timings" in s["reason"]
               for s in corpus["skipped"])
    assert corpus["manifests"][0]["wall_seconds"] == 2.0
    with pytest.raises(FileNotFoundError):
        corpus_mod.build_corpus([tmp_path / "missing"])


def test_run_fit_end_to_end(tmp_path, capsys):
    results = tmp_path / "results"
    results.mkdir()
    rng_wires = [(512, 1), (8192, 1), (65536, 3), (524288, 7),
                 (1048576, 1), (4194304, 3)]
    i = 0
    for elems, _ in rng_wires:
        for ranks in (4, 8):
            for variant in ("default", "overlap_ring"):
                op = "ag_matmul" if variant == "overlap_ring" else \
                    "allreduce"
                art = _artifact(op=op, ranks=ranks, elems=elems,
                                variant=variant)
                if op == "ag_matmul":
                    art["tensor_shape"] = [1, 32, 64]
                meas = 300.0 + elems / 2000.0
                art["timings"] = [[meas * 1e-6] * 5]
                (results / f"r{i}.json").write_text(json.dumps(art))
                i += 1
    out = run_fit([results], fit_dir=tmp_path / "db", min_samples=8)
    assert "cpu-sim" in out["fits"]
    assert fit_db_path("cpu-sim", tmp_path / "db").exists()
    # an explicitly requested unfittable tier fails closed
    with pytest.raises(FitError):
        run_fit([results], tiers=["tpu-v5lite"], fit_dir=tmp_path / "db2",
                min_samples=8)


# ---------------------------------------------------------------------------
# schedule meta + calibration schema
# ---------------------------------------------------------------------------

_TINY_HLO = """
HloModule tiny, entry_computation_layout={()->f32[4]}

ENTRY %main () -> f32[4] {
  %c = f32[4] constant({1, 2, 3, 4})
  ROOT %ar = f32[4] all-reduce(%c), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
"""


def test_schedule_meta_carries_dispatch_overhead():
    from dlbb_tpu.analysis.expectations import TargetExpectation
    from dlbb_tpu.analysis.schedule_audit import analyze_schedule

    exp = TargetExpectation(allowed={"all-reduce"})
    fitted = CostTier(name="cpu-sim", alpha_us=10.0,
                      beta_bytes_per_us=1000.0,
                      peak_flops_per_us=1000.0,
                      gamma_dispatch_us=500.0, version=CM2_VERSION)
    _, meta = analyze_schedule(_TINY_HLO, exp, "t", tier=fitted)
    assert meta["cost_model_version"] == CM2_VERSION
    assert meta["dispatch_count"] == 1
    assert meta["dispatch_overhead_us"] == pytest.approx(500.0)
    assert meta["predicted_wall_us"] == pytest.approx(
        meta["critical_path_us"] + 500.0)
    # cm1 pricing: γ = 0, wall == critical path, version recorded cm1
    _, meta1 = analyze_schedule(_TINY_HLO, exp, "t", tier="cpu-sim")
    assert meta1["cost_model_version"] == COST_MODEL_VERSION
    assert meta1["dispatch_overhead_us"] == 0.0
    assert meta1["predicted_wall_us"] == meta1["critical_path_us"]


def _fake_report(model, tier="cpu-sim", n=3, factor=2.0):
    from dlbb_tpu.obs.calibration import aggregate_errors

    rows = []
    for i in range(n):
        pred, meas = 100.0 * (i + 1), 100.0 * (i + 1) * factor
        rows.append({
            "target": f"t{i}", "tier": tier, "cost_model_version": model,
            "predicted_us": pred, "dispatch_count": 1,
            "predicted_dispatch_overhead_us": 50.0 if model == "cm2"
            else 0.0,
            "measured_us": meas,
            "signed_rel_error": (meas - pred) / pred,
            "error_factor": max(meas, pred) / min(meas, pred),
            "reps": 5,
        })
    return {
        "schema": "dlbb_calibration_v1", "tier": tier,
        "cost_model_version": model, "aggregate": aggregate_errors(rows),
        "targets": rows, "skipped": [], "timestamp": 0.0,
        **({"fit": {"fit_version": 3, "db_path": "x", "samples_used": 40,
                    "residuals": {"geomean_error_factor": 1.5,
                                  "rms_log_error": 0.3}}}
           if model == "cm2" else {}),
    }


def test_calibration_csv_columns_and_report_write(tmp_path):
    from dlbb_tpu.obs.calibration import CSV_COLUMNS, write_report

    assert "dispatch_count" in CSV_COLUMNS
    assert "predicted_dispatch_overhead_us" in CSV_COLUMNS
    report = _fake_report(CM2_VERSION)
    write_report(report, tmp_path)
    csv_text = (tmp_path / "calibration_report.csv").read_text()
    header = csv_text.splitlines()[0].split(",")
    assert header == list(CSV_COLUMNS)
    assert ",1,50.0," in csv_text
    manifest = json.loads((tmp_path / "sweep_manifest.json").read_text())
    assert manifest["calibration"]["fit_version"] == 3
    prom = (tmp_path / "metrics.prom").read_text()
    assert 'dlbb_obs_calibration_error_factor{model="cm2",' \
        'tier="cpu-sim"}' in prom
    assert "dlbb_obs_fit_residual_error_factor" in prom
    assert "dlbb_obs_fit_version" in prom


def test_per_model_calibration_baselines(tmp_path):
    from dlbb_tpu.obs.calibration import (
        baseline_name,
        diff_calibration,
        save_calibration_baseline,
    )

    assert baseline_name("cm1") == "calibration_baseline.json"
    assert baseline_name("cm2") == "calibration_baseline_cm2.json"
    rep1 = _fake_report(COST_MODEL_VERSION)
    rep2 = _fake_report(CM2_VERSION)
    p1 = save_calibration_baseline(rep1, tmp_path)
    p2 = save_calibration_baseline(rep2, tmp_path)
    assert p1.name != p2.name
    # each model diffs against ITS committed baseline: both clean
    assert diff_calibration(rep1, tmp_path) == []
    assert diff_calibration(rep2, tmp_path) == []
    # a cm2 report with no cm2 baseline is a missing-baseline error even
    # though the cm1 file exists
    p2.unlink()
    findings = diff_calibration(rep2, tmp_path)
    assert [f.rule for f in findings] == ["missing-calibration-baseline"]
    assert "cm2" in findings[0].message


# ---------------------------------------------------------------------------
# merged journal trace (sweep + serving streams)
# ---------------------------------------------------------------------------


def test_journal_to_trace_merges_sweep_and_serving_streams(tmp_path):
    from dlbb_tpu.obs.spans import journal_to_trace, validate_trace_events

    recs = [
        {"ts": 1.0, "event": "sweep-start", "mode": "sweep"},
        {"ts": 2.0, "event": "started", "config": "cfg_a.json"},
        {"ts": 3.0, "event": "completed", "config": "cfg_a.json"},
        {"ts": 4.0, "event": "sweep-start", "mode": "serve",
         "name": "mini"},
        {"ts": 5.0, "event": "request-arrived", "config": "request-0"},
        {"ts": 6.0, "event": "request-completed", "config": "request-0",
         "output_tokens": 3},
    ]
    with open(tmp_path / "sweep_journal.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    path, n, torn = journal_to_trace(tmp_path, tmp_path / "trace.json")
    trace = json.loads(path.read_text())
    events = trace["traceEvents"]
    assert validate_trace_events(events) == []
    pids = {e["pid"] for e in events}
    assert pids == {1, 2}
    names = {(e["pid"], e["args"]["name"]) for e in events
             if e["ph"] == "M"}
    assert names == {(1, "sweep"), (2, "serving")}
    spans = {(e["pid"], e["name"]): e for e in events if e["ph"] == "X"}
    assert (1, "cfg_a.json") in spans and (2, "request-0") in spans
    assert trace["otherData"]["streams"] == {"1": "sweep", "2": "serving"}


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


def test_partition_trace_sums_to_wall():
    ev = []

    def b(name, ts, tid=7):
        ev.append({"name": name, "ph": "B", "ts": ts, "pid": 1,
                   "tid": tid})

    def e(name, ts, tid=7):
        ev.append({"name": name, "ph": "E", "ts": ts, "pid": 1,
                   "tid": tid})

    b("plan", 0.0); e("plan", 100.0)                     # noqa: E702
    b("cfg.json", 150.0)                                 # unmapped parent
    b("compile-wait", 160.0); e("compile-wait", 400.0)   # noqa: E702
    b("measure", 420.0); e("measure", 900.0)             # noqa: E702
    b("write", 900.0); e("write", 950.0)                 # noqa: E702
    e("cfg.json", 960.0)
    phases, wall, _ = partition_trace(ev)
    assert wall == pytest.approx(960.0)
    assert sum(phases.values()) == pytest.approx(wall)
    assert phases["plan"] == pytest.approx(100.0)
    assert phases["compile"] == pytest.approx(240.0)
    assert phases["execute"] == pytest.approx(480.0)
    assert phases["write"] == pytest.approx(50.0)
    assert phases["idle"] == pytest.approx(50.0)   # 100->150
    assert phases["host"] == pytest.approx(40.0)   # unmapped cfg glue
    assert set(phases) <= set(PHASES)


def test_partition_journal_sums_to_wall():
    recs = [
        {"ts": 0.0, "event": "sweep-start"},
        {"ts": 0.5, "event": "request-arrived", "config": "request-0"},
        {"ts": 0.6, "event": "request-admitted", "config": "request-0"},
        {"ts": 0.9, "event": "request-prefill", "config": "request-0"},
        {"ts": 1.5, "event": "request-completed", "config": "request-0"},
    ]
    phases, wall = partition_journal(recs)
    assert wall == pytest.approx(1.5e6)
    assert sum(phases.values()) == pytest.approx(wall)
    assert phases["queue-wait"] == pytest.approx(0.1e6)
    assert phases["prefill"] == pytest.approx(0.3e6)
    assert phases["decode"] == pytest.approx(0.6e6)


def _serving_dir(tmp_path):
    recs = [
        {"ts": 10.0, "event": "sweep-start", "mode": "serve",
         "name": "mini"},
        {"ts": 10.1, "event": "request-arrived", "config": "request-0",
         "prompt": 8, "output": 4},
        {"ts": 10.2, "event": "request-admitted", "config": "request-0",
         "queue_depth": 1},
        {"ts": 10.5, "event": "request-prefill", "config": "request-0",
         "slot": 0, "ttft_s": 0.4},
        {"ts": 11.4, "event": "request-completed", "config": "request-0",
         "output_tokens": 4, "latency_s": 1.3},
        {"ts": 11.5, "event": "request-arrived", "config": "request-1"},
        {"ts": 11.6, "event": "request-rejected", "config": "request-1",
         "reason": "queue-full"},
    ]
    with open(tmp_path / "sweep_journal.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    report = {
        "schema": "dlbb_serving_report_v1",
        "model": {"hidden_size": 64, "num_layers": 2, "dtype": "float32"},
        "mesh": {"dp": 2, "tp": 4},
        "serving": {"max_batch": 4, "max_seq": 64,
                    "prefill_buckets": [16, 64], "decode_horizon": 1},
        "requests": {"arrived": 2, "admitted": 1, "completed": 1,
                     "rejected": 1},
        "decode_units": 4, "decode_steps": 4,
        "fast_path": {"prefill_chunks": 0},
    }
    (tmp_path / "serving_mini.json").write_text(json.dumps(report))
    return tmp_path


def test_attribution_serving_from_journal(tmp_path, capsys):
    _serving_dir(tmp_path)
    out = tmp_path / "attr"
    record = run_attribution(tmp_path, out_dir=out, name="mini")
    assert validate_attribution(record) == []
    assert record["kind"] == "serving" and record["source"] == "journal"
    # wall spans sweep-start (10.0) to the last journal event, the
    # request-1 rejection at 11.6
    assert record["wall_us"] == pytest.approx(1.6e6)
    assert sum(record["phases_us"].values()) == pytest.approx(
        record["wall_us"], rel=0.0001)
    md = (out / "mini.md").read_text()
    assert ATTRIBUTION_SCHEMA in md and "queue-wait" in md
    csv_text = (out / "mini.csv").read_text()
    assert csv_text.splitlines()[0].startswith("kind,name,")
    assert "request,request-0" in csv_text
    rows = {e["name"]: e for e in record["entities"]}
    assert rows["request-0"]["queue_wait_us"] == pytest.approx(0.1e6)
    assert rows["request-0"]["decode_us"] == pytest.approx(0.9e6)
    assert rows["request-0"]["tokens"] == 4
    assert rows["request-1"]["outcome"] == "rejected"
    # predictions priced the report's exact dispatch counts
    assert record["predicted_us"]["decode_units"] == 4
    assert record["predicted_us"]["prefill_dispatches"] == 1


def test_attribution_validates_partition_gap():
    rec = {
        "schema": ATTRIBUTION_SCHEMA, "name": "x", "kind": "sweep",
        "cost_model_version": "cm1", "wall_us": 100.0,
        "phases_us": {"execute": 10.0}, "entities": [],
    }
    problems = validate_attribution(rec)
    assert problems and "phases cover" in problems[0]
    rec["phases_us"] = {"execute": 97.0}
    assert validate_attribution(rec) == []
    rec["phases_us"] = {"warpdrive": 100.0}
    assert any("unknown phase" in p for p in validate_attribution(rec))


def test_predict_iteration_decomposition():
    tier = CostTier(name="t", alpha_us=10.0, beta_bytes_per_us=100.0,
                    peak_flops_per_us=50.0, gamma_dispatch_us=200.0,
                    version=CM2_VERSION)
    parts = predict_iteration_us(
        {"dispatches": 1.0, "collectives": 3.0, "wire_bytes": 1000,
         "flops": 500}, tier)
    assert parts["dispatch"] == pytest.approx(200.0)
    assert parts["wire"] == pytest.approx(3 * 10.0 + 1000 / 100.0)
    assert parts["compute"] == pytest.approx(10.0)
    assert parts["total"] == pytest.approx(
        parts["dispatch"] + parts["wire"] + parts["compute"])


# ---------------------------------------------------------------------------
# fit_smoke: the committed corpus -> fit -> cm2 DB round trip (also run
# standalone by scripts/run_static_analysis.sh)
# ---------------------------------------------------------------------------


@pytest.mark.fit_smoke
def test_fit_smoke_committed_corpus(tmp_path):
    import pathlib

    repo = pathlib.Path(__file__).resolve().parents[1]
    corpus_dir = repo / "results" / "fit_corpus"
    if not corpus_dir.exists():
        pytest.skip("no committed fit corpus")
    out = run_fit([corpus_dir], fit_dir=tmp_path, verbose=False)
    fit = out["fits"]["cpu-sim"]
    c = fit["coefficients"]
    assert c["gamma_dispatch_us"]["value"] > 0
    assert math.isfinite(c["beta_bytes_per_us"]["value"])
    assert fit["residuals"]["geomean_error_factor"] < 10.0
    tier = load_fitted_tier("cpu-sim", tmp_path)
    assert tier.version == CM2_VERSION


@pytest.mark.fit_smoke
def test_fit_smoke_committed_db_prices_cm2(tmp_path):
    """The COMMITTED fitted DB resolves and the committed cm2
    calibration baseline exists, joins, and carries the dispatch
    columns — the acceptance surface of `obs calibrate --model cm2` +
    `obs diff` without re-measuring (the CI shell stage runs the live
    measurement)."""
    import pathlib

    from dlbb_tpu.obs.calibration import (
        DEFAULT_CALIBRATION_DIR,
        load_calibration_baseline,
    )

    repo = pathlib.Path(__file__).resolve().parents[1]
    if not fit_db_path("cpu-sim", repo / "stats/analysis/costmodel_fit"
                       ).exists():
        pytest.skip("no committed cm2 DB")
    tier = load_fitted_tier(
        "cpu-sim", repo / "stats/analysis/costmodel_fit")
    assert tier.version == CM2_VERSION and tier.gamma_dispatch_us > 0
    base = load_calibration_baseline(
        repo / DEFAULT_CALIBRATION_DIR, model=CM2_VERSION)
    assert base["cost_model_version"] == CM2_VERSION
    agg = base["aggregate"]
    # the acceptance number: fitted-model geomean error <= 3x on the
    # cpu-sim tier (vs cm1's committed ~289x)
    assert agg["geomean_error_factor"] <= 3.0
    for row in base["targets"]:
        assert row["dispatch_count"] >= 1
        assert row["predicted_dispatch_overhead_us"] > 0
