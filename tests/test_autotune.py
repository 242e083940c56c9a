"""Plan autotuner tests (``docs/autotune.md``, ``cli plan --auto``).

The load-bearing contracts: (1) the full plan space is accounted for —
every enumerated point is either ranked or journaled with a prune
reason from the fixed vocabulary, never silently dropped; (2) ranking
is deterministic with the documented tie-break (predicted cost, then
plan complexity, then lexical key); (3) a missing cm2 fit fails the
whole search CLOSED (ranking with unfitted analytic seeds would
launder cm1 guesses as "model-picked"); (4) the pinned
calibration-grid agreement regression — cm2's top-2 contains the
measured winner for >= 70% of the committed baseline families; and
(5) the measured smoke: predict-prune-measure end-to-end through the
real serving engine with the agreement table, manifest, and metrics
surfaces all consistent.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from dlbb_tpu.analysis.costmodel import CostTier, load_fitted_tier
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.plan.autotune import (
    CAL_FAMILIES,
    DEFAULT_PLAN_INPUT,
    DEFAULT_PLAN_MODEL,
    DEFAULT_PLAN_SERVING,
    PRUNE_FIT,
    PRUNE_HBM,
    PRUNE_REASONS,
    PRUNE_VALIDATION,
    PlanPoint,
    calibration_agreement,
    enumerate_serving_space,
    enumerate_train_space,
    heuristic_point,
    predict_point_us,
    prune_point,
    rank_points,
    run_plan_search,
)
from dlbb_tpu.resilience.journal import read_journal
from dlbb_tpu.stats.parallelism_report import write_autotune_report
from dlbb_tpu.stats.serving_report import publish_capacity_curve

REPO = Path(__file__).resolve().parents[1]
FIT_DIR = REPO / "stats" / "analysis" / "costmodel_fit"
CAL_BASELINE = (REPO / "stats" / "analysis" / "calibration"
                / "calibration_baseline_cm2.json")

MODEL = ModelConfig.from_dict(DEFAULT_PLAN_MODEL)


@pytest.fixture(scope="module")
def tier():
    return load_fitted_tier("cpu-sim", FIT_DIR)


# ---------------------------------------------------------------------------
# enumeration
# ---------------------------------------------------------------------------


def test_serving_space_is_the_full_grid():
    """(dp,tp) factorizations x K x W x chunk — 4*5*2*2 for an
    8-device mesh, every key unique (the journal identifier)."""
    pts = enumerate_serving_space(MODEL, 8, DEFAULT_PLAN_SERVING)
    assert len(pts) == 4 * 5 * 2 * 2
    keys = [p.key() for p in pts]
    assert len(set(keys)) == len(keys)
    assert all(p.dp * p.tp == 8 for p in pts)


def test_train_space_covers_variant_axis():
    """Every ordered mesh factorization appears, and sp > 1 points
    enumerate BOTH attention variants (the per-op variant axis)."""
    pts = enumerate_train_space(MODEL, 8)
    assert all(p.dp * p.sp * p.pp * p.tp == 8 for p in pts)
    sp2 = {p.attention for p in pts if p.sp > 1}
    assert sp2 == {"ring", "ulysses"}
    assert {p.attention for p in pts if p.sp == 1} == {None}


# ---------------------------------------------------------------------------
# pruning: reasons, never silent
# ---------------------------------------------------------------------------


def test_every_prune_carries_a_vocabulary_reason(tier):
    """Full-grid accounting: each serving point either survives or is
    rejected with (reason, detail), reason from the fixed vocabulary."""
    pts = enumerate_serving_space(MODEL, 8, DEFAULT_PLAN_SERVING)
    kept = pruned = 0
    for p in pts:
        res = prune_point(p, MODEL, tier, 8,
                          serving=DEFAULT_PLAN_SERVING)
        if res is None:
            kept += 1
        else:
            reason, detail = res
            assert reason in PRUNE_REASONS
            assert detail  # the contract's message, not a bare code
            pruned += 1
    assert kept + pruned == len(pts)
    assert kept > 0 and pruned > 0


def test_validation_reject_quotes_the_contract(tier):
    """A plan wider than the mesh and a tp that breaks the engine's own
    ServingConfig.validate both reject with actionable detail."""
    wide = PlanPoint(target="serving", dp=4, tp=4)
    reason, detail = prune_point(wide, MODEL, tier, 8,
                                 serving=DEFAULT_PLAN_SERVING)
    assert reason == PRUNE_VALIDATION
    assert "16" in detail and "8" in detail
    # tp=8 > kv_heads=4: the engine contract's rejection, quoted
    tp8 = PlanPoint(target="serving", dp=1, tp=8)
    reason, detail = prune_point(tp8, MODEL, tier, 8,
                                 serving=DEFAULT_PLAN_SERVING)
    assert reason == PRUNE_VALIDATION and detail


def test_infeasible_hbm_prunes_with_headroom_detail(tier):
    """A tier with a 1-byte HBM capacity rejects every plan with the
    infeasible-hbm reason and the peak-bytes arithmetic in the detail;
    hbm_bytes=0 (unknown) never prunes."""
    tiny = CostTier(name="cpu-sim-tiny", alpha_us=tier.alpha_us,
                    beta_bytes_per_us=tier.beta_bytes_per_us,
                    peak_flops_per_us=tier.peak_flops_per_us,
                    gamma_dispatch_us=tier.gamma_dispatch_us,
                    hbm_bytes=1.0, version=tier.version, fit=tier.fit)
    ok = PlanPoint(target="serving", dp=2, tp=4)
    reason, detail = prune_point(ok, MODEL, tiny, 8,
                                 serving=DEFAULT_PLAN_SERVING)
    assert reason == PRUNE_HBM
    assert "peak" in detail and "headroom" in detail
    unknown = CostTier(name="cpu-sim-nohbm", alpha_us=1,
                       beta_bytes_per_us=1, peak_flops_per_us=1,
                       hbm_bytes=0.0)
    assert prune_point(ok, MODEL, unknown, 8,
                       serving=DEFAULT_PLAN_SERVING) is None


def test_train_prune_divisibility(tier):
    """Train-side validate_* family: a batch that does not divide dp*sp
    rejects with the divisibility message."""
    p = PlanPoint(target="train", dp=8)
    res = prune_point(p, MODEL, tier, 8,
                      input_cfg={**DEFAULT_PLAN_INPUT, "batch_size": 6})
    assert res is not None and res[0] == PRUNE_VALIDATION
    assert "divisible" in res[1]


# ---------------------------------------------------------------------------
# ranking: deterministic tie-break
# ---------------------------------------------------------------------------


def test_tie_break_prefers_simpler_then_lexical():
    """Equal predicted cost: the plan with fewer engaged knobs wins;
    equal complexity falls through to the lexical key."""
    plain = PlanPoint(target="serving", dp=8, tp=1)
    knobby = PlanPoint(target="serving", dp=8, tp=1, decode_horizon=16,
                       inflight_window=2)
    cost = {"cost_us": 100.0}
    ranked = rank_points([(knobby, cost), (plain, cost)])
    assert ranked[0][0] is plain  # complexity 0 beats complexity 2
    a = PlanPoint(target="serving", dp=2, tp=4)
    b = PlanPoint(target="serving", dp=4, tp=2)
    ranked = rank_points([(b, cost), (a, cost)])
    assert [p.key() for p, _ in ranked] == [a.key(), b.key()]


def test_rank_orders_by_predicted_cost():
    a = PlanPoint(target="serving", dp=8, tp=1, decode_horizon=16)
    b = PlanPoint(target="serving", dp=8, tp=1)
    ranked = rank_points([(b, {"cost_us": 50.0}), (a, {"cost_us": 5.0})])
    assert ranked[0][0] is a


def test_fused_horizon_shrinks_predicted_dispatch(tier):
    """The predictor prices the knobs' purpose: K=16,W=2 amortizes the
    fitted gamma term below the K=1 plan on the same mesh."""
    slow = predict_point_us(PlanPoint(target="serving", dp=2, tp=4),
                            MODEL, tier, serving=DEFAULT_PLAN_SERVING)
    fast = predict_point_us(
        PlanPoint(target="serving", dp=2, tp=4, decode_horizon=16,
                  inflight_window=2),
        MODEL, tier, serving=DEFAULT_PLAN_SERVING)
    assert fast["dispatch_us"] < slow["dispatch_us"]
    assert fast["cost_us"] < slow["cost_us"]


# ---------------------------------------------------------------------------
# the pinned agreement regression (satellite gate: >= 0.70)
# ---------------------------------------------------------------------------


@pytest.mark.autotune_smoke
def test_calibration_grid_agreement_regression():
    """cm2's top-2 must contain the measured winner for >= 70% of the
    pinned validation-grid families over the COMMITTED calibration
    baseline — the seeded regression that keeps the ranking model
    honest across fit refreshes."""
    cal = calibration_agreement(CAL_BASELINE)
    assert cal.get("error") is None
    assert cal["total"] == len(CAL_FAMILIES)  # no missing-target rows
    assert all(f["status"] == "ok" for f in cal["families"])
    assert cal["ratio"] >= 0.70


def test_agreement_reports_missing_targets_visibly(tmp_path):
    """A family whose members are absent from the baseline is reported
    with status missing-target and excluded from the denominator —
    visibly, never silently."""
    baseline = tmp_path / "cal.json"
    baseline.write_text(json.dumps({"targets": [
        {"target": "a", "predicted_us": 1.0, "measured_us": 1.0},
        {"target": "b", "predicted_us": 2.0, "measured_us": 0.5},
    ]}))
    cal = calibration_agreement(baseline, families={
        "present": [("a", 1), ("b", 1)],
        "absent": [("a", 1), ("ghost", 1)],
    })
    assert cal["total"] == 1 and cal["ratio"] == 1.0
    statuses = {f["family"]: f["status"] for f in cal["families"]}
    assert statuses == {"present": "ok", "absent": "missing-target"}
    absent = next(f for f in cal["families"] if f["family"] == "absent")
    assert absent["missing"] == ["ghost"]


# ---------------------------------------------------------------------------
# fail-closed: cm2 fit missing
# ---------------------------------------------------------------------------


def test_missing_fit_fails_closed_and_journals_every_point(tmp_path):
    """No fitted cm2 tier -> NO ranking happens at all: every point is
    journaled pruned cm2-fit-missing, the manifest accounts for the
    full grid, and the report carries the error."""
    out = tmp_path / "search"
    res = run_plan_search(
        target="serving", n_devices=8, measure=False, verbose=False,
        output_dir=out, fit_dir=tmp_path / "no_fit_here",
        cal_baseline=CAL_BASELINE,
    )
    assert res["error"].startswith(PRUNE_FIT)
    assert res["ranked"] == [] and res["measured"] == []
    manifest = json.loads((out / "sweep_manifest.json").read_text())
    assert manifest["pruned"][PRUNE_FIT] == manifest["searched"] > 0
    events, bad = read_journal(out)
    assert bad == 0
    pruned = [e for e in events if e.get("event") == "plan-pruned"]
    assert len(pruned) == manifest["searched"]
    assert all(e["reason"] == PRUNE_FIT for e in pruned)


# ---------------------------------------------------------------------------
# static search accounting (no measurement)
# ---------------------------------------------------------------------------


@pytest.mark.autotune_smoke
def test_static_search_accounts_for_every_point(tmp_path):
    """searched == pruned + ranked, the journal carries one event per
    pruned point with a vocabulary reason, the manifest and metrics.prom
    agree with the report, and a re-run ranks identically."""
    out = tmp_path / "auto"
    res = run_plan_search(
        target="serving", n_devices=8, measure=False, verbose=False,
        output_dir=out, fit_dir=FIT_DIR, cal_baseline=CAL_BASELINE,
    )
    n_pruned = sum(res["pruned"].values())
    assert res["searched"] == n_pruned + len(res["ranked"])
    assert set(res["pruned"]) == set(PRUNE_REASONS)
    assert all(r["reason"] in PRUNE_REASONS for r in res["pruned_points"])
    assert len(res["pruned_points"]) == n_pruned

    events, bad = read_journal(out)
    assert bad == 0
    assert len([e for e in events if e.get("event") == "plan-pruned"]) \
        == n_pruned
    assert len([e for e in events if e.get("event") == "plan-ranked"]) \
        == len(res["ranked"])

    manifest = json.loads((out / "sweep_manifest.json").read_text())
    assert manifest["searched"] == res["searched"]
    assert manifest["pruned"] == res["pruned"]

    prom = (out / "metrics.prom").read_text()
    assert ('dlbb_plan_search_points_total{outcome="searched"} '
            f'{res["searched"]}') in prom
    assert 'dlbb_plan_agreement_ratio{scope="calibration-grid"}' in prom

    again = run_plan_search(
        target="serving", n_devices=8, measure=False, verbose=False,
        output_dir=tmp_path / "auto2", fit_dir=FIT_DIR,
        cal_baseline=CAL_BASELINE,
    )
    assert [r["plan"] for r in again["ranked"]] \
        == [r["plan"] for r in res["ranked"]]


@pytest.mark.autotune_smoke
def test_train_static_search_ranks_and_accounts(tmp_path):
    """The train target's grid goes through the same accounting; the
    default-heuristic plan (plain DDP) is a known key."""
    res = run_plan_search(
        target="train", n_devices=8, measure=False, verbose=False,
        output_dir=tmp_path / "train", fit_dir=FIT_DIR,
        cal_baseline=CAL_BASELINE,
    )
    assert res["searched"] == sum(res["pruned"].values()) \
        + len(res["ranked"])
    assert len(res["ranked"]) > 0
    assert heuristic_point("train", 8, MODEL).key() \
        == "train[dp8,tp1,sp1,pp1]"


# ---------------------------------------------------------------------------
# measured smoke: predict-prune-measure end-to-end
# ---------------------------------------------------------------------------


@pytest.mark.autotune_smoke
def test_measured_search_smoke(tmp_path, devices):
    """Top-1 + the default heuristic measured through the real serving
    engine on one shared seeded trace: agreement rows carry both rank
    columns, the manifest's measured count matches, and the bench
    artifact says it is a CPU artifact."""
    out = tmp_path / "auto"
    bench = tmp_path / "BENCH_autotune.json"
    res = run_plan_search(
        target="serving", n_devices=8, top_k=1, mesh_champions=False,
        num_requests=4, seed=11, rate=500.0,
        trace_params={"prompt_range": (8, 16), "output_range": (16, 24)},
        output_dir=out, fit_dir=FIT_DIR, cal_baseline=CAL_BASELINE,
        devices=devices, verbose=False, bench_out=bench,
    )
    roles = {r["role"] for r in res["measured"]}
    assert roles == {"top-k", "default-heuristic"}
    assert res["winner"] in {r["plan"] for r in res["measured"]}
    assert res["speedup_vs_default"] is not None
    for row in res["agreement"]["rows"]:
        assert row["predicted_rank"] >= 1
        assert row["measured_rank"] >= 1
        assert row["goodput_tokens_per_s"] > 0

    manifest = json.loads((out / "sweep_manifest.json").read_text())
    assert manifest["measured"] == len(res["measured"])
    events, _ = read_journal(out)
    assert len([e for e in events if e.get("event") == "plan-measured"]) \
        == len(res["measured"])
    prom = (out / "metrics.prom").read_text()
    assert 'dlbb_plan_agreement_ratio{scope="measured-topk"}' in prom

    payload = json.loads(bench.read_text())
    assert payload["schema"] == "dlbb_bench_autotune_v1"
    assert payload["backend"] == "cpu" and "chip" not in payload
    assert payload["measured"] == res["measured"]


# ---------------------------------------------------------------------------
# report consolidation + capacity publishing
# ---------------------------------------------------------------------------


def _bench_payload():
    return {
        "schema": "dlbb_bench_autotune_v1", "target": "serving",
        "devices": 8, "searched": 10,
        "pruned": {"validation-reject": 4, "infeasible-hbm": 0,
                   "cm2-fit-missing": 0},
        "tier": {"name": "cpu-sim", "fit": {"fit_version": 2}},
        "ranked": [{"plan": "serve[dp8,tp1,K16,W2]"}],
        "default_plan": "serve[dp2,tp4,K1,W1]",
        "speedup_vs_default": 1.4,
        "agreement": {
            "rows": [
                {"plan": "serve[dp4,tp2,K16,W2]", "role": "top-k",
                 "predicted_us": 300.0, "predicted_rank": 1,
                 "measured_rank": 1, "goodput_tokens_per_s": 1600.0,
                 "ttft_p50_s": 0.02},
                {"plan": "serve[dp2,tp4,K1,W1]",
                 "role": "default-heuristic", "predicted_us": 400.0,
                 "predicted_rank": 2, "measured_rank": 2,
                 "goodput_tokens_per_s": 900.0, "ttft_p50_s": 0.03},
            ],
            "measured_winner": "serve[dp4,tp2,K16,W2]",
            "predicted_winner": "serve[dp4,tp2,K16,W2]",
            "top1_match": True, "top2_contains": True,
        },
        "calibration_agreement": {
            "ratio": 1.0, "agree": 1, "total": 1, "baseline": "b.json",
            "families": [{
                "family": "decode_path", "status": "ok",
                "predicted_order": ["a::x", "a::y"],
                "measured_winner": "a::x",
                "top2_contains_winner": True,
            }],
        },
    }


def test_write_autotune_report(tmp_path):
    bench = tmp_path / "BENCH_autotune.json"
    bench.write_text(json.dumps(_bench_payload()))
    rows = write_autotune_report(bench, tmp_path / "stats")
    assert len(rows) == 2
    md = (tmp_path / "stats" / "AUTOTUNE.md").read_text()
    assert "## Search accounting" in md
    assert "## Measured agreement" in md
    assert "## Calibration-grid agreement" in md
    assert "serve[dp4,tp2,K16,W2]" in md
    assert "**1.40x**" in md


def test_autotune_report_never_clobbers_on_empty(tmp_path):
    """No measured rows -> no rewrite: the committed AUTOTUNE.md from
    the last real run survives a dry regeneration."""
    stats = tmp_path / "stats"
    stats.mkdir()
    (stats / "AUTOTUNE.md").write_text("committed")
    payload = _bench_payload()
    payload["agreement"]["rows"] = []
    bench = tmp_path / "BENCH_autotune.json"
    bench.write_text(json.dumps(payload))
    assert write_autotune_report(bench, stats) == []
    assert (stats / "AUTOTUNE.md").read_text() == "committed"
    assert write_autotune_report(tmp_path / "nope.json", stats) == []


def _capacity_report():
    curve = [
        {"users": 4, "demand_tokens_per_s": 160.0,
         "replicas_predicted": 1, "replicas_measured": 1},
        {"users": 64, "demand_tokens_per_s": 2560.0,
         "replicas_predicted": 2, "replicas_measured": None},
    ]
    return {
        "schema": "dlbb_capacity_v1", "devices": 8, "slo_s": 30.0,
        "user_rate_req_per_s": 0.2, "mean_output_tokens": 200.0,
        "trace": {"kind": "poisson", "num_requests": 24, "seed": 42},
        "plans": [
            {"plan": "serve[dp4,tp2,K16,W2]", "slo_attainable": True,
             "predicted_goodput_tokens_per_s": 3000.0,
             "measured_goodput_tokens_per_s": 1600.0,
             "predicted_ttft_s": 0.004, "measured_ttft_p50_s": 0.02,
             "completed": 24, "total": 24, "curve": curve},
        ],
    }


def test_publish_capacity_curve_idempotent(tmp_path):
    """Publishing writes capacity.json + the SERVING.md section; a
    second publish replaces the section instead of stacking two."""
    out = tmp_path / "serving"
    md = publish_capacity_curve(_capacity_report(), out)
    text = md.read_text()
    assert text.count("## Fleet capacity curve") == 1
    assert "serve[dp4,tp2,K16,W2]" in text
    assert "2 / —" in text  # blown-TTFT cell renders as a dash
    assert (out / "capacity.json").exists()
    publish_capacity_curve(_capacity_report(), out)
    assert md.read_text().count("## Fleet capacity curve") == 1
