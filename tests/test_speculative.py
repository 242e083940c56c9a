"""Speculative decoding tests (``docs/serving.md``, "Speculative
decoding"): draft-and-verify multi-token decode on the serving engine.

The load-bearing contract is TOKEN IDENTITY: greedy speculative decode
(n-gram or draft-model drafter, per-step or fused, adaptive or fixed γ)
must produce completed-token sequences IDENTICAL to the per-step greedy
token-feedback engine on the same trace — speculation buys forwards,
never different results.  Sampled decode weakens the gate to
DISTRIBUTION identity, which the residual-sampling helpers pin
empirically here.  On top of that: the scheduler edges speculation
makes reachable (mid-verify completion, cold-drafter fallback,
rejection rollback leaving the ledger clean, dispatch failure during a
verify unit), the drafter's pure-function determinism, the validation
ladder, and the report/metrics/journal surfaces.
"""

import dataclasses

import jax
import numpy as np
import pytest

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.resilience import inject
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.speculative import (
    _ngram_propose,
    residual_distribution,
    speculative_sample,
)
from dlbb_tpu.serve.traffic import Request, TrafficTrace, generate_trace

TINY = dict(hidden_size=64, num_layers=2, num_heads=4,
            ffn_intermediate=128, dtype="float32", attention="full")
MODEL = ModelConfig(**TINY)
SERVE = dict(max_batch=8, block_size=8, max_seq=96, hbm_budget_gb=None)


def _trace(reqs):
    return TrafficTrace(kind="poisson", seed=0, params={},
                        requests=tuple(reqs))


def _spec_trace(n=10, seed=7, out=(40, 56)):
    """The repeating-structure mini-trace: motif prompts (period 4)
    warm the n-gram drafter from the first decode, and the outputs are
    long enough for greedy-feedback cycles to form mid-sequence."""
    return generate_trace("poisson", n, seed=seed, rate=500.0,
                          prompt_range=(8, 16), output_range=out,
                          prompt_period=4)


@pytest.fixture(scope="module")
def oracle_engine(mesh2x4):
    """Per-step greedy token feedback, no drafting — the identity
    oracle every speculative configuration is gated against."""
    return ServingEngine(
        MODEL, ServingConfig(**SERVE, speculation="greedy"), mesh2x4,
        verbose=False, capture_tokens=True)


def _engine(mesh, **extra):
    return ServingEngine(MODEL, ServingConfig(**SERVE, **extra), mesh,
                         verbose=False, capture_tokens=True)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_spec_config_validation_ladder():
    with pytest.raises(ValueError, match="speculation"):
        ServingConfig(**SERVE, speculation="turbo").validate(MODEL)
    # a drafter with no draft budget is a silent no-op trap
    with pytest.raises(ValueError, match="spec_gamma"):
        ServingConfig(**SERVE, speculation="ngram").validate(MODEL)
    # γ without a drafter: no verify step would ever run
    with pytest.raises(ValueError, match="drafting"):
        ServingConfig(**SERVE, spec_gamma=4).validate(MODEL)
    with pytest.raises(ValueError, match="drafting"):
        ServingConfig(**SERVE, speculation="greedy",
                      spec_gamma=4).validate(MODEL)
    with pytest.raises(ValueError, match="exceed"):
        ServingConfig(**SERVE, speculation="ngram",
                      spec_gamma=96).validate(MODEL)
    with pytest.raises(ValueError, match="spec_adaptive"):
        ServingConfig(**SERVE, spec_adaptive=True).validate(MODEL)
    # token-feedback modes have their own fused-scan ladder
    ServingConfig(**SERVE, speculation="ngram", spec_gamma=4,
                  decode_horizon=16).validate(MODEL)
    with pytest.raises(ValueError, match="spec_draft_layers"):
        ServingConfig(**SERVE, speculation="draft-model", spec_gamma=4,
                      spec_draft_layers=0).validate(MODEL)


def test_ngram_propose_pure_and_cyclic():
    """The drafter is a pure, deterministic function of the history;
    a trailing match at distance d extends CYCLICALLY (the history is
    locally d-periodic), and a cold history proposes nothing."""
    hist = [1, 2, 5, 6, 7, 5, 6, 7]
    got = _ngram_propose(hist, gamma=5)
    # trailing 3-gram [5,6,7] matched 3 back -> period-3 extension
    assert got == [5, 6, 7, 5, 6]
    assert _ngram_propose(list(hist), gamma=5) == got  # deterministic
    # cold: the last token never occurred before
    assert _ngram_propose([1, 2, 3], gamma=4) is None
    # exact continuation when the match is far enough back
    assert _ngram_propose([9, 4, 4, 8, 9, 4], gamma=2) == [4, 8]


# ---------------------------------------------------------------------------
# token identity: every speculative configuration == the greedy oracle
# ---------------------------------------------------------------------------


@pytest.mark.spec_smoke
def test_ngram_fused_matches_oracle(oracle_engine, mesh2x4):
    """The CI gate: n-gram drafting on the fused-scan fast path serves
    the seeded mini-trace token-identical to the per-step greedy
    engine, with real verify traffic and nonzero acceptance."""
    trace = _spec_trace()
    base = oracle_engine.run_trace(trace)
    spec = _engine(mesh2x4, speculation="ngram", spec_gamma=4,
                   decode_horizon=16).run_trace(trace)
    assert base["requests"]["completed"] == len(trace)
    assert spec["requests"]["completed"] == len(trace)
    assert spec["completed_tokens"] == base["completed_tokens"]
    s = spec["speculation"]
    assert s["mode"] == "ngram" and s["gamma"] == 4
    assert s["verify_units"] > 0
    assert s["proposed_tokens"] >= s["accepted_tokens"] > 0
    assert 0.0 < s["acceptance_rate"] <= 1.0
    # accepted draft tokens shrank the dispatch count below one-per-token
    assert spec["decode_units"] < spec["decode_steps"]
    # rollback left the ledger clean
    assert spec["cache"]["blocks_reserved"] == 0


@pytest.mark.spec_smoke
def test_draft_model_matches_oracle(oracle_engine, mesh2x4):
    """Model drafting: a 1-layer draft transformer on the SAME mesh
    with its own KV plane stays token-identical to the oracle (the
    verify step re-derives every committed token from the target)."""
    trace = _spec_trace(n=6, out=(24, 32))
    base = oracle_engine.run_trace(trace)
    spec = _engine(mesh2x4, speculation="draft-model", spec_gamma=4,
                   spec_draft_layers=1).run_trace(trace)
    assert spec["completed_tokens"] == base["completed_tokens"]
    assert spec["speculation"]["verify_units"] > 0
    assert spec["cache"]["blocks_reserved"] == 0


def test_greedy_fused_and_ngram_per_step_match_oracle(oracle_engine,
                                                      mesh2x4):
    """The two remaining grid corners: greedy token feedback through
    the fused scan (no drafting), and n-gram drafting on the per-step
    engine, each token-identical to the per-step greedy oracle."""
    trace = _spec_trace(n=8)
    base = oracle_engine.run_trace(trace)
    fused = _engine(mesh2x4, speculation="greedy",
                    decode_horizon=16).run_trace(trace)
    assert fused["completed_tokens"] == base["completed_tokens"]
    assert fused["fast_path"]["fused_scans"] > 0
    perstep = _engine(mesh2x4, speculation="ngram",
                      spec_gamma=8).run_trace(trace)
    assert perstep["completed_tokens"] == base["completed_tokens"]
    assert perstep["speculation"]["verify_units"] > 0


@pytest.mark.parametrize("variant", ["tp2_gqa", "bf16"])
def test_identity_across_model_variants(variant, mesh2x4):
    """Token identity is a property of the acceptance rule, not the
    sharding or dtype: a (tp)-only GQA mesh (grouped cache reads,
    kv-head shard) and a bf16 (dp, tp) model each stay identical to
    THEIR per-step greedy oracle — same weights, same mesh — under
    n-gram drafting on the fused scan.  bf16 needs no tolerance: the
    verify step commits via argmax over the same table, and the oracle
    runs the same quantised feedback."""
    if variant == "tp2_gqa":
        cfg = ModelConfig(**{**TINY, "num_kv_heads": 2})
        mesh = build_parallelism_mesh(tensor_parallel=2,
                                      devices=jax.devices()[:2])
    else:
        cfg = ModelConfig(**{**TINY, "dtype": "bfloat16"})
        mesh = mesh2x4
    trace = _spec_trace(n=6, out=(24, 32))
    base = ServingEngine(
        cfg, ServingConfig(**SERVE, speculation="greedy"), mesh,
        verbose=False, capture_tokens=True).run_trace(trace)
    spec = ServingEngine(
        cfg, ServingConfig(**SERVE, speculation="ngram", spec_gamma=4,
                           decode_horizon=16), mesh,
        verbose=False, capture_tokens=True).run_trace(trace)
    assert spec["completed_tokens"] == base["completed_tokens"]
    assert spec["speculation"]["verify_units"] > 0
    # rejection rollback left the ledger in the never-drafted state
    for key in ("total_blocks", "blocks_reserved", "blocks_in_use"):
        assert spec["cache"][key] == base["cache"][key]


def test_adaptive_gamma_matches_oracle(oracle_engine, mesh2x4):
    """Per-request adaptive γ (the EMA ladder backoff) changes which
    verify widths run, never which tokens commit."""
    trace = _spec_trace(n=8)
    base = oracle_engine.run_trace(trace)
    spec = _engine(mesh2x4, speculation="ngram", spec_gamma=8,
                   spec_adaptive=True,
                   decode_horizon=16).run_trace(trace)
    assert spec["completed_tokens"] == base["completed_tokens"]
    assert spec["speculation"]["adaptive"] is True
    assert spec["speculation"]["verify_units"] > 0


# ---------------------------------------------------------------------------
# scheduler edges speculation makes reachable
# ---------------------------------------------------------------------------


def test_mid_verify_completion_clamps_commits(oracle_engine, mesh2x4):
    """A request whose remaining budget is smaller than γ completes
    mid-verify: commits clamp to remaining, the slot frees, and no
    token past output_len ever lands."""
    engine = _engine(mesh2x4, speculation="ngram", spec_gamma=8)
    trace = _trace([
        # period-4 prompt: drafter warm from the first decode, so the
        # very first verify unit overshoots rid 0's 3-token budget
        Request(rid=0, arrival_s=0.0, prompt_len=8, output_len=3,
                seed=11, prompt_period=4),
        Request(rid=1, arrival_s=0.0, prompt_len=8, output_len=24,
                seed=12, prompt_period=4),
    ])
    report = engine.run_trace(trace)
    base = oracle_engine.run_trace(trace)
    assert report["completed_tokens"] == base["completed_tokens"]
    assert len(report["completed_tokens"]["0"]) == 3
    assert len(report["completed_tokens"]["1"]) == 24
    assert report["requests"]["completed"] == 2
    assert report["cache"]["blocks_reserved"] == 0


def test_cold_drafter_falls_back_to_plain_decode(oracle_engine,
                                                 mesh2x4):
    """Random prompts (no period) leave the n-gram drafter cold at
    admission: those slots dispatch plain decode units (counted as
    fallbacks) until history warms, and identity still holds."""
    engine = _engine(mesh2x4, speculation="ngram", spec_gamma=4)
    trace = generate_trace("poisson", 6, seed=13, rate=500.0,
                           prompt_range=(4, 8), output_range=(30, 40))
    report = engine.run_trace(trace)
    base = oracle_engine.run_trace(trace)
    assert report["completed_tokens"] == base["completed_tokens"]
    assert report["speculation"]["fallback_units"] > 0


def test_decode_fail_during_verify_retries_cleanly(oracle_engine,
                                                   mesh2x4):
    """serve-decode-fail firing at the verify dispatch site: the host
    rollback (ledger snapshot + slot lengths) replays the unit and the
    completed tokens stay identical to an un-faulted oracle run."""
    engine = _engine(mesh2x4, speculation="ngram", spec_gamma=4,
                     decode_horizon=16)
    trace = _spec_trace(n=6, out=(24, 32))
    with inject.plan_scope("serve-decode-fail:1"):
        report = engine.run_trace(trace)
    base = oracle_engine.run_trace(trace)
    assert report["resilience"]["retries"] >= 1
    assert report["requests"]["completed"] == len(trace)
    assert report["completed_tokens"] == base["completed_tokens"]
    assert report["speculation"]["verify_units"] > 0
    assert report["cache"]["blocks_reserved"] == 0


# ---------------------------------------------------------------------------
# sampled decode: distribution identity
# ---------------------------------------------------------------------------


def test_residual_distribution_degenerates_to_p():
    p = np.array([0.5, 0.3, 0.2])
    # q dominates p everywhere -> rejection has zero probability and
    # the residual is defined as p itself
    assert np.allclose(residual_distribution(p, np.ones(3)), p)
    r = residual_distribution(p, np.array([0.1, 0.6, 0.3]))
    assert np.isclose(r.sum(), 1.0)
    assert r[1] == 0.0 and r[2] == 0.0 and r[0] == 1.0


def test_speculative_sample_distribution_identity():
    """The Leviathan accept/residual composite law equals the target
    distribution exactly — sampled speculative decode is
    DISTRIBUTION-identical to the sequential sampler (the documented
    weakening of the greedy token-identity gate)."""
    rng = np.random.default_rng(0)
    p = np.array([0.45, 0.35, 0.15, 0.05])
    q = np.array([0.10, 0.60, 0.20, 0.10])
    n = 20000
    counts = np.zeros(4)
    for _ in range(n):
        draft = rng.choice(4, p=q)
        tok, _accepted = speculative_sample(p, q, draft, rng)
        counts[tok] += 1
    emp = counts / n
    # 4 sigma of a binomial at n=20k is ~1.4e-2 on the largest cell
    assert np.abs(emp - p).max() < 0.015


# ---------------------------------------------------------------------------
# observability: journal events, metrics export, report writers
# ---------------------------------------------------------------------------


@pytest.mark.spec_smoke
def test_spec_verify_journal_events_and_metrics(mesh2x4, tmp_path):
    """Every verify unit journals one ``spec-verify`` event per slot
    (gamma/accepted/committed), the journal replays un-torn, and the
    prometheus export carries the speculation counters."""
    from dlbb_tpu.obs import spans
    from dlbb_tpu.obs.export import serving_metrics
    from dlbb_tpu.resilience.journal import SweepJournal, read_journal

    engine = _engine(mesh2x4, speculation="ngram", spec_gamma=4,
                     decode_horizon=16)
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        report = engine.run_trace(_spec_trace(n=6, out=(24, 32)))
    finally:
        engine.journal = None
        journal.close()
    events, torn = read_journal(tmp_path)
    assert torn == 0
    verifies = [e for e in events if e["event"] == "spec-verify"]
    assert len(verifies) > 0
    for e in verifies:
        assert 1 <= e["gamma"] <= 4
        assert 0 <= e["accepted"] <= e["gamma"]
        assert 1 <= e["committed"] <= e["gamma"] + 1
    registry = serving_metrics(report, engine.registry)
    prom = registry.to_prometheus()
    assert "serve_spec_proposed_total" in prom
    assert "serve_spec_accepted_total" in prom
    assert "serve_spec_acceptance_ema" in prom
    s = report["speculation"]
    assert registry.get("serve_spec_proposed_total",
                        drafter="ngram") == s["proposed_tokens"]
    assert registry.get("serve_spec_accepted_total",
                        drafter="ngram") == s["accepted_tokens"]


def test_serving_report_spec_columns(tmp_path):
    from dlbb_tpu.stats.serving_report import write_serving_report
    from dlbb_tpu.utils.config import save_json

    fake = {
        "schema": "dlbb_serving_report_v1",
        "trace": {"kind": "poisson", "num_requests": 4},
        "requests": {"arrived": 4, "completed": 4, "rejected": 0,
                     "shed_rate": 0.0, "rejected_detail": []},
        "mesh": {"dp": 2, "tp": 4},
        "serving": {"max_batch": 8, "block_size": 8, "max_seq": 96},
        "speculation": {"mode": "ngram", "gamma": 4, "adaptive": False,
                        "verify_units": 10, "fallback_units": 2,
                        "proposed_tokens": 40, "accepted_tokens": 25,
                        "acceptance_rate": 0.625,
                        "mean_accepted_len": 3.5,
                        "draft_overhead_s": 0.01},
        "goodput_tokens_per_s": 100.0,
        "ttft": {"median": 0.01, "p99": 0.02, "p999": 0.03},
        "per_token_latency": {"median": 0.001, "p99": 0.002,
                              "p999": 0.003},
        "cache": {"peak_blocks_in_use": 12},
        "timeseries": {"queue_depth": [0, 1]},
        "decode_steps": 42,
        "wall_seconds": 1.5,
    }
    results = tmp_path / "results"
    save_json(fake, results / "serving_specrun.json")
    rows = write_serving_report(results, tmp_path / "stats")
    assert len(rows) == 1
    row = rows[0]
    assert row["speculation"] == "ngram"
    assert row["spec_gamma"] == 4
    assert row["acceptance_rate"] == 0.625
    assert row["mean_accepted_len"] == 3.5
    md = (tmp_path / "stats" / "SERVING.md").read_text()
    assert "ngram" in md


def test_speculative_report_writer(tmp_path):
    from dlbb_tpu.stats.serving_report import write_speculative_report
    from dlbb_tpu.utils.config import save_json

    bench = {
        "schema": "dlbb_bench_spec_v1",
        "baseline": "off_fused16",
        "settings": {
            "off_fused16": {
                "speculation": "off", "decode_horizon": 16,
                "output_tokens_per_s": {"median": 100.0, "min": 95.0,
                                        "max": 105.0},
                "ttft_p50_ms": 10.0, "per_token_p50_ms": 2.0,
            },
            "ngram_g4_fused16": {
                "speculation": "ngram", "spec_gamma": 4,
                "decode_horizon": 16,
                "output_tokens_per_s": {"median": 150.0, "min": 140.0,
                                        "max": 160.0},
                "ttft_p50_ms": 8.0, "per_token_p50_ms": 1.2,
                "acceptance_rate": 0.7, "mean_accepted_len": 3.8,
                "draft_overhead_s": 0.01, "token_identical": True,
            },
        },
    }
    path = tmp_path / "BENCH_spec.json"
    save_json(bench, path)
    rows = write_speculative_report(path, tmp_path / "stats")
    assert len(rows) == 2
    by_name = {r["setting"]: r for r in rows}
    assert by_name["ngram_g4_fused16"]["speedup_vs_baseline"] == 1.5
    assert by_name["ngram_g4_fused16"]["token_identical"] is True
    md = (tmp_path / "stats" / "SPECULATIVE.md").read_text()
    assert "1.50x" in md and "ngram_g4_fused16" in md and "yes" in md
    # missing artifact: no rows, nothing clobbered
    assert write_speculative_report(tmp_path / "nope.json",
                                    tmp_path / "stats2") == []


# ---------------------------------------------------------------------------
# sampled decode (temperature > 0): in-engine residual sampling
# ---------------------------------------------------------------------------


def test_sampled_validation_ladder():
    """temperature > 0 routes decode through the verify unit's residual
    sampler — every configuration where the knob would silently emit
    greedy tokens is rejected up front."""
    with pytest.raises(ValueError, match="requires a drafting"):
        ServingConfig(**SERVE, temperature=0.8).validate(MODEL)
    with pytest.raises(ValueError, match="decode_horizon=1"):
        ServingConfig(**SERVE, speculation="ngram", spec_gamma=4,
                      temperature=0.8,
                      decode_horizon=16).validate(MODEL)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingConfig(**SERVE, speculation="ngram", spec_gamma=4,
                      temperature=0.8, prefill_chunk=16).validate(MODEL)
    with pytest.raises(ValueError, match="requires temperature"):
        ServingConfig(**SERVE, sample_seed=3).validate(MODEL)
    with pytest.raises(ValueError, match=">= 0"):
        ServingConfig(**SERVE, temperature=-0.1).validate(MODEL)


@pytest.mark.spec_smoke
def test_sampled_run_replayable_and_seed_sensitive(mesh2x4):
    """The sampled path runs in-engine through the scheduler: the same
    (trace seed, sample_seed) pair replays token-identically, a
    different sample_seed diverges, and the report records the sampled
    law (temperature, seed, sampled=True)."""
    # every request due at 0: the host's sampler is drawn from in the
    # order the scheduler meets the requests, and with arrivals 2 ms
    # apart that order is the machine's load, not the seeds' (the run
    # replayed differently under a busy xdist suite)
    trace = _trace([dataclasses.replace(r, arrival_s=0.0)
                    for r in _spec_trace(n=6, out=(24, 32)).requests])
    kw = dict(speculation="ngram", spec_gamma=4, temperature=0.8)
    a = _engine(mesh2x4, **kw, sample_seed=3).run_trace(trace)
    b = _engine(mesh2x4, **kw, sample_seed=3).run_trace(trace)
    c = _engine(mesh2x4, **kw, sample_seed=4).run_trace(trace)
    assert a["requests"]["completed"] == len(trace)
    assert a["completed_tokens"] == b["completed_tokens"]
    assert a["completed_tokens"] != c["completed_tokens"]
    s = a["speculation"]
    assert s["sampled"] is True
    assert s["temperature"] == 0.8 and s["sample_seed"] == 3
    assert s["verify_units"] > 0
    assert a["cache"]["blocks_reserved"] == 0
