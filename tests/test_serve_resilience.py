"""Serving resilience tests (PR 11): the chaos harness extended into
the continuous-batching engine.

The serving fault matrix — transient prefill/decode dispatch failures
retried after rolling the host ledger/slot state back to the
pre-dispatch snapshot, torn bookkeeping replayed, exhausted retries
failing only the affected requests with journaled exception chains, a
hung dispatch abandoned by the EMA-scaled watchdog while the engine
continues on a fresh carry, per-request SLO deadlines shedding blown
queue heads, and SIGTERM drain + ``cli serve --resume`` reproducing an
uninterrupted run's artifact set.  Plus the static zero-instruction pin
on the decode hot path: the jitted device programs never reference the
injection registry, so an inactive (or active) plan adds zero
instructions to the fused-scan body.
"""

import ast
import json
import time
from dataclasses import replace
from pathlib import Path

import pytest

from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.obs import spans
from dlbb_tpu.resilience import inject
from dlbb_tpu.resilience.journal import SweepJournal, read_journal
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.traffic import Request, TrafficTrace, generate_trace

REPO = Path(__file__).resolve().parents[1]

TINY = dict(hidden_size=64, num_layers=2, num_heads=4,
            ffn_intermediate=128, dtype="float32", attention="full")

SMOKE_MODEL = ModelConfig(**TINY)
# fast backoff so retry tests don't sleep their wall budget away
SMOKE_SERVING = ServingConfig(max_batch=8, block_size=8, max_seq=64,
                              queue_capacity=64, hbm_budget_gb=None,
                              retry_backoff_s=0.01)


def _trace(n=10, seed=5, rate=200.0, **kw):
    kw.setdefault("prompt_range", (4, 12))
    kw.setdefault("output_range", (3, 6))
    return generate_trace("poisson", n, seed=seed, rate=rate, **kw)


@pytest.fixture(scope="module")
def chaos_engine(mesh2x4):
    """One compiled engine shared by the fault-matrix tests (fresh
    cache per run_trace; registry counters accumulate, so tests assert
    per-run report fields, not absolute counter values)."""
    return ServingEngine(SMOKE_MODEL, SMOKE_SERVING, mesh2x4,
                         verbose=False)


# ---------------------------------------------------------------------------
# injection registry + the static hot-path pin
# ---------------------------------------------------------------------------


def test_serve_sites_registered_and_parse():
    for site in ("serve-prefill-fail", "serve-decode-fail",
                 "serve-decode-hang", "serve-cache-torn",
                 "serve-trace-corrupt", "serve-preempt"):
        assert site in inject.SITES
    plan = inject.FaultPlan.parse(
        "serve-decode-fail:2,serve-decode-hang:@1,hang_seconds=5")
    assert plan.fire("serve-decode-fail")
    assert plan.fire("serve-decode-hang")
    assert plan.param("hang_seconds") == 5.0


def test_decode_hot_path_static_zero_injection_pin():
    """The PR-5 zero-overhead contract extended to serving: the modules
    that hold the jitted device programs (both block families, the
    attention helpers under them, the cache, the decode kernel) never
    reference the injection registry, whatever they come to hold.
    Fault sites live strictly on the HOST side of the dispatch boundary,
    in the scheduler, so a lowered program is byte-identical with or
    without a plan (the serve_fastpath per-step == fused equivalence
    tests run unmodified against this same code).  That none of them
    IMPORTS the registry or the scheduler is
    ``test_serve_families.py``'s."""
    for module in ("serve/gpt.py", "serve/hybrid.py", "serve/attend.py",
                   "serve/kvcache.py", "ops/decode_attention.py"):
        src = (REPO / "dlbb_tpu" / module).read_text()
        for sub in ast.walk(ast.parse(src)):
            # any reference to the inject module (inject.fire,
            # inject.param, a bare name); name-substring matches
            # (gpt.inject_token itself) do not count
            if isinstance(sub, ast.Name) and sub.id == "inject":
                raise AssertionError(
                    f"injection reference inside {module}, line "
                    f"{sub.lineno}")
    # the scheduler is where the sites fire
    assert "inject.fire(" in (
        REPO / "dlbb_tpu" / "serve" / "engine.py").read_text()
    for module in ("serve/kvcache.py", "ops/decode_attention.py"):
        assert "inject" not in (REPO / "dlbb_tpu" / module).read_text()
    assert "def decode_attention(" in (
        REPO / "dlbb_tpu" / "ops" / "decode_attention.py").read_text()


# ---------------------------------------------------------------------------
# traffic: deadlines + corrupt-trace load
# ---------------------------------------------------------------------------


def test_request_deadline_field_roundtrip(tmp_path):
    t = _trace(deadline_s=0.5)
    assert all(r.deadline_s == 0.5 for r in t)
    assert t.params["deadline_s"] == 0.5
    path = tmp_path / "t.json"
    t.save(path)
    assert TrafficTrace.load(path) == t
    # deadline-free traces serialise exactly as the original v1 schema
    plain = _trace()
    payload = plain.to_dict()
    assert all("deadline_s" not in r for r in payload["requests"])
    with pytest.raises(ValueError, match="deadline_s"):
        _trace(deadline_s=0.0)


def test_trace_corrupt_load_fails_closed(tmp_path):
    path = tmp_path / "t.json"
    _trace().save(path)
    with inject.plan_scope("serve-trace-corrupt:@1"):
        with pytest.raises(ValueError,
                           match="corrupt or truncated") as ei:
            TrafficTrace.load(path)
        assert ei.value.__cause__ is not None  # the chained JSON error
        # the site is exhausted: the very next load succeeds — the file
        # itself was never touched
        assert len(TrafficTrace.load(path)) == 10


# ---------------------------------------------------------------------------
# the fault matrix through the engine (serve_chaos_smoke)
# ---------------------------------------------------------------------------


@pytest.mark.serve_chaos_smoke
def test_transient_dispatch_failures_retry_and_recover(chaos_engine,
                                                       tmp_path):
    """serve-prefill-fail + serve-decode-fail fire once each BEFORE the
    jit consumes the carry; the engine restores the pre-dispatch
    snapshot, backs off, re-issues — every request still completes and
    the retries are journaled + counted."""
    engine = chaos_engine
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        with inject.plan_scope("serve-prefill-fail:1,serve-decode-fail:1"):
            report = engine.run_trace(_trace())
    finally:
        engine.journal = None
        journal.close()
    assert report["requests"]["completed"] == 10
    assert report["requests"]["failed"] == 0
    assert report["resilience"]["retries"] >= 2
    assert all(v == "completed"
               for v in report["requests"]["outcomes"].values())
    events, _ = read_journal(tmp_path)
    phases = {e.get("phase") for e in events
              if e["event"] == "dispatch-retry"}
    assert {"prefill", "decode"} <= phases
    # the reason-labelled retry counters landed in the registry
    assert engine.registry.get("serve_request_retries",
                               phase="prefill") >= 1
    assert engine.registry.get("serve_request_retries",
                               phase="decode") >= 1


@pytest.mark.serve_chaos_smoke
def test_cache_torn_bookkeeping_rolls_back_and_replays(chaos_engine):
    """serve-cache-torn raises mid-way through the per-slot accounting
    loop, leaving tokens_done advanced for some slots but not the
    ledger: the rollback restores the pre-dispatch snapshot and the
    replay recomputes the whole unit's accounting from the device
    result already in hand."""
    engine = chaos_engine
    with inject.plan_scope("serve-cache-torn:1"):
        report = engine.run_trace(_trace())
    assert report["requests"]["completed"] == 10
    assert report["resilience"]["retries"] >= 1
    # ledger fully consistent after rollback: nothing dangling
    assert report["cache"]["blocks_reserved"] == 0
    assert report["cache"]["blocks_in_use"] == 0
    assert engine.registry.get("serve_request_retries",
                               phase="bookkeeping") >= 1


@pytest.mark.serve_chaos_smoke
def test_permanent_decode_failure_fails_only_affected_requests(
        chaos_engine, tmp_path):
    """Retries exhausted -> the resident requests fail CLOSED (journaled
    request-failed with the full exception chain), the run itself
    drains, and the engine stays serviceable for the next trace."""
    engine = chaos_engine
    original = engine.serving
    engine.serving = replace(original, max_dispatch_retries=0)
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        with inject.plan_scope("serve-decode-fail:*"):
            report = engine.run_trace(_trace())
    finally:
        engine.serving = original
        engine.journal = None
        journal.close()
    req = report["requests"]
    assert req["failed"] == 10 and req["completed"] == 0
    assert len(req["outcomes"]) == 10  # every request has a terminal state
    assert all(v == "failed[dispatch-failed]"
               for v in req["outcomes"].values())
    detail = report["resilience"]["failed"]
    assert detail and detail[0]["traceback"]
    assert "TransientFault" in detail[0]["error"]
    events, _ = read_journal(tmp_path)
    failed = [e for e in events if e["event"] == "request-failed"]
    assert len(failed) == 10
    assert all(e["reason"] == "dispatch-failed" for e in failed)
    # blocks freed, and the engine serves the next trace cleanly
    assert report["cache"]["blocks_reserved"] == 0
    clean = engine.run_trace(_trace(seed=6))
    assert clean["requests"]["completed"] == 10


@pytest.mark.serve_chaos_smoke
def test_hung_dispatch_abandoned_by_watchdog(chaos_engine, tmp_path):
    """serve-decode-hang sleeps 10s on the dispatch; the watchdog
    (EMA-scaled, 0.3s floor) abandons it on its daemon thread, fails
    the resident requests as hung-dispatch, and the engine continues
    on a fresh carry — later requests complete."""
    engine = chaos_engine
    original = engine.serving
    engine.serving = replace(original, dispatch_deadline_factor=50.0,
                             dispatch_deadline_min_s=0.3)
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    t0 = time.perf_counter()
    try:
        with inject.plan_scope(
                "serve-decode-hang:@1,hang_seconds=10"):
            report = engine.run_trace(_trace())
    finally:
        engine.serving = original
        engine.journal = None
        journal.close()
    wall = time.perf_counter() - t0
    assert wall < 8.0, f"engine blocked behind the hang ({wall:.1f}s)"
    assert report["resilience"]["hung_dispatches"] == 1
    outcomes = report["requests"]["outcomes"]
    hung = [r for r, o in outcomes.items()
            if o == "failed[hung-dispatch]"]
    assert len(hung) >= 1
    assert report["requests"]["completed"] == 10 - len(hung)
    events, _ = read_journal(tmp_path)
    assert any(e["event"] == "request-failed"
               and e["reason"] == "hung-dispatch" for e in events)
    assert engine.registry.get("serve_hung_dispatches") >= 1


@pytest.mark.serve_chaos_smoke
def test_carry_reset_mid_chunked_prefill_restarts_prefill(mesh2x4):
    """A catastrophic decode failure during the chunked-prefill
    interleave replaces the carry with a fresh cache — taking the
    admitting request's already-written chunks with it.  The prefill
    must RESTART on the fresh carry (chunk writes are deterministic, so
    the replay is exact), not keep chunking into an empty cache and
    report a silently-corrupted request as completed.  Pinned at token
    level: the victim is only the resident request; the admitting
    request's completed tokens equal an unfaulted run's."""
    engine = ServingEngine(
        SMOKE_MODEL,
        replace(SMOKE_SERVING, prefill_chunk=8,
                dispatch_deadline_factor=50.0,
                dispatch_deadline_min_s=0.3),
        mesh2x4, verbose=False, capture_tokens=True)
    # A (1 chunk) is resident when B's 3-chunk prefill interleaves —
    # the FIRST decode-site evaluation of the run is that interleaved
    # dispatch, so @1 aims the hang exactly at it
    trace = TrafficTrace(
        kind="poisson", seed=0, params={},
        requests=(
            Request(rid=0, arrival_s=0.0, prompt_len=4, output_len=4,
                    seed=11),
            Request(rid=1, arrival_s=0.0, prompt_len=20, output_len=4,
                    seed=12),
        ),
    )
    baseline = engine.run_trace(trace)
    assert baseline["requests"]["completed"] == 2
    with inject.plan_scope("serve-decode-hang:@1,hang_seconds=10"):
        report = engine.run_trace(trace)
    outcomes = report["requests"]["outcomes"]
    assert outcomes["0"] == "failed[hung-dispatch]"
    assert outcomes["1"] == "completed"
    assert report["resilience"]["hung_dispatches"] == 1
    assert report["resilience"]["retries"] >= 1  # the prefill restart
    assert engine.registry.get("serve_request_retries",
                               phase="prefill") >= 1
    # the corruption pin: B's tokens survive the mid-prefill reset
    assert (report["completed_tokens"]["1"]
            == baseline["completed_tokens"]["1"])


@pytest.mark.serve_chaos_smoke
def test_deferred_settle_past_its_deadline_restarts_the_prefill(
        mesh2x4, monkeypatch, tmp_path):
    """An interleaved step is waited for only after the next chunk was
    called.  If that wait blows its deadline the window is abandoned
    and the carry replaced UNDER a chunk already sent on the old cache:
    the prefill restarts on the fresh carry, only the resident batch
    fails, and the admitting request's tokens are an unfaulted run's."""
    from dlbb_tpu.resilience.errors import DeadlineExceeded
    from dlbb_tpu.serve import engine as engine_module

    engine = ServingEngine(
        SMOKE_MODEL, replace(SMOKE_SERVING, prefill_chunk=8),
        mesh2x4, verbose=False, capture_tokens=True)
    # A (1 chunk) is resident when B's 3-chunk prefill interleaves
    trace = TrafficTrace(
        kind="poisson", seed=0, params={},
        requests=(
            Request(rid=0, arrival_s=0.0, prompt_len=4, output_len=4,
                    seed=11),
            Request(rid=1, arrival_s=0.0, prompt_len=20, output_len=4,
                    seed=12),
        ),
    )
    baseline = engine.run_trace(trace)
    assert baseline["requests"]["completed"] == 2
    real = engine_module._with_deadline
    hung = []

    def first_wait_hangs(fn, deadline, label, phase):
        if phase == "serve-sync" and not hung:
            hung.append(label)
            raise DeadlineExceeded(label, 0.3, phase=phase)
        return real(fn, deadline, label, phase)

    monkeypatch.setattr(engine_module, "_with_deadline", first_wait_hangs)
    with spans.tracing(tmp_path / "spans.json"):
        report = engine.run_trace(trace)
    assert hung == ["decode[k=1]"]
    outcomes = report["requests"]["outcomes"]
    assert outcomes == {"0": "failed[hung-dispatch]", "1": "completed"}
    assert report["resilience"]["hung_dispatches"] == 1
    assert report["resilience"]["retries"] == 1      # the prefill restart
    assert (report["completed_tokens"]["1"]
            == baseline["completed_tokens"]["1"])
    # the wait that hung began after B's second chunk had been called,
    # and the restart sent all three chunks again
    begins = [ev for ev in
              spans.load_trace(tmp_path / "spans.json")["traceEvents"]
              if ev["ph"] == "B"]
    names = [(ev["name"], ev.get("args", {}).get("chunk")) for ev in begins
             if ev["name"] in ("serve-prefill-chunk", "serve-decode-sync")
             and ev.get("args", {}).get("rid", 1) == 1]
    assert names[:6] == [
        ("serve-prefill-chunk", 0), ("serve-prefill-chunk", 1),
        ("serve-decode-sync", None), ("serve-prefill-chunk", 0),
        ("serve-prefill-chunk", 1), ("serve-prefill-chunk", 2)]
    assert report["fast_path"]["prefill_chunks"] == 4  # A's one, B's three


@pytest.mark.serve_chaos_smoke
def test_deadline_sheds_queue_heads_and_counts_late_completions(
        chaos_engine, tmp_path):
    """A t=0 burst with a 20ms SLO: the first grant wave is admitted
    within microseconds (wait << SLO, so it serves — and completes
    LATE, since 8 serial prefills alone exceed 20ms — counted, not
    rejected), while the queue heads left behind are re-examined only
    after those prefills and are shed with reason=deadline, DISTINCT
    from queue-full (shed_rate stays 0).  Arrivals pinned at 0 and the
    SLO at 20ms keep both outcomes deterministic on any host speed:
    the first admission check happens before any dispatch (µs), and
    every later boundary sits behind ≥8 prefill dispatches (≫20ms)."""
    engine = chaos_engine
    burst = TrafficTrace(
        kind="poisson", seed=0, params={"deadline_s": 0.02},
        requests=tuple(
            Request(rid=i, arrival_s=0.0, prompt_len=8, output_len=4,
                    seed=100 + i, deadline_s=0.02)
            for i in range(12)
        ),
    )
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        report = engine.run_trace(burst)
    finally:
        engine.journal = None
        journal.close()
    req = report["requests"]
    assert req["deadline_shed"] >= 1
    assert req["completed_past_deadline"] >= 1
    assert req["shed_rate"] == 0.0  # no queue-full rejection happened
    assert req["completed"] + req["deadline_shed"] == 12
    shed = [d for d in req["rejected_detail"]
            if d["reason"] == "deadline"]
    assert len(shed) == req["deadline_shed"]
    assert all(d["queue_wait_s"] > d["deadline_s"] for d in shed)
    assert all(req["outcomes"][str(d["rid"])] == "rejected[deadline]"
               for d in shed)
    events, _ = read_journal(tmp_path)
    assert any(e["event"] == "request-rejected"
               and e.get("reason") == "deadline" for e in events)
    assert any(e["event"] == "request-completed"
               and e.get("past_deadline") for e in events)


@pytest.mark.serve_chaos_smoke
def test_preempt_drains_and_journals(chaos_engine, tmp_path):
    """serve-preempt SIGTERMs the process at a scheduler boundary; the
    engine's own PreemptionGuard turns it into a graceful drain:
    admission stops, the in-flight window settles, resident requests
    are journaled request-preempted, and the report carries the
    remaining-rid cursor for --resume."""
    engine = chaos_engine
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        with inject.plan_scope("serve-preempt:@3"):
            report = engine.run_trace(_trace())
    finally:
        engine.journal = None
        journal.close()
    assert report["preempted"] is True
    assert report["remaining_rids"]
    assert report["raw_samples"] is not None  # checkpoint merge input
    preempted = [r for r, o in report["requests"]["outcomes"].items()
                 if o == "preempted"]
    done = report["requests"]["completed"]
    assert done + len(report["remaining_rids"]) == 10
    assert report["cache"]["blocks_reserved"] == 0  # drained clean
    events, _ = read_journal(tmp_path)
    assert any(e["event"] == "preempted" for e in events)
    assert len([e for e in events
                if e["event"] == "request-preempted"]) == len(preempted)


@pytest.mark.serve_chaos_smoke
def test_kill_mid_trace_resume_equals_uninterrupted(tmp_path, devices):
    """The serving resume invariant end to end (serve/bench.py):
    SIGTERM mid-trace writes the checkpoint INSTEAD of the result
    artifact; `--resume` replays the remaining trace and merges both
    sessions into an artifact set with the same names, report schema,
    and per-request outcomes (for non-preempted requests) as an
    uninterrupted run."""
    from dlbb_tpu.serve.bench import (
        RESUME_CHECKPOINT,
        resume_serving,
        run_serving,
    )

    config = {
        "experiment": {"name": "x"},
        "model": dict(TINY),
        "parallelism": {"data_parallel": 2, "world_size": 4},
        "serving": {"max_batch": 8, "block_size": 8, "max_seq": 64,
                    "queue_capacity": 64, "hbm_budget_gb": None},
    }
    trace = _trace()
    ref = tmp_path / "ref"
    out = tmp_path / "preempted"
    run_serving(config, trace, str(ref), verbose=False)
    rep = run_serving(config, trace, str(out), verbose=False,
                      fault_plan="serve-preempt:@3")
    assert rep["preempted"]
    assert (out / RESUME_CHECKPOINT).exists()
    assert not (out / "serving_x.json").exists()
    preempted_rids = {r for r, o in rep["requests"]["outcomes"].items()
                      if o == "preempted"}
    merged = resume_serving(str(out), verbose=False)
    assert not (out / RESUME_CHECKPOINT).exists()
    assert merged["requests"]["sessions"] == 2
    # artifact-set equality: names, schema keys, per-request outcomes
    assert (sorted(p.name for p in ref.iterdir())
            == sorted(p.name for p in out.iterdir()))
    a = json.loads((ref / "serving_x.json").read_text())
    b = json.loads((out / "serving_x.json").read_text())
    assert sorted(a) == sorted(b)
    oa, ob = a["requests"]["outcomes"], b["requests"]["outcomes"]
    assert set(oa) == set(ob)
    for rid in oa:
        if rid not in preempted_rids:
            assert oa[rid] == ob[rid], rid
    # the merged summaries were re-summarized over both sessions' raw
    # samples; a preempted request replayed in session 2 may contribute
    # a second TTFT sample (it was prefilled twice — honest accounting)
    assert b["ttft"]["count"] >= a["ttft"]["count"]
    assert "raw_samples" not in b
    # the append-only journal holds BOTH sessions
    events, torn = read_journal(out)
    assert torn == 0
    assert [e for e in events if e["event"] == "sweep-start"
            and e.get("resume")]
    assert any(e["event"] == "request-preempted" for e in events)


@pytest.mark.serve_chaos_smoke
def test_journal_to_trace_pairs_failed_and_preempted(tmp_path):
    """obs/spans.journal_to_trace reconstructs failed/retried/preempted
    request lifecycles into per-request X spans — a crashed serving run
    stays debuggable from the fsync'd journal alone."""
    with SweepJournal(tmp_path, meta={"mode": "serve"}) as j:
        j.event("request-arrived", config="request-1", prompt=4)
        j.event("dispatch-retry", phase="decode", attempt=1)
        j.event("request-failed", config="request-1",
                reason="hung-dispatch", error="DeadlineExceeded: ...")
        j.event("request-arrived", config="request-2", prompt=8)
        j.event("request-preempted", config="request-2", tokens_done=3)
        j.event("preempted", remaining=1)
    path, n, torn = spans.journal_to_trace(tmp_path,
                                           tmp_path / "trace.json")
    assert torn == 0
    payload = spans.load_trace(path)
    xs = {e["name"]: e for e in payload["traceEvents"]
          if e["ph"] == "X"}
    assert xs["request-1"]["cat"] == "config-failed"
    assert xs["request-1"]["args"]["reason"] == "hung-dispatch"
    assert xs["request-2"]["cat"] == "config-preempted"
    # instants for every journal line (the retry included) still there
    names = [e["name"] for e in payload["traceEvents"]
             if e["ph"] == "i"]
    assert "dispatch-retry" in names


# ---------------------------------------------------------------------------
# config validation, metrics folding, report columns
# ---------------------------------------------------------------------------


def test_resilience_config_validation_ladder():
    cfg = ModelConfig(**TINY)
    good = ServingConfig(max_batch=4, block_size=8, max_seq=32,
                         hbm_budget_gb=None,
                         dispatch_deadline_factor=8.0)
    good.validate(cfg)
    for bad in (
        dict(max_dispatch_retries=-1),
        dict(retry_backoff_s=-0.1),
        dict(dispatch_deadline_factor=0.0),
        dict(dispatch_deadline_min_s=0.0),
    ):
        with pytest.raises(ValueError, match=next(iter(bad))):
            ServingConfig(max_batch=4, block_size=8, max_seq=32,
                          hbm_budget_gb=None, **bad).validate(cfg)
    # knobs round-trip the config dict
    rt = ServingConfig.from_dict(good.to_dict())
    assert rt.dispatch_deadline_factor == 8.0
    assert rt.max_dispatch_retries == good.max_dispatch_retries


def test_serving_metrics_folds_resilience_and_deadlines():
    from dlbb_tpu.obs.export import serving_metrics

    report = {
        "goodput_tokens_per_s": 100.0,
        "requests": {"shed_rate": 0.1, "deadline_shed": 3,
                     "completed_past_deadline": 2, "failed": 1,
                     "preempted": 0},
        "resilience": {"retries": 4, "hung_dispatches": 1},
    }
    reg = serving_metrics(report)
    assert reg.get("serve_deadline_shed") == 3
    assert reg.get("serve_completed_past_deadline") == 2
    assert reg.get("serve_failed_requests") == 1
    assert reg.get("serve_request_retries", phase="decode") == 4
    assert reg.get("serve_hung_dispatches") == 1
    text = reg.to_prometheus()
    assert "dlbb_serve_deadline_shed" in text
    assert "dlbb_serve_request_retries_total" in text
    assert "dlbb_serve_hung_dispatches_total" in text
    # a live registry whose retries were ALL bookkeeping-phase (the
    # cache-torn scenario) is already seeded — folding the report on
    # top must NOT re-add the total under phase=decode
    from dlbb_tpu.obs.export import MetricsRegistry

    live = MetricsRegistry()
    live.labeled_counter("serve_request_retries", "phase")["bookkeeping"] \
        += 4
    reg2 = serving_metrics(report, registry=live)
    assert reg2.get("serve_request_retries", phase="decode") == 0
    assert reg2.get("serve_request_retries", phase="bookkeeping") == 4


def test_serving_report_gains_resilience_columns(tmp_path):
    from dlbb_tpu.stats.serving_report import write_serving_report
    from dlbb_tpu.utils.config import save_json

    fake = {
        "schema": "dlbb_serving_report_v1",
        "trace": {"kind": "poisson", "num_requests": 10},
        "requests": {"completed": 7, "rejected": 2, "failed": 1,
                     "deadline_shed": 2, "completed_past_deadline": 3},
        "resilience": {"retries": 5},
        "mesh": {"dp": 2, "tp": 4},
        "serving": {"max_batch": 8, "block_size": 16, "max_seq": 256},
        "goodput_tokens_per_s": 10.0,
        "ttft": {"median": 0.01, "p99": 0.02, "p999": 0.03},
        "per_token_latency": {"median": 0.001, "p99": 0.002,
                              "p999": 0.003},
        "cache": {"peak_blocks_in_use": 4},
        "timeseries": {"queue_depth": [0, 1]},
        "decode_steps": 9,
        "wall_seconds": 1.0,
    }
    save_json(fake, tmp_path / "results" / "serving_r1.json")
    rows = write_serving_report(tmp_path / "results", tmp_path / "stats")
    assert rows[0]["failed"] == 1
    assert rows[0]["deadline_shed"] == 2
    assert rows[0]["past_deadline"] == 3
    assert rows[0]["retries"] == 5
    md = (tmp_path / "stats" / "SERVING.md").read_text()
    assert "| late |" in md.replace("  ", " ")
    csv_head = (tmp_path / "stats" / "serving.csv").read_text()
    assert "failed" in csv_head and "past_deadline" in csv_head
