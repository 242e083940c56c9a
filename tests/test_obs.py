"""Observability subsystem tests (docs/observability.md): span-tracer
schema + concurrency, the zero-overhead disabled path (pinned statically
like ``resilience/inject.py``), journal→span sink equivalence, the
metrics registry / Prometheus export, the calibration diff gate's pinned
exit codes, and the ``obs_smoke`` gate — a traced + device-captured
sweep must emit a Perfetto-loadable trace while publishing stats
equivalent to an untraced run (profile reps never enter the series)."""

import json
import threading
import time
from pathlib import Path

import pytest

from dlbb_tpu.analysis.findings import (
    EXIT_CLEAN,
    EXIT_CRASH,
    EXIT_FINDINGS,
)
from dlbb_tpu.obs import calibration as cal
from dlbb_tpu.obs import spans
from dlbb_tpu.obs.export import MetricsRegistry
from dlbb_tpu.obs.spans import (
    SpanTracer,
    journal_to_trace,
    validate_trace_events,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """A test that crashes mid-scope must not leak a process-global
    tracer into the rest of the suite."""
    yield
    spans.stop()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_trace_schema_valid(tmp_path):
    tracer = SpanTracer(tmp_path / "t.json", meta={"who": "test"})
    with tracer.span("outer", cat="a", key="v"):
        with tracer.span("inner", cat="b"):
            tracer.instant("marker", cat="j", args={"n": 1})
    path = tracer.finish()
    data = json.loads(path.read_text())
    evs = data["traceEvents"]
    assert validate_trace_events(evs) == []
    assert data["otherData"]["schema"] == spans.SPAN_SCHEMA
    assert data["otherData"]["who"] == "test"
    # B/E pairs + instant, all with the required keys and µs timestamps
    assert [e["ph"] for e in evs] == ["B", "B", "i", "E", "E"]
    names = [e["name"] for e in evs]
    assert names == ["outer", "inner", "marker", "inner", "outer"]
    assert all(e["ts"] >= 0 for e in evs)
    assert evs[0]["args"] == {"key": "v"}


def test_span_end_emitted_on_exception(tmp_path):
    tracer = SpanTracer(tmp_path / "t.json")
    with pytest.raises(ValueError):
        with tracer.span("doomed"):
            raise ValueError("boom")
    assert validate_trace_events(tracer.events()) == []


def test_concurrent_thread_nesting(tmp_path):
    """Spans from concurrently-running threads must stay properly nested
    per tid (the invariant Perfetto's flame view needs)."""
    tracer = SpanTracer(tmp_path / "t.json")
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()  # all threads alive at once: tids are distinct
        with tracer.span(f"outer{i}", cat="t"):
            time.sleep(0.002)
            with tracer.span(f"inner{i}", cat="t"):
                tracer.instant(f"tick{i}")
                time.sleep(0.002)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tracer.events()
    assert validate_trace_events(evs) == []
    assert len({e["tid"] for e in evs}) == 4
    assert sum(1 for e in evs if e["ph"] == "B") == 8


def test_misnested_trace_detected():
    bad = [
        {"name": "a", "ph": "B", "ts": 0.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "E", "ts": 1.0, "pid": 1, "tid": 1},
    ]
    assert any("misnested" in p for p in validate_trace_events(bad))
    assert any("unclosed" in p
               for p in validate_trace_events(bad[:1]))


def test_disabled_span_is_shared_singleton():
    """Zero-overhead contract, dynamically: with no tracer active,
    span() hands back ONE shared nullcontext (no allocation per call)
    and instant() is a no-op."""
    assert spans.active() is None
    assert spans.span("a") is spans.span("b", cat="x", arg=1)
    spans.instant("nothing-happens")  # must not raise, must not allocate


def test_active_span_opens_the_profiler_annotation_of_its_name(
        tmp_path, monkeypatch):
    """One clock: with a tracer on, ``spans.span`` opens the profiler
    annotation of the same name and arguments through the sanctioned
    home (``utils/profiling.annotate``), so a device capture shows the
    program's spans on its host line; with no tracer nothing is
    opened."""
    import contextlib

    from dlbb_tpu.utils import profiling

    opened = []

    @contextlib.contextmanager
    def fake_annotate(name, **args):
        opened.append(("enter", name, args))
        yield
        opened.append(("exit", name, args))

    monkeypatch.setattr(profiling, "annotate", fake_annotate)
    with spans.span("serve-prefill", rid=3):
        pass
    assert opened == []
    with spans.tracing(tmp_path / "t.json"):
        with spans.span("serve-prefill", rid=3, path=tmp_path):
            with spans.span("serve-prefill-chunk", chunk=0):
                pass
    assert [(kind, name) for kind, name, _a in opened] == [
        ("enter", "serve-prefill"), ("enter", "serve-prefill-chunk"),
        ("exit", "serve-prefill-chunk"), ("exit", "serve-prefill")]
    # the arguments reach the annotation as the file gets them
    assert opened[0][2] == {"rid": 3, "path": str(tmp_path)}


def test_cell_paths_open_no_annotation_by_hand():
    """One system: on the paths the benchmark's cells run, the only way
    the program opens a host annotation is ``spans.span`` — no module
    there imports the profiling wrappers or ``jax.profiler`` itself."""
    import ast

    for rel in ("serve/engine.py", "train/loop.py",
                "models/transformer.py", "ops/flash_attention.py"):
        tree = ast.parse((REPO / "dlbb_tpu" / rel).read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                assert "profiling" not in (node.module or ""), rel
            if isinstance(node, ast.Attribute):
                assert node.attr != "profiler", rel


def test_timed_regions_carry_zero_obs_instructions():
    """The zero-overhead contract, statically (same pin shape as
    ``resilience/inject.py``): ``utils/timing.py`` — the only module
    that brackets device work with clocks — must never reference the
    obs package, so tracing state can add zero instructions to any
    timed region."""
    import ast

    src = (REPO / "dlbb_tpu" / "utils" / "timing.py").read_text()
    assert "spans" not in src
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            continue
        assert not any("obs" in m for m in mods), (
            f"timing.py imports {mods} — the timed-region module must "
            "never reference dlbb_tpu.obs"
        )


def test_tracing_scope_first_starter_wins(tmp_path):
    outer_path = tmp_path / "outer.json"
    inner_path = tmp_path / "inner.json"
    with spans.tracing(outer_path) as outer:
        assert spans.active() is outer
        with spans.tracing(inner_path) as inner:
            assert inner is outer  # pass-through, no second tracer
            spans.span("x").__enter__()  # lands in the outer trace
            spans.active().end("x")
    assert outer_path.exists() and not inner_path.exists()
    assert spans.active() is None
    names = [e["name"]
             for e in json.loads(outer_path.read_text())["traceEvents"]]
    assert "x" in names


def test_tracing_disabled_path_noop():
    with spans.tracing(None) as tracer:
        assert tracer is None
        assert spans.span("x") is spans.span("y")


# ---------------------------------------------------------------------------
# journal -> span sink
# ---------------------------------------------------------------------------


def test_journal_sink_equivalence(tmp_path):
    """Every journal event must appear as exactly one trace instant with
    the same name and payload — the two artifacts tell one story."""
    from dlbb_tpu.resilience.journal import SweepJournal, read_journal

    with spans.tracing(tmp_path / "t.json") as tracer:
        j = SweepJournal(tmp_path, meta={"kind": "test"},
                         sink=spans.journal_sink)
        j.event("planned", config="a.json")
        j.event("started", config="a.json")
        j.event("completed", config="a.json", retries=0)
        j.close()
        instants = [e for e in tracer.events() if e["cat"] == "journal"]
    events, torn = read_journal(tmp_path)
    assert torn == 0
    assert [e["event"] for e in events] == \
        [i["name"] for i in instants]  # sweep-start included, in order
    by_name = {i["name"]: i for i in instants}
    assert by_name["completed"]["args"]["config"] == "a.json"
    assert by_name["completed"]["args"]["retries"] == 0


def test_journal_sink_fires_even_when_file_journal_disabled(tmp_path):
    from dlbb_tpu.resilience.journal import SweepJournal

    with spans.tracing(tmp_path / "t.json") as tracer:
        j = SweepJournal(tmp_path, enabled=False, sink=spans.journal_sink)
        j.event("planned", config="a.json")
        assert not (tmp_path / "sweep_journal.jsonl").exists()
        assert [e["name"] for e in tracer.events()
                if e["cat"] == "journal"] == ["planned"]


def test_journal_sink_exceptions_contained(tmp_path):
    from dlbb_tpu.resilience.journal import SweepJournal, read_journal

    def bad_sink(event, record):
        raise RuntimeError("observer crash")

    j = SweepJournal(tmp_path, sink=bad_sink)
    j.event("planned", config="a.json")  # must not raise
    j.close()
    events, _ = read_journal(tmp_path)
    assert [e["event"] for e in events] == ["sweep-start", "planned"]


def test_journal_to_trace_reconstruction(tmp_path):
    from dlbb_tpu.resilience.journal import SweepJournal

    j = SweepJournal(tmp_path, meta={"kind": "1d"})
    j.event("planned", config="a.json")
    j.event("started", config="a.json")
    j.event("completed", config="a.json")
    j.event("started", config="b.json")
    j.event("failed", config="b.json", error="boom")
    j.close()
    path, n, torn = journal_to_trace(tmp_path, tmp_path / "trace.json")
    data = json.loads(path.read_text())
    evs = data["traceEvents"]
    assert torn == 0 and n == len(evs)
    assert validate_trace_events(evs) == []
    complete = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(complete) == {"a.json", "b.json"}
    assert complete["a.json"]["cat"] == "config-completed"
    assert complete["b.json"]["cat"] == "config-failed"
    assert complete["b.json"]["args"]["error"] == "boom"


def test_journal_to_trace_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        journal_to_trace(tmp_path, tmp_path / "trace.json")


# ---------------------------------------------------------------------------
# metrics registry / Prometheus export
# ---------------------------------------------------------------------------


def test_metrics_registry_counters_and_gauges():
    m = MetricsRegistry()
    m.inc("requests", outcome="ok")
    m.inc("requests", 2, outcome="ok")
    m.inc("requests", outcome="err")
    m.set_gauge("depth", 7.5)
    assert m.get("requests", outcome="ok") == 3
    assert m.get("requests", outcome="err") == 1
    assert m.get("never-registered") == 0
    with pytest.raises(ValueError):
        m.inc("requests", -1, outcome="ok")
    with pytest.raises(ValueError):
        m.set_gauge("requests", 1)  # kind clash
    text = m.to_prometheus()
    assert 'dlbb_requests_total{outcome="ok"} 3' in text
    assert "# TYPE dlbb_depth gauge" in text
    assert "dlbb_depth 7.5" in text


def test_labeled_counter_backs_manifest_dict():
    m = MetricsRegistry()
    counts = m.labeled_counter("sweep_configs", "outcome",
                               initial=("measured", "failed"))
    counts["measured"] += 2
    counts["failed"] += 1
    assert dict(counts) == {"measured": 2, "failed": 1}
    # the SAME numbers are in the registry (one source of truth)
    assert m.get("sweep_configs", outcome="measured") == 2
    assert 'dlbb_sweep_configs_total{outcome="measured"} 2' \
        in m.to_prometheus()
    with pytest.raises(ValueError):
        counts["measured"] = 0  # counters never decrease


def test_prometheus_textfile_write(tmp_path):
    m = MetricsRegistry()
    m.inc("x")
    path = m.write_textfile(tmp_path / "metrics.prom")
    assert path.read_text().rstrip().endswith("dlbb_x_total 1")


# ---------------------------------------------------------------------------
# calibration diff gate (seeded fixtures; pinned EXIT_* contract)
# ---------------------------------------------------------------------------


def _fake_report(targets, tier="cpu-sim", version="cm1"):
    rows = []
    for name, (pred, meas) in sorted(targets.items()):
        rows.append({
            "target": name, "tier": tier, "cost_model_version": version,
            "predicted_us": pred, "measured_us": meas,
            "signed_rel_error": (meas - pred) / pred,
            "error_factor": max(meas, pred) / min(meas, pred),
            "reps": 5,
        })
    return {
        "schema": cal.CALIBRATION_SCHEMA, "tier": tier,
        "cost_model_version": version,
        "aggregate": cal.aggregate_errors(rows),
        "targets": rows, "skipped": [], "timestamp": 0.0,
    }


def _diff_rc(tmp_path, report, baseline, name="case"):
    from dlbb_tpu.cli import main

    base_dir = tmp_path / f"{name}_base"
    cal.save_calibration_baseline(baseline, base_dir)
    rep_path = tmp_path / f"{name}_report.json"
    rep_path.write_text(json.dumps(report))
    return main(["obs", "diff", "--report", str(rep_path),
                 "--calibration", str(base_dir)])


def test_obs_diff_clean_exit_zero(tmp_path):
    base = _fake_report({"t::a": (10.0, 100.0), "t::b": (5.0, 40.0)})
    cur = _fake_report({"t::a": (10.0, 120.0), "t::b": (5.0, 35.0)})
    assert _diff_rc(tmp_path, cur, base) == EXIT_CLEAN


def test_obs_diff_regression_exit_one(tmp_path):
    base = _fake_report({"t::a": (10.0, 100.0), "t::b": (5.0, 40.0)})
    # error factors blew up 10x across the board -> aggregate gate trips
    cur = _fake_report({"t::a": (10.0, 1000.0), "t::b": (5.0, 400.0)})
    assert _diff_rc(tmp_path, cur, base) == EXIT_FINDINGS


def test_obs_diff_missing_baseline_exit_one(tmp_path):
    cur = _fake_report({"t::a": (10.0, 100.0)})
    rep_path = tmp_path / "r.json"
    rep_path.write_text(json.dumps(cur))
    from dlbb_tpu.cli import main

    assert main(["obs", "diff", "--report", str(rep_path),
                 "--calibration", str(tmp_path / "nope")]) == EXIT_FINDINGS


def test_obs_diff_cost_model_skew_exit_one(tmp_path):
    base = _fake_report({"t::a": (10.0, 100.0)}, version="cm0")
    cur = _fake_report({"t::a": (10.0, 100.0)})
    assert _diff_rc(tmp_path, cur, base) == EXIT_FINDINGS


def test_obs_diff_crash_exit_two(tmp_path):
    from dlbb_tpu.cli import main

    # unreadable report -> the analyzer crashed, not "findings"
    assert main(["obs", "diff", "--report",
                 str(tmp_path / "missing.json")]) == EXIT_CRASH


def test_obs_diff_subset_joins_soundly(tmp_path):
    """A subset run (the obs_smoke stage) must diff against the JOINED
    target set — committed-only targets cannot fail it, new targets only
    warn."""
    base = _fake_report({
        "t::a": (10.0, 100.0), "t::b": (5.0, 40.0), "t::c": (2.0, 30.0),
    })
    cur = _fake_report({"t::a": (10.0, 110.0), "t::new": (1.0, 500.0)})
    assert _diff_rc(tmp_path, cur, base) == EXIT_CLEAN
    findings = cal.diff_calibration(cur, tmp_path / "case_base")
    assert {f.rule for f in findings} == {"uncalibrated-target"}
    assert all(f.severity == "warning" for f in findings)


def test_aggregate_errors_empty_and_signed():
    agg = cal.aggregate_errors([])
    assert agg["targets_measured"] == 0
    assert agg["geomean_error_factor"] is None
    rows = _fake_report({"t::a": (10.0, 5.0)})["targets"]
    agg = cal.aggregate_errors(rows)
    # UNDER-prediction carries its sign: measured half of predicted
    assert agg["median_signed_rel_error"] == pytest.approx(-0.5)
    assert agg["geomean_error_factor"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# summarize satellites (p999 + empty-series contract)
# ---------------------------------------------------------------------------


def test_summarize_p999_and_empty_contract():
    import numpy as np

    from dlbb_tpu.utils.metrics import SUMMARY_KEYS, summarize

    xs = np.random.default_rng(7).lognormal(size=4096).tolist()
    out = summarize(xs)
    assert set(out) == set(SUMMARY_KEYS)
    np.testing.assert_allclose(out["p999"], np.percentile(xs, 99.9),
                               rtol=1e-12)
    empty = summarize([])
    assert set(empty) == set(SUMMARY_KEYS)
    assert empty["count"] == 0
    assert all(np.isnan(v) for k, v in empty.items() if k != "count")
    # downstream stats consumers index these keys on quarantined-empty
    # series — they must exist (no KeyError), never a bare {}
    assert empty["median"] != empty["median"]  # NaN


# ---------------------------------------------------------------------------
# obs_smoke gate: traced sweep equivalence + calibration round trip
# ---------------------------------------------------------------------------

_VOLATILE = {
    # timing fields + everything derived from them or from the run moment
    "timings", "timestamp", "compile_seconds", "compile_cache_hit",
    "forced_completion_s", "forced_completion_probe_skipped",
    "system_info", "device_trace",
}


def _tiny_sweep(tmp_path, out, **kw):
    from dlbb_tpu.bench import Sweep1D

    return Sweep1D(
        operations=("allreduce", "allgather"),
        data_sizes=(("1KB", 256),),
        rank_counts=(4,),
        warmup_iterations=2,
        measurement_iterations=8,
        output_dir=str(tmp_path / out),
        pipeline=False,
        compile_cache="off",
        **kw,
    )


@pytest.mark.obs_smoke
def test_traced_sweep_equivalent_to_untraced(tmp_path, devices):
    """The acceptance gate: span tracing + device capture ON must emit a
    Perfetto-loadable trace AND publish stats equivalent to an untraced
    serial run (same proof style as the PR-3 serial-vs-pipelined gate);
    the dedicated profile reps never enter the stats series."""
    from dlbb_tpu.bench import run_sweep
    from dlbb_tpu.obs.capture import xplane_files

    trace_path = tmp_path / "spans.json"
    dev_dir = tmp_path / "dev"
    ft = run_sweep(_tiny_sweep(tmp_path, "traced",
                               span_trace=str(trace_path),
                               device_trace_dir=str(dev_dir)),
                   verbose=False)
    fu = run_sweep(_tiny_sweep(tmp_path, "untraced"), verbose=False)
    assert [p.name for p in ft] == [p.name for p in fu]
    for pt, pu in zip(ft, fu):
        dt, du = json.loads(pt.read_text()), json.loads(pu.read_text())
        # identical schema modulo the capture metadata...
        assert sorted(set(dt) - {"device_trace"}) == sorted(du)
        # ...identical non-timing content...
        for k in sorted(set(dt) & set(du) - _VOLATILE):
            assert dt[k] == du[k], k
        # ...and the stats series is exactly the configured length on
        # BOTH sides: profile reps never joined it
        for d in (dt, du):
            assert d["measurement_iterations"] == 8
            assert all(len(row) == 8 for row in d["timings"])
        assert dt["device_trace"]["excluded_from_stats"] is True

    # the span trace is valid Perfetto-loadable trace-event JSON with
    # the whole phase taxonomy present
    trace = json.loads(trace_path.read_text())
    evs = trace["traceEvents"]
    assert validate_trace_events(evs) == []
    cats = {e.get("cat") for e in evs}
    assert {"sweep", "compile", "measure", "payload", "io", "capture",
            "journal"} <= cats
    # device capture produced real xplane traces, one dir per config
    assert xplane_files(dev_dir)
    manifest = json.loads(
        (tmp_path / "traced" / "sweep_manifest.json").read_text())
    assert manifest["observability"]["device_captures"] == 2
    assert manifest["observability"]["span_trace"] == str(trace_path)
    untraced_manifest = json.loads(
        (tmp_path / "untraced" / "sweep_manifest.json").read_text())
    assert untraced_manifest["observability"]["span_trace"] is None
    assert untraced_manifest["observability"]["device_captures"] == 0


@pytest.mark.obs_smoke
def test_obs_calibrate_and_diff_roundtrip(tmp_path, devices):
    """``obs calibrate`` on a micro-op subset produces a signed-error
    report + manifest aggregate, and ``obs diff`` round-trips against a
    same-process baseline (clean) and catches a seeded regression.

    The diff against the COMMITTED sim-tier baseline deliberately lives
    in ``scripts/run_static_analysis.sh`` (a fresh ``cli obs diff``
    process), not here: measured medians inside the fully-loaded tier-1
    pytest process run several-x hotter than any fresh-process baseline,
    which is host-load noise, not cost-model drift — exactly what the
    gate must not fire on."""
    from dlbb_tpu.cli import main

    out = tmp_path / "cal"
    rc = main(["obs", "calibrate", "--output", str(out),
               "--targets", "::allgather", "::alltoall", "::barrier",
               "--reps", "15", "--warmup", "5"])
    assert rc == EXIT_CLEAN
    report = json.loads((out / cal.REPORT_NAME).read_text())
    assert report["tier"] == "cpu-sim"
    assert report["cost_model_version"] == "cm1"
    measured = {r["target"] for r in report["targets"]}
    assert measured == {"comm/ops.py::allgather", "comm/ops.py::alltoall",
                        "comm/ops.py::barrier"}
    for r in report["targets"]:
        assert r["predicted_us"] > 0 and r["measured_us"] > 0
        assert r["error_factor"] >= 1.0
        # signed error and factor must agree on direction
        assert (r["signed_rel_error"] >= 0) == (
            r["measured_us"] >= r["predicted_us"])
        # the prediction must match the committed schedule baseline the
        # calibration claims to join against
        committed = json.loads(
            (REPO / "stats" / "analysis" / "baselines" /
             f"comm_ops.py_{r['target'].rsplit(':', 1)[-1]}.json")
            .read_text())
        assert r["predicted_us"] == committed["critical_path_us"]
    agg = report["aggregate"]
    assert agg["targets_measured"] == 3
    assert agg["geomean_error_factor"] >= 1.0
    # the aggregate also landed in the manifest (acceptance criterion)
    manifest = json.loads((out / "sweep_manifest.json").read_text())
    assert manifest["calibration"]["geomean_error_factor"] == \
        agg["geomean_error_factor"]
    assert (out / cal.CSV_NAME).read_text().startswith("target,")

    # self-baseline diff: clean by construction
    base_dir = tmp_path / "base"
    cal.save_calibration_baseline(report, base_dir)
    rc = main(["obs", "diff", "--report", str(out / cal.REPORT_NAME),
               "--calibration", str(base_dir)])
    assert rc == EXIT_CLEAN
    # seeded regression on the REAL measured data: a baseline whose
    # errors were 100x smaller means this run's model got 100x worse
    shrunk = json.loads(json.dumps(report))
    for row in shrunk["targets"]:
        row["measured_us"] = row["predicted_us"] * (
            1 + (row["measured_us"] / row["predicted_us"] - 1) / 100)
        row["error_factor"] = max(row["measured_us"], row["predicted_us"]) \
            / min(row["measured_us"], row["predicted_us"])
    cal.save_calibration_baseline(shrunk, tmp_path / "shrunk")
    rc = main(["obs", "diff", "--report", str(out / cal.REPORT_NAME),
               "--calibration", str(tmp_path / "shrunk")])
    assert rc == EXIT_FINDINGS
