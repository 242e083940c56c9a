"""Device-trace analysis tests (``dlbb_tpu/obs/devtrace.py``).

Unit surface: op-kind bucket classification, warmup-window exclusion,
the fail-closed contract (missing/truncated/empty captures are explicit
findings, never silent empty reports), the static-vs-measured overlap
gate (a seeded serialized-ring fixture on a demonstrably-concurrent
runtime exits 1 with ``runtime-serialized-collective``; a single-stream
runtime downgrades to a warning), the corpus op-sample extraction, and
a β-identified fit on a synthetic device-op corpus recovering known
coefficients.

The ``devtrace_smoke`` marker test drives the whole pipeline through a
real captured mini-sweep on the simulated mesh: captured stats stay
equivalent to an uncaptured run, ``obs devtrace`` is green, and the
report lists measured overlap efficiency beside the committed static
value for the overlap-proof target.
"""

import gzip
import json
from pathlib import Path

import pytest

from dlbb_tpu.analysis.findings import EXIT_CLEAN, EXIT_FINDINGS
from dlbb_tpu.obs.devtrace import (
    CaptureError,
    analyze_capture,
    analyze_run,
    audit_target_name,
    bucket_of,
    parse_capture,
    run_devtrace,
)

REPO = Path(__file__).resolve().parent.parent
GOLDEN = REPO / "tests" / "data" / "golden_capture"
BASELINES = REPO / "stats" / "analysis" / "baselines"


def _dev(name, ts, dur, tid=1, pid=7):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": float(ts),
            "dur": float(dur), "name": name,
            "args": {"hlo_module": "jit_f", "hlo_op": name}}


def _annot(name, ts, dur, tid=99, pid=7):
    short = name.rsplit(":", 1)[-1]
    return {"ph": "X", "pid": pid, "tid": tid, "ts": float(ts),
            "dur": float(dur), "name": short,
            "args": {"long_name": name}}


def _write_capture(directory: Path, events) -> Path:
    d = directory / "plugins" / "profile" / "run"
    d.mkdir(parents=True, exist_ok=True)
    path = d / "perfetto_trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"displayTimeUnit": "ms", "traceEvents": events}, f)
    return path


def _result_json(tmp_path: Path, trace_dir: Path, *,
                 op="ag_matmul", variant="overlap_ring",
                 name="xla_tpu_fixture.json") -> Path:
    data = {
        "implementation": "xla_tpu",
        "operation": op,
        "variant": variant,
        "num_ranks": 8,
        "num_elements": 4096,
        "dtype": "float32",
        "timings": [[0.001, 0.001]],
        "timing_mode": "per_iter",
        "system_info": {"backend": "cpu", "platform": "linux",
                        "cpu_count": 2, "num_devices": 8},
        "device_trace": {
            "schema": "dlbb_device_capture_v1",
            "label": name.rsplit(".", 1)[0],
            "trace_dir": str(trace_dir),
            "profile_reps": 1,
            "excluded_from_stats": True,
        },
    }
    path = tmp_path / name
    path.write_text(json.dumps(data))
    return path


# ---------------------------------------------------------------------------
# bucket classification
# ---------------------------------------------------------------------------


def test_bucket_classification():
    assert bucket_of("all-reduce.2") == "collective"
    assert bucket_of("all-gather-start.1") == "collective"
    assert bucket_of("reduce-scatter.7") == "collective"
    assert bucket_of("all-to-all") == "collective"
    assert bucket_of("collective-permute.21") == "permute"
    assert bucket_of("collective-permute-done.3") == "permute"
    assert bucket_of("dot.39") == "dot"
    assert bucket_of("convolution.1") == "dot"
    assert bucket_of("broadcast_multiply_fusion") == "fusion"
    assert bucket_of("convert_bitcast_fusion.5.clone") == "fusion"
    assert bucket_of("convert.12") == "other"
    assert bucket_of("partition-id.7") == "other"


def test_audit_target_name_matches_committed_baselines():
    """The (op, variant) -> audit-target mapping must produce names the
    committed schedule baselines actually use — the static join breaks
    silently otherwise."""
    from dlbb_tpu.analysis.schedule_audit import baseline_path

    for op, variant in (("allreduce", "default"),
                        ("allgather", "default"),
                        ("ag_matmul", "overlap_ring"),
                        ("ag_matmul", "overlap_bidir"),
                        ("matmul_rs", "overlap_ring"),
                        ("allreduce_q", "compress_int8"),
                        ("reducescatter_q", "compress_fp8")):
        target = audit_target_name(op, variant)
        assert baseline_path(BASELINES, target).exists(), (op, variant,
                                                          target)


# ---------------------------------------------------------------------------
# parsing: golden capture, warmup exclusion, fail-closed
# ---------------------------------------------------------------------------


def test_parse_golden_capture():
    """The committed golden capture (a real sim-mesh allreduce capture,
    host noise stripped) parses into 8 devices x one all-reduce each,
    keyed by the HLO instruction name."""
    from dlbb_tpu.obs.capture import perfetto_trace_files

    trace = perfetto_trace_files(GOLDEN / "trace")
    assert trace, "golden capture fixture missing"
    timeline = parse_capture(trace[0])
    assert len(timeline["devices"]) == 8
    analysis = analyze_capture(timeline)
    by_name = {r["name"]: r for r in analysis["per_op"]}
    assert by_name["all-reduce.2"]["count"] == 8
    assert by_name["all-reduce.2"]["bucket"] == "collective"
    assert analysis["comm_events"] == 8
    assert analysis["buckets_us"]["collective"] > 0
    # the join key is the HLO instruction name — exactly what the
    # hlo_audit inventory records per instruction
    assert all("." in n or "fusion" in n or n.isidentifier()
               for n in by_name)


def test_warmup_exclusion(tmp_path):
    """Device events inside a ``warmup`` annotation window are dropped;
    with ``measure``/``profile_rep`` windows present, only in-window
    events are kept."""
    events = [
        _annot("warmup", 0, 100),
        _annot("measure", 200, 100),
        _dev("all-reduce.1", 10, 50, tid=1),    # inside warmup: dropped
        _dev("all-reduce.1", 220, 50, tid=1),   # inside measure: kept
        _dev("all-reduce.1", 400, 50, tid=1),   # outside both: dropped
    ]
    path = _write_capture(tmp_path, events)
    timeline = parse_capture(path)
    assert timeline["device_events"] == 1
    assert timeline["excluded_warmup"] == 2
    analysis = analyze_capture(timeline)
    assert analysis["comm_events"] == 1
    assert analysis["comm_total_us"] == 50.0


def test_profile_rep_window_selects(tmp_path):
    events = [
        _annot("profile_rep:cfg", 100, 200),
        _dev("all-gather.1", 150, 20),
        _dev("all-gather.1", 500, 20),  # outside the rep window
    ]
    timeline = parse_capture(_write_capture(tmp_path, events))
    assert timeline["device_events"] == 1


def test_container_thunks_not_double_counted(tmp_path):
    """``call`` wraps a computation whose fusions appear as their own
    events — counting both would double-charge the fusion bucket."""
    events = [
        _dev("call.3", 0, 100),
        _dev("convert_fusion.1", 1, 98),
        _dev("all-reduce.1", 200, 10),
    ]
    analysis = analyze_capture(parse_capture(_write_capture(tmp_path,
                                                            events)))
    assert analysis["buckets_us"]["fusion"] == 98.0
    assert all(r["name"] != "call.3" for r in analysis["per_op"])


def test_async_pair_counts_one_collective_done_never_serialized(tmp_path):
    """An async collective lowers to a ``-start``/``-done`` pair: the
    wait time charges the collective bucket, but the pair is ONE
    logical instruction (α's analytic convention) and the often
    zero-length ``-done`` must not classify as a serialized hop."""
    from dlbb_tpu.obs.devtrace import device_comm_samples

    events = [
        _dev("all-gather-start.1", 0, 100),
        _dev("all-gather-done.1", 100, 0),
        _dev("dot.1", 10, 50),
    ]
    timeline = parse_capture(_write_capture(tmp_path, events))
    analysis = analyze_capture(timeline)
    assert analysis["comm_total_us"] == 100.0  # both halves' time
    assert analysis["comm_events"] == 1  # one logical hop
    assert analysis["comm_serialized_events"] == 0
    assert analysis["comm_straddled_events"] == 1
    comm = device_comm_samples(timeline)
    assert comm["comm_instructions"] == 1


def test_capture_resolves_from_foreign_cwd(tmp_path):
    """Relative ``trace_dir`` records from a run launched in another
    cwd resolve through the run directory's capture subdir."""
    label = "xla_tpu_fixture"
    _write_capture(tmp_path / "captures" / label,
                   [_dev("all-gather.1", 0, 10)])
    _result_json(tmp_path,
                 Path("who/knows/where") / "captures" / label)
    report, findings = analyze_run(tmp_path, BASELINES)
    assert not any(f.rule in ("capture-missing", "no-captures")
                   for f in findings)
    assert report["captures"][0]["device_events"] == 1


def test_missing_capture_fail_closed(tmp_path):
    with pytest.raises(CaptureError):
        parse_capture(tmp_path / "nope.json.gz")


def test_truncated_capture_fail_closed(tmp_path):
    path = tmp_path / "perfetto_trace.json.gz"
    good = gzip.compress(json.dumps(
        {"traceEvents": [_dev("all-reduce.1", 0, 1)]}).encode())
    path.write_bytes(good[: len(good) // 2])  # torn mid-write
    with pytest.raises(CaptureError, match="truncated|unparseable"):
        parse_capture(path)


def test_empty_capture_fail_closed(tmp_path):
    path = tmp_path / "perfetto_trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "/host:CPU"}},
        ]}, f)
    with pytest.raises(CaptureError, match="no device events"):
        parse_capture(path)


def test_run_with_no_captures_is_error(tmp_path):
    (tmp_path / "unrelated.json").write_text("{}")
    report, findings = analyze_run(tmp_path, BASELINES)
    assert [f.rule for f in findings] == ["no-captures"]
    assert findings[0].severity == "error"
    assert report["captures"] == []


def test_recorded_capture_missing_on_disk_is_error(tmp_path):
    _result_json(tmp_path, tmp_path / "deleted_dir")
    _report, findings = analyze_run(tmp_path, BASELINES)
    rules = {f.rule for f in findings}
    assert "capture-missing" in rules
    # no parseable capture at all -> the run-level fail-closed finding
    assert "no-captures" in rules


def test_run_time_contained_failure_surfaces_as_warning(tmp_path):
    path = _result_json(tmp_path, tmp_path / "dev")
    data = json.loads(path.read_text())
    data["device_trace"]["error"] = "RuntimeError: profiler held"
    data["device_trace"]["error_kind"] = "RuntimeError"
    path.write_text(json.dumps(data))
    _report, findings = analyze_run(tmp_path, BASELINES)
    by_rule = {f.rule: f for f in findings}
    assert by_rule["capture-failed"].severity == "warning"


# ---------------------------------------------------------------------------
# the static-vs-measured overlap gate
# ---------------------------------------------------------------------------


def _ring_events(*, concurrent: bool):
    """Eight serialized ring-hop permutes on device lane 1 (no
    straddling compute there), plus compute on lane 2 — overlapping
    (proving the runtime CAN run thunks concurrently) or sequential
    (single-stream)."""
    events = [_dev(f"collective-permute.{i}", i * 100, 90, tid=1)
              for i in range(8)]
    if concurrent:
        events += [_dev("dot_fusion.1", 0, 60, tid=2),
                   _dev("dot_fusion.2", 30, 60, tid=2)]
    else:
        events += [_dev("dot_fusion.1", 0, 30, tid=2),
                   _dev("dot_fusion.2", 40, 30, tid=2)]
    return events


def test_serialized_ring_on_concurrent_runtime_exits_one(tmp_path):
    """THE acceptance fixture: the committed static baseline proves the
    ring hidden (overlap_efficiency 0.87), the measured timeline shows
    every hop serialized, and the capture demonstrates the runtime can
    overlap — a ``runtime-serialized-collective`` ERROR, exit 1."""
    from dlbb_tpu.obs import run_obs

    cap_dir = tmp_path / "cap"
    _write_capture(cap_dir, _ring_events(concurrent=True))
    _result_json(tmp_path, cap_dir)
    report, findings = analyze_run(tmp_path, BASELINES)
    f = next(f for f in findings
             if f.rule == "runtime-serialized-collective")
    assert f.severity == "error"
    assert f.details["static_overlap_efficiency"] > 0
    assert f.details["serialized_events"] == 8
    assert f.details["runtime_concurrent"] is True
    # measured sits beside static in the report row
    row = next(c for c in report["captures"] if "error" not in c)
    assert row["static"]["overlap_efficiency"] > 0
    assert row["measured_overlap_efficiency"] == 0.0
    rc = run_obs("devtrace", journal=str(tmp_path),
                 output=str(tmp_path / "out"),
                 baselines=str(BASELINES), verbose=False)
    assert rc == EXIT_FINDINGS


def test_serialized_ring_on_single_stream_runtime_warns(tmp_path):
    """The cpu-sim reality: no thunk concurrency anywhere in the
    capture means hop hiding is unobservable, not disproved — the gate
    downgrades to a warning and CI stays green."""
    from dlbb_tpu.obs import run_obs

    cap_dir = tmp_path / "cap"
    _write_capture(cap_dir, _ring_events(concurrent=False))
    _result_json(tmp_path, cap_dir)
    _report, findings = analyze_run(tmp_path, BASELINES)
    f = next(f for f in findings
             if f.rule == "runtime-serialized-collective")
    assert f.severity == "warning"
    rc = run_obs("devtrace", journal=str(tmp_path),
                 output=str(tmp_path / "out"),
                 baselines=str(BASELINES), verbose=False)
    assert rc == EXIT_CLEAN


def test_hidden_ring_passes_gate(tmp_path):
    """Hops with straddling compute occupancy on their own device do
    NOT trip the gate, and measured overlap efficiency is positive."""
    events = []
    for i in range(4):
        events.append(_dev(f"collective-permute.{i}", i * 100, 80,
                           tid=1))
        events.append(_dev(f"dot_fusion.{i}", i * 100 + 10, 60, tid=1))
    cap_dir = tmp_path / "cap"
    _write_capture(cap_dir, events)
    _result_json(tmp_path, cap_dir)
    report, findings = analyze_run(tmp_path, BASELINES)
    assert not [f for f in findings
                if f.rule == "runtime-serialized-collective"]
    row = next(c for c in report["captures"] if "error" not in c)
    assert row["measured_overlap_efficiency"] > 0.5
    assert row["runtime_concurrent"] is True


def test_qring_exempt_from_gate(tmp_path):
    """Quantised-ring ops are deliberately sequential — exempt exactly
    as in the static auditor."""
    cap_dir = tmp_path / "cap"
    _write_capture(cap_dir, _ring_events(concurrent=True))
    _result_json(tmp_path, cap_dir, op="allreduce_q",
                 variant="compress_int8")
    _report, findings = analyze_run(tmp_path, BASELINES)
    assert not [f for f in findings
                if f.rule == "runtime-serialized-collective"]


# ---------------------------------------------------------------------------
# corpus op-sample extraction + β-identified fit
# ---------------------------------------------------------------------------


def test_golden_capture_op_sample_extraction(tmp_path):
    """devtrace on the committed golden capture emits a corpus fit row
    (device-timed: dispatches 0, flops 0, analytic wire joined from the
    artifact), and ``build_corpus`` ingests the written report as the
    ``devtrace`` source."""
    from dlbb_tpu.obs.corpus import build_corpus

    report, findings = run_devtrace(GOLDEN, out_dir=tmp_path,
                                    baselines_dir=BASELINES,
                                    verbose=False)
    assert not [f for f in findings if f.severity == "error"]
    assert len(report["op_samples"]) == 1
    s = report["op_samples"][0]
    assert s["op"] == "allreduce"
    assert s["source"] == "devtrace"
    assert s["dispatches"] == 0.0
    assert s["flops"] == 0
    # analytic ring wire of a 256-elem f32 allreduce on 8 ranks
    assert s["wire_bytes"] == 896
    assert s["collectives"] == 1.0
    assert s["measured_median_us"] > 0
    corpus = build_corpus([tmp_path / "golden_capture.json"])
    assert len(corpus["samples"]) == 1
    assert corpus["samples"][0]["source"] == "devtrace"
    assert corpus["samples"][0]["tier"] == "cpu-sim"


def test_fit_identifies_beta_from_device_samples():
    """A synthetic device-op corpus generated from known coefficients
    (α = 300 µs, β = 500 B/µs) is recovered by ``fit_tier`` with β
    FITTED (confidence interval recorded, no ``pinned`` marker) — the
    identification program-scale samples alone cannot do."""
    from dlbb_tpu.obs.fit import fit_tier

    alpha, beta = 300.0, 500.0
    samples = []
    for i, wire in enumerate((1e3, 4e3, 1.6e4, 6.4e4, 2.56e5, 1.024e6,
                              4.096e6, 1.6384e7, 6.5536e7)):
        for colls in (1.0, 7.0):
            samples.append({
                "file": f"synth{i}", "source": "devtrace",
                "op": "allreduce", "variant": "default",
                "kind": "all-reduce", "ranks": 8, "dtype": "float32",
                "num_elements": int(wire // 4),
                "wire_bytes": int(wire), "flops": 0,
                "collectives": colls, "dispatches": 0.0,
                "measured_median_us": alpha * colls + wire / beta,
                "measured_p90_us": alpha * colls + wire / beta,
                "measured_p99_us": None, "iterations": 1,
                "tier": "cpu-sim", "host": "synth",
            })
    fit = fit_tier(samples, "cpu-sim")
    c = fit["coefficients"]
    assert c["beta_bytes_per_us"]["value"] == pytest.approx(beta,
                                                            rel=0.05)
    assert "pinned" not in c["beta_bytes_per_us"]
    assert "ci95" in c["beta_bytes_per_us"]
    assert c["alpha_us"]["value"] == pytest.approx(alpha, rel=0.05)
    assert fit["device_samples"] == len(samples)


def test_fit_host_filter_exempts_device_samples():
    """``host_filter`` isolates the host-runtime dispatch term; device
    rows carry none and must survive the filter (they are what
    identifies β)."""
    from dlbb_tpu.obs.fit import fit_tier

    device = []
    for i, wire in enumerate((1e3, 1e4, 1e5, 1e6, 4e6, 1.6e7)):
        device.append({
            "file": f"d{i}", "source": "devtrace", "op": "allgather",
            "variant": "default", "kind": "all-gather", "ranks": 8,
            "dtype": "float32", "num_elements": int(wire // 4),
            "wire_bytes": int(wire), "flops": 0, "collectives": 1.0,
            "dispatches": 0.0,
            "measured_median_us": 100.0 + wire / 200.0,
            "measured_p90_us": 100.0 + wire / 200.0,
            "measured_p99_us": None, "iterations": 1,
            "tier": "cpu-sim", "host": "laptop",
        })
    host = []
    for i in range(12):
        wire = 1e4 * (i + 1)
        host.append({
            "file": f"h{i}", "op": f"prog{i}", "variant": "calibration",
            "kind": "program", "ranks": 8, "dtype": None,
            "num_elements": 0, "wire_bytes": int(wire), "flops": 0,
            "collectives": 2.0 + (i % 3), "dispatches": 1.0,
            "measured_median_us": 98.5 + 100.0 * (2.0 + (i % 3))
            + wire / 200.0,
            "measured_p90_us": 0.0, "measured_p99_us": None,
            "iterations": 1, "tier": "cpu-sim", "host": "calibration",
        })
    fit = fit_tier(device + host, "cpu-sim", min_samples=12,
                   host_filter="calibration")
    # the device rows were NOT filtered out: β is fitted, not pinned
    assert fit["device_samples"] == len(device)
    assert "pinned" not in fit["coefficients"]["beta_bytes_per_us"]
    assert fit["coefficients"]["beta_bytes_per_us"]["value"] == \
        pytest.approx(200.0, rel=0.1)


# ---------------------------------------------------------------------------
# serving rows + journal instants
# ---------------------------------------------------------------------------


def test_serving_capture_phase_rows(tmp_path):
    """Serving capture metas (report ``observability.device_captures``)
    parse into per-phase rows."""
    cap = tmp_path / "cap_decode"
    _write_capture(cap, [_dev("all-reduce.1", 0, 10),
                         _dev("loop_fusion.1", 20, 40)])
    report = {
        "schema": "dlbb_serving_report_v1",
        "observability": {"device_captures": [{
            "schema": "dlbb_device_capture_v1",
            "label": "serve_decode_fused_k2",
            "trace_dir": str(cap), "profile_reps": 1,
            "excluded_from_stats": True, "phase": "decode",
        }]},
    }
    (tmp_path / "serving_test.json").write_text(json.dumps(report))
    out, findings = analyze_run(tmp_path, BASELINES)
    assert not [f for f in findings if f.severity == "error"]
    row = out["captures"][0]
    assert row["kind"] == "serving"
    assert row["phase"] == "decode"
    assert row["buckets_us"]["fusion"] == 40.0


def test_journal_topology_event_renders_plain_instant(tmp_path):
    """Journal events with no renderer of their own (``topology``, which
    every sweep and serving run journals first) become thread-scoped
    ``journal`` instants carrying their fields — and the config pairing
    around them still works."""
    from dlbb_tpu.obs.spans import journal_to_trace

    journal = tmp_path / "sweep_journal.jsonl"
    records = [
        {"ts": 1.0, "event": "sweep-start"},
        {"ts": 1.5, "event": "topology", "platform": "tpu",
         "num_devices": 4, "simulated": False},
        {"ts": 2.0, "event": "started", "config": "cfg_a.json"},
        {"ts": 3.0, "event": "completed", "config": "cfg_a.json"},
    ]
    journal.write_text("".join(json.dumps(r) + "\n" for r in records))
    out, _n, torn = journal_to_trace(tmp_path, tmp_path / "trace.json")
    assert torn == 0
    events = json.loads(out.read_text())["traceEvents"]
    topo = [e for e in events if e["name"] == "topology"]
    assert len(topo) == 1
    assert topo[0]["cat"] == "journal"
    assert topo[0]["ph"] == "i" and topo[0]["s"] == "t"
    assert topo[0]["args"]["num_devices"] == 4
    # the started -> completed pairing still yields the config X span
    spans = [e for e in events if e.get("ph") == "X"]
    assert any(e["name"] == "cfg_a.json" for e in spans)


# ---------------------------------------------------------------------------
# devtrace_smoke: the real captured pipeline on the simulated mesh
# ---------------------------------------------------------------------------

_VOLATILE = {
    "timings", "timestamp", "compile_seconds", "compile_cache_hit",
    "forced_completion_s", "forced_completion_probe_skipped",
    "system_info", "device_trace",
    # load-dependent branches in utils/timing.py record different
    # metadata KEYS run to run (the >=50ms probe-skip threshold, the
    # implausible-timing chained fallback, the time-budget clamp) —
    # volatile for the same reason the timings themselves are
    "per_iter_sanity_failed", "per_iter_median_s",
    "measurement_iterations", "warmup_iterations",
    "time_budget_s", "time_budget_clamped",
}


@pytest.mark.devtrace_smoke
def test_captured_sweep_devtrace_green_and_stats_equivalent(tmp_path,
                                                            devices):
    """The CI gate: a device-captured overlap-variant mini-sweep stays
    stats-equivalent to an uncaptured run, ``obs devtrace`` on it is
    green (exit 0 — the cpu-sim single-stream downgrade), the report
    lists measured overlap efficiency beside the committed static value
    for the overlap-proof target, and the op-level fit samples are
    mined."""
    from dlbb_tpu.bench import Sweep3D, run_sweep
    from dlbb_tpu.obs import run_obs

    def sweep(out, **kw):
        return Sweep3D(
            operations=("ag_matmul",), variant="overlap_ring",
            batch_sizes=(4,), seq_lengths=(32,), hidden_dims=(64,),
            rank_counts=(8,), warmup_iterations=1,
            measurement_iterations=4, output_dir=str(tmp_path / out),
            pipeline=False, compile_cache="off", **kw,
        )

    fc = run_sweep(sweep("captured",
                         device_trace_dir=str(tmp_path / "dev")),
                   verbose=False)
    fu = run_sweep(sweep("uncaptured"), verbose=False)
    assert [p.name for p in fc] == [p.name for p in fu]
    for pc, pu in zip(fc, fu):
        dc, du = json.loads(pc.read_text()), json.loads(pu.read_text())
        assert "device_trace" in dc and "device_trace" not in du
        assert sorted(set(dc) - _VOLATILE) == sorted(set(du) - _VOLATILE)
        for k in sorted(set(dc) & set(du) - _VOLATILE):
            assert dc[k] == du[k], k
        assert dc["device_trace"]["excluded_from_stats"] is True
        # the parseable artifact the devtrace parser keys on, with the
        # xplane kept alongside and the capture cost accounted
        assert Path(dc["device_trace"]["perfetto_trace"]).exists()
        assert dc["device_trace"]["trace_bytes"] > 0
        assert dc["device_trace"]["wall_seconds"] > 0
    from dlbb_tpu.obs.capture import xplane_files

    assert xplane_files(tmp_path / "dev")

    rc = run_obs("devtrace", journal=str(tmp_path / "captured"),
                 output=str(tmp_path / "report"),
                 baselines=str(BASELINES), verbose=False)
    assert rc == EXIT_CLEAN
    report = json.loads((tmp_path / "report" / "captured.json")
                        .read_text())
    row = next(c for c in report["captures"] if "error" not in c)
    # measured overlap listed beside the committed static value for the
    # overlap-proof target (the acceptance criterion)
    assert row["static"]["target"] == "comm/ops.py::ag_matmul[ring]"
    assert row["static"]["overlap_efficiency"] > 0
    assert row["measured_overlap_efficiency"] is not None
    assert report["op_samples"], "op-level fit samples were mined"
    # the MD report renders both columns
    md = (tmp_path / "report" / "captured.md").read_text()
    assert "measured overlap" in md and "static overlap" in md
