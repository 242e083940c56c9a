"""Bench harness + stats pipeline integration tests on the simulated mesh.

The reference's benchmark scripts double as integration tests (SURVEY §4);
here a miniature sweep runs end-to-end — payload → timed collective → JSON —
and the stats pipeline consumes the artifacts, mirroring the
results/ → stats/ flow of the reference.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from dlbb_tpu.bench import Sweep1D, Sweep3D, run_sweep
from dlbb_tpu.stats import process_1d_results, process_3d_results


def _tiny_1d(tmp_path, **kw):
    defaults = dict(
        implementation="xla_test",
        operations=("allreduce", "broadcast", "sendrecv"),
        data_sizes=(("1KB", 256), ("64KB", 16384)),
        rank_counts=(2, 4, 16),  # 16 must be skipped (only 8 devices)
        dtype="float32",
        warmup_iterations=1,
        measurement_iterations=3,
        output_dir=str(tmp_path / "results"),
    )
    defaults.update(kw)
    return Sweep1D(**defaults)


def test_sweep_1d_writes_reference_schema(tmp_path, devices):
    files = run_sweep(_tiny_1d(tmp_path), verbose=False)
    # 3 ops x 2 sizes x 2 feasible rank counts
    assert len(files) == 12
    data = json.loads(files[0].read_text())
    for key in (
        "implementation", "operation", "num_ranks", "data_size_name",
        "num_elements", "dtype", "warmup_iterations",
        "measurement_iterations", "timings",
    ):
        assert key in data, key
    assert data["num_ranks"] in (2, 4)
    timings = np.asarray(data["timings"])
    assert timings.ndim == 2 and timings.shape[1] == 3
    assert (timings > 0).all()


def test_sweep_1d_resume_skips_existing(tmp_path, devices):
    """resume=True picks an interrupted sweep back up: configs whose artifact
    already exists are not re-measured (their files are untouched), missing
    ones still run, and the returned list covers the full grid either way."""
    sweep = _tiny_1d(tmp_path)
    first = run_sweep(sweep, verbose=False)
    assert len(first) == 12
    # delete two artifacts to simulate an interruption mid-grid
    removed = {first[3], first[7]}
    for p in removed:
        p.unlink()
    mtimes = {p: p.stat().st_mtime_ns for p in first if p not in removed}
    resumed = run_sweep(_tiny_1d(tmp_path, resume=True), verbose=False)
    assert sorted(resumed) == sorted(first)
    for p, t in mtimes.items():
        assert p.stat().st_mtime_ns == t, f"{p.name} was re-measured"
    for p in removed:
        assert p.exists(), f"{p.name} was not re-run"


def test_sweep_1d_rank_gate(tmp_path, devices):
    files = run_sweep(_tiny_1d(tmp_path, rank_counts=(16,)), verbose=False)
    assert files == []  # all configs infeasible on 8 devices


def test_sweep_1d_hierarchical_variant(tmp_path, devices):
    sweep = _tiny_1d(
        tmp_path,
        variant="hier2x2x2",
        operations=("allreduce",),
        rank_counts=(8,),
    )
    files = run_sweep(sweep, verbose=False)
    assert len(files) == 2
    data = json.loads(files[0].read_text())
    assert data["implementation"] == "xla_test_hier2x2x2"
    assert data["mesh_shape"] == [2, 2, 2]


def test_sweep_1d_time_budget_clamps_iterations(tmp_path, devices):
    """max_config_seconds scales iteration counts down and records the
    actual counts — artifacts never overstate the sample size."""
    sweep = _tiny_1d(
        tmp_path, operations=("allreduce",), data_sizes=(("1MB", 262144),),
        rank_counts=(8,), measurement_iterations=10_000,
        max_config_seconds=0.05,
    )
    files = run_sweep(sweep, verbose=False)
    assert len(files) == 1
    data = json.loads(files[0].read_text())
    assert data["time_budget_clamped"] is True
    assert data["measurement_iterations"] < 10_000
    assert data["measurement_iterations"] == len(data["timings"][0])
    assert data["time_budget_s"] == 0.05


def test_sweep_1d_nofuse_variant(tmp_path, devices):
    """The fusion-off variant (combiner HLO passes disabled via
    per-computation compiler options) executes and is labeled."""
    sweep = _tiny_1d(
        tmp_path, variant="nofuse", operations=("allreduce",),
        data_sizes=(("1KB", 256),), rank_counts=(8,),
    )
    files = run_sweep(sweep, verbose=False)
    assert len(files) == 1
    data = json.loads(files[0].read_text())
    assert data["implementation"] == "xla_test_nofuse"


def test_estimate_global_bytes_pinned_per_op():
    """The memory-cap estimator derives its input AND output multipliers
    from the op registry's declared buffer kinds (per_rank -> P,
    per_peer -> P^2) — pinned here for every registered op so a registry
    change that alters an estimate is a visible diff, and a new op can
    never silently fall back to a hard-coded name list's default.

    (For the pre-registry hard-coded list the per_rank-output ops —
    sendrecv/broadcast included — all multiply by exactly P; the pins
    freeze that contract.)"""
    from dlbb_tpu.bench.runner import _estimate_global_bytes
    from dlbb_tpu.comm.ops import OPERATIONS

    p, n, itemsize = 4, 256, 4  # ranks, elements, float32
    expected_mults = {  # (in + out) multiplier per op
        "allreduce": p + p,
        "allgather": p + p * p,
        "broadcast": p + p,
        "gather": p + p * p,
        "scatter": p * p + p,
        "reduce": p + p,
        "alltoall": p * p + p * p,
        "sendrecv": p + p,
        "reducescatter": p * p + p,
        "allreduce_hierarchical": p + p,
        # collective-matmul micro-ops: per-rank in AND out (ag_matmul's
        # output is byte-for-byte the input size; matmul_rs's is input/P,
        # conservatively estimated at the per_rank multiplier) PLUS the
        # registry-declared transient — the fused ag_matmul materialises
        # the gathered [B, P*S, H] activation on every device (P^2), the
        # fused matmul_rs a full per-device partial product (P)
        "ag_matmul": p + p + p * p,
        "matmul_rs": p + p + p,
        # compressed micro-ops (docs/compression.md): same declared buffer
        # kinds as their uncompressed counterparts — the quantised copies
        # are byte-wide transients well inside the in+out envelope
        "allreduce_q": p + p,
        "reducescatter_q": p * p + p,
    }
    assert sorted(expected_mults) == sorted(OPERATIONS)  # full coverage
    s = Sweep1D(dtype="float32")
    for op_name, mult in expected_mults.items():
        est = _estimate_global_bytes(
            s, {"operation": op_name, "num_elements": n}, p
        )
        assert est == mult * n * itemsize, op_name
    # the transient term models the FUSED schedule only: under the
    # overlap variants the decomposed ring never materialises it, so the
    # estimate drops back to in+out (a fused-sized cap must not skip
    # ring configs that fit)
    for op_name in ("ag_matmul", "matmul_rs"):
        est = _estimate_global_bytes(
            Sweep1D(dtype="float32", variant="overlap_ring"),
            {"operation": op_name, "num_elements": n}, p,
        )
        assert est == (p + p) * n * itemsize, op_name


@pytest.mark.pipeline_smoke
def test_pipeline_smoke_two_op_mini_sweep(tmp_path, devices):
    """Marker-gated smoke for the compile-ahead engine (also invoked by
    scripts/run_static_analysis.sh): a 2-op pipelined mini-sweep measures,
    records compile accounting in every artifact, and writes the sweep
    manifest."""
    sweep = _tiny_1d(
        tmp_path, operations=("allreduce", "allgather"),
        data_sizes=(("1KB", 256),), rank_counts=(4,),
        pipeline=True,
    )
    files = run_sweep(sweep, verbose=False)
    assert len(files) == 2
    for f in files:
        data = json.loads(f.read_text())
        assert data["compile_seconds"] >= 0.0
        assert isinstance(data["compile_cache_hit"], bool)
    man = json.loads(
        (tmp_path / "results" / "sweep_manifest.json").read_text()
    )
    assert man["pipeline"] is True
    assert man["work_units"]["unique"] == 2
    assert man["configs"]["measured"] == 2


def test_variant_axis_order_meshes():
    """grid/hier axis-order variants resolve to transposed meshes; ring
    fallback covers other rank counts."""
    from dlbb_tpu.comm.variants import get_variant

    assert get_variant("grid2x4").mesh_spec(8).shape == (2, 4)
    assert get_variant("grid4x2").mesh_spec(8).shape == (4, 2)
    assert get_variant("hier2x4").hierarchical
    import pytest

    with pytest.raises(ValueError):
        get_variant("grid4x2").mesh_spec(4)


def test_stats_1d_pipeline(tmp_path, devices):
    run_sweep(_tiny_1d(tmp_path), verbose=False)
    results = process_1d_results(
        tmp_path / "results", tmp_path / "stats", verbose=False
    )
    assert len(results) == 12
    r = results[0]
    for key in (
        "mean_time_us", "median_time_us", "p95_time_us", "p99_time_us",
        "load_imbalance_percent", "bandwidth_gbps", "per_rank_means_us",
    ):
        assert key in r, key
    assert r["bandwidth_gbps"] > 0
    # consolidated CSV with reference columns
    csv_text = (tmp_path / "stats" / "benchmark_statistics.csv").read_text()
    header = csv_text.splitlines()[0]
    assert header.startswith("mpi_implementation,operation,num_ranks")
    assert "bandwidth_gbps" in header
    # per-file stats JSONs exist
    assert len(list((tmp_path / "stats").glob("*_stats.json"))) == 12


def test_sweep_3d_and_stats(tmp_path, devices):
    sweep = Sweep3D(
        implementation="xla_test",
        operations=("allreduce", "allgather"),
        batch_sizes=(1, 2),
        seq_lengths=(8,),
        hidden_dims=(16,),
        rank_counts=(4,),
        dtype="bfloat16",
        warmup_iterations=1,
        measurement_iterations=2,
        output_dir=str(tmp_path / "results3d"),
    )
    files = run_sweep(sweep, verbose=False)
    assert len(files) == 4
    data = json.loads(files[0].read_text())
    assert data["tensor_shape"] == {"batch": 1, "seq_len": 8, "hidden_dim": 16}
    assert data["tensor_size_mb"] == 1 * 8 * 16 * 2 / 2**20

    results = process_3d_results(
        tmp_path / "results3d", tmp_path / "stats3d", "xla_test", verbose=False
    )
    assert len(results) == 4
    std = tmp_path / "stats3d" / "benchmark_statistics_3d_xla_test_standard.csv"
    tr = tmp_path / "stats3d" / "benchmark_statistics_3d_xla_test_transpose.csv"
    assert std.exists() and tr.exists()
    header = std.read_text().splitlines()[0]
    assert header == (
        "implementation,operation,num_ranks,hidden_dim,seq_len,batch,"
        "tensor_size_mb,num_elements,mean_time_ms,median_time_ms,"
        "min_time_ms,max_time_ms"
    )
    # transpose CSV: metrics as rows, config ids as columns
    lines = tr.read_text().splitlines()
    assert lines[0].startswith("Metric,allgather_r4_h16_s8_b1")
    assert lines[1].startswith("mean_time_ms,")


def test_stats_1d_granularity_marker(tmp_path):
    """Chained-mode artifacts (whose samples are chunk MEANS — percentiles
    are not per-iteration tails) must be distinguishable from per-iteration
    ones in both the per-file stats JSON and the consolidated CSV."""
    base = {
        "implementation": "xla_test", "operation": "allreduce",
        "num_ranks": 4, "data_size_name": "1KB", "num_elements": 256,
        "dtype": "bfloat16", "warmup_iterations": 1,
        "measurement_iterations": 3, "timings": [[1e-4, 1.2e-4, 0.9e-4]],
    }
    chained = dict(
        base, operation="broadcast", timing_granularity="chunked(5)",
        percentile_caveat="percentiles are over 5-iteration chunk means, "
                          "not per-iteration tails",
    )
    d = tmp_path / "r"
    d.mkdir()
    (d / "xla_test_allreduce_ranks4_1KB.json").write_text(json.dumps(base))
    (d / "xla_test_broadcast_ranks4_1KB.json").write_text(json.dumps(chained))
    results = process_1d_results(d, tmp_path / "s", verbose=False)
    by_op = {r["operation"]: r for r in results}
    assert by_op["allreduce"]["timing_granularity"] == "per_iteration"
    assert by_op["broadcast"]["timing_granularity"] == "chunked(5)"
    csv_lines = (
        tmp_path / "s" / "benchmark_statistics.csv"
    ).read_text().splitlines()
    # extension columns: granularity marker + dtype (the corpus carries
    # the north-star curve in both bf16 and fp32) + the analytic wire
    # volume (docs/compression.md)
    assert csv_lines[0].endswith("timing_granularity,dtype,bytes_on_wire")
    assert any("chunked(5)" in line for line in csv_lines[1:])
    assert any("per_iteration" in line for line in csv_lines[1:])
    # the full caveat text lands in the per-file stats JSON
    stats = json.loads(
        (tmp_path / "s" / "xla_test_broadcast_ranks4_1KB_stats.json")
        .read_text()
    )
    assert "chunk means" in stats["percentile_caveat"]


def test_stats_1d_null_system_info(tmp_path):
    """An artifact with an explicit ``"system_info": null`` (as opposed to
    a missing key) must process cleanly with ``backend`` = None — the
    ``.get`` default only covers the missing-key case."""
    artifact = {
        "implementation": "xla_test", "operation": "allreduce",
        "num_ranks": 4, "data_size_name": "1KB", "num_elements": 256,
        "dtype": "bfloat16", "warmup_iterations": 1,
        "measurement_iterations": 3, "timings": [[1e-4, 1.2e-4, 0.9e-4]],
        "system_info": None,
    }
    d = tmp_path / "r"
    d.mkdir()
    (d / "xla_test_allreduce_ranks4_1KB.json").write_text(
        json.dumps(artifact))
    results = process_1d_results(d, tmp_path / "s", verbose=False)
    assert len(results) == 1
    assert results[0]["backend"] is None


def test_stats_3d_granularity_marker(tmp_path):
    """3D: the standard CSV header is the reference contract (unchanged);
    the granularity marker rides the transposed CSV's metadata block."""
    art = {
        "implementation": "xla_test", "operation": "allreduce",
        "num_ranks": 4, "num_elements": 128,
        "tensor_shape": {"batch": 1, "seq_len": 8, "hidden_dim": 16},
        "tensor_size_mb": 0.000244140625,
        "timing_granularity": "chunked(5)",
        "timings": [[1e-3, 1.1e-3]],
    }
    d = tmp_path / "r3"
    d.mkdir()
    (d / "xla_test_allreduce_ranks4_b1_s8_h16.json").write_text(
        json.dumps(art)
    )
    process_3d_results(d, tmp_path / "s3", "xla_test", verbose=False)
    header = (
        tmp_path / "s3" / "benchmark_statistics_3d_xla_test_standard.csv"
    ).read_text().splitlines()[0]
    assert "timing_granularity" not in header  # reference contract intact
    tr = (
        tmp_path / "s3" / "benchmark_statistics_3d_xla_test_transpose.csv"
    ).read_text()
    assert "timing_granularity,chunked(5)" in tr


def _write_1d_artifact(path, impl, op, ranks, size_name, n, mean_s,
                       backend=None):
    path.parent.mkdir(parents=True, exist_ok=True)
    artifact = {
        "mpi_implementation": impl, "operation": op, "num_ranks": ranks,
        "data_size_name": size_name, "num_elements": n, "dtype": "bfloat16",
        "warmup_iterations": 1, "measurement_iterations": 2,
        "timings": [[mean_s, mean_s]] * ranks,
    }
    if backend is not None:
        artifact["system_info"] = {"backend": backend}
    path.write_text(json.dumps(artifact))


def test_compare_1d_verdicts(tmp_path):
    """The comparison join picks the best reference backend per config and
    classifies beat/match/lose by the speedup thresholds."""
    from dlbb_tpu.stats.compare import compare_1d

    ref = tmp_path / "ref"
    # slow backend and fast backend: best must be 'fast' (1 ms)
    _write_1d_artifact(ref / "slow" / "a.json", "slow", "allreduce", 4,
                       "1KB", 256, 5e-3)
    _write_1d_artifact(ref / "fast" / "a.json", "fast", "allreduce", 4,
                       "1KB", 256, 1e-3)
    # config only the reference covers (ranks=16) must not produce a row
    _write_1d_artifact(ref / "fast" / "b.json", "fast", "allreduce", 16,
                       "1KB", 256, 1e-3)
    own = tmp_path / "own"
    _write_1d_artifact(own / "a.json", "xla_tpu", "allreduce", 4,
                       "1KB", 256, 0.5e-3)  # 2x faster -> beat
    _write_1d_artifact(own / "c.json", "xla_tpu", "broadcast", 4,
                       "1KB", 256, 1e-3)    # no ref config -> dropped
    rows = compare_1d(ref, own)
    assert len(rows) == 1
    r = rows[0]
    assert r["ref_best_backend"] == "fast"
    assert r["speedup"] == 2.0
    assert r["verdict"] == "beat"
    assert r["raw_verdict"] == "beat"


def test_fp32_artifacts_dtype_suffixed_and_joined(tmp_path):
    """The fp32 half of the north-star curve: float32 sweeps write
    dtype-suffixed filenames next to the bf16 corpus, and the comparison
    emits one row per (config, dtype) with the dtype column filled."""
    from dlbb_tpu.bench.runner import _result_filename
    from dlbb_tpu.stats.compare import compare_1d

    sweep32 = _tiny_1d(tmp_path, operations=("allreduce",),
                       data_sizes=(("1KB", 256),), rank_counts=(2,),
                       implementation="xla_tpu", dtype="float32")
    cfg = {"operation": "allreduce", "size_label": "1KB",
           "num_elements": 256}
    assert _result_filename(sweep32, "xla_tpu", 2, cfg) \
        == "xla_tpu_allreduce_ranks2_1KB_fp32.json"
    run_sweep(sweep32, verbose=False)
    out = tmp_path / "results" / "xla_tpu_allreduce_ranks2_1KB_fp32.json"
    assert out.exists()
    assert json.loads(out.read_text())["dtype"] == "float32"

    ref = tmp_path / "ref"
    _write_1d_artifact(ref / "fast" / "a.json", "fast", "allreduce", 2,
                       "1KB", 256, 1e-3)
    own = tmp_path / "own"
    _write_1d_artifact(own / "a.json", "xla_tpu", "allreduce", 2,
                       "1KB", 256, 1e-3)
    art32 = json.loads(out.read_text())
    (own / "a_fp32.json").write_text(json.dumps(art32))
    rows = compare_1d(ref, own)
    assert len(rows) == 2
    assert {r["xla_dtype"] for r in rows} == {"bfloat16", "float32"}


def test_compare_1d_simulated_rows_are_not_comparable(tmp_path):
    """Own-side artifacts measured on the simulated mesh (system_info.backend
    == 'cpu') get the structural not_comparable(simulated) verdict — never
    'lose' — while the speedup-only raw_verdict is preserved."""
    from dlbb_tpu.stats.compare import NOT_COMPARABLE, compare_1d

    ref = tmp_path / "ref"
    _write_1d_artifact(ref / "fast" / "a.json", "fast", "allreduce", 4,
                       "1KB", 256, 1e-3)
    own = tmp_path / "own"
    _write_1d_artifact(own / "a.json", "xla_tpu", "allreduce", 4,
                       "1KB", 256, 10e-3, backend="cpu")  # 10x slower
    rows = compare_1d(ref, own)
    assert len(rows) == 1
    assert rows[0]["verdict"] == NOT_COMPARABLE
    assert rows[0]["raw_verdict"] == "lose"
    assert rows[0]["speedup"] == 0.1


def test_compare_report_against_reference_corpus(tmp_path, devices):
    """End-to-end: a real (tiny) sweep's artifacts joined against the
    reference's actual checked-in 1D corpus produce the committed report
    files with a verdict per covered config."""
    import pytest

    from dlbb_tpu.stats.compare import write_comparison

    ref_root = __import__("pathlib").Path("/root/reference")
    if not (ref_root / "collectives" / "1d" / "results").exists():
        pytest.skip("reference corpus not available")
    run_sweep(
        _tiny_1d(tmp_path, operations=("allreduce",),
                 data_sizes=(("1KB", 256),), rank_counts=(2, 4),
                 implementation="xla_tpu"),
        verbose=False,
    )
    out = tmp_path / "cmp"
    summary = write_comparison(
        ref_root, tmp_path / "results", tmp_path / "none3d", out
    )
    assert summary["1d"]["configs"] == 2  # ranks 2 and 4 joined
    # the sweep ran on the CPU-simulated mesh -> structurally
    # not_comparable(simulated), never counted as a loss; the speedup-only
    # raw verdicts are preserved in the sub-breakdown
    assert summary["1d"]["not_comparable_simulated"] == 2
    assert sum(summary["1d"][k] for k in ("beat", "match", "lose")) == 0
    raw = summary["1d"]["not_comparable_raw_verdicts"]
    assert sum(raw.values()) == 2
    assert (out / "COMPARISON.md").exists()
    assert (out / "comparison_1d.csv").exists()
    md = (out / "COMPARISON.md").read_text()
    assert "allreduce" in md and "Caveats" in md


def test_compare_e2e_reads_results_corpus(tmp_path):
    """The E2E section is built from the results/e2e artifacts alone: a
    chip artifact at the baseline's shape gets a speedup, a simulated-mesh
    artifact never does."""
    from dlbb_tpu.stats.compare import _e2e_rows

    (tmp_path / "bench_baseline_cpu.json").write_text(json.dumps(
        {"tokens_per_second": 100.0}
    ))
    e2e = tmp_path / "results" / "e2e"
    e2e.mkdir(parents=True)
    for name, backend, tps in (("1b_full_s512_world1", "tpu", 250.0),
                               ("1b_simplified_s512_tp2_sim", "cpu", 50.0)):
        (e2e / f"xla_tpu_{name}.json").write_text(json.dumps({
            "experiment": {"name": name}, "tokens_per_second": tps,
            "system_info": {"backend": backend, "device_kind": "x",
                            "num_devices": 1},
        }))
    rows = _e2e_rows(tmp_path)
    assert len(rows) == 2
    assert rows[0]["speedup"] == 2.5 and rows[0]["verdict"] == "beat"
    assert rows[1]["speedup"] is None
    assert "simulated" in rows[1]["verdict"]


def test_bench_allreduce_multichip_schema(devices):
    """The headline multi-chip branch of bench.py (never taken on the
    single-chip image) runs on the simulated 8-device mesh: schema keys,
    positive bandwidth, and the vs_baseline arithmetic hold."""
    import bench

    out = bench.bench_allreduce_multichip(
        8, num_elements=262_144, warmup=1, iterations=5
    )
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in out, key
    assert out["metric"] == "1d_allreduce_1MB_bus_bandwidth_8ranks"
    assert out["unit"] == "GB/s"
    assert out["value"] > 0
    assert out["max_time_s"] > 0
    np.testing.assert_allclose(
        out["vs_baseline"],
        round(out["value"] / bench.ONECCL_BASELINE_GBPS, 3),
        rtol=1e-9,
    )


@pytest.mark.parametrize("argv", [
    ["bench1d", "--ranks", "2", "--sizes", "1KB"],
    ["bench3d", "--ranks", "2"],
    ["e2e", "--config", "dlbb_tpu/configs/baseline_config.yaml"],
    ["train", "--config", "dlbb_tpu/configs/baseline_config.yaml"],
    ["serve", "--requests", "2"],
], ids=lambda a: a[0])
def test_device_command_without_simulate_refuses_cpu(
        argv, tmp_path, monkeypatch, capsys):
    """The no-chip rule: a device command that lands on the CPU backend
    without ``--simulate`` exits non-zero, says why, and writes nothing —
    there is no CPU path to fall back to."""
    from dlbb_tpu.cli import main as cli_main
    from dlbb_tpu.utils import simulate

    # as in a process that never asked for the simulated mesh
    monkeypatch.setattr(simulate, "_SIMULATION_FORCED", False)
    monkeypatch.chdir(Path(__file__).resolve().parents[1])
    out = tmp_path / "out"
    assert cli_main(argv + ["--output", str(out)]) == 2
    err = capsys.readouterr().err
    assert "no accelerator" in err and "--simulate N" in err
    assert not out.exists()


def test_bench_py_without_accelerator_raises(monkeypatch, capsys):
    """bench.py has no fallback either: no chip, no headline."""
    import bench
    from dlbb_tpu.utils import simulate

    monkeypatch.setattr(simulate, "_SIMULATION_FORCED", False)
    with pytest.raises(simulate.NoAcceleratorError):
        bench.main()
    assert capsys.readouterr().out == ""


def _serve_cli_rc(monkeypatch, failed, extra=()):
    from dlbb_tpu import cli
    from dlbb_tpu.serve import bench as serve_bench

    def fake_run(*a, **kw):
        return {"requests": {"completed": 3, "rejected": 0,
                             "failed": failed},
                "goodput_tokens_per_s": 1.0}

    monkeypatch.setattr(serve_bench, "run_serve_from_config", fake_run)
    return cli.main(["serve", "--simulate", "8", *extra])


def test_serve_cli_exit_code_reports_failed_requests(monkeypatch, capsys):
    """The engine contains a failed dispatch and serves on; the CLI must
    not: a failed request with no fault plan active is a non-zero exit.
    Under a fault plan (flag or env) failures are the experiment."""
    monkeypatch.delenv("DLBB_FAULT_PLAN", raising=False)
    assert _serve_cli_rc(monkeypatch, failed=0) == 0
    assert _serve_cli_rc(monkeypatch, failed=2) == 1
    assert "2 request(s) failed" in capsys.readouterr().err
    assert _serve_cli_rc(
        monkeypatch, failed=2,
        extra=("--fault-plan", "serve-decode-fail:1")) == 0
    monkeypatch.setenv("DLBB_FAULT_PLAN", "serve-decode-fail:1")
    assert _serve_cli_rc(monkeypatch, failed=2) == 0


def test_variants_report_picks_winner(tmp_path):
    """The tuning-comparison capstone: per-size join over variant stats
    CSVs, winner + speedup-vs-default computed, fixed-shape variants with
    missing rank rows dropped rather than guessed."""
    import csv

    from dlbb_tpu.stats import write_variants_report

    cols = ["mpi_implementation", "operation", "num_ranks",
            "data_size_name", "mean_time_us"]

    def fake(impl, rows):
        d = tmp_path / impl
        d.mkdir()
        with (d / "benchmark_statistics.csv").open("w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=cols)
            w.writeheader()
            for size, mean in rows:
                w.writerow({"mpi_implementation": impl,
                            "operation": "allreduce", "num_ranks": 8,
                            "data_size_name": size, "mean_time_us": mean})

    fake("xla_tpu", [("1KB", 100.0), ("16MB", 9000.0)])
    fake("xla_tpu_hier2x4", [("1KB", 50.0), ("16MB", 12000.0)])
    fake("xla_tpu_grid2x2x2", [("1KB", 200.0)])  # no 16MB row

    summary = write_variants_report(tmp_path)
    assert summary["winners"]["1KB"]["winner"] == "xla_tpu_hier2x4"
    assert summary["winners"]["1KB"]["speedup_vs_default"] == 2.0
    assert summary["winners"]["16MB"]["winner"] == "xla_tpu"
    assert (tmp_path / "VARIANTS.md").exists()
    with (tmp_path / "variants_comparison.csv").open() as f:
        rows = {r["data_size_name"]: r for r in csv.DictReader(f)}
    assert rows["16MB"]["xla_tpu_grid2x2x2"] == ""  # absent, not guessed
    # markdown renders absent cells blank, never the string "None"
    assert "None" not in (tmp_path / "VARIANTS.md").read_text()


def test_variants_report_fresh_tree(tmp_path):
    from dlbb_tpu.stats import write_variants_report

    summary = write_variants_report(tmp_path / "does_not_exist")
    assert summary == {"sizes": [], "winners": {}}


def test_stats_reads_reference_artifact(tmp_path):
    """The pipeline must ingest the reference's own result JSONs (same
    schema, 'mpi_implementation' key)."""
    ref = {
        "mpi_implementation": "openmpi",
        "operation": "allreduce",
        "num_ranks": 4,
        "data_size_name": "1KB",
        "num_elements": 256,
        "dtype": "<class 'numpy.float16'>",
        "warmup_iterations": 10,
        "measurement_iterations": 3,
        "timings": [[1e-4, 1.2e-4, 0.9e-4]] * 4,
    }
    d = tmp_path / "ref"
    d.mkdir()
    (d / "openmpi_allreduce_ranks4_1KB.json").write_text(json.dumps(ref))
    results = process_1d_results(d, tmp_path / "refstats", verbose=False)
    assert len(results) == 1
    assert results[0]["mpi_implementation"] == "openmpi"
    # fp16 element size resolved from the numpy-repr dtype string
    expected_bw = 256 * 2 * 4 / (1.2e-4) / 2**30
    np.testing.assert_allclose(results[0]["bandwidth_gbps"], expected_bw, rtol=1e-9)


def test_variants3d_report(tmp_path):
    """3D-shape variant comparison: joins variant standard CSVs with the
    default 3D corpus per config, picks the winner, and drops configs only
    one implementation measured."""
    import csv as _csv

    from dlbb_tpu.stats.variants_report import write_variants3d_report

    cols = ["implementation", "operation", "num_ranks", "hidden_dim",
            "seq_len", "batch", "tensor_size_mb", "num_elements",
            "mean_time_ms", "median_time_ms", "min_time_ms", "max_time_ms"]

    def std_csv(path, impl, rows):
        path.parent.mkdir(parents=True, exist_ok=True)
        with path.open("w", newline="") as f:
            w = _csv.DictWriter(f, fieldnames=cols)
            w.writeheader()
            for ranks, b, s, h, mean in rows:
                w.writerow({
                    "implementation": impl, "operation": "allreduce",
                    "num_ranks": ranks, "hidden_dim": h, "seq_len": s,
                    "batch": b, "tensor_size_mb": 1, "num_elements": 1,
                    "mean_time_ms": mean, "median_time_ms": mean,
                    "min_time_ms": mean, "max_time_ms": mean,
                })

    base = tmp_path / "3d" / "base_standard.csv"
    std_csv(base, "xla_tpu", [(8, 1, 2048, 2048, 10.0),
                              (8, 8, 2048, 2048, 80.0)])
    std_csv(tmp_path / "v3d" / "xla_tpu_ring" / "r_standard.csv",
            "xla_tpu_ring", [(8, 1, 2048, 2048, 5.0),
                             (4, 1, 1, 2048, 1.0)])  # ranks-4: ring only
    rows = write_variants3d_report(tmp_path / "v3d", base,
                                   tmp_path / "out")
    assert len(rows) == 1  # the single config both measured
    r = rows[0]
    assert r["winner"] == "xla_tpu_ring"
    assert r["winner_speedup_vs_default"] == 2.0
    assert (tmp_path / "out" / "VARIANTS3D.md").exists()
    assert (tmp_path / "out" / "variants3d_comparison.csv").exists()

    # a scanned dir named xla_tpu would shadow the baseline — rejected
    import pytest

    std_csv(tmp_path / "v3d" / "xla_tpu" / "x_standard.csv",
            "xla_tpu", [(8, 1, 2048, 2048, 3.0)])
    with pytest.raises(ValueError, match="shadow"):
        write_variants3d_report(tmp_path / "v3d", base, tmp_path / "out")


def test_northstar_report(tmp_path):
    """The driver-metric table: one row per size label (payload order),
    one column per (ranks, dtype), median/bandwidth cells, honest blanks
    for unmeasured combinations."""
    import csv as _csv

    from dlbb_tpu.stats.northstar import write_northstar_report

    cols = ["mpi_implementation", "operation", "num_ranks",
            "data_size_name", "num_elements", "median_time_us",
            "bandwidth_gbps", "dtype"]
    stats_csv = tmp_path / "benchmark_statistics.csv"
    with stats_csv.open("w", newline="") as f:
        w = _csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        for ranks, size, n, dtype, med, bw in (
            (2, "1KB", 256, "bfloat16", 100.0, 0.01),
            (2, "1KB", 256, "float32", 80.0, 0.02),
            (2, "16MB", 4194304, "bfloat16", 9000.0, 1.5),
            # 16MB fp32 unmeasured -> blank cell
        ):
            w.writerow({"mpi_implementation": "xla_tpu",
                        "operation": "allreduce", "num_ranks": ranks,
                        "data_size_name": size, "num_elements": n,
                        "median_time_us": med, "bandwidth_gbps": bw,
                        "dtype": dtype})
    counts = write_northstar_report(stats_csv, tmp_path / "out",
                                    operations=("allreduce",))
    assert counts == {"allreduce": 2}
    with (tmp_path / "out" / "northstar_allreduce.csv").open() as f:
        rows = list(_csv.DictReader(f))
    assert [r["size"] for r in rows] == ["1KB", "16MB"]  # payload order
    assert rows[0]["2r/fp32"].startswith("80us")
    assert rows[1]["2r/fp32"] == ""  # honest blank
    md = (tmp_path / "out" / "NORTHSTAR.md").read_text()
    assert "allreduce" in md and "p50" in md

    # absent stats CSV -> no-op, nothing written
    assert write_northstar_report(tmp_path / "missing.csv",
                                  tmp_path / "out2") == {}
    assert not (tmp_path / "out2").exists()

    # stats CSV without any north-star op rows -> no-op too: a partial
    # regeneration must not clobber the committed report with a shell
    empty_csv = tmp_path / "empty_stats.csv"
    with empty_csv.open("w", newline="") as f:
        w = _csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        w.writerow({"mpi_implementation": "xla_tpu",
                    "operation": "reducescatter", "num_ranks": 2,
                    "data_size_name": "1KB", "num_elements": 256,
                    "median_time_us": 1.0, "bandwidth_gbps": 0.1,
                    "dtype": "bfloat16"})
    assert write_northstar_report(empty_csv, tmp_path / "out3") == {}
    assert not (tmp_path / "out3").exists()
