"""Publisher-script contract tests (corpus provenance guards).

The committed ``results/``+``stats/`` corpus is only as trustworthy as the
scripts that claim to produce it; these tests pin the failure-handling
contracts of ``scripts/publish_tpu_e2e.py``'s parent loop without a chip:
boundary artifacts are written only for expected-infeasible configs whose
stderr matches a memory/compile signature, other failures still fail the
run, and success unlinks a stale boundary artifact.
"""

import importlib.util
import json
import sys
import types
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _load(monkeypatch, tmp_path, run_results):
    """Import publish_tpu_e2e with subprocess.run faked.

    ``run_results``: {(size, attention, seq): (returncode, stderr)} —
    configs absent from the dict succeed.
    """
    spec = importlib.util.spec_from_file_location(
        "publish_tpu_e2e", REPO / "scripts" / "publish_tpu_e2e.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    calls = []

    def fake_run(cmd, capture_output=True, text=True):
        only = cmd[cmd.index("--only") + 1]
        size, attention, seq = only.split(",")
        key = (size, attention, int(seq))
        calls.append(key)
        rc, stderr = run_results.get(key, (0, ""))
        return types.SimpleNamespace(
            returncode=rc, stdout=f"ran {only}\n", stderr=stderr
        )

    import subprocess

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setattr(
        sys, "argv", ["publish_tpu_e2e.py", "--output", str(tmp_path)]
    )
    return mod, calls


def test_boundary_artifact_only_for_memory_signature(monkeypatch, tmp_path):
    mod, _ = _load(
        monkeypatch, tmp_path,
        {("1B", "dense", 8192): (1, "jax: RESOURCE_EXHAUSTED while x\n")},
    )
    assert mod.main() == 0
    art = tmp_path / "xla_tpu_1b_dense_s8192_world1_infeasible.json"
    data = json.loads(art.read_text())
    assert data["status"] == "infeasible"
    assert "RESOURCE_EXHAUSTED" in data["observed_error"]
    # the deterministic reason comes from the script, not the stderr
    assert "score tensor" in data["reason"]


def test_unexpected_error_at_boundary_config_still_fails(monkeypatch,
                                                         tmp_path):
    mod, _ = _load(
        monkeypatch, tmp_path,
        {("1B", "dense", 8192): (1, "ImportError: no module named foo\n")},
    )
    assert mod.main() == 1  # NOT silently recorded as infeasible
    assert not list(tmp_path.glob("*_infeasible.json"))


def test_failure_outside_expected_set_fails(monkeypatch, tmp_path):
    mod, _ = _load(
        monkeypatch, tmp_path,
        {("7B", "full", 512): (1, "RESOURCE_EXHAUSTED\n")},
    )
    assert mod.main() == 1
    assert not list(tmp_path.glob("*_infeasible.json"))


def test_success_unlinks_stale_boundary_artifact(monkeypatch, tmp_path):
    stale = tmp_path / "xla_tpu_1b_dense_s8192_world1_infeasible.json"
    stale.write_text("{}")
    mod, calls = _load(monkeypatch, tmp_path, {})
    assert mod.main() == 0
    assert not stale.exists()
    assert ("1B", "dense", 8192) in calls


def test_boundary_unlinks_stale_measured_artifact(monkeypatch, tmp_path):
    """A config that regressed to infeasible must not leave its stale
    measured JSON shadowing the fresh boundary artifact (the mirror of the
    success-path stale-boundary unlink)."""
    stale = tmp_path / "xla_tpu_1b_dense_s8192_world1.json"
    stale.write_text("{}")
    mod, _ = _load(
        monkeypatch, tmp_path,
        {("1B", "dense", 8192): (1, "jax: RESOURCE_EXHAUSTED while x\n")},
    )
    assert mod.main() == 0
    assert not stale.exists()
    assert (tmp_path
            / "xla_tpu_1b_dense_s8192_world1_infeasible.json").exists()


def test_boundary_reason_computed_from_config(monkeypatch, tmp_path):
    """The deterministic boundary reason reflects the config's own shape
    parameters (head count from the model table, the actual seq), not a
    hardcoded dense-1B-8192 string."""
    mod, _ = _load(monkeypatch, tmp_path, {})
    reason = mod._boundary_reason("1B", "dense", 8192)
    # 1B: 16 heads; 8 * 16 * 8192^2 * 4 B = 32 GiB
    assert "N=16" in reason and "S=8192" in reason and "32 GiB" in reason
    reason7b = mod._boundary_reason("7B", "dense", 4096)
    # 7B: 32 heads; 8 * 32 * 4096^2 * 4 B = 16 GiB
    assert "N=32" in reason7b and "S=4096" in reason7b
    assert "16 GiB fp32" in reason7b


def _load_train(monkeypatch, tmp_path, run_results):
    """Import publish_tpu_train with subprocess.run faked.

    ``run_results``: {suffix: (returncode, stderr)}; absent configs
    succeed."""
    spec = importlib.util.spec_from_file_location(
        "publish_tpu_train", REPO / "scripts" / "publish_tpu_train.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    calls = []

    def fake_run(cmd, capture_output=True, text=True):
        suffix = cmd[cmd.index("--only") + 1]
        calls.append(suffix)
        rc, stderr = run_results.get(suffix, (0, ""))
        return types.SimpleNamespace(
            returncode=rc, stdout=f"ran {suffix}\n", stderr=stderr
        )

    import subprocess

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setattr(
        sys, "argv", ["publish_tpu_train.py", "--output", str(tmp_path)]
    )
    return mod, calls


def test_train_boundary_only_for_remat_off(monkeypatch, tmp_path):
    """sgd_remat_off's memory failure is the no-remat ladder point; the
    boundary artifact records a reason computed from the 1B geometry."""
    mod, calls = _load_train(
        monkeypatch, tmp_path,
        {"sgd_remat_off": (1, "XLA ... RESOURCE_EXHAUSTED hbm\n")},
    )
    assert mod.main() == 0
    art = tmp_path / "train_ddp_1B_train_chip_sgd_remat_off_infeasible.json"
    data = json.loads(art.read_text())
    assert data["status"] == "infeasible"
    assert "remat" in data["reason"]
    # every other config ran
    assert set(calls) == {s for s, _, _, _ in mod.CONFIGS}


def test_train_shape_ladder_boundary(monkeypatch, tmp_path):
    """The big shape-ladder rungs may OOM; their boundary reason is
    computed from the rung's own (batch, seq), and the small rungs are
    never allowed to fail silently."""
    mod, calls = _load_train(
        monkeypatch, tmp_path,
        {"adam_bf16m_dots_b32_s1024": (1, "RESOURCE_EXHAUSTED hbm\n")},
    )
    assert mod.main() == 0
    art = tmp_path / ("train_ddp_1B_train_chip_adam_bf16m_dots_b32_s1024"
                      "_infeasible.json")
    data = json.loads(art.read_text())
    assert data["status"] == "infeasible"
    assert "B=32" in data["reason"] and "S=1024" in data["reason"]
    # every Adam shape rung is measured-infeasible on the 16 GiB chip
    # (b16/s512 needs 16.35G of 15.75G), so all of them are boundary;
    # the smallest STATELESS-SGD rungs are the ones that must never
    # fail silently — an OOM there would be a regression
    assert "adam_bf16m_dots_b16_s512" in mod.EXPECTED_FAIL_OK
    assert "sgd_dots_b16_s512" not in mod.EXPECTED_FAIL_OK
    assert "sgd_dots_b8_s1024" not in mod.EXPECTED_FAIL_OK
    assert mod._ladder_shape("adam_bf16m_dots_b16_s512") == (16, 512)
    assert mod._ladder_shape("sgd_dots_b8_s1024") == (8, 1024)


def test_train_adam_fp32m_failure_is_real(monkeypatch, tmp_path):
    """adam_fp32m is measured since the timing-loop donation fix; an OOM
    there is a regression, never silently recorded as infeasible."""
    mod, _ = _load_train(
        monkeypatch, tmp_path,
        {"adam_fp32m": (1, "RESOURCE_EXHAUSTED\n")},
    )
    assert mod.main() == 1
    assert not list(tmp_path.glob("*adam_fp32m*_infeasible.json"))


def test_train_missing_mode_runs_only_absent_configs(monkeypatch,
                                                     tmp_path):
    """--missing resumes an interrupted matrix: configs
    with a measured OR boundary artifact are excluded; only absent ones
    re-run."""
    mod, calls = _load_train(monkeypatch, tmp_path, {})
    measured = [s for s, _, _, _ in mod.CONFIGS]
    pending = {"sgd_dots_b16_s512", "adam_bf16m_dots_b8_s1024"}
    for s in measured:
        if s in pending:
            continue
        # half land as measured artifacts, half as boundaries — both
        # must count as "present"
        name = mod._artifact_name(s)
        suffix = "_infeasible" if s in mod.EXPECTED_FAIL_OK else ""
        (tmp_path / f"{name}{suffix}.json").write_text("{}")
    monkeypatch.setattr(sys, "argv", [
        "publish_tpu_train.py", "--output", str(tmp_path), "--missing",
    ])
    assert mod.main() == 0
    assert set(calls) == pending


def test_train_unknown_only_suffix_rejected(monkeypatch, tmp_path):
    mod, _ = _load_train(monkeypatch, tmp_path, {})
    monkeypatch.setattr(
        sys, "argv",
        ["publish_tpu_train.py", "--output", str(tmp_path),
         "--only", "adam_bf16"],
    )
    import pytest

    with pytest.raises(SystemExit, match="unknown config"):
        mod.main()


def _load_baselines():
    spec = importlib.util.spec_from_file_location(
        "publish_baselines", REPO / "scripts" / "publish_baselines.py"
    )
    # the module force-selects the simulated backend at import; that is
    # already this test session's backend, so importing is safe
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_parallelism_stage_families_consistent():
    """Every family member has a runnable config, every config belongs to
    a family, and each config's mesh product fits the 8-device stage."""
    mod = _load_baselines()

    members = {m for ms in mod.PARALLELISM_FAMILIES.values() for m in ms}
    configs = set(mod._PARALLELISM_CONFIGS)
    assert members == configs
    for name, (_, par, _) in mod._PARALLELISM_CONFIGS.items():
        product = 1
        for v in par.values():
            if isinstance(v, int) and v > 0:
                product *= v
        # num_microbatches is a schedule knob, not a mesh axis
        if "num_microbatches" in par:
            product //= par["num_microbatches"]
        assert product <= 8, (name, par)


def test_cp_scaling_skip_ladder(monkeypatch, tmp_path):
    """The cp_scaling stage's skip ladder in priority order: a
    known-infeasible cell writes its boundary WITHOUT executing (the
    rendezvous crash is a fatal CHECK — re-running it would kill a
    --fresh publisher), the footprint cap wins over the time budget
    (Ulysses at S=32768 must say 96 GiB, not 'time'), and only
    footprint-fitting cells outside the long-S allowance get time
    skips.  Measured cells call run_train exactly once each."""
    mod = _load_baselines()
    monkeypatch.setattr(mod, "RESULTS", tmp_path / "results")
    monkeypatch.setattr(mod, "STATS", tmp_path / "stats")

    ran = []

    def fake_run_train(config, zero_stage=0, output_dir=None, **kw):
        name = config["experiment"]["name"]
        ran.append(name)
        out = Path(output_dir) / f"train_ddp_{name}.json"
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps({
            "experiment": {"name": name},
            "mesh": {"dp": 1, "sp": 2, "pp": 1, "ep": 1, "tp": 1},
            "step_time": {"mean": 1.0},
            "tokens_per_second": 100.0,
        }))
        return {"tokens_per_second": 100.0}

    import dlbb_tpu.train.loop as loop_mod

    monkeypatch.setattr(loop_mod, "run_train", fake_run_train)
    mod.stage_cp_scaling()

    out = tmp_path / "results" / "parallelism" / "cp_scaling"
    art = {p.stem.removeprefix("train_ddp_"): json.loads(p.read_text())
           for p in out.glob("train_ddp_cp_*.json")}
    # full grid accounted for: every (S, sp, impl) cell has an artifact
    assert len(art) == 18
    # measured cells executed exactly once each, none of the capped ones
    assert sorted(ran) == sorted(
        n for n, a in art.items() if "status" not in a)
    # the rendezvous cell never executed and carries the infeasible class
    assert art["cp_s32768_sp8_ring"]["status"] == "infeasible"
    assert "cp_s32768_sp8_ring" not in ran
    # Ulysses at S=32768: footprint attribution at EVERY sp (never time)
    for sp in (2, 4, 8):
        a = art[f"cp_s32768_sp{sp}_ulysses"]
        assert a["status"] == "skipped_estimated_footprint", (sp, a)
    # ring at S=32768 outside the sp allowance: time attribution
    for sp in (2, 4):
        a = art[f"cp_s32768_sp{sp}_ring"]
        assert a["status"] == "skipped_estimated_time", (sp, a)
    # the report renders over the mixed cells without error
    assert (tmp_path / "stats" / "parallelism" / "CP_SCALING.md").exists()


def test_reports_regeneration_is_byte_stable(tmp_path):
    """``reports`` over the committed corpus must be a byte-level no-op.

    The derived tables (VARIANTS.md, VARIANTS3D.md, PARALLELISM.md,
    NORTHSTAR.md and their CSVs) are committed artifacts; the native-core
    stats path claims byte-stable regeneration — this pins it.  The whole
    ``stats/`` tree is copied aside, regenerated in place, and every file
    compared back byte-for-byte (inputs trivially identical, derived
    outputs must round-trip)."""
    import filecmp
    import shutil

    from dlbb_tpu.cli import main as cli_main

    stats_copy = tmp_path / "stats"
    par_copy = tmp_path / "results" / "parallelism"
    shutil.copytree(REPO / "stats", stats_copy)
    shutil.copytree(REPO / "results" / "parallelism", par_copy)

    rc = cli_main([
        "reports",
        "--stats", str(stats_copy),
        "--results", str(tmp_path / "results"),
    ])
    assert rc == 0

    mismatches = []
    for f in sorted(stats_copy.rglob("*")):
        if not f.is_file():
            continue
        committed = REPO / "stats" / f.relative_to(stats_copy)
        if not committed.is_file():
            mismatches.append(f"{f.relative_to(stats_copy)}: new file")
        elif not filecmp.cmp(f, committed, shallow=False):
            mismatches.append(f"{f.relative_to(stats_copy)}: differs")
    assert not mismatches, mismatches


def _load_baselines():
    """Import publish_baselines (guarded main; import is side-effect
    free on the simulated mesh)."""
    spec = importlib.util.spec_from_file_location(
        "publish_baselines", REPO / "scripts" / "publish_baselines.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tuning_grid_dedups_full_grid_variants():
    """ADVICE r5: the reduced tuning grid must not re-run VARIANTS_3D
    members at the full-grid stage's rank counts (same output dirs,
    different max_global_bytes -> --fresh artifacts for shared cells
    would be order-dependent).  Rank counts the full-grid stage does NOT
    cover (ring @ 16) are kept, and member order is the deterministic
    input order."""
    mod = _load_baselines()
    members = mod._tuning_grid_members(mod.EXECUTABLE_VARIANTS, (4, 8))
    names = [n for n, _ in members]
    # no full-grid variant re-measured at full-grid rank counts
    assert not set(names) & set(mod.VARIANTS_3D), names
    # "default" excluded, order deterministic (input order)
    assert "default" not in names
    expected = [n for n in mod.EXECUTABLE_VARIANTS
                if n != "default" and n not in mod.VARIANTS_3D]
    assert names == expected
    # every surviving member sweeps the full requested rank tuple
    assert all(ranks == (4, 8) for _, ranks in members)
    # the 16-rank rung keeps ring: stage_variants3d only covers (4, 8)
    members16 = mod._tuning_grid_members(mod.VARIANTS_16, (16,))
    assert ("ring", (16,)) in members16


def test_cp_time_skip_reason_wording():
    """ADVICE r5: the skipped_estimated_time reason must say the measured
    S axis ends at 16384 and S=32768 is boundary-documented only — not
    point readers at an sp allowance that produced no measurement."""
    mod = _load_baselines()
    reason = mod._cp_time_skip_reason(32768, (8,))
    assert "boundary-documented only" in reason
    assert "measured S axis ends at 16384" in reason
    assert "to carry the S axis" not in reason


def test_cp_scaling_report_wording(tmp_path):
    """The CP_SCALING.md prose must match: no claim that an sp degree
    'carries the S axis' at S=32768 (that cell is the rendezvous-timeout
    infeasible cell; all Ulysses S=32768 cells are footprint-capped)."""
    from dlbb_tpu.stats.parallelism_report import write_cp_scaling_report

    write_cp_scaling_report(tmp_path / "empty", tmp_path / "out")
    md = (tmp_path / "out" / "CP_SCALING.md").read_text()
    assert "boundary-documented only" in md
    assert "carries the S axis" not in md
    assert "ends at S=16384" in md
