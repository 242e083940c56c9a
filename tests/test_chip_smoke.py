"""``chip_smoke.py`` stays runnable: the rehearsal drives every phase on the
simulated mesh, and without a chip (or without the repo) the real mode
fails fast, prints no result and starts no phase."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
PHASES = ("forward_7b", "train_1b", "serve_default", "serve_fastpath",
          "bench1d_r4", "oracle_r4", "forward_7b_tp4", "train_1b_dp2_tp2",
          "serve_dp2_tp2")


def _run(argv, cwd=REPO, env=None, timeout=600):
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_runs_every_phase():
    r = _run(["chip_smoke.py", "--rehearse", "8"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
        "rehearsal": "REHEARSAL (cpu)",
    }
    # an explicit rehearsal can never be read as a chip run
    assert all(ln.startswith("REHEARSAL (cpu) ") for ln in lines[:-1])
    for phase in PHASES:
        assert f"== {phase}: " in r.stdout, phase
    assert "[FAIL]" not in r.stdout
    assert "24 of 24 requests completed" in r.stdout
    assert "last loss below first" in r.stdout


def test_no_chip_is_a_fast_failure_without_a_result():
    t0 = time.monotonic()
    r = _run(["chip_smoke.py"], timeout=120)
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "platform cpu" in r.stdout
    assert "not 'tpu'" in r.stderr
    assert "== forward_7b" not in r.stdout      # no phase was started
    assert '"ok"' not in r.stdout               # and no result printed


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script proves the program starts; without the program there is
    nothing to prove."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run(["chip_smoke.py"], cwd=tmp_path, env=env, timeout=120)
    assert r.returncode != 0
    assert "No module named 'dlbb_tpu'" in r.stdout
    assert '"ok"' not in r.stdout
