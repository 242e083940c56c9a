"""Shared parent-loop for the real-chip publisher scripts.

The parent never imports JAX (``dlbb_tpu.utils.config`` does not), so it
never holds the chip its workers need.  One subprocess per config (fresh
HBM arena per measurement), one
boundary-handling contract: a config whose failure is expected AND whose
stderr matches a memory/compile signature gets a deterministic
``*_infeasible.json`` boundary artifact (and its stale measured artifact
is unlinked); a config that succeeds unlinks its stale boundary artifact;
every other failure fails the run.  Used by ``publish_tpu_e2e.py`` and
``publish_tpu_train.py`` — the contract is pinned by
``tests/test_publish_scripts.py`` against both.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Callable, Iterable

# error signatures that qualify a failure as the memory boundary.  A
# compile that cannot fit says, on libtpu 0.0.34: "RESOURCE_EXHAUSTED:
# XLA:TPU compile permanent error. Ran out of memory in memory space hbm.
# Used 16.00G of 15.75G hbm."
BOUNDARY_SIGNATURES = ("RESOURCE_EXHAUSTED",
                       "Ran out of memory in memory space", "Allocat")


def require_tpu() -> None:
    """A publisher worker measures the chip or nothing."""
    import jax

    from dlbb_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    devices = jax.devices()
    print(f"devices: {devices}", flush=True)
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"not a TPU backend (platform={devices[0].platform!r}): the "
            "publisher scripts write chip artifacts only"
        )


def run_worker_matrix(
    script_path: str,
    items: Iterable[Any],
    only_str: Callable[[Any], str],
    artifact_name: Callable[[Any], str],
    expected_fail_ok: set,
    write_boundary: Callable[[Any, str, int, str], Path],
    output: str,
    iters: int,
    label: Callable[[Any], str] = str,
) -> int:
    """Run every item as a ``--only`` worker subprocess; returns the exit
    code for ``main()``."""
    import subprocess

    failures = []
    for item in items:
        cmd = [sys.executable, script_path, "--iters", str(iters),
               "--output", output, "--only", only_str(item)]
        r = subprocess.run(cmd, capture_output=True, text=True)
        sys.stdout.write(r.stdout)
        if r.returncode == 0:
            # a previously-infeasible config that now measures cleanly
            # must not leave a stale boundary artifact shadowing it
            stale = Path(output) / f"{artifact_name(item)}_infeasible.json"
            stale.unlink(missing_ok=True)
            continue
        err_lines = [l for l in r.stderr.splitlines() if l.strip()]
        observed = err_lines[-1] if err_lines else f"exit {r.returncode}"
        is_boundary = (
            item in expected_fail_ok
            and any(sig in r.stderr for sig in BOUNDARY_SIGNATURES)
        )
        if is_boundary:
            # a config that regressed to infeasible must not leave its
            # stale measured artifact shadowing the fresh boundary file
            stale = Path(output) / f"{artifact_name(item)}.json"
            stale.unlink(missing_ok=True)
            write_boundary(item, output, r.returncode, observed)
            print(f"EXPECTED-INFEASIBLE {label(item)} "
                  "(boundary artifact written)", flush=True)
            continue
        sys.stderr.write(r.stderr)
        print(f"FAILED {label(item)} (exit {r.returncode})", flush=True)
        failures.append(item)
    if failures:
        print(f"{len(failures)} config(s) failed: "
              f"{[label(f) for f in failures]}", flush=True)
        return 1
    print(f"artifacts in {output}", flush=True)
    return 0
