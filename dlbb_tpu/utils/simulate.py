"""CPU-simulated multi-device mesh setup — the dev-path analogue of
``mpirun -np N`` on localhost (SURVEY §4) — and the no-chip rule.

:func:`force_cpu_simulation` must run before the JAX backend initialises:
it sets the ``xla_force_host_platform_device_count`` flag and selects the
CPU platform.  Shared by the CLI (``--simulate N``), ``tests/conftest.py``
and the CPU bench scripts.

The program measures an accelerator.  A process that finds itself on the
CPU backend without having asked for the simulated mesh has lost its chip,
and every number it would write is a CPU number under a device's name — so
:func:`require_accelerator` makes that an error instead of a label.  It is
the one check: the CLI calls it before any device command, and
:func:`topology_record` (which every sweep and serving run calls before it
measures) calls it for library users.
"""

from __future__ import annotations

import importlib
import os
import re
import threading
from typing import Any, Optional

# Set by force_cpu_simulation: the CPU backend was explicitly requested
# (CLI --simulate, tests, a bench script).
_SIMULATION_FORCED = False


class NoAcceleratorError(RuntimeError):
    """The process is on the CPU backend and never asked to be."""


def force_cpu_simulation(num_devices: int) -> None:
    """Stand up an ``num_devices``-device CPU-simulated mesh."""
    global _SIMULATION_FORCED
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            f"--xla_force_host_platform_device_count={num_devices}",
            flags,
        )
    else:
        flags = f"{flags} --xla_force_host_platform_device_count={num_devices}"
    os.environ["XLA_FLAGS"] = flags.strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    _SIMULATION_FORCED = True

    import jax

    jax.config.update("jax_platforms", "cpu")
    # XLA:CPU aborts executing some programs deserialised from a warm
    # persistent cache (utils/compile_cache.py has the observation), so
    # the simulated mesh runs with the cache off outside sweeps — wherever
    # JAX_COMPILATION_CACHE_DIR points
    jax.config.update("jax_enable_compilation_cache", False)


def simulation_forced() -> bool:
    """Whether this process explicitly requested the CPU-simulated mesh."""
    return _SIMULATION_FORCED


_PALLAS_IMPORT: Optional[threading.Thread] = None


def _import_pallas_meanwhile() -> None:
    """Start importing Pallas on a thread of its own, once.  Every device
    command's programs hold a Pallas kernel (``ops/``) and the import
    takes 1.5 s on the chip's host (two thirds of it Mosaic GPU, which
    ``pallas_call`` imports whatever the backend; ``PERF.md`` §6, PR 28).
    The caller's next step starts the TPU client, 4-5 s in which the main
    thread waits outside the interpreter: the import runs then.  Whoever
    imports the same modules before it is done waits on the import lock
    for what is left, as for any import."""
    global _PALLAS_IMPORT
    if _PALLAS_IMPORT is None:
        _PALLAS_IMPORT = threading.Thread(
            target=importlib.import_module,
            args=("jax.experimental.pallas.tpu",),
            name="import-pallas", daemon=True)
        _PALLAS_IMPORT.start()


def require_accelerator() -> None:
    """Raise :class:`NoAcceleratorError` when the backend is the CPU and
    :func:`force_cpu_simulation` was not called.  Initialises the backend
    (so it must follow any ``jax.distributed`` handshake); where that is
    to be an accelerator's client, Pallas is imported beside its
    start-up."""
    import jax

    if not _SIMULATION_FORCED and jax.config.jax_platforms != "cpu":
        _import_pallas_meanwhile()
    if jax.default_backend() == "cpu" and not _SIMULATION_FORCED:
        raise NoAcceleratorError(
            "JAX found no accelerator (backend 'cpu', JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}) and the CPU-simulated "
            "mesh was not requested: pass --simulate N (library callers: "
            "dlbb_tpu.utils.simulate.force_cpu_simulation(N) before any "
            "JAX use) to run on N simulated CPU devices on purpose"
        )


def topology_record(
    fault_domains: Optional[dict[str, Any]] = None,
) -> dict[str, Any]:
    """The topology fingerprint every sweep artifact set carries
    (``sweep_manifest.json`` ``topology`` key + a ``topology`` journal
    event): which platform backs the mesh, how many devices and
    processes, and whether it is the simulated mesh.

    ``fault_domains`` (serving fleets only — ``serve/fleet.py``) maps
    replica id -> device ids; its presence marks the artifact as a
    FLEET run, and overlay/report tooling keys on it so fleet numbers
    never silently aggregate with single-replica numbers."""
    import jax

    require_accelerator()
    platform = jax.default_backend()
    rec: dict[str, Any] = {
        "platform": platform,
        "num_devices": len(jax.devices()),
        "process_count": jax.process_count(),
        "simulated": platform == "cpu",
    }
    if fault_domains is not None:
        rec["fault_domains"] = dict(fault_domains)
    return rec
