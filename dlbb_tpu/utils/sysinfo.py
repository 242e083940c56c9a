"""System / device info for result provenance (reference
``utils.py:132-151`` collect_system_info: platform + psutil + torch versions;
here: platform + JAX + device topology).

Every harness calls :func:`collect_system_info` once, at the end of its run
and outside every timed region, so the record also carries what is only
known then: each device's ``memory_stats()`` (peak bytes in use) and the
process's persistent-compilation-cache hit/miss totals."""

from __future__ import annotations

import platform
from importlib import metadata
from typing import Any


def _libtpu_version() -> str | None:
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


def device_spread(tree: Any) -> int:
    """How many devices the first array of ``tree`` is laid out over — what
    a harness records to show where its result (or its weights) ended
    up."""
    import jax

    return len(jax.tree.leaves(tree)[0].sharding.device_set)


def collect_system_info() -> dict[str, Any]:
    import jax
    import jaxlib

    from dlbb_tpu.utils.compile_cache import CACHE_EVENTS

    devices = jax.devices()
    hits, misses = CACHE_EVENTS.snapshot()
    info: dict[str, Any] = {
        "platform": platform.platform(),
        "python_version": platform.python_version(),
        "processor": platform.processor(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "libtpu_version": _libtpu_version(),
        "backend": jax.default_backend(),
        "num_devices": len(devices),
        "num_processes": jax.process_count(),
        "device_kind": devices[0].device_kind if devices else "none",
        # enumeration order is the order build_mesh lays a ring over
        "devices": [
            {"id": d.id, "coords": getattr(d, "coords", None),
             # None where the backend reports nothing (XLA:CPU)
             "memory_stats": d.memory_stats()}
            for d in jax.local_devices()
        ],
        "compile_cache": {
            "dir": (jax.config.jax_compilation_cache_dir
                    if jax.config.jax_enable_compilation_cache else None),
            "hits": hits,
            "misses": misses,
        },
    }
    try:
        import psutil

        info["cpu_count"] = psutil.cpu_count()
        info["memory_gb"] = round(psutil.virtual_memory().total / 2**30, 2)
    except ImportError:
        pass
    return info
