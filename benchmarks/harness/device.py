"""The chip as the benchmark sees it: the no-chip rule, the device record
of the result line, and the count of compilations inside a window."""

from __future__ import annotations

from typing import Any


class WrongDeviceError(RuntimeError):
    """Not an accelerator, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> None:
    """A cell runs on an accelerator with at least ``chips`` devices, or
    not at all: a CPU number under a device metric's name is worse than
    no number."""
    import jax

    from dlbb_tpu.utils.simulate import require_accelerator

    require_accelerator()
    if len(jax.devices()) < chips:
        raise WrongDeviceError(
            f"the cell needs {chips} chip(s), JAX finds "
            f"{len(jax.devices())}")


def device_record() -> dict[str, Any]:
    """``device`` of the result line, as JAX reports it;
    ``memory_peak_bytes`` is the peak on the fullest chip."""
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


class CompileCounter:
    """Counts every program JAX compiles or fetches from its persistent
    cache.  Either means a shape the warm-up missed, so the count must
    not move across a measured window.  ``jax.monitoring`` listeners
    cannot be removed, so make one per process."""

    _EVENTS = ("/jax/compilation_cache/cache_hits",
               "/jax/compilation_cache/cache_misses")
    _DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_: Any) -> None:
        if event in self._EVENTS:
            self.count += 1

    def _on_duration(self, event: str, _secs: float, **_kw: Any) -> None:
        if event in self._DURATIONS:
            self.count += 1
