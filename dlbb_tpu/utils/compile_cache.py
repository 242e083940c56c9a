"""The one place the persistent XLA compilation cache is configured.

:func:`configure_compile_cache` is called first thing by ``cli.main`` for
every device command (so also by every child of ``chip_smoke.py``), by
``bench.py`` and by ``run_sweep``.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already read it at import; the
  directory is left alone.  Nothing in this repo updates
  ``jax_compilation_cache_dir`` on that path, not even to restore it.
- Not set: ``<checkout>/.jax_cache`` (gitignored), resolved from this
  package's location and never from the working directory — the path is
  part of the cache key, so a directory that moves never hits.

On the TPU the cache is process-wide: the long compiles are the e2e, train
and serving programs, not the sweeps.  On the CPU-simulated mesh
``force_cpu_simulation`` switches it off and it is on only inside a sweep
(:func:`sweep_scope`): XLA:CPU on jaxlib 0.9.0 aborts the process
(``Fatal Python error: Aborted``, no message) when it *executes* some
non-sweep programs deserialised from a warm cache — observed
deterministically on the second run of
``tests/test_train.py::test_zero23_matches_ddp_numerics[3]`` against one
cache directory.  Sweep programs (shard_map collectives and the chained
timing loop) round-trip fine.  The switch is
``jax_enable_compilation_cache`` — never the directory — plus a
``reset_cache()``, because JAX latches "is the cache used" at the first
compile of the process (``compilation_cache._cache_checked``).
"""

from __future__ import annotations

import contextlib
import os
import threading
from pathlib import Path
from typing import Any, Iterator, Optional

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at its directory (see module
    docstring), cache every program however small, and start counting
    hit/miss events.  Idempotent.  Returns the directory."""
    import jax

    if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        _reset_latch()  # a compile before this call latched "no cache"
    # the thresholds are zeroed: the serving engine and the sweeps compile
    # many sub-second programs, and a second run should recompile none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    CACHE_EVENTS.ensure_registered()
    return jax.config.jax_compilation_cache_dir


def _reset_latch() -> None:
    """Clear JAX's first-compile "is the cache used" latch (module
    docstring) so a changed setting takes effect."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cc.reset_cache()


def _set_enabled(enabled: bool) -> None:
    import jax

    jax.config.update("jax_enable_compilation_cache", enabled)
    _reset_latch()


@contextlib.contextmanager
def sweep_scope(setting: Optional[str] = "auto") -> Iterator[Optional[str]]:
    """The cache as one sweep sees it: on for ``"auto"`` (also on the
    simulated mesh, where it is otherwise off), off for ``None``/``"off"``
    (the chaos gate, which must see real compiles).  Yields the cache
    directory, or None when off; restores the process's setting on exit."""
    import jax

    if setting not in ("auto", "off", None):
        raise ValueError(
            f"compile_cache={setting!r}: only 'auto' and 'off' are accepted; "
            "the directory comes from JAX_COMPILATION_CACHE_DIR (default "
            f"{DEFAULT_CACHE_DIR})"
        )
    want = setting == "auto"
    cache_dir = configure_compile_cache()
    before = bool(jax.config.jax_enable_compilation_cache)
    if want != before:
        _set_enabled(want)
    try:
        yield cache_dir if want else None
    finally:
        if want != before:
            _set_enabled(before)


class _CacheEventCounter:
    """Counts JAX persistent-compilation-cache hit/miss monitoring events.

    ``jax.monitoring`` listeners are global and cannot be unregistered, so
    one process-wide counter is registered once; compile sites that need
    per-program attribution sample it before/after (the sweep scheduler,
    under its compile lock), and ``collect_system_info`` records the
    process totals in every artifact.
    """

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self._registered = False
        self._lock = threading.Lock()

    def ensure_registered(self) -> None:
        with self._lock:
            if self._registered:
                return
            from jax import monitoring

            def _listener(event: str, **kwargs: Any) -> None:
                if event == self.HIT:
                    self.hits += 1
                elif event == self.MISS:
                    self.misses += 1

            monitoring.register_event_listener(_listener)
            self._registered = True

    def snapshot(self) -> tuple[int, int]:
        return self.hits, self.misses


CACHE_EVENTS = _CacheEventCounter()
