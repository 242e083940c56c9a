"""Tracing/profiling subsystem tests (XLA-profiler analogue of the
reference's CCL_LOG_LEVEL / I_MPI_DEBUG env tracing, SURVEY §5.1)."""

import os

import jax
import jax.numpy as jnp

from dlbb_tpu.utils.profiling import (
    annotate,
    default_trace_dir,
    maybe_trace,
)


def _xplane_files(root):
    return [
        os.path.join(dirpath, f)
        for dirpath, _, files in os.walk(root)
        for f in files
        if f.endswith(".xplane.pb")
    ]


def test_maybe_trace_writes_xplane(devices, tmp_path):
    trace_dir = str(tmp_path / "trace")
    with maybe_trace(trace_dir) as resolved:
        assert resolved == trace_dir
        with annotate("measure"):
            for i in range(2):
                with annotate("step", step=i):
                    y = jax.jit(lambda x: x @ x)(jnp.ones((64, 64)))
                    jax.block_until_ready(y)
    assert _xplane_files(trace_dir), "no xplane trace emitted"


def test_maybe_trace_noop_without_dir(devices, tmp_path, monkeypatch):
    monkeypatch.delenv("DLBB_TRACE_DIR", raising=False)
    assert default_trace_dir() is None
    with maybe_trace(None) as resolved:
        assert resolved is None
    assert list(tmp_path.iterdir()) == []


def test_maybe_trace_env_default(devices, tmp_path, monkeypatch):
    trace_dir = str(tmp_path / "envtrace")
    monkeypatch.setenv("DLBB_TRACE_DIR", trace_dir)
    with maybe_trace(None) as resolved:
        assert resolved == trace_dir
        jax.block_until_ready(jnp.ones((8, 8)) * 2)
    assert _xplane_files(trace_dir)


def test_cli_train_with_trace(devices, tmp_path):
    """--trace on the CLI wraps the whole run and emits a trace."""
    import yaml

    from dlbb_tpu.cli import main

    cfg = {
        "experiment": {"name": "trace_smoke"},
        "model": {
            "hidden_size": 32, "num_layers": 1, "num_heads": 2,
            "ffn_intermediate": 64, "attention": "full", "dtype": "float32",
        },
        "parallelism": {"world_size": 2, "data_parallel": 2},
        "input": {"batch_size": 4, "sequence_length": 8, "seed": 42},
        "execution": {"warmup_iterations": 1, "benchmark_iterations": 2},
        "training": {"learning_rate": 1e-2},
    }
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))
    trace_dir = str(tmp_path / "clitrace")
    rc = main([
        "train", "--config", str(cfg_path), "--trace", trace_dir,
        "--output", str(tmp_path / "out"),
    ])
    assert rc == 0
    assert _xplane_files(trace_dir)
