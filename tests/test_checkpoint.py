"""Checkpoint / resume tests (orbax-backed; no reference analogue —
SURVEY §5.4 records the reference has none)."""

import jax
import numpy as np
import optax
import pytest

from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.models.transformer import init_params
from dlbb_tpu.train.checkpoint import (
    CheckpointConfig,
    Checkpointer,
    latest_step,
)
from dlbb_tpu.train.loop import make_train_step, run_train

TINY = ModelConfig(hidden_size=32, num_layers=2, num_heads=4,
                   ffn_intermediate=64, attention="full", dtype="float32")


def _setup(zero1=False):
    mesh = build_mesh(MeshSpec.grid((4, 2), ("dp", "tp")))
    params = init_params(TINY, jax.random.key(0))
    jit_step, state = make_train_step(
        TINY, mesh, optax.adam(1e-2), params, zero1=zero1
    )
    x = jax.random.normal(jax.random.key(1), (8, 16, 32))
    y = jax.random.normal(jax.random.key(2), (8, 16, 32))
    return jit_step, state, x, y


@pytest.mark.parametrize("zero1", [False, True])
def test_save_restore_roundtrip(devices, tmp_path, zero1):
    """Restored state is bit-identical (values + shardings) to the saved
    state — including the dp-sharded ZeRO-1 optimizer state."""
    jit_step, state, x, y = _setup(zero1)
    for _ in range(3):
        state, _ = jit_step(state, x, y)

    with Checkpointer(CheckpointConfig(str(tmp_path / "ck"))) as ckpt:
        assert ckpt.maybe_save(state, force=True)
        restored = ckpt.restore(state)

    assert int(restored.step) == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.sharding == b.sharding, (a.sharding, b.sharding)


def test_save_restore_pp_ep_mesh(devices, tmp_path):
    """Checkpointing preserves shardings on a pp x ep mesh too (MoE model
    with the layer stack sharded across pipeline stages and experts
    sharded over ep, ZeRO-3)."""
    mesh = build_mesh(MeshSpec.grid((2, 2, 2), ("dp", "pp", "ep")))
    moe = TINY.with_(num_experts=4, moe_top_k=2)
    params = init_params(moe, jax.random.key(0))
    jit_step, state = make_train_step(
        moe, mesh, optax.adam(1e-2), params, zero_stage=3,
    )
    x = jax.random.normal(jax.random.key(1), (8, 16, 32))
    y = jax.random.normal(jax.random.key(2), (8, 16, 32))
    state, _ = jit_step(state, x, y)

    with Checkpointer(CheckpointConfig(str(tmp_path / "ck"))) as ckpt:
        assert ckpt.maybe_save(state, force=True)
        restored = ckpt.restore(state)

    assert int(restored.step) == 1
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.sharding == b.sharding, (a.sharding, b.sharding)


def test_resume_continues_trajectory(devices, tmp_path):
    """save at step k, keep training to step n; a fresh state restored from
    the checkpoint and stepped n-k more times lands on the same losses."""
    jit_step, state, x, y = _setup()
    for _ in range(2):
        state, _ = jit_step(state, x, y)

    with Checkpointer(CheckpointConfig(str(tmp_path / "ck"))) as ckpt:
        ckpt.maybe_save(state, force=True)

        ref_losses = []
        for _ in range(3):
            state, loss = jit_step(state, x, y)
            ref_losses.append(float(loss))

        # fresh (wrong) state, resumed from the checkpoint
        _, fresh, _, _ = _setup()
        resumed = ckpt.restore_or(fresh)
    assert int(resumed.step) == 2
    res_losses = []
    for _ in range(3):
        resumed, loss = jit_step(resumed, x, y)
        res_losses.append(float(loss))
    np.testing.assert_allclose(res_losses, ref_losses, rtol=1e-5)


def test_restore_or_passthrough(devices, tmp_path):
    """No checkpoint on disk -> restore_or returns the input unchanged."""
    _, state, _, _ = _setup()
    with Checkpointer(CheckpointConfig(str(tmp_path / "empty"))) as ckpt:
        out = ckpt.restore_or(state)
    assert out is state
    assert latest_step(str(tmp_path / "missing")) is None


def test_retention_policy(devices, tmp_path):
    """max_to_keep prunes old steps; save_interval_steps skips saves."""
    jit_step, state, x, y = _setup()
    cfg = CheckpointConfig(
        str(tmp_path / "ck"), save_interval_steps=2, max_to_keep=2
    )
    with Checkpointer(cfg) as ckpt:
        for _ in range(6):
            state, _ = jit_step(state, x, y)
            ckpt.maybe_save(state)
        ckpt.wait()
        assert ckpt.latest_step() == 6
        steps = sorted(ckpt._mgr.all_steps())
    assert steps == [4, 6], steps  # interval=2 -> 2,4,6; keep last 2


def test_run_train_resume_via_config(devices, tmp_path):
    """Config-driven flow: a second run_train with the same checkpoint dir
    resumes where the first left off."""
    config = {
        "experiment": {"name": "ck_smoke"},
        "model": {
            "hidden_size": 32, "num_layers": 2, "num_heads": 4,
            "ffn_intermediate": 64, "attention": "full", "dtype": "float32",
        },
        "parallelism": {"world_size": 2, "data_parallel": 4},
        "input": {"batch_size": 8, "sequence_length": 16, "seed": 42},
        "execution": {"warmup_iterations": 1, "benchmark_iterations": 3},
        "training": {
            "learning_rate": 1e-2,
            "checkpoint": {"directory": str(tmp_path / "ck")},
        },
    }
    r1 = run_train(config, verbose=False)
    assert r1["resumed_from_step"] is None
    assert r1["final_step"] == 4  # 1 warmup + 3 measured

    r2 = run_train(config, verbose=False)
    assert r2["resumed_from_step"] == 4
    assert r2["final_step"] == 8
    # resumed run continues the optimisation, not restarts it
    assert r2["losses"][0] < r1["losses"][0]


def test_checkpoint_disabled_no_restore(devices, tmp_path):
    """enabled: false must disable the whole subsystem — a stale checkpoint
    in the directory is neither restored nor overwritten."""
    config = {
        "experiment": {"name": "ck_disabled"},
        "model": {
            "hidden_size": 32, "num_layers": 2, "num_heads": 4,
            "ffn_intermediate": 64, "attention": "full", "dtype": "float32",
        },
        "parallelism": {"world_size": 2, "data_parallel": 4},
        "input": {"batch_size": 8, "sequence_length": 16, "seed": 42},
        "execution": {"warmup_iterations": 1, "benchmark_iterations": 2},
        "training": {
            "learning_rate": 1e-2,
            "checkpoint": {"directory": str(tmp_path / "ck")},
        },
    }
    r1 = run_train(config, verbose=False)
    assert r1["final_step"] == 3

    config["training"]["checkpoint"]["enabled"] = False
    r2 = run_train(config, verbose=False)
    assert r2["resumed_from_step"] is None
    assert r2["final_step"] == 3  # fresh run, not resumed
    assert latest_step(str(tmp_path / "ck")) == 3  # stale ckpt untouched
