"""Pipeline-parallelism tests: exactness of the GPipe engine vs the plain
layer scan, composition with dp/tp, and the training path (capability
extension — the reference has no PP, SURVEY §2.2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.models.transformer import forward, init_params, shard_params
from dlbb_tpu.parallel.pipeline import validate_pipeline
from dlbb_tpu.train.loop import run_train

TINY = ModelConfig(hidden_size=32, num_layers=4, num_heads=4,
                   ffn_intermediate=64, attention="full", dtype="float32")


def _x(batch=8, seq=16, hidden=32, seed=1):
    return jax.random.normal(jax.random.key(seed), (batch, seq, hidden),
                             dtype=jnp.float32)


def test_pipeline_matches_single_device(devices):
    """pp=4 pipeline output must equal the unsharded layer scan exactly."""
    params = init_params(TINY, jax.random.key(0))
    x = _x()
    y_ref = jax.jit(lambda p, x: forward(p, x, TINY))(params, x)

    mesh = build_mesh(MeshSpec.grid((4,), ("pp",)))
    params_pp = shard_params(params, mesh)
    y_pp = jax.jit(
        lambda p, x: forward(p, x, TINY, mesh=mesh)
    )(params_pp, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pp),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_with_dp_tp(devices):
    """pp composes with dp and tp on a (dp=2, pp=2, tp=2) mesh."""
    params = init_params(TINY, jax.random.key(0))
    x = _x()
    y_ref = jax.jit(lambda p, x: forward(p, x, TINY))(params, x)

    mesh = build_mesh(MeshSpec.grid((2, 2, 2), ("dp", "pp", "tp")))
    params_s = shard_params(params, mesh)
    y = jax.jit(lambda p, x: forward(p, x, TINY, mesh=mesh))(params_s, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_microbatch_count(devices):
    """More microbatches than stages (bubble amortisation) stays exact."""
    params = init_params(TINY, jax.random.key(0))
    x = _x()
    y_ref = jax.jit(lambda p, x: forward(p, x, TINY))(params, x)

    mesh = build_mesh(MeshSpec.grid((2,), ("pp",)))
    params_pp = shard_params(params, mesh)
    y = jax.jit(
        lambda p, x: forward(p, x, TINY, mesh=mesh, num_microbatches=8)
    )(params_pp, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def _train_config(pp=1):
    cfg = {
        "experiment": {"name": "train_pp"},
        "model": {
            "hidden_size": 32, "num_layers": 4, "num_heads": 4,
            "ffn_intermediate": 64, "attention": "full", "dtype": "float32",
        },
        "parallelism": {"world_size": 2, "data_parallel": 2},
        "input": {"batch_size": 8, "sequence_length": 16, "seed": 42},
        "execution": {"warmup_iterations": 1, "benchmark_iterations": 4},
        "training": {"learning_rate": 1e-2},
    }
    if pp > 1:
        cfg["parallelism"]["pipeline_parallel"] = pp
    return cfg


def test_pipeline_train_matches_plain(devices):
    """The pipelined train step must follow the same optimisation
    trajectory as the unpipelined one (same global math)."""
    r_plain = run_train(_train_config(pp=1), verbose=False)
    r_pp = run_train(_train_config(pp=2), verbose=False)
    assert r_pp["mesh"]["pp"] == 2
    np.testing.assert_allclose(
        r_plain["losses"], r_pp["losses"], rtol=1e-4, atol=1e-5
    )


def test_pipeline_train_zero3(devices):
    """pp composes with ZeRO-3/FSDP: same trajectory as plain DDP."""
    r_plain = run_train(_train_config(pp=1), verbose=False)
    cfg = _train_config(pp=2)
    r = run_train(cfg, zero_stage=3, verbose=False)
    assert r["mode"] == "zero3" and r["mesh"]["pp"] == 2
    np.testing.assert_allclose(
        r_plain["losses"], r["losses"], rtol=1e-4, atol=1e-5
    )


def test_moe_pipeline_forward(devices):
    """MoE FFN inside the pipelined layer scan stays exact (pp x ep)."""
    moe = TINY.with_(num_experts=4, moe_top_k=2)
    params = init_params(moe, jax.random.key(0))
    x = _x()
    y_ref = jax.jit(lambda p, x: forward(p, x, moe))(params, x)

    mesh = build_mesh(MeshSpec.grid((2, 2, 2), ("dp", "pp", "ep")))
    params_s = shard_params(params, mesh)
    y = jax.jit(lambda p, x: forward(p, x, moe, mesh=mesh))(params_s, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_moe_pipeline_with_aux(devices):
    """with_aux under pp: the pipelined aux (per-stage masked accumulation
    + psum, averaged over layers x microbatches) equals the mean of the
    per-microbatch unpipelined auxes — and equals the unpipelined
    full-batch aux when every microbatch routes identically (the fixed
    test batch at m=1)."""
    moe = TINY.with_(num_experts=4, moe_top_k=2)
    params = init_params(moe, jax.random.key(0))
    x = _x()
    mesh = build_mesh(MeshSpec.grid((2, 2), ("pp", "ep")))
    params_s = shard_params(params, mesh)

    # m == batch-size 8 microbatches of 1 row: oracle = mean over rows
    y_pp, aux_pp = jax.jit(
        lambda p, a: forward(p, a, moe, mesh=mesh, num_microbatches=8,
                             with_aux=True)
    )(params_s, x)
    per_row = [
        float(forward(params, x[i:i + 1], moe, with_aux=True)[1])
        for i in range(8)
    ]
    np.testing.assert_allclose(float(aux_pp), np.mean(per_row),
                               rtol=1e-5, atol=1e-6)
    y_ref, _ = forward(params, x, moe, with_aux=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pp),
                               rtol=1e-5, atol=1e-5)


def test_moe_pipeline_train_with_aux_weight(devices):
    """MoE + pipeline + load-balancing loss trains end-to-end (the
    combination previously raised)."""
    cfg = _train_config(pp=2)
    cfg["model"].update(num_experts=4, moe_top_k=2)
    cfg["training"]["moe_aux_loss_weight"] = 0.01
    r = run_train(cfg, verbose=False)
    assert r["mesh"]["pp"] == 2
    assert all(np.isfinite(r["losses"]))
    assert r["losses"][-1] < r["losses"][0]


def test_1f1b_schedule_invariants():
    """The wavefront schedule: one-pair producer->consumer lag for both
    hops, every microbatch forwarded and backwarded exactly once per
    stage, and in-flight microbatches bounded by 2P-1 (the O(pp)
    activation live-range, independent of m)."""
    from dlbb_tpu.parallel.pipeline import schedule_1f1b

    for P, m in ((2, 4), (4, 8), (4, 4), (2, 2), (4, 2)):
        pairs, fwd, bwd = schedule_1f1b(P, m)
        assert pairs == m + 2 * (P - 1)
        for i in range(P):
            f_u = {int(fwd[u, i]): u for u in range(pairs)
                   if 0 <= fwd[u, i] < m}
            b_u = {int(bwd[u, i]): u for u in range(pairs)
                   if 0 <= bwd[u, i] < m}
            assert sorted(f_u) == sorted(b_u) == list(range(m))
            for q in range(m):
                # forward at or before backward (the last stage runs both
                # in one pair: the body's F part precedes its B part)
                assert f_u[q] <= b_u[q]
                if i > 0:  # activation produced one pair earlier upstream
                    f_up = {int(fwd[u, i - 1]): u for u in range(pairs)
                            if 0 <= fwd[u, i - 1] < m}
                    assert f_u[q] == f_up[q] + 1
                if i < P - 1:  # cotangent produced one pair earlier below
                    b_dn = {int(bwd[u, i + 1]): u for u in range(pairs)
                            if 0 <= bwd[u, i + 1] < m}
                    assert b_u[q] == b_dn[q] + 1
            inflight = max(
                sum(1 for q in range(m) if f_u[q] <= u < b_u[q])
                for u in range(pairs)
            )
            assert inflight <= 2 * P - 1


def test_1f1b_grads_match_unpipelined(devices):
    """pipeline_1f1b_grads == jax.value_and_grad of the unpipelined loss
    (same math; recompute-based backward; fp accumulation order differs)."""
    from dlbb_tpu.parallel.pipeline import pipeline_1f1b_grads
    from dlbb_tpu.train.loop import mse_loss

    params = init_params(TINY, jax.random.key(0))
    x, t = _x(seed=1), _x(seed=2)
    loss_ref, grads_ref = jax.value_and_grad(mse_loss)(params, x, t, TINY)

    mesh = build_mesh(MeshSpec.grid((4,), ("pp",)))
    ps = shard_params(params, mesh)
    loss_pp, grads_pp = jax.jit(
        lambda p, a, b: pipeline_1f1b_grads(p, a, b, TINY, mesh,
                                            num_microbatches=8)
    )(ps, x, t)
    np.testing.assert_allclose(float(loss_ref), float(loss_pp), rtol=1e-6)
    for (ka, ga), (kb, gb) in zip(
        jax.tree_util.tree_leaves_with_path(grads_ref),
        jax.tree_util.tree_leaves_with_path(grads_pp),
    ):
        np.testing.assert_allclose(
            np.asarray(ga), np.asarray(gb), rtol=1e-4, atol=1e-6,
            err_msg=str(ka),
        )


def test_1f1b_train_matches_gpipe(devices):
    """training.pipeline_schedule='1f1b' follows the same optimisation
    trajectory as GPipe autodiff and the unpipelined step."""
    r_plain = run_train(_train_config(pp=1), verbose=False)
    cfg = _train_config(pp=2)
    cfg["training"]["pipeline_schedule"] = "1f1b"
    r_1f1b = run_train(cfg, verbose=False)
    assert r_1f1b["pipeline_schedule"] == "1f1b"
    np.testing.assert_allclose(
        r_plain["losses"], r_1f1b["losses"], rtol=1e-4, atol=1e-5
    )


def test_1f1b_moe_aux_matches_gpipe(devices):
    """MoE + aux loss under 1F1B == the GPipe with_aux path (same
    per-microbatch aux averaging)."""
    base = _train_config(pp=2)
    base["model"].update(num_experts=4, moe_top_k=2)
    base["training"]["moe_aux_loss_weight"] = 0.01
    r_gpipe = run_train(base, verbose=False)
    cfg = _train_config(pp=2)
    cfg["model"].update(num_experts=4, moe_top_k=2)
    cfg["training"]["moe_aux_loss_weight"] = 0.01
    cfg["training"]["pipeline_schedule"] = "1f1b"
    r_1f1b = run_train(cfg, verbose=False)
    np.testing.assert_allclose(
        r_gpipe["losses"], r_1f1b["losses"], rtol=1e-4, atol=1e-5
    )


def test_1f1b_without_pp_rejected(devices):
    cfg = _train_config(pp=1)
    cfg["training"]["pipeline_schedule"] = "1f1b"
    with pytest.raises(ValueError, match="pipeline_parallel"):
        run_train(cfg, verbose=False)


def test_microbatches_without_pp_rejected(devices):
    """num_microbatches without pipeline_parallel must error, not be
    silently ignored."""
    cfg = _train_config(pp=1)
    cfg["parallelism"]["num_microbatches"] = 4
    with pytest.raises(ValueError, match="pipeline_parallel"):
        run_train(cfg, verbose=False)


def test_validate_pipeline_errors():
    with pytest.raises(ValueError, match="not divisible by"):
        validate_pipeline(TINY, 3, 8, None)  # 4 layers % 3 stages
    with pytest.raises(ValueError, match="num_microbatches"):
        validate_pipeline(TINY, 2, 8, 3)  # batch 8 % 3 microbatches
    ring = TINY.with_(attention="ring")
    with pytest.raises(ValueError, match="pipeline"):
        validate_pipeline(ring, 2, 8, None)
    assert validate_pipeline(TINY, 2, 8, None) == 2
    assert validate_pipeline(TINY, 2, 8, 4) == 4
