"""TP transformer correctness on the simulated mesh.

Key property the reference cannot test (it has no single-rank reference
implementation): TP-sharded execution must produce the same numbers as
single-device execution — the sharding layout only changes *where* compute
happens, XLA's inserted all-reduces replacing the reference's hand-written
``comm.Allreduce`` (``models.py:95``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.models import (
    MODEL_CONFIGS,
    ModelConfig,
    forward,
    init_params,
    num_parameters,
    shard_params,
)
from dlbb_tpu.models.sharding import batch_spec
from jax.sharding import NamedSharding

TINY = ModelConfig(hidden_size=64, num_layers=3, num_heads=4,
                   ffn_intermediate=128, attention="full", dtype="float32")


def _batch(cfg, b=2, s=16, dtype=jnp.float32, seed=0):
    return jax.random.normal(
        jax.random.key(seed), (b, s, cfg.hidden_size), dtype=dtype
    )


def test_forward_shapes_and_dtype():
    params = init_params(TINY, jax.random.key(1))
    x = _batch(TINY)
    y = forward(params, x, TINY)
    assert y.shape == x.shape
    assert y.dtype == x.dtype
    assert np.isfinite(np.asarray(y)).all()


def test_gqa_forward_and_param_accounting():
    """Grouped-query attention: smaller QKV projection, same output shape;
    num_parameters matches the actual pytree; MQA (kv=1) included."""
    for kv in (2, 1):
        cfg = TINY.with_(num_kv_heads=kv)
        assert cfg.qkv_width == cfg.hidden_size + 2 * kv * cfg.head_dim
        params = init_params(cfg, jax.random.key(1))
        qkv_kernel = params["layers"]["qkv"]["kernel"]
        assert qkv_kernel.shape == (
            cfg.num_layers, cfg.hidden_size, cfg.qkv_width
        )
        counted = sum(int(x.size) for x in jax.tree.leaves(params))
        assert counted == num_parameters(cfg)
        y = forward(params, _batch(cfg), cfg)
        assert y.shape == (2, 16, cfg.hidden_size)
        assert np.isfinite(np.asarray(y)).all()


def test_grouped_dense_attention_matches_repeat():
    """dense_attention with kv_heads-width K/V == MHA over repeated K/V —
    the no-materialised-repeat GQA path is numerically identical."""
    from dlbb_tpu.models.attention import dense_attention

    q = jax.random.normal(jax.random.key(0), (2, 8, 16, 4))
    k = jax.random.normal(jax.random.key(1), (2, 2, 16, 4))
    v = jax.random.normal(jax.random.key(2), (2, 2, 16, 4))
    for causal in (True, False):
        got = np.asarray(dense_attention(q, k, v, causal=causal))
        want = np.asarray(dense_attention(
            q, jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1),
            causal=causal,
        ))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_gqa_full_group_matches_mha():
    """num_kv_heads == num_heads is exactly MHA — same params, same output."""
    cfg_mha = TINY
    cfg_gqa = TINY.with_(num_kv_heads=TINY.num_heads)
    params = init_params(cfg_mha, jax.random.key(1))
    x = _batch(cfg_mha)
    np.testing.assert_array_equal(
        np.asarray(forward(params, x, cfg_mha)),
        np.asarray(forward(params, x, cfg_gqa)),
    )


def test_non_causal_attention():
    """causal=False: bidirectional dense attention — output differs from
    causal, matches a manual fp32 softmax reference, and the dense/ulysses
    kernels agree (ulysses covered in test_context_parallel)."""
    from dlbb_tpu.models.attention import dense_attention

    cfg = TINY.with_(causal=False)
    params = init_params(cfg, jax.random.key(1))
    x = _batch(cfg)
    y_bi = np.asarray(forward(params, x, cfg))
    y_causal = np.asarray(forward(params, x, TINY))
    assert np.isfinite(y_bi).all()
    assert not np.allclose(y_bi, y_causal)

    from conftest import dense_attention_ref

    q = jax.random.normal(jax.random.key(3), (2, 4, 8, 16))
    k = jax.random.normal(jax.random.key(4), (2, 4, 8, 16))
    v = jax.random.normal(jax.random.key(5), (2, 4, 8, 16))
    got = np.asarray(dense_attention(q, k, v, causal=False))
    want = dense_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ring_non_causal_accepted():
    """Bidirectional ring attention is a supported combination (oracle
    parity in tests/test_context_parallel.py); 'dense' is the explicit
    always-einsum mode and unknown modes still reject."""
    cfg = TINY.with_(attention="ring", causal=False)
    assert not cfg.causal
    assert TINY.with_(attention="dense").attention == "dense"
    with pytest.raises(ValueError, match="unknown attention"):
        TINY.with_(attention="sparse")


@pytest.mark.parametrize("attention", ["full", "simplified", "flash"])
def test_tp_matches_single_device(mesh2x4, attention):
    """Sharded == unsharded, across attention modes (flash exercises the
    shard_map-over-tp kernel dispatch)."""
    cfg = TINY.with_(attention=attention)
    params = init_params(cfg, jax.random.key(1))
    x = _batch(cfg)
    y_single = forward(params, x, cfg)

    sharded = shard_params(params, mesh2x4)
    xs = jax.device_put(x, NamedSharding(mesh2x4, batch_spec()))
    y_tp = jax.jit(lambda p, a: forward(p, a, cfg, mesh=mesh2x4))(sharded, xs)
    np.testing.assert_allclose(
        np.asarray(y_single), np.asarray(y_tp), rtol=2e-3, atol=2e-3
    )


def test_causal_masking():
    """Full attention must be causal: truncating the suffix of the sequence
    cannot change the prefix outputs."""
    cfg = TINY
    params = init_params(cfg, jax.random.key(1))
    x = _batch(cfg, b=1, s=16)
    full = np.asarray(forward(params, x, cfg))
    trunc = np.asarray(forward(params, x[:, :8], cfg))
    np.testing.assert_allclose(full[:, :8], trunc, rtol=2e-4, atol=2e-4)


def test_simplified_attention_is_query_slice():
    """Simplified mode takes the first third of QKV (reference
    ``models.py:162-167``), so outputs differ from full attention."""
    params = init_params(TINY, jax.random.key(1))
    x = _batch(TINY)
    y_full = np.asarray(forward(params, x, TINY))
    y_simpl = np.asarray(
        forward(params, x, TINY.with_(attention="simplified"))
    )
    assert not np.allclose(y_full, y_simpl)


def test_num_parameters_matches_pytree():
    params = init_params(TINY, jax.random.key(0))
    actual = sum(p.size for p in jax.tree.leaves(params))
    assert num_parameters(TINY) == actual


def test_reference_model_sizes():
    """1B/7B/13B configs (reference ``models.py:252-271``) have the expected
    parameter scale."""
    sizes = {k: num_parameters(v) for k, v in MODEL_CONFIGS.items()}
    assert 1.0e9 < sizes["1B"] < 1.5e9
    assert 6.0e9 < sizes["7B"] < 8.5e9
    assert 11.5e9 < sizes["13B"] < 14.5e9


def test_invalid_config_rejected():
    with pytest.raises(ValueError):
        ModelConfig(hidden_size=100, num_layers=1, num_heads=3,
                    ffn_intermediate=64)
    with pytest.raises(ValueError):
        ModelConfig(hidden_size=64, num_layers=1, num_heads=4,
                    ffn_intermediate=64, attention="flash??")


def test_remat_matches_no_remat(devices):
    """Activation rematerialisation must not change forward or gradient
    numerics — it only changes what is stored vs recomputed."""
    from dlbb_tpu.train.loop import mse_loss

    remat_cfg = TINY.with_(remat=True)
    params = init_params(TINY, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 8, TINY.hidden_size))
    t = jax.random.normal(jax.random.key(2), (4, 8, TINY.hidden_size))

    y_plain = jax.jit(lambda p, x: forward(p, x, TINY))(params, x)
    y_remat = jax.jit(lambda p, x: forward(p, x, remat_cfg))(params, x)
    np.testing.assert_allclose(np.asarray(y_plain), np.asarray(y_remat),
                               rtol=1e-6, atol=1e-6)

    g_plain = jax.jit(
        lambda p, x, t: jax.grad(mse_loss)(p, x, t, TINY)
    )(params, x, t)
    g_remat = jax.jit(
        lambda p, x, t: jax.grad(mse_loss)(p, x, t, remat_cfg)
    )(params, x, t)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    # the "dots" policy (save matmul outputs, recompute elementwise only)
    # is a scheduling choice, never a numerics choice
    dots_cfg = TINY.with_(remat=True, remat_policy="dots")
    g_dots = jax.jit(
        lambda p, x, t: jax.grad(mse_loss)(p, x, t, dots_cfg)
    )(params, x, t)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_dots)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="remat_policy"):
        TINY.with_(remat_policy="selective??")


def test_forward_flops_accounting():
    """Analytic FLOPs: spot-check the dense formula and the mode
    relationships (simplified < full; capacity < dense MoE)."""
    from dlbb_tpu.models.transformer import forward_flops

    h, f, L = TINY.hidden_size, TINY.ffn_intermediate, TINY.num_layers
    b, s = 4, 8
    expected = L * (
        2 * b * s * h * 3 * h          # qkv
        + 4 * b * s * s * h            # QK^T + AV
        + 2 * b * s * h * h            # out proj
        + 2 * b * s * h * f * 2        # ffn
    )
    assert forward_flops(TINY, b, s) == expected
    assert (forward_flops(TINY.with_(attention="simplified"), b, s)
            < forward_flops(TINY, b, s))
    moe = TINY.with_(num_experts=4, moe_top_k=2)
    cap = moe.with_(moe_dispatch="capacity", moe_capacity_factor=1.0)
    assert forward_flops(cap, b, s) < forward_flops(moe, b, s)


def test_tp_forward_compiles_megatron_allreduce_pattern(devices):
    """The reference hand-writes two all-reduces per decoder layer
    (attention-out + FFN-out row-parallel matmuls, ``models.py:95``);
    here they are DECLARED via weight PartitionSpecs and must appear in
    the compiled program — all-reduce ops inside the scanned layer body
    under TP, and none at all without TP."""
    import re

    from dlbb_tpu.models.transformer import init_params_sharded
    from dlbb_tpu.parallel.plan import build_parallelism_mesh

    cfg = TINY.with_(attention="simplified", dtype="float32")

    def compiled_hlo(tp):
        mesh = build_parallelism_mesh(1, 1, 1, tp, 1)
        params = init_params_sharded(cfg, jax.random.key(0), mesh)
        x = jnp.zeros((2, 8, cfg.hidden_size))
        return jax.jit(
            lambda p, b: forward(p, b, cfg)
        ).lower(params, x).compile().as_text()

    hlo_tp = compiled_hlo(4)
    hlo_single = compiled_hlo(1)
    assert "while" in hlo_tp  # layers execute under lax.scan
    # the all-reduces must live INSIDE the scanned layer body (the while
    # loop's called computations), not hoisted to top level — extract the
    # non-entry computations and look there
    body_text = hlo_tp.split("ENTRY")[0]
    assert len(re.findall(r"\ball-reduce", body_text)) >= 2, \
        "TP forward compiled without the Megatron all-reduces in the " \
        "scanned layer body"
    assert "all-reduce" not in hlo_single, \
        "single-device forward must need no collectives"


def test_forward_program_is_named_and_carries_every_phase(mesh2x4):
    """The e2e harness's jitted forward is ``forward`` in a device
    trace, and its lowered text carries every phase scope of the block
    — the names a trace reduction groups device time by."""
    import re

    from dlbb_tpu.bench.e2e import build_forward_step
    from dlbb_tpu.models.transformer import BLOCK_PHASES

    params = shard_params(init_params(TINY, jax.random.key(1)), mesh2x4)
    step = build_forward_step(TINY, mesh2x4)
    assert step.__wrapped__.__name__ == "forward"
    text = step.lower(params, _batch(TINY)).as_text(debug_info=True)
    assert "module @jit_forward" in text
    for phase in BLOCK_PHASES:
        assert re.search(rf'[/("]{phase}/', text), phase
