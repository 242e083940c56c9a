"""Pallas flash attention vs the dense reference path.

Runs in pallas interpret mode on the CPU-simulated mesh (the kernel
auto-selects interpret off TPU); the same code path compiles natively on
a real chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.models.attention import dense_causal
from dlbb_tpu.ops import flash_attention


def _qkv(key, b, n, s, d, dtype):
    ks = jax.random.split(key, 3)
    shape = (b, n, s, d)
    return tuple(jax.random.normal(k, shape, dtype=dtype) for k in ks)


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (64, 128), (128, 64)])
def test_flash_matches_dense_fp32(block_q, block_k):
    q, k, v = _qkv(jax.random.key(0), 2, 2, 256, 64, jnp.float32)
    out = flash_attention(q, k, v, block_q=block_q, block_k=block_k)
    ref = dense_causal(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_matches_dense_bf16():
    q, k, v = _qkv(jax.random.key(1), 1, 4, 256, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = dense_causal(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_flash_noncausal_matches_softmax():
    q, k, v = _qkv(jax.random.key(2), 1, 2, 128, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    d = q.shape[-1]
    logits = jnp.einsum("bnqd,bnkd->bnqk", q, k) / jnp.sqrt(jnp.float32(d))
    ref = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_grads_match_dense():
    q, k, v = _qkv(jax.random.key(3), 1, 2, 128, 64, jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_causal(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name} mismatch",
        )


@pytest.mark.parametrize("kvh", [1, 2, 4])
def test_flash_gqa_matches_grouped_dense(kvh):
    """Grouped K/V ([B, kv_heads, S, D]) through the kernel == dense
    grouped attention; K/V never materialise at num_heads width."""
    from dlbb_tpu.models.attention import dense_attention

    b, n, s, d = 1, 8, 128, 64
    ks = jax.random.split(jax.random.key(10), 3)
    q = jax.random.normal(ks[0], (b, n, s, d))
    k = jax.random.normal(ks[1], (b, kvh, s, d))
    v = jax.random.normal(ks[2], (b, kvh, s, d))
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # and == the repeated-K/V MHA oracle
    ref_rep = dense_causal(q, jnp.repeat(k, n // kvh, 1),
                           jnp.repeat(v, n // kvh, 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_rep),
                               atol=2e-5, rtol=2e-5)


def test_flash_gqa_grads_match_dense():
    """dk/dv of the grouped kernel accumulate over the sharing query heads
    and stay at kv_heads width; all three grads match the dense grouped
    path."""
    from dlbb_tpu.models.attention import dense_attention

    b, n, kvh, s, d = 1, 4, 2, 128, 64
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, n, s, d))
    k = jax.random.normal(ks[1], (b, kvh, s, d))
    v = jax.random.normal(ks[2], (b, kvh, s, d))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    assert g_flash[1].shape == (b, kvh, s, d)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name} mismatch",
        )


def test_flash_gqa_noncausal():
    from dlbb_tpu.models.attention import dense_attention

    b, n, kvh, s, d = 1, 4, 2, 128, 64
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (b, n, s, d))
    k = jax.random.normal(ks[1], (b, kvh, s, d))
    v = jax.random.normal(ks[2], (b, kvh, s, d))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_model_forward_flash_matches_full():
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.models.transformer import forward, init_params

    kw = dict(hidden_size=128, num_layers=2, num_heads=2,
              ffn_intermediate=256, dtype="float32")
    cfg_full = ModelConfig(attention="full", **kw)
    cfg_flash = ModelConfig(attention="flash", **kw)
    params = init_params(cfg_full, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 128, 128))
    out_full = forward(params, x, cfg_full)
    out_flash = forward(params, x, cfg_flash)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_full),
                               atol=1e-4, rtol=1e-4)


def test_flash_autofits_indivisible_seq():
    # S=96 doesn't divide the requested 64 block — the kernel falls back to
    # the largest divisor (48) instead of failing
    q, k, v = _qkv(jax.random.key(4), 1, 1, 96, 64, jnp.float32)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = dense_causal(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_kv_cache_decode():
    # sk > s: the single query row is the LAST position and must attend to
    # the whole cache (diagonal anchored at the end of the key axis)
    b, n, sk, d = 1, 2, 128, 64
    key = jax.random.key(5)
    q_full, k, v = _qkv(key, b, n, sk, d, jnp.float32)
    ref_full = dense_causal(q_full, k, v)
    q_last = q_full[:, :, -1:, :]
    out = flash_attention(q_last, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(
        np.asarray(out[:, :, 0]), np.asarray(ref_full[:, :, -1]),
        atol=2e-5, rtol=2e-5,
    )


def test_flash_tp_shard_map_matches_unsharded(mesh2x4):
    from jax.sharding import PartitionSpec as P

    from dlbb_tpu.compat import shard_map

    q, k, v = _qkv(jax.random.key(6), 2, 4, 128, 64, jnp.float32)
    spec = P("dp", "tp", None, None)
    out_sharded = shard_map(
        lambda q, k, v: flash_attention(q, k, v),
        mesh=mesh2x4, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
    ref = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out_sharded), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_grads_fully_masked_rows_zero():
    """sk < s with causal: rows r with r + (sk - s) < 0 attend to nothing —
    forward emits zeros there and the backward must emit zero gradients
    (regression: p = exp(NEG_INF - NEG_INF) = 1 injected garbage)."""
    b, n, s, d = 1, 2, 128, 64
    sk = 64  # rows 0..63 are fully masked (offset = -64)
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, n, s, d))
    k = jax.random.normal(ks[1], (b, n, sk, d))
    v = jax.random.normal(ks[2], (b, n, sk, d))

    out = flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_array_equal(np.asarray(out[:, :, :64]), 0.0)

    def dense_ref(q, k, v):
        dd = q.shape[-1]
        logits = jnp.einsum("bnqd,bnkd->bnqk", q, k) / jnp.sqrt(jnp.float32(dd))
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(sk)[None, :]
        mask = rows + (sk - s) >= cols
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, -1)
        p = jnp.where(jnp.any(mask, -1, keepdims=True), p, 0.0)
        return jnp.einsum("bnqk,bnkd->bnqd", p, v)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_ref(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    # masked q rows get exactly zero gradient
    np.testing.assert_array_equal(np.asarray(g_flash[0][:, :, :64]), 0.0)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name} mismatch",
        )


def test_flash_dp_only_mesh_no_allgather(devices):
    """On a dp-only mesh, flash attention must go through shard_map so the
    batch stays sharded — the compiled forward contains no all-gather
    (regression: bare pallas_call made GSPMD replicate the batch)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.models.transformer import forward, init_params

    mesh = build_mesh(MeshSpec.grid((8,), ("dp",)))
    cfg = ModelConfig(hidden_size=128, num_layers=1, num_heads=2,
                      ffn_intermediate=256, attention="flash", dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), (8, 128, 128)),
        NamedSharding(mesh, P("dp")),
    )
    lowered = jax.jit(lambda p, x: forward(p, x, cfg, mesh=mesh)).lower(params, x)
    hlo = lowered.compile().as_text()
    assert "all-gather" not in hlo, "dp-sharded flash forward all-gathers"

    # and numerics still match the unsharded run
    out = jax.jit(lambda p, x: forward(p, x, cfg, mesh=mesh))(params, x)
    ref = forward(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_flash_rejects_sequence_parallel_mesh(devices):
    from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.models.transformer import forward, init_params

    mesh = build_mesh(MeshSpec.grid((4, 2), ("sp", "tp")))
    cfg = ModelConfig(hidden_size=64, num_layers=1, num_heads=2,
                      ffn_intermediate=128, attention="flash", dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 64, 64))
    with pytest.raises(ValueError, match="ring"):
        forward(params, x, cfg, mesh=mesh)


def test_flash_kernels_carry_their_names_forward_and_backward():
    """Each ``pl.pallas_call`` has a ``name=`` and runs under a scope of
    the same name, so a device trace tells forward, dq and dkv apart."""
    import re

    from dlbb_tpu.ops.flash_attention import KERNEL_NAMES

    assert KERNEL_NAMES == ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    q = k = v = jnp.ones((1, 2, 128, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).as_text(debug_info=True)
    for name in KERNEL_NAMES:
        assert f"name={name}" in jaxpr, name      # the pallas_call's own
        # the scope (under jvp()/transpose() when differentiated),
        # then the kernel's own name
        assert re.search(rf"{name}\)+/{name}/pallas_call", text), name
