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
    """Simplified mode takes the query projection (reference
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


# ---------------------------------------------------------------------------
# the fused qkv projection's column order
# ---------------------------------------------------------------------------
#
# ``init_params`` states the order: by kv-head group, a group's query
# heads, then its key head, then its value head.  These tests build the
# three projections apart, pack them as that sentence says, and compare
# with attention written out here from the three matrices — nothing
# below calls the package's own split.

# name -> (num_heads, kv_heads) at hidden 64
LAYOUTS = {"mha": (4, 4), "gqa": (8, 2), "mqa": (4, 1)}


def _layout_cfg(heads, attention="dense"):
    n, kvh = heads
    return ModelConfig(hidden_size=64, num_layers=2, num_heads=n,
                       num_kv_heads=kvh, ffn_intermediate=128,
                       attention=attention, dtype="float32")


def _separate_projections(cfg, seed=5):
    """Per layer: W_q [h, n, d], W_k, W_v [h, kvh, d] and their biases
    (non-zero, so a bias left in another order shows)."""
    rng = np.random.default_rng(seed)
    L, h, d = cfg.num_layers, cfg.hidden_size, cfg.head_dim
    out = {}
    for name, heads in (("q", cfg.num_heads), ("k", cfg.kv_heads),
                        ("v", cfg.kv_heads)):
        out["w" + name] = (rng.standard_normal((L, h, heads, d))
                           / np.sqrt(h)).astype(np.float32)
        out["b" + name] = (0.1 * rng.standard_normal((L, heads, d))
                           ).astype(np.float32)
    return out


def _packed_params(cfg, sep, seed=1):
    """``init_params``' tree with the fused qkv kernel and bias packed
    from the separate projections, group by group."""
    g = cfg.num_heads // cfg.kv_heads
    kernel, bias = [], []
    for j in range(cfg.kv_heads):
        for i in range(j * g, (j + 1) * g):          # the group's queries
            kernel.append(sep["wq"][:, :, i])
            bias.append(sep["bq"][:, i])
        kernel.append(sep["wk"][:, :, j])             # its key head
        bias.append(sep["bk"][:, j])
        kernel.append(sep["wv"][:, :, j])             # its value head
        bias.append(sep["bv"][:, j])
    params = init_params(cfg, jax.random.key(seed))
    qkv = params["layers"]["qkv"]
    packed = {"kernel": jnp.asarray(np.concatenate(kernel, axis=-1)),
              "bias": jnp.asarray(np.concatenate(bias, axis=-1))}
    assert packed["kernel"].shape == qkv["kernel"].shape
    assert packed["bias"].shape == qkv["bias"].shape
    params["layers"]["qkv"] = packed
    return params


def _written_out_forward(params, sep, x, cfg):
    """The model in numpy float64 from W_q, W_k, W_v: one head at a
    time, query head i with kv head i // (n / kvh), causal."""
    def ln(t, p, l=None):
        scale, bias = (np.asarray(p[k], np.float64) for k in
                       ("scale", "bias"))
        if l is not None:
            scale, bias = scale[l], bias[l]
        mu = t.mean(-1, keepdims=True)
        var = t.var(-1, keepdims=True)
        return (t - mu) / np.sqrt(var + 1e-5) * scale + bias

    def dense(t, p, l):
        return (t @ np.asarray(p["kernel"][l], np.float64)
                + np.asarray(p["bias"][l], np.float64))

    n, d, g = cfg.num_heads, cfg.head_dim, cfg.num_heads // cfg.kv_heads
    layers = params["layers"]
    x = np.asarray(x, np.float64)
    s = x.shape[1]
    mask = np.tril(np.ones((s, s), bool))
    for l in range(cfg.num_layers):
        y = ln(x, layers["ln1"], l)
        heads = []
        for i in range(n):
            q = y @ sep["wq"][l, :, i] + sep["bq"][l, i]
            if cfg.attention == "simplified":
                heads.append(q)
                continue
            k = y @ sep["wk"][l, :, i // g] + sep["bk"][l, i // g]
            v = y @ sep["wv"][l, :, i // g] + sep["bv"][l, i // g]
            scores = q @ k.transpose(0, 2, 1) / np.sqrt(d)
            scores = np.where(mask, scores, -np.inf)
            w = np.exp(scores - scores.max(-1, keepdims=True))
            heads.append(w / w.sum(-1, keepdims=True) @ v)
        x = dense(np.concatenate(heads, axis=-1), layers["out"], l) + x
        y = np.asarray(jax.nn.gelu(jnp.asarray(
            dense(ln(x, layers["ln2"], l), layers["ffn_up"], l),
            jnp.float32)), np.float64)
        x = dense(y, layers["ffn_down"], l) + x
    return ln(x, params["ln_f"])


@pytest.mark.parametrize("attention", ["dense", "simplified"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_qkv_columns_by_group_forward(layout, attention):
    """``forward`` on the packed tree is the written-out model;
    ``simplified`` hands on ``ln1(x) @ W_q + b_q`` with the query heads
    in order, whatever lies between them in the fused kernel."""
    cfg = _layout_cfg(LAYOUTS[layout], attention)
    sep = _separate_projections(cfg)
    params = _packed_params(cfg, sep)
    x = _batch(cfg)
    want = _written_out_forward(params, sep, x, cfg)
    np.testing.assert_allclose(np.asarray(forward(params, x, cfg)), want,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["mha", "gqa"])
def test_qkv_columns_by_group_serving(layout):
    """The serving programs read the same parameter tree: a prompt
    prefilled in chunks, then decoded a token at a time, gives the
    written-out model's output at every position it produces."""
    from dlbb_tpu.comm.mesh import build_parallelism_mesh
    from dlbb_tpu.serve.gpt import (
        build_decode_step,
        build_prefill_chunk,
        create_prefix,
        inject_token,
    )
    from dlbb_tpu.serve.kvcache import create_kv_cache

    cfg = _layout_cfg(LAYOUTS[layout])
    sep = _separate_projections(cfg)
    mesh = build_parallelism_mesh(tensor_parallel=2,
                                  devices=jax.devices()[:2])
    params = shard_params(_packed_params(cfg, sep), mesh)
    seq, prompt, chunk, slot, max_batch = 24, 13, 8, 2, 4
    x = _batch(cfg, b=1, s=seq, seed=3)
    want = _written_out_forward(params, sep, x, cfg)[0]

    cache = create_kv_cache(cfg, max_batch, 4, 8, mesh=mesh)
    prefix = create_prefix(cfg, mesh)
    n_chunks = -(-prompt // chunk)
    xp = jnp.zeros((1, n_chunks * chunk, cfg.hidden_size),
                   jnp.float32).at[:, :prompt].set(x[:, :prompt])
    for ci in range(n_chunks):
        cache, prefix, y_last = build_prefill_chunk(
            cfg, mesh, chunk, ci * chunk)(
                cache, prefix, params, xp[:, ci * chunk:(ci + 1) * chunk],
                np.int32(slot), np.int32(prompt))
    np.testing.assert_allclose(np.asarray(y_last), want[prompt - 1],
                               rtol=2e-5, atol=2e-5)

    decode = build_decode_step(cfg, mesh)
    active = jnp.asarray(np.arange(max_batch) == slot)
    carry = (cache, jnp.zeros((max_batch, 1, cfg.hidden_size), jnp.float32))
    for i in range(prompt, seq):
        carry = inject_token(carry, np.int32(slot), x[0, i])
        carry, y = decode(carry, params, active)
        np.testing.assert_allclose(np.asarray(y[slot, 0]), want[i],
                                   rtol=2e-5, atol=2e-5)


# name -> (num_heads, kv_heads): whole groups on a shard when tp=4
# divides kv_heads; "gqa_split" has fewer kv heads than shards, where
# GSPMD realigns and the answer must still be the same
TP_LAYOUTS = {"mha": (4, 4), "gqa": (8, 4), "gqa_split": (8, 2)}


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("layout", list(TP_LAYOUTS))
def test_tp_forward_equals_one_device_f32(mesh2x4, layout, attention):
    cfg = _layout_cfg(TP_LAYOUTS[layout], attention)
    params = init_params(cfg, jax.random.key(1))
    params["layers"]["qkv"]["bias"] = 0.1 * jax.random.normal(
        jax.random.key(2), params["layers"]["qkv"]["bias"].shape)
    x = _batch(cfg, b=4)
    y_one = forward(params, x, cfg)
    y_tp = jax.jit(lambda p, a: forward(p, a, cfg, mesh=mesh2x4))(
        shard_params(params, mesh2x4),
        jax.device_put(x, NamedSharding(mesh2x4, batch_spec())))
    np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_one),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("builder", [
    "_tp_forward_target", "_decode_step_target", "_prefill_chunk_target",
    "_prefill_target", "_decode_fused_target", "_verify_step_target",
    "_decode_quant_target", "_tp_overlap_forward_target"])
def test_tp_programs_hold_no_collective_permute(devices, builder):
    """The audit's own tiny ``dp x tp`` programs, compiled: the two
    row-parallel all-reduces of the layer body (as many as before the
    columns lay by group) and not one collective-permute.  A permute
    here means the column order and its reader fell apart, and on the
    chip it was 14% of the 13B forward's step (``PERF.md`` §6, PR 32).

    The overlapped forward, the route the 13B cell takes since PR 34, is
    held to the same: EVERY permute it has is a ring's hop (under a
    ``ring_hop_*`` scope of ``parallel/collective_matmul.py``), none
    realigns q, k and v, and no all-reduce is left."""
    from collections import Counter

    from dlbb_tpu.analysis import hlo_audit
    from dlbb_tpu.analysis.hlo_parse import parse_collectives
    from dlbb_tpu.parallel.collective_matmul import AUTO_SCHEDULE

    overlapped = builder == "_tp_overlap_forward_target"
    target = getattr(hlo_audit, builder)
    fn, args = (target(AUTO_SCHEDULE) if overlapped else target()).build()
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    collectives = parse_collectives(jitted.lower(*args).compile().as_text())
    kinds = Counter(c.kind for c in collectives)
    if overlapped:
        stray = [c.op_name for c in collectives
                 if c.kind == "collective-permute"
                 and "ring_hop_" not in (c.op_name or "")]
        assert not stray, stray
        assert kinds["collective-permute"] > 0, kinds
        assert kinds["all-reduce"] == 0, kinds
        return
    assert kinds["collective-permute"] == 0, kinds
    assert kinds["all-reduce"] == 2, kinds


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
