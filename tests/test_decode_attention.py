"""The length-aware decode attention kernel (``ops/decode_attention.py``)
against the dense path it replaced, ``serve/engine.py::_cached_attention``,
in Pallas interpret mode on the CPU-simulated mesh; the same code compiles
for the chip (``tests/test_serve_fastpath.py`` holds the v5e compiles).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dlbb_tpu.ops import decode_attention as op
from dlbb_tpu.ops.decode_attention import (
    check_kernel_takes,
    decode_attention,
    live_tile_counts,
    plane_tile_tokens,
    tile_tokens,
)
from dlbb_tpu.serve import attend as E

# a plane of 3 layers x 5 slots x 8 blocks of 4 tokens, read in tiles of 8
L, B, NB, BS, TILE, LAYER = 3, 5, 8, 4, 8, 1
S_MAX = NB * BS
# slot 0 holds one token; 1 ends one position under a tile's edge and 3
# one over it; 2, between them, is inactive; 4 is full
LENGTHS = np.array([0, TILE - 1, 17, TILE, S_MAX - 1], np.int32)
ACTIVE = np.array([True, True, False, True, True])
LIVE_TILES = [1, 1, 0, 2, S_MAX // TILE]


def _mesh(dp=1, tp=1):
    devices = np.array(jax.devices()[:dp * tp]).reshape(dp, tp)
    return Mesh(devices, ("dp", "tp"))


def _tiles_of(monkeypatch, tokens, k_plane, tp=1):
    """Make ``tokens`` tokens of a ``tp`` shard of ``k_plane`` one tile."""
    _, _, _, _, kvh, d = k_plane.shape
    monkeypatch.setattr(
        op, "TILE_BYTES", tokens * (kvh // tp) * d * k_plane.dtype.itemsize)


def _planes(kvh, d, dtype):
    rng = np.random.default_rng(0)
    shape = (L, B, NB, BS, kvh, d)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _oracle(q, k_plane, v_plane, layer, lengths):
    s_max = k_plane.shape[2] * k_plane.shape[3]
    valid = jnp.arange(s_max)[None, :] <= jnp.asarray(lengths)[:, None]
    return E._cached_attention(q, E._layer_tokens(k_plane, layer),
                               E._layer_tokens(v_plane, layer), valid)


def _unread_as_nan(plane, counts):
    """``plane`` with NaN in every tile the kernel must not fetch: an
    inactive slot's, those past a slot's length, and every other layer."""
    keep = np.zeros(plane.shape, bool)
    for b, count in enumerate(counts):
        keep[LAYER, b, :count * TILE // BS] = True
    return jnp.where(keep, plane, jnp.asarray(np.nan, plane.dtype))


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("n, kvh, extra", [(4, 4, 0), (8, 2, 0), (30, 30, 2)],
                         ids=["mha", "gqa", "30-held-as-32"])
def test_kernel_matches_the_dense_path(n, kvh, extra, dtype, tol,
                                       monkeypatch):
    """Lengths at 0, either side of a tile's edge and ``max_seq - 1``, at
    ``layer`` 1 of 3; the 30 heads of the hybrid ride planes of 32 with
    zero queries and are cut by the caller."""
    d = 16
    rng = np.random.default_rng(1)
    k_plane, v_plane = _planes(kvh, d, dtype)
    q = jnp.asarray(rng.standard_normal((B, n, 1, d)), dtype)
    want = _oracle(q, k_plane, v_plane, LAYER, LENGTHS)

    counts = live_tile_counts(LENGTHS, ACTIVE, TILE, S_MAX // TILE)
    assert counts.tolist() == LIVE_TILES
    pad = [(0, 0)] * 4 + [(0, extra), (0, 0)]
    # what is not fetched cannot reach the answer: NaN there
    k_held = _unread_as_nan(jnp.pad(k_plane, pad), counts)
    v_held = _unread_as_nan(jnp.pad(v_plane, pad), counts)
    q_held = jnp.pad(q, [(0, 0), (0, extra), (0, 0), (0, 0)])
    _tiles_of(monkeypatch, TILE, k_held)
    got = jax.jit(lambda *a: decode_attention(*a, _mesh()))(
        q_held, k_held, v_held, jnp.int32(LAYER), jnp.asarray(LENGTHS),
        jnp.asarray(ACTIVE))[:, :n]

    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[ACTIVE], want[ACTIVE], rtol=tol, atol=tol)
    assert (got[~ACTIVE] == 0).all()


def test_probabilities_enter_the_value_product_unrounded():
    """Two tokens whose probabilities differ by 3e-4 around 1/2, values +1
    and -1: rounded to bf16 both are 1/2 and the answer is 0."""
    d, kvh = 16, 2
    k = np.zeros((1, 1, 2, 4, kvh, d), np.float32)
    v = np.zeros_like(k)
    k[0, 0, 0, 0, :, 0] = 5 * 2.0 ** -7
    v[0, 0, 0, 0], v[0, 0, 0, 1] = 1.0, -1.0
    q = np.zeros((1, kvh, 1, d), np.float32)
    q[..., 0] = 2.0 ** -4
    args = [jnp.asarray(a, jnp.bfloat16) for a in (q, k, v)]
    lengths = jnp.asarray([1], jnp.int32)
    got = decode_attention(*args, jnp.int32(0), lengths, jnp.asarray([True]),
                           _mesh())
    x = 2.0 ** -4 * 5 * 2.0 ** -7 / 4               # the one logit, of 0
    p = np.array([1 / (1 + np.exp(-x)), 1 / (1 + np.exp(x))], np.float32)
    assert (p.astype(jnp.bfloat16) == 0.5).all()    # what rounding does
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.full((1, kvh, 1, d), p[0] - p[1]),
                               rtol=2e-2)
    assert float(_oracle(*args, 0, lengths)[0, 0, 0, 0]) > 0


@pytest.mark.parametrize("dp, tp", [(1, 1), (2, 2), (2, 4)])
def test_sharded_kernel_equals_the_dense_path_and_adds_no_collective(
        dp, tp, monkeypatch):
    """Slots over ``dp``, kv-heads over ``tp``: every shard attends its
    own, so the op alone lowers to zero collectives."""
    from dlbb_tpu.analysis.hlo_audit import parse_collectives
    from dlbb_tpu.analysis.hlo_parse import parse_module

    n, kvh, d = 16, 8, 16
    lengths = np.array([3, 9, 0, 31, 8, 16, 5, 20], np.int32)
    active = np.array([1, 1, 0, 1, 1, 0, 1, 1], bool)
    rng = np.random.default_rng(2)
    shape = (L, 8, NB, BS, kvh, d)
    k_plane = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v_plane = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((8, n, 1, d)), jnp.float32)
    _tiles_of(monkeypatch, TILE, k_plane, tp)
    fn = jax.jit(lambda *a: decode_attention(*a, _mesh(dp, tp)))
    args = (q, k_plane, v_plane, jnp.int32(2), jnp.asarray(lengths),
            jnp.asarray(active))
    want = np.asarray(_oracle(q, k_plane, v_plane, 2, lengths))
    np.testing.assert_allclose(np.asarray(fn(*args))[active], want[active],
                               rtol=1e-5, atol=1e-5)
    module = parse_module(fn.lower(*args).compile().as_text())
    assert parse_collectives(module) == []


def test_decode_audit_target_is_clean_on_the_simulated_mesh(devices):
    """The decode step on dp=2 x tp=4 keeps its comm contract: only the
    projections' tiny tp collectives, nothing of the cache's size."""
    from dlbb_tpu.analysis.hlo_audit import _decode_step_target, audit_target

    findings, _ = audit_target(_decode_step_target())
    assert findings == [], [f.render() for f in findings]


@pytest.mark.parametrize("kvh, d, tp, on_chip", [
    (32, 128, 1, True), (32, 128, 4, True), (16, 256, 2, True),  # cells, tp
    (30, 128, 2, False), (30, 128, 1, False), (4, 16, 1, False),
    (8, 64, 1, False),
])
def test_the_chip_refuses_planes_that_are_no_whole_tiles_with_the_reason(
        kvh, d, tp, on_chip, monkeypatch):
    """No dense path stands behind the kernel: shards Mosaic's copies
    cannot move are an error when the engine is built on the chip, and
    any shape runs interpreted."""
    plane = jax.ShapeDtypeStruct((2, 4, 8, 16, kvh, d), jnp.bfloat16)
    check_kernel_takes(plane, _mesh(1, tp))         # here: interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if on_chip:
        check_kernel_takes(plane, _mesh(1, tp))
    else:
        with pytest.raises(ValueError, match=f"{kvh // tp} kv-heads of {d}"):
            check_kernel_takes(plane, _mesh(1, tp))


@pytest.mark.parametrize("shape, itemsize, want", [
    # the two serving cells' planes: 512 KiB tiles of 64 tokens
    ((64, 16, 32, 128), 2, 64), ((128, 16, 32, 128), 2, 64),
    # a tp=4 shard of the 7B: 8 kv-heads, 256 tokens
    ((64, 16, 8, 128), 2, 256),
    # a ring shorter than a tile: the whole ring; an odd ring: a divisor
    ((4, 4, 2, 16), 4, 16), ((6, 16, 32, 128), 2, 48),
])
def test_tile_is_whole_blocks_that_divide_the_ring(shape, itemsize, want):
    nb, bs = shape[:2]
    tile = tile_tokens(*shape, itemsize)
    assert tile == want and tile % bs == 0 and (nb * bs) % tile == 0


@pytest.mark.parametrize("cell", ["serve7b_backlog",
                                  "olmohyb_longgen_backlog"])
def test_both_cells_planes_are_read_in_tiles_of_64_tokens(cell):
    """The planes the two serving cells' engines carry, as shapes: the
    kernel and the engine's counters take their tile from the plane
    itself (``plane_tile_tokens``), 512 KiB of 32 held kv-heads."""
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import cells
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.serve.config import ServingConfig
    from dlbb_tpu.serve.kvcache import create_hybrid_cache, create_kv_cache

    program = cells.resolve_cell(cell).config["program"]
    model = ModelConfig.from_dict(program["model"])
    serving = ServingConfig.from_dict(program["serving"])
    create = create_hybrid_cache if model.is_hybrid else create_kv_cache
    k_plane = jax.eval_shape(lambda: create(
        model, serving.max_batch, serving.num_blocks, serving.block_size,
        mesh=_mesh())).k
    assert k_plane.shape[1:] == (serving.max_batch, serving.num_blocks,
                                 serving.block_size, 32, 128)
    assert k_plane.dtype == jnp.bfloat16
    assert plane_tile_tokens(k_plane, _mesh()) == 64


@pytest.mark.parametrize("horizon", [1, 4], ids=["per-step", "fused"])
def test_tile_counters_equal_the_share_of_the_traces_own_lengths(
        mesh2x4, monkeypatch, tmp_path, horizon):
    """``serve_kv_tiles_live`` is what the trace's lengths say whatever
    the schedule: request ``(p, o)`` decodes at lengths ``p .. p + o - 2``
    and each step fetches the tiles under its length; ``held`` is the
    planes' tiles times the steps run.  Report and ``metrics.prom`` carry
    both."""
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.serve.config import ServingConfig
    from dlbb_tpu.serve.engine import ServingEngine
    from dlbb_tpu.serve.traffic import generate_trace

    # tiles of 16 of the 64 tokens a slot's ring holds (one kv-head a
    # tp shard, 8-token blocks of 16 floats)
    monkeypatch.setattr(op, "TILE_BYTES", 1024)
    model = ModelConfig(hidden_size=64, num_layers=2, num_heads=4,
                        ffn_intermediate=128, dtype="float32",
                        attention="full")
    serving = ServingConfig(max_batch=8, block_size=8, max_seq=64,
                            hbm_budget_gb=None, decode_horizon=horizon)
    engine = ServingEngine(model, serving, mesh2x4, verbose=False)
    # the counters' tile is the kernel's, from the plane the engine carries
    assert engine._kv_tile == plane_tile_tokens(
        engine._fresh_carry()[0].k, mesh2x4) == 16
    trace = generate_trace("poisson", 10, seed=5, rate=500.0,
                           prompt_range=(5, 40), output_range=(2, 20))
    report = engine.run_trace(trace)
    assert report["requests"]["completed"] == len(trace)

    tile, max_tiles = 16, 4
    live = sum((r.prompt_len + j) // tile + 1
               for r in trace for j in range(r.output_len - 1))
    held = report["decode_steps"] * serving.max_batch * max_tiles
    assert report["fast_path"]["kv_tiles_live"] == live
    assert report["fast_path"]["kv_tiles_held"] == held
    assert report["kv_live_share"] == pytest.approx(live / held)
    assert 0.0 < report["kv_live_share"] < 1.0
    prom = engine.registry.write_textfile(tmp_path / "metrics.prom")
    text = prom.read_text()
    assert f"dlbb_serve_kv_tiles_live_total {live}\n" in text
    assert f"dlbb_serve_kv_tiles_held_total {held}\n" in text
    # a model without state-space layers counts no state stepped
    assert "state_live_share" not in report
    assert "serve_state_slots" not in text


@pytest.mark.parametrize("horizon", [1, 4], ids=["per-step", "fused"])
def test_state_counters_equal_what_the_ledger_says_with_a_slot_idle(
        tmp_path, horizon):
    """``serve_state_slots_stepped`` is the (slot, step) pairs that held
    a request times the state-space layers, which is what the state
    kernel moves (``ops/state_plane.py``): request ``(p, o)`` takes ``o -
    1`` decode steps whatever the schedule; ``serve_state_slots_held`` is
    what the plane holds, every slot every step.  Three requests on four
    slots leave one idle throughout.  Report and ``metrics.prom`` carry
    both; a model without such layers carries neither (the test
    above)."""
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.serve.config import ServingConfig
    from dlbb_tpu.serve.engine import ServingEngine
    from dlbb_tpu.serve.traffic import Request, TrafficTrace

    model = ModelConfig.from_dict(dict(
        hidden_size=128, num_layers=4, num_heads=2, num_kv_heads=1,
        ffn_intermediate=64, dtype="float32", norm="rmsnorm", mlp="swiglu",
        bias=False, qk_norm=False, norm_placement="pre", vocab_size=64,
        layer_types=["mamba", "mamba", "full_attention", "mamba"],
        mamba_n_heads=4, mamba_d_head=64, mamba_d_state=16,
        mamba_n_groups=1, mamba_expand=2, mamba_d_conv=4,
        mamba_chunk_size=16, tie_word_embeddings=True))
    serving = ServingConfig(max_batch=4, max_seq=64, block_size=8,
                            prefill_chunk=16, decode_horizon=horizon)
    engine = ServingEngine(model, serving, _mesh(), verbose=False)
    trace = TrafficTrace(kind="test", seed=0, params={}, requests=tuple(
        Request(rid=i, arrival_s=0.0, prompt_len=p, output_len=o,
                seed=100 + i)
        for i, (p, o) in enumerate([(20, 9), (5, 4), (33, 12)])))
    report = engine.run_trace(trace)
    assert report["requests"]["completed"] == len(trace)

    layers = 3
    stepped = layers * sum(r.output_len - 1 for r in trace)
    held = layers * report["decode_steps"] * serving.max_batch
    assert report["state_live_share"] == pytest.approx(stepped / held)
    assert 0.0 < report["state_live_share"] <= 0.75
    text = engine.registry.write_textfile(
        tmp_path / "metrics.prom").read_text()
    assert f"dlbb_serve_state_slots_stepped_total {stepped}\n" in text
    assert f"dlbb_serve_state_slots_held_total {held}\n" in text
