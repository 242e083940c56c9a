"""The state-space (``mamba``) layer kind and what came with it (Granite
4.0-H: grouped-query attention without positions, the four multipliers,
a tied head) at toy widths on the CPU, against the plain float32
reference ``benchmarks/reference/granite4_hybrid.py`` on seeded weights.

(a) ``ops/ssd.py``: the chunked scan equals its own recurrent step,
across chunk boundaries and from a carried state, and the decode kernel
over the carried plane (``ssm_state_step``, interpreted here) equals that
step for the active slots and touches nothing else, alone and through
the fused scan; (b) prefill in chunks
then decode through the cache, per-step and fused, on logits and on
every state-space layer's state; (c) ``models.forward``; (d) every
listed fault is read by the comparison; (e) an inactive slot's state and
convolution inputs are untouched by a decode step; (f) the cache's bytes
are what the gate prices; (g) the decode kernel over planes of whole
rows, with a group and a given scale, against the dense path; (h) the
published sizes' parameter count.

The toy keeps the structure: a period with both kinds, a group of 4
query heads a K/V head, heads of 64 (no whole lane-row, so the K/V
planes hold whole rows of 2 x 64), one group of B and C, all four
multipliers different from 1, a tied head.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models import hybrid
from dlbb_tpu.models.configs import (
    ModelConfig,
    kv_cache_bytes,
    kv_rows,
    state_cache_bytes,
)
from dlbb_tpu.ops import ssd, state_plane
from dlbb_tpu.serve import hybrid as serve_hybrid
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine, family_for
from dlbb_tpu.serve.kvcache import create_hybrid_cache
from dlbb_tpu.serve.traffic import Request, TrafficTrace

ROOT = Path(__file__).resolve().parents[1]
for path in (ROOT, ROOT / "scripts"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import granite4h_controls as controls                           # noqa: E402
from benchmarks.reference import granite4_hybrid as reference   # noqa: E402

TOY = dict(
    hidden_size=512, num_layers=8, num_heads=8, num_kv_heads=2,
    ffn_intermediate=128, dtype="float32", norm="rmsnorm", mlp="swiglu",
    bias=False, qk_norm=False, norm_placement="pre", rms_norm_eps=1e-5,
    vocab_size=256,
    layer_types=["mamba", "mamba", "full_attention", "mamba"],
    mamba_n_heads=16, mamba_d_head=64, mamba_d_state=16, mamba_n_groups=1,
    mamba_expand=2, mamba_d_conv=4, mamba_chunk_size=16,
    mamba_conv_bias=True, attention_multiplier=0.05,
    embedding_multiplier=3.0, residual_multiplier=0.5, logits_scaling=2.0,
    tie_word_embeddings=True)
CONFIG = ModelConfig.from_dict(TOY)
# float32 system against float32 reference: what is left is the order of
# the sums (chunked against recurrent, cached against whole-sequence)
TIGHT = 2e-4
# what a fault must move the comparison by, at the least
LOOSE = 5e-3
SERVING = dict(max_batch=4, max_seq=128, block_size=8, prefill_chunk=16,
               queue_capacity=64)
# prompts of one to five chunks that end inside a chunk, on a chunk edge
# and inside a block; with 4 slots the last three take recycled ones
LENGTHS = [(37, 9), (16, 5), (50, 12), (8, 3), (33, 7), (21, 6), (70, 10)]


def _relative(system, ref):
    """Largest ``|system - ref| / |ref|`` over the leading axis (L2 over
    the rest)."""
    system, ref = (np.asarray(t, np.float32).reshape(len(t), -1)
                   for t in (system, ref))
    return float(np.max(np.linalg.norm(system - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


def _mesh():
    return build_parallelism_mesh(1, 1, 1, 1, 1, devices=jax.devices()[:1])


def _trace(lengths=LENGTHS):
    return TrafficTrace(kind="test", seed=0, params={}, requests=tuple(
        Request(rid=i, arrival_s=0.0, prompt_len=p, output_len=o,
                seed=100 + i) for i, (p, o) in enumerate(lengths)))


def _engine(config=CONFIG, **serving):
    return ServingEngine(config, ServingConfig(**{**SERVING, **serving}),
                         _mesh(), seed=5, verbose=False, capture_tokens=True)


_ENGINES: dict = {}


def _sound(**serving):
    key = tuple(sorted(serving.items()))
    if key not in _ENGINES:
        _ENGINES[key] = _engine(**serving)
    return _ENGINES[key]


def _probed_against_reference(engine, results):
    """Each probed request's logits (last prompt position and every
    decode step) and its slot's state in every state-space layer (after
    the prompt and after the last step) against the reference's forward
    over the prompt and the tokens the engine committed: ``{rid:
    (logits' error, the FIRST layer's state's, any layer's)}``."""
    errors = {}
    for rid, rec in results.items():
        ids = list(rec["prompt_ids"]) + rec["tokens"][:-1]
        first = len(rec["prompt_ids"]) - 1
        ref, states = reference.forward_logits(
            engine.params, ids, TOY, positions=list(range(first, len(ids))),
            state_at=[first, len(ids) - 1])
        kept = np.stack([rec["prompt_state"], rec["end_state"]])
        assert kept.shape == states.shape == (2, 6, 16, 64, 16)
        errors[rid] = (_relative(np.stack(rec["logits"]), ref),
                       _relative(kept[:, 0], states[:, 0]),
                       _relative(kept.reshape(12, -1),
                                 np.asarray(states).reshape(12, -1)))
    return errors


# -- (a) chunked form == recurrence --------------------------------------------


@pytest.mark.parametrize("seq, chunk", [(1, 16), (15, 16), (16, 16),
                                        (37, 16), (37, 8), (64, 256)])
def test_chunked_scan_equals_the_token_recurrence_from_a_carried_state(
        seq, chunk):
    rng = np.random.default_rng(seq + chunk)
    b, h, p, n = 2, 3, 8, 16

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    x, bb, cc = draw(b, seq, h, p), draw(b, seq, n), draw(b, seq, n)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (b, seq, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    d = draw(h)
    state0 = draw(b, h, p, n)
    out, state = ssd.ssd_chunked(x, dt, a, bb, cc, d, state0, chunk)
    want, st, by_hand = [], state0, np.asarray(state0, np.float64)
    for t in range(seq):
        y_t, st = ssd.ssd_step(x[:, t], dt[:, t], a, bb[:, t], cc[:, t], d,
                               st)
        want.append(y_t)
        # ... and the recurrence written out, in float64
        decay = np.exp(np.asarray(dt[:, t] * a, np.float64))
        by_hand = (decay[..., None, None] * by_hand
                   + np.asarray(dt[:, t, :, None] * x[:, t],
                                np.float64)[..., None]
                   * np.asarray(bb[:, t], np.float64)[:, None, None, :])
    np.testing.assert_allclose(out, jnp.stack(want, 1), atol=3e-5)
    np.testing.assert_allclose(state, st, atol=3e-5)
    np.testing.assert_allclose(state, by_hand, atol=3e-5)


def test_a_step_of_zero_leaves_the_state_as_it_was():
    """How padding is masked: no decay and no write."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 5, 2, 4)), jnp.float32)
    bc = jnp.asarray(rng.standard_normal((1, 5, 8)), jnp.float32)
    state0 = jnp.asarray(rng.standard_normal((1, 2, 4, 8)), jnp.float32)
    _, state = ssd.ssd_chunked(x, jnp.zeros((1, 5, 2)), -jnp.ones(2), bc, bc,
                               jnp.ones(2), state0, 4)
    np.testing.assert_array_equal(state, state0)


def _select_plane_step(x, dt, a, b, c, d, plane, layer, active, mesh):
    """The step as it was before the kernel: :func:`ssd.ssd_step` over
    the sliced layer, and a ``select`` that keeps an inactive slot's."""
    old = jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
    y, new = ssd.ssd_step(x, dt, a, b, c, d, old)
    return y, jax.lax.dynamic_update_index_in_dim(
        plane, jnp.where(active[:, None, None, None], new, old), layer, 0)


@pytest.mark.parametrize("mask", ["full", "sparse", "single", "empty"])
@pytest.mark.parametrize("shape, block, layer", [
    # (slots, heads, P, N); heads a block; the layer of three
    ((4, 4, 8, 16), 4, 0),        # one block a slot
    ((5, 6, 8, 128), 2, 2),       # three: a slot starts in either buffer
    ((3, 4, 16, 32), 1, 1),       # four
], ids=["4x4x8x16", "5x6x8x128", "3x4x16x32"])
def test_plane_kernel_is_the_plain_step_of_the_active_slots_and_touches_no_other(
        monkeypatch, shape, block, layer, mask):
    """``ssd_plane_step`` against ``ssd_step``: ``y`` and the state of
    the active slots within float32's rounding (one reduction in another
    order), every inactive slot and every other layer of the plane bit
    for bit, whatever the mask and however a slot's heads fall into
    blocks."""
    slots, heads, p, n = shape
    monkeypatch.setattr(state_plane, "BLOCK_BYTES", block * p * n * 4)
    assert state_plane.block_heads(heads, p, n, 4) == block
    active = {"full": np.ones(slots, bool),
              "sparse": np.arange(slots) % 2 == 1,
              "single": np.arange(slots) == slots - 1,
              "empty": np.zeros(slots, bool)}[mask]
    keys = jax.random.split(jax.random.key(slots * heads + layer), 7)
    plane = jax.random.normal(keys[0], (3, slots, heads, p, n), jnp.float32)
    x = jax.random.normal(keys[1], (slots, heads, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[2], (slots, heads)))
    a = -jnp.exp(jax.random.normal(keys[3], (heads,)))
    d = jax.random.normal(keys[4], (heads,))
    b, c = (jax.random.normal(k, (slots, n), jnp.float32) for k in keys[5:])
    y_ref, new_ref = ssd.ssd_step(x, dt, a, b, c, d, plane[layer])
    y, after = jax.jit(ssd.ssd_plane_step, static_argnums=(9,))(
        x, dt, a, b, c, d, plane, jnp.int32(layer), jnp.asarray(active),
        _mesh())
    was, now = np.asarray(plane), np.asarray(after)
    np.testing.assert_allclose(np.asarray(y)[active],
                               np.asarray(y_ref)[active],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(now[layer][active],
                               np.asarray(new_ref)[active],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(now[layer][~active], was[layer][~active])
    others = [i for i in range(3) if i != layer]
    np.testing.assert_array_equal(now[others], was[others])


def test_fused_scan_steps_through_the_kernel_the_slots_each_trip_still_has(
        monkeypatch):
    """``build_decode_fused``: a trip's mask is ``active & (i <
    remaining)``, another one every trip.  Against the same program over
    the step as it was before the kernel (``ssd_step`` and a ``select``):
    the same tokens, the stepping slots' state within float32's
    rounding, and the slot that has no step left and the inactive one
    bit for bit as they were."""
    mesh = _mesh()
    params = hybrid.init_params(CONFIG, jax.random.key(0))
    rng = np.random.default_rng(1)
    cache = create_hybrid_cache(CONFIG, 4, 16, 8, mesh=mesh)
    cache = cache._replace(
        state=jnp.asarray(rng.standard_normal(cache.state.shape),
                          jnp.float32),
        conv=jnp.asarray(rng.standard_normal(cache.conv.shape), jnp.float32),
        lengths=jnp.asarray([5, 9, 7, 3], jnp.int32))
    before = np.asarray(cache.state)
    args = (params, jnp.asarray([True, True, True, False]),
            jnp.asarray([4, 2, 0, 1], jnp.int32),
            jnp.zeros((serve_hybrid.PROBES,), jnp.int32))
    tok = jnp.asarray([1, 2, 3, 4], jnp.int32)

    def run():
        fused = serve_hybrid.build_decode_fused(CONFIG, mesh, 4)
        (after, _), toks, seen, _ = fused(          # the carry is donated
            jax.tree.map(jnp.copy, (cache, tok)), *args)
        return np.asarray(after.state), np.asarray(toks), np.asarray(seen)

    state, toks, seen = run()
    monkeypatch.setattr(serve_hybrid, "ssd_plane_step", _select_plane_step)
    state_was, toks_was, seen_was = run()
    np.testing.assert_array_equal(toks, toks_was)
    np.testing.assert_allclose(seen, seen_was, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state[:, :2], state_was[:, :2],
                               rtol=1e-5, atol=1e-5)
    assert not np.array_equal(state[:, :2], before[:, :2])
    for stays in (state, state_was):
        np.testing.assert_array_equal(stays[:, 2:], before[:, 2:])


def test_a_state_plane_the_chip_cannot_take_is_refused_when_the_engine_is_built(
        monkeypatch):
    """On the chip the kernel copies a head's state as whole (8, 128)
    tiles and no dense path stands behind it: an engine whose heads hold
    ``[64, 16]`` says so, with the reason, when it is built; the cell's
    ``[64, 128]`` are taken, in float32 and in the bfloat16 of the
    control, whose tiles are (16, 128)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError,
                       match=r"whole \(8, 128\) tiles.*heads of \[64, 16\]"):
        _engine()
    like = jax.ShapeDtypeStruct
    state_plane.check_kernel_takes(like((36, 64, 64, 64, 128), jnp.float32))
    state_plane.check_kernel_takes(like((36, 64, 64, 64, 128), jnp.bfloat16))
    with pytest.raises(ValueError, match=r"\(16, 128\) tiles"):
        state_plane.check_kernel_takes(like((2, 4, 4, 8, 128), jnp.bfloat16))


# -- (c) the whole-sequence forward --------------------------------------------


@pytest.mark.parametrize("seq", [3, 16, 45])
def test_forward_logits_match_the_reference(seq):
    params = hybrid.init_params(CONFIG, jax.random.key(3))
    assert not reference.weight_faults(params, TOY)
    ids = np.random.default_rng(seq).integers(0, 256, size=(2, seq))
    logits = np.asarray(hybrid.forward(params, jnp.asarray(ids), CONFIG))
    assert logits.shape == (2, seq, 256) and logits.dtype == np.float32
    for row in range(2):
        want = reference.forward_logits(params, ids[row], TOY)
        assert _relative(logits[row], want) < TIGHT


# -- (b) prefill, then decode through the cache --------------------------------


@pytest.mark.parametrize("horizon, window", [(1, 1), (4, 2)],
                         ids=["per-step", "fused-k4"])
@pytest.mark.parametrize("rids", [(0, 5), (2, 6), (1, 4)],
                         ids=lambda r: f"rids{r[0]}-{r[1]}")
def test_cached_logits_and_state_match_the_reference(horizon, window, rids):
    engine = _sound(decode_horizon=horizon, inflight_window=window)
    engine.probe(rids)
    report = engine.run_trace(_trace(), collect_raw=True)
    assert report["requests"]["completed"] == len(LENGTHS)
    if horizon > 1:
        assert report["fast_path"]["fused_scans"] > 0
    results = engine.probe_results()
    assert sorted(results) == sorted(rids)
    # one request into a slot nobody had used, one into a recycled slot
    assert [results[r]["recycled"] for r in rids] == [False, True]
    for rid, rec in results.items():
        assert rec["tokens"] == report["completed_tokens"][str(rid)]
        assert len(rec["tokens"]) == LENGTHS[rid][1]
    errors = _probed_against_reference(engine, results)
    assert max(max(e) for e in errors.values()) < TIGHT, errors
    # what the programs counted: each chunk's real tokens and its rows
    raw = report["raw_samples"]
    assert sum(raw["chunk_real_tokens"]) == sum(p for p, _ in LENGTHS)
    assert set(raw["chunk_rows"]) == {16}
    assert len(raw["chunk_rows"]) == report["fast_path"]["prefill_chunks"]
    assert report["chunk_real_token_share"] == pytest.approx(
        sum(p for p, _ in LENGTHS) / (16 * len(raw["chunk_rows"])))
    assert sum(raw["unit_slot_steps"]) == \
        report["generated_tokens"] - len(LENGTHS)
    reg = engine.registry
    # three recycled slots a trace (the engine serves several here)
    resets = reg.get("serve_state_resets")
    assert resets >= 3 and resets % 3 == 0
    assert reg.get("serve_state_bytes") == state_cache_bytes(CONFIG, 4)
    assert reg.get("serve_kv_bytes") == kv_cache_bytes(CONFIG, 4, 128)


# -- (d) every fault is read by the comparison ---------------------------------


def _worst(engine, rids=(0, 5)):
    engine.probe(rids)
    engine.run_trace(_trace())
    return _probed_against_reference(engine, engine.probe_results())


# request 0 takes an unused slot with a prompt of three chunks, request 5
# a recycled one; which of them a fault must show in
@pytest.mark.parametrize("fault, shows_in", [
    ("decay_skipped", "both"),
    ("skip_left_out", "both"),
    ("gate_after_norm", "both"),
    ("conv_bias_dropped", "both"),
    ("b_c_swapped", "both"),
    ("residual_multiplier_1", "both"),
    ("wrong_kv_head", "both"),
    ("stale_state", "recycled"),
    ("stale_conv", "recycled"),
])
def test_every_fault_of_the_program_is_read_by_the_comparison(
        fault, shows_in, monkeypatch):
    controls.apply(fault, monkeypatch.setattr, TOY)
    errors = _worst(_engine(decode_horizon=4, inflight_window=2))
    if shows_in == "both":
        assert errors[0][0] > LOOSE and errors[5][0] > LOOSE, errors
    else:
        # three stale inputs of the convolution move the logits of a
        # position dozens of tokens on by less than the state they wrote
        assert max(errors[5][:2]) > LOOSE, errors
        # the slot nobody had used holds zeros: nothing stale to keep
        assert max(errors[0]) < TIGHT, errors
    # ... and the whole-sequence forward reads the block's faults too
    if shows_in == "both":
        params = hybrid.init_params(CONFIG, jax.random.key(3))
        ids = np.random.default_rng(1).integers(0, 256, size=(1, 24))
        logits = hybrid.forward(params, jnp.asarray(ids), CONFIG)[0]
        assert _relative(logits, reference.forward_logits(
            params, ids[0], TOY)) > LOOSE


@pytest.mark.parametrize("change", [
    dict(embedding_multiplier=1.0),
    dict(residual_multiplier=1.0),
    dict(logits_scaling=1.0),
    dict(attention_multiplier=1.0),
    dict(attention_multiplier=None),        # 1 / sqrt(d)
    dict(tie_word_embeddings=False),
], ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()))
def test_a_multiplier_left_at_one_or_an_untied_head_is_another_function(
        change):
    """The program under a configuration wrong in one key, the reference
    under the true one, over the same layers' weights."""
    wrong = CONFIG.with_(**change)
    params = hybrid.init_params(CONFIG, jax.random.key(3))
    if not wrong.tie_word_embeddings:
        params = {**params, "lm_head": hybrid.init_params(
            wrong, jax.random.key(3))["lm_head"]}
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 24))
    logits = hybrid.forward(params, jnp.asarray(ids), wrong)[0]
    want = reference.forward_logits(params, ids[0], TOY)
    assert _relative(logits, want) > LOOSE
    if "lm_head" in params:
        assert any("lm_head is not expected" in f
                   for f in reference.weight_faults(params, TOY))


def test_state_planes_in_bfloat16_are_read_by_the_states_error_alone():
    """The nearest precision below the configuration's: the logits
    hardly move, the first layer's state does."""
    sound = _worst(_sound(decode_horizon=4, inflight_window=2))
    with pytest.MonkeyPatch.context() as patch:
        controls.apply("state_bfloat16", patch.setattr, TOY)
        rounded = _worst(_engine(decode_horizon=4, inflight_window=2))
    for rid in (0, 5):
        assert sound[rid][1] < 1e-5 and rounded[rid][1] > 1e-3, (sound,
                                                                  rounded)


@pytest.mark.parametrize("fault, said", [
    ("ssm_norm_ones", "ssm_norm spans"),
    ("conv_bias_zero", "ssm_conv_b spans"),
    ("skip_not_one", "ssm_D is not all ones"),
    ("extra_head", "lm_head is not expected"),
    ("kv_heads", "has shape (2, 512, 8, 64)"),
    ("decay_narrow", "exp(A_log) spans"),
])
def test_the_reference_judges_the_weights_it_is_handed(fault, said):
    params = hybrid.init_params(CONFIG, jax.random.key(0))
    first = dict(params["periods"][0])
    if fault == "ssm_norm_ones":
        first["ssm_norm"] = jnp.ones_like(first["ssm_norm"])
    elif fault == "conv_bias_zero":
        first["ssm_conv_b"] = jnp.zeros_like(first["ssm_conv_b"])
    elif fault == "skip_not_one":
        first["ssm_D"] = first["ssm_D"] * 0.5
    elif fault == "decay_narrow":
        first["A_log"] = jnp.zeros_like(first["A_log"]) - 1.0
    periods = (first,) + params["periods"][1:]
    if fault == "kv_heads":
        mha = hybrid.init_params(CONFIG.with_(num_kv_heads=8),
                                 jax.random.key(0))
        periods = mha["periods"]
    params = {**params, "periods": periods}
    if fault == "extra_head":
        params["lm_head"] = params["embed"].T
    faults = reference.weight_faults(params, TOY)
    assert faults and all(said in f for f in faults), faults


# -- (e) an inactive slot is left alone ----------------------------------------


def test_a_decode_step_leaves_an_inactive_slots_state_and_inputs_bit_for_bit():
    mesh = _mesh()
    params = hybrid.init_params(CONFIG, jax.random.key(0))
    cache = create_hybrid_cache(CONFIG, 4, 16, 8, mesh=mesh)
    rng = np.random.default_rng(0)
    cache = cache._replace(
        state=jnp.asarray(rng.standard_normal(cache.state.shape),
                          jnp.float32),
        conv=jnp.asarray(rng.standard_normal(cache.conv.shape), jnp.float32),
        lengths=jnp.asarray([5, 9, 0, 3], jnp.int32))
    before = jax.tree.map(np.asarray, cache)
    active = jnp.asarray([True, False, False, True])
    step = serve_hybrid.build_decode_step(CONFIG, mesh)
    (after, _), _, _, counts = step(
        (cache, jnp.asarray([1, 2, 3, 4], jnp.int32)), params, active,
        jnp.zeros((serve_hybrid.PROBES,), jnp.int32))
    assert counts is None
    for plane in ("state", "conv", "k", "v"):
        was, now = before._asdict()[plane], np.asarray(getattr(after, plane))
        np.testing.assert_array_equal(now[:, 1:3], was[:, 1:3])
        assert not np.array_equal(now[:, 0], was[:, 0]), plane
        assert not np.array_equal(now[:, 3], was[:, 3]), plane
    np.testing.assert_array_equal(after.lengths, [6, 9, 0, 4])


# -- (f) what the cache holds is what the gate prices --------------------------


def test_the_cache_holds_one_state_plane_and_rows_of_kv_and_is_priced_so():
    assert kv_rows(CONFIG) and not kv_rows(CONFIG, tp=2)
    cache = create_hybrid_cache(CONFIG, 4, 16, 8)
    # L_full = 2 of 8 layers; a token's 2 heads of 64 as ONE row of 128
    assert cache.k.shape == cache.v.shape == (2, 4, 16, 8, 128)
    assert cache.state.shape == (6, 4, 16, 64, 16)
    assert cache.state.dtype == jnp.float32
    # the last 3 inputs of x (1024), B and C (16 each), flat
    assert cache.conv.shape == (6, 4, 3 * 1056)
    assert cache.latent.size == 0
    assert kv_cache_bytes(CONFIG, 4, 128) == cache.k.nbytes + cache.v.nbytes
    assert state_cache_bytes(CONFIG, 4) == \
        cache.state.nbytes + cache.conv.nbytes
    with pytest.raises(ValueError, match="recurrent state"):
        ServingConfig(**{**SERVING, "hbm_budget_gb": 1e-4}).validate(CONFIG)
    # the carry a prompt starts from: all zeros, the same two kinds
    prefix = serve_hybrid.create_prefix(CONFIG, _mesh())
    assert [t.shape for t in prefix] == [
        (2, 0, 2, 64), (2, 0, 2, 64), (6, 16, 64, 16), (6, 3, 1056)]


def test_the_published_sizes_are_priced_as_the_issue_reckons_them():
    with open(ROOT / "benchmarks/configs/granite-4.0-h-micro-serve.json") as f:
        program = json.load(f)["program"]
    config = ModelConfig.from_dict(program["model"])
    # (h) 36 state-space layers of 76,182,976, 4 attention layers of
    # 60,821,504, the tied table once, the final norm
    assert hybrid.num_parameters(config) == (
        36 * 76_182_976 + 4 * 60_821_504 + 100_352 * 2048 + 2048) \
        == 3_191_396_096
    assert kv_rows(config)
    slots = program["serving"]["max_batch"]
    per_slot = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert state_cache_bytes(config, slots) == slots * per_slot
    assert kv_cache_bytes(config, slots, 1280) == slots * 1280 * 8192
    shapes = jax.eval_shape(lambda: create_hybrid_cache(
        config, slots, 80, 16))
    assert shapes.k.shape == (4, slots, 80, 16, 512)
    assert shapes.state.shape == (36, slots, 64, 64, 128)
    assert shapes.conv.shape == (36, slots, 3 * 4352)


@pytest.mark.parametrize("name, heads, kv, state, latent, shapes", [
    ("paper7b-16l-serve", 32, 4_294_967_296, 0, 0, None),
    ("olmo-hybrid-7b-16l-serve", 32, 4_294_967_296, 875_888_640, 0,
     dict(k=(4, 32, 128, 16, 32, 128), state=(12, 32, 30, 192, 96),
          conv=(12, 32, 3, 30, 384), latent=(0, 32, 128, 16, 0))),
    ("kanana2-30b-a3b-8l-serve", 32, 0, 0, 3_019_898_880,
     dict(k=(0, 64, 288, 16, 32, 64), state=(0, 64, 0, 0, 0),
          conv=(0, 64, 0, 0, 0), latent=(8, 64, 288, 16, 640))),
    ("ouro-2.6b-serve", 16, 8_053_063_680, 0, 0,
     dict(k=(192, 8, 40, 16, 16, 128), state=(0, 8, 0, 0, 0),
          conv=(0, 8, 0, 0, 0), latent=(0, 8, 40, 16, 0))),
])
def test_the_four_older_serving_cells_hold_what_they_held(
        name, heads, kv, state, latent, shapes):
    """What the parent of PR 37 gave for the four serving configurations
    the benchmark had (read off the parent once, by hand): a model
    without the new kind holds no byte more, its planes keep their
    shapes (kanana's 32 heads of 64 are a whole row of lanes too, and it
    has no K/V plane to hold them in), and so its programs are the
    parent's."""
    from dlbb_tpu.models.configs import cache_kv_heads, latent_cache_bytes

    with open(ROOT / f"benchmarks/configs/{name}.json") as f:
        program = json.load(f)["program"]
    config = ModelConfig.from_dict(program["model"])
    sv = ServingConfig.from_dict(program["serving"])
    assert not kv_rows(config)
    assert config.attention_multiplier is None
    assert cache_kv_heads(config) == heads
    assert kv_cache_bytes(config, sv.max_batch, sv.max_seq) == kv
    assert state_cache_bytes(config, sv.max_batch) == state
    assert latent_cache_bytes(config, sv.max_batch, sv.max_seq) == latent
    if shapes:
        cache = jax.eval_shape(lambda: create_hybrid_cache(
            config, sv.max_batch, sv.num_blocks, sv.block_size))
        assert {k: getattr(cache, k).shape for k in shapes} == shapes
        assert cache.v.shape == cache.k.shape


# -- (g) the decode kernel over whole rows -------------------------------------


@pytest.mark.parametrize("scale", [None, 0.015625, 0.3])
@pytest.mark.parametrize("rows", [True, False], ids=["rows", "heads"])
def test_decode_kernel_reads_narrow_grouped_heads_with_a_given_scale(
        scale, rows):
    """``kv_attend_decode`` (interpreted) over 8 query heads of 64 on 2
    K/V heads, from planes of whole rows and from planes of heads,
    against the dense attention over each slot's live tokens."""
    from dlbb_tpu.ops.decode_attention import decode_attention

    rng = np.random.default_rng(3)
    layers, b, nb, bs, kvh, n, d = 2, 3, 4, 8, 2, 8, 64
    k = rng.standard_normal((layers, b, nb, bs, kvh, d)).astype(np.float32)
    v = rng.standard_normal((layers, b, nb, bs, kvh, d)).astype(np.float32)
    q = rng.standard_normal((b, n, 1, d)).astype(np.float32)
    lengths = np.array([0, 17, 31], np.int32)
    active = np.array([True, True, False])
    planes = [jnp.asarray(t.reshape(layers, b, nb, bs, kvh * d) if rows
                          else t) for t in (k, v)]
    out = np.asarray(decode_attention(
        jnp.asarray(q), *planes, jnp.int32(1), jnp.asarray(lengths),
        jnp.asarray(active), _mesh(), scale))
    assert out.shape == (b, n, 1, d)
    used = d ** -0.5 if scale is None else scale
    for slot in range(b):
        if not active[slot]:
            np.testing.assert_array_equal(out[slot], 0.0)
            continue
        live = lengths[slot] + 1
        ks = k[1, slot].reshape(nb * bs, kvh, d)[:live]
        vs = v[1, slot].reshape(nb * bs, kvh, d)[:live]
        for head in range(n):
            s = ks[:, head // 4] @ q[slot, head, 0] * used
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vs[:, head // 4]
            np.testing.assert_allclose(out[slot, head, 0], want, atol=2e-5)


# -- what is refused, and why --------------------------------------------------


@pytest.mark.parametrize("serving, reason", [
    (dict(speculation="greedy"), "state rolled back"),
    (dict(prefix_caching=True), "state as it was at the block boundary"),
    (dict(kv_quantization="int8"), "fp K/V layout only"),
    (dict(prefill_chunk=None), "prefilled in chunks"),
])
def test_serving_refuses_for_the_new_kind_what_it_refuses_for_state_layers(
        serving, reason):
    sv = ServingConfig(**{**SERVING, **serving})
    with pytest.raises(ValueError, match=reason):
        family_for(CONFIG).check_serving(CONFIG, sv)
        sv.validate(CONFIG)


@pytest.mark.parametrize("change, reason", [
    (dict(mamba_n_groups=2), "mamba_n_groups=2 is not implemented"),
    (dict(mamba_d_state=0), "mamba layers need"),
    (dict(mamba_expand=3), "is not mamba_expand x hidden_size"),
    (dict(layer_types=["mamba", "linear_attention"], num_layers=2,
          linear_num_key_heads=4, linear_num_value_heads=4,
          linear_key_head_dim=8, linear_value_head_dim=8,
          linear_conv_kernel_dim=4), "ONE recurrent-state plane"),
    (dict(total_ut_steps=2), "only the K/V planes are laid out"),
    (dict(layer_types=["mamba"], num_layers=2, qk_norm=True),
     "model family not implemented"),
])
def test_model_config_refuses(change, reason):
    with pytest.raises(ValueError, match=reason):
        ModelConfig.from_dict({**TOY, **change})


def test_tensor_parallelism_is_refused_for_the_new_kind_by_its_mechanism():
    with pytest.raises(ValueError, match="B and C are shared by every head"):
        ServingConfig(**SERVING).validate(CONFIG, tp=2)
