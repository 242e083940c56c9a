"""Sliding-window layers beside global ones, and softmax-routed ReGLU
experts routed before the attention, in the ``layer_types`` family
(SmallThinker-21BA3B's block) at toy widths on the CPU, against the
plain float32 reference ``benchmarks/reference/smallthinker.py`` on
seeded weights.

(a) ``models.forward`` and its routing; (b) prefill in chunks that cross
the window, then decode past it, per-step and fused, for an unused and a
recycled slot; (c) the decode kernel over a ring, interpreted, across the
wrap, with a group of 7 query heads a K/V head; (d) the cache: window
planes of ``W`` tokens, K/V heads held as rows; (e) softmax routing and
ReGLU experts against plain formulas, and the sigmoid path of the
kanana configuration unchanged; (f) what a ring cannot do yet refused.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models import forward, hybrid, init_params
from dlbb_tpu.models.configs import (
    ModelConfig,
    kv_cache_bytes_per_device,
    kv_rows,
    validate_serving,
    window_cache_bytes,
)
from dlbb_tpu.ops import routed_experts as moe
from dlbb_tpu.ops.decode_attention import decode_attention
from dlbb_tpu.serve import attend
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.kvcache import create_hybrid_cache, ring_positions
from dlbb_tpu.serve.traffic import Request, TrafficTrace

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import smallthinker as reference     # noqa: E402

TOY = dict(
    hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2,
    attn_head_dim=64, ffn_intermediate=32, dtype="float32", norm="rmsnorm",
    mlp="reglu", bias=False, qk_norm=False, norm_placement="pre",
    rms_norm_eps=1e-6, vocab_size=256,
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention"],
    rope_layout=[0, 1, 1, 1], rope_theta=1.5e6, sliding_window=16,
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    scoring_func="softmax", router_before_attention=True)
CONFIG = ModelConfig.from_dict(TOY)
# float32 system against float32 reference: what is left is the order of
# the sums (chunked against whole-sequence, ring against mask, grouped
# products against the loop over every expert)
TIGHT = 2e-4
SERVING = dict(max_batch=4, max_seq=128, block_size=8, prefill_chunk=8,
               queue_capacity=64)
# prompts past twice the window, inside it, on a chunk's edge; outputs
# that carry short contexts past it; with 4 slots the last three take
# recycled slots
LENGTHS = [(37, 9), (5, 20), (50, 12), (16, 5), (9, 14), (21, 6), (70, 10)]


def _relative(system, ref):
    return float(np.max(np.linalg.norm(system - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


def _mesh():
    return build_parallelism_mesh(1, 1, 1, 1, 1, devices=jax.devices()[:1])


def _trace(lengths=LENGTHS):
    return TrafficTrace(kind="test", seed=0, params={}, requests=tuple(
        Request(rid=i, arrival_s=0.0, prompt_len=p, output_len=o,
                seed=100 + i) for i, (p, o) in enumerate(lengths)))


_ENGINES: dict = {}


def _engine(**serving):
    key = tuple(sorted(serving.items()))
    if key not in _ENGINES:
        _ENGINES[key] = ServingEngine(
            CONFIG, ServingConfig(**{**SERVING, **serving}), _mesh(),
            seed=5, verbose=False, capture_tokens=True)
    return _ENGINES[key]


# -- (a) the whole-sequence forward -------------------------------------------


@pytest.mark.parametrize("seq", [3, 40, 100])
def test_forward_logits_and_routing_match_the_reference(seq):
    params = init_params(CONFIG, jax.random.key(3))
    ids = np.random.default_rng(seq).integers(0, 256, size=(2, seq))
    logits, chosen = hybrid.forward(params, jnp.asarray(ids), CONFIG,
                                    with_routing=True)
    logits = np.asarray(logits)
    assert logits.shape == (2, seq, 256)
    # every layer is an expert layer
    assert chosen.shape == (4, 2 * seq, 2)
    for row in range(2):
        ref, _select, want, _gates, _window = reference.forward_logits(
            params, ids[row], TOY, with_routing=True)
        assert _relative(logits[row], np.asarray(ref)) < TIGHT
        got = np.asarray(chosen)[:, row * seq:(row + 1) * seq]
        assert (np.sort(got, -1)
                == np.sort(np.asarray(want).transpose(1, 0, 2), -1)).all()
    np.testing.assert_array_equal(
        logits, np.asarray(forward(params, jnp.asarray(ids), CONFIG)))


def test_the_window_and_the_rotary_change_the_result():
    """The reference sees what it is told: the window, its edge and the
    rotary positions each move the logits of a sequence past the
    window (so that agreeing with it says something)."""
    params = init_params(CONFIG, jax.random.key(3))
    ids = np.random.default_rng(0).integers(0, 256, size=60)
    base = np.asarray(reference.forward_logits(params, ids, TOY))
    for change in (dict(window=17), dict(window=60)):
        other = np.asarray(reference.forward_logits(params, ids, TOY,
                                                    **change))
        np.testing.assert_array_equal(other[:16], base[:16])
        assert _relative(other[16:], base[16:]) > 1e-4
    flat = np.asarray(reference.forward_logits(
        params, ids, {**TOY, "rope_layout": [0, 0, 0, 0]}))
    assert _relative(flat, base) > 1e-3


# -- (b) chunked prefill past the window, decode past it ----------------------


def _probed_against_reference(engine, results):
    errors, window, same = {}, {}, {}
    for rid, rec in results.items():
        ids = list(rec["prompt_ids"]) + rec["tokens"][:-1]
        first = len(rec["prompt_ids"]) - 1
        ref, _select, chosen, _gates, heads = reference.forward_logits(
            engine.params, ids, TOY, positions=list(range(first, len(ids))),
            with_routing=True)
        errors[rid] = _relative(np.stack(rec["logits"]), np.asarray(ref))
        attn = np.stack(rec["window_attn"])
        # the reference's output at the window W (then W - 1, W + 1)
        window[rid] = _relative(attn.reshape(len(attn), -1),
                                np.asarray(heads)[0].reshape(len(attn), -1))
        same[rid] = (np.sort(np.stack(rec["experts"]), axis=-1)
                     == np.sort(np.asarray(chosen), axis=-1)).all()
    return errors, window, same


def _cached_against_reference(engine, horizon, rids):
    """One trace through ``engine`` with ``rids`` probed: logits, routing
    and the first window layer's attention against the reference, and
    what the programs counted."""
    reg = engine.registry
    booked = (reg.get("serve_chunk_attn_kernel")
              + reg.get("serve_chunk_attn_dense"))
    products = reg.get("serve_gmm_products", k_tiles="whole")
    engine.probe(rids)
    report = engine.run_trace(_trace(), collect_raw=True)
    assert report["requests"]["completed"] == len(LENGTHS)
    if horizon > 1:
        assert report["fast_path"]["fused_scans"] > 0
    results = engine.probe_results()
    assert [results[r]["recycled"] for r in rids] == [False, True]
    for rid, rec in results.items():
        assert rec["tokens"] == report["completed_tokens"][str(rid)]
        assert np.stack(rec["experts"]).shape == (LENGTHS[rid][1], 4, 2)
        assert np.stack(rec["window_attn"]).shape == (LENGTHS[rid][1], 4, 64)
    errors, window_errors, same = _probed_against_reference(engine, results)
    assert max(errors.values()) < TIGHT, errors
    assert max(window_errors.values()) < TIGHT, window_errors
    assert all(same.values()), same
    # what the programs counted: every step of a slot attends its length
    # plus itself in a global layer and at most the window in a window
    # layer; the ring tiles the kernel read (one tile of 16 a ring)
    raw = report["raw_samples"]
    assert sum(raw["unit_live_tokens"]) == sum(
        sum(p + i + 1 for i in range(o - 1)) for p, o in LENGTHS)
    assert sum(raw["unit_ring_tokens"]) == sum(
        sum(min(p + i + 1, 16) for i in range(o - 1)) for p, o in LENGTHS)
    steps = sum(raw["unit_slot_steps"])
    assert sum(raw["unit_ring_tiles"]) == 3 * steps
    assert report["window_live_share"] > 0
    assert reg.get("serve_window_kv_bytes") == window_cache_bytes(
        CONFIG, 4) == 3 * 4 * 16 * 128 * 4 * 2
    assert reg.get("serve_window_tiles_live") >= 3 * steps
    assert report["kv_slot_bytes"] < report["kv_slot_bytes_flat"]
    # every chunk's four layer attentions, each booked once
    chunks = sum(-(-p // 8) for p, _ in LENGTHS)
    assert (reg.get("serve_chunk_attn_kernel")
            + reg.get("serve_chunk_attn_dense")) - booked == 4 * chunks
    # three grouped products an expert layer, four layers, in every
    # chunk and decode step; a toy width is one contraction tile
    assert reg.get("serve_gmm_products", k_tiles="whole") - products == (
        3 * 4 * (chunks + report["decode_steps"]))
    assert reg.get("serve_gmm_products", k_tiles="split") == 0
    assert report["gmm_whole_k_share"] == 1.0
    return report


@pytest.mark.parametrize("horizon, window", [(1, 1), (4, 2)],
                         ids=["per-step", "fused-k4"])
@pytest.mark.parametrize("rids", [(0, 6), (2, 4)],
                         ids=lambda r: f"rids{r[0]}-{r[1]}")
def test_cached_logits_routing_and_window_match_the_reference(horizon, window,
                                                              rids):
    engine = _engine(decode_horizon=horizon, inflight_window=window)
    report = _cached_against_reference(engine, horizon, rids)
    # toy chunks never pass CHUNK_SCORES_BYTES: every attention dense
    assert engine.registry.get("serve_chunk_attn_kernel") == 0
    assert report["chunk_attn_kernel_share"] == 0.0


def test_cached_logits_match_the_reference_with_every_chunk_on_the_kernel(
        monkeypatch):
    """The same engine with ``CHUNK_SCORES_BYTES`` at 0: every prompt
    chunk's attention, global and window, runs as ``chunk_attend``
    (interpreted), and the cached logits, routing and window attention
    still match the float32 reference within the same limits."""
    monkeypatch.setattr(attend, "CHUNK_SCORES_BYTES", 0)
    engine = ServingEngine(
        CONFIG, ServingConfig(**{**SERVING, "decode_horizon": 4,
                                 "inflight_window": 2}),
        _mesh(), seed=5, verbose=False, capture_tokens=True)
    report = _cached_against_reference(engine, 4, (0, 6))
    assert engine.registry.get("serve_chunk_attn_dense") == 0
    assert engine.registry.get("serve_chunk_attn_kernel") > 0
    assert report["chunk_attn_kernel_share"] == 1.0


def test_recycled_slot_gives_a_fresh_engines_logits():
    lengths = [(37, 9), (5, 20)]
    engine = _engine(max_batch=1)
    engine.probe([1])
    engine.run_trace(_trace(lengths))
    reused = engine.probe_results()[1]
    assert reused["slot"] == 0 and reused["recycled"]
    engine.run_trace(TrafficTrace(kind="test", seed=0, params={},
                                  requests=_trace(lengths).requests[1:]))
    fresh = engine.probe_results()[1]
    assert not fresh["recycled"] and fresh["tokens"] == reused["tokens"]
    np.testing.assert_allclose(np.stack(reused["logits"]),
                               np.stack(fresh["logits"]), atol=1e-5)


# -- (c) the decode kernel over a ring ----------------------------------------


@pytest.mark.parametrize("lengths", [[5, 31, 63, 64, 100, 200],
                                     [0, 64, 127, 128, 129, 250]])
def test_ring_decode_kernel_reads_the_window_across_the_wrap(lengths):
    """Interpreted: 7 query heads a K/V head (one head of 128, a row of
    128), a ring of 64 tokens in blocks of 8, slots whose lengths lie
    before, at and past the wrap; against a dense softmax over the
    positions ``p - 64 < j <= p`` written in position order."""
    rng = np.random.default_rng(len(lengths) + lengths[-1])
    b, n, d, w, bs = len(lengths), 7, 128, 64, 8
    longest = max(lengths) + 1
    keys = rng.standard_normal((b, longest, d)).astype(np.float32)
    values = rng.standard_normal((b, longest, d)).astype(np.float32)
    q = rng.standard_normal((b, n, 1, d)).astype(np.float32)
    ring_k = np.zeros((1, b, w // bs, bs, d), np.float32)
    ring_v = np.zeros_like(ring_k)
    for slot, length in enumerate(lengths):
        # what is left of an older request behind a short one
        ring_k[0, slot] = rng.standard_normal((w // bs, bs, d))
        for p in range(length + 1):
            ring_k[0, slot, (p % w) // bs, p % bs] = keys[slot, p]
            ring_v[0, slot, (p % w) // bs, p % bs] = values[slot, p]
    active = np.array([True] * (b - 1) + [False])
    out = np.asarray(decode_attention(
        jnp.asarray(q), jnp.asarray(ring_k), jnp.asarray(ring_v),
        jnp.int32(0), jnp.asarray(lengths, jnp.int32), jnp.asarray(active),
        _mesh()))
    for slot, length in enumerate(lengths):
        if not active[slot]:
            assert not out[slot].any()
            continue
        seen = slice(max(0, length - w + 1), length + 1)
        scores = q[slot, :, 0] @ keys[slot, seen].T / np.sqrt(d)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        np.testing.assert_allclose(out[slot, :, 0], probs @ values[slot, seen],
                                   atol=2e-5, rtol=2e-5)


def test_ring_positions_of_a_chunk():
    # before the ring is full: the places written so far
    assert list(ring_positions(12, 16)) == list(range(12)) + [-1] * 4
    # past it: the last position before the chunk that lies at each place
    assert list(ring_positions(40, 16)) == [32 + i if i < 8 else 16 + i
                                            for i in range(16)]
    assert list(ring_positions(0, 16)) == [-1] * 16


# -- (d) the cache ------------------------------------------------------------


def test_window_planes_hold_the_window_and_heads_as_rows():
    cache = jax.eval_shape(lambda: create_hybrid_cache(CONFIG, 4, 16, 8))
    # one global layer of max_seq, three window layers of W, a token's 2
    # heads of 64 as one row of 128
    assert cache.k.shape == (1, 4, 16, 8, 128)
    assert cache.wk.shape == cache.wv.shape == (3, 4, 2, 8, 128)
    assert kv_rows(CONFIG)
    # the published shape: 4 heads of 128 are a row of 512, not 8 heads
    program = dict(TOY, hidden_size=2560, num_heads=28, num_kv_heads=4,
                   attn_head_dim=128, sliding_window=4096)
    big = ModelConfig.from_dict(program)
    shapes = jax.eval_shape(lambda: create_hybrid_cache(big, 2, 1024, 16))
    assert shapes.k.shape[-1] == shapes.wk.shape[-1] == 512
    assert shapes.wk.shape[2] * shapes.wk.shape[3] == 4096
    # the gate prices the rings by what they hold, not by max_seq: K and
    # V of 4 heads of 128 in float32, one global layer and three rings
    assert kv_cache_bytes_per_device(big, 48, 16384) == \
        48 * (16384 * 1 + 4096 * 3) * 2 * 4 * 128 * 4
    # a model without window layers carries no ring at all
    olmo = ModelConfig.from_dict(dict(TOY, layer_types=["full_attention"],
                                      rope_layout=None, sliding_window=0))
    none = jax.eval_shape(lambda: create_hybrid_cache(olmo, 4, 16, 8))
    assert none.wk is None and none.wv is None
    assert window_cache_bytes(olmo, 4) == 0


# -- (e) routing and experts against plain formulas ---------------------------


def _expert_weights(rng, h=16, e=8, f=12):
    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    return {"router": w(h, e), "exp_gate": w(e, h, f),
            "exp_up": w(e, h, f), "exp_down": w(e, f, h)}


def test_softmax_route_and_reglu_experts_equal_plain_formulas():
    rng = np.random.default_rng(7)
    w = _expert_weights(rng)
    u = jnp.asarray(rng.standard_normal((10, 16)), jnp.float32)
    routing = moe.route(u, w["router"], None, 3, 1.0, "softmax")
    logits = np.asarray(u) @ np.asarray(w["router"])
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    top = np.argsort(-s, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(routing.experts), top)
    chosen = np.take_along_axis(s, top, -1)
    np.testing.assert_allclose(np.asarray(routing.gates),
                               chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    y, _, counts = moe.expert_layer(u, w, 3, 1.0, act=jax.nn.relu,
                                    scoring="softmax")
    want = np.zeros_like(np.asarray(u))
    with jax.default_matmul_precision("highest"):
        for t in range(10):
            for e, g in zip(top[t], chosen[t] / chosen[t].sum()):
                x = np.asarray(u[t])
                hid = (np.maximum(x @ np.asarray(w["exp_gate"][e]), 0)
                       * (x @ np.asarray(w["exp_up"][e])))
                want[t] += g * hid @ np.asarray(w["exp_down"][e])
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert int(counts[0]) == 30


def test_the_sigmoid_route_and_swiglu_experts_are_the_defaults():
    """The kanana configuration builds the program it built before: its
    route and its expert layer are the sigmoid and SwiGLU ones, traced
    to the same operations with and without the new options spelt out."""
    from benchmarks.harness import cells

    model = cells.resolve_cell("kanana_serve_longctx_backlog") \
        .config["program"]["model"]
    kanana = ModelConfig.from_dict(model)
    assert (kanana.scoring_func, kanana.mlp, kanana.rope_layout,
            kanana.router_before_attention, kanana.attn_head_dim) == (
        "sigmoid", "swiglu", None, False, 0)
    shapes = hybrid._layer_shapes(kanana, "latent_attention", True)
    assert shapes["router_bias"] == (128,)
    rng = np.random.default_rng(1)
    w = {**_expert_weights(rng), "router_bias": jnp.zeros(8)}
    u = jnp.ones((4, 16), jnp.float32)

    def text(fn):
        return str(jax.make_jaxpr(fn)(u))

    assert text(lambda x: moe.expert_layer(x, w, 2, 2.448)) == text(
        lambda x: moe.expert_layer(x, w, 2, 2.448, act=jax.nn.silu,
                                   scoring="sigmoid"))
    sigmoid = text(lambda x: moe.route(x, w["router"], w["router_bias"], 2,
                                       2.448))
    assert "logistic" in sigmoid and "reduce_max" not in sigmoid
    reglu = text(lambda x: moe.expert_layer(x, w, 2, 1.0, act=jax.nn.relu))
    assert "max" in reglu and "logistic" not in reglu.split("gmm")[-1]


# -- (f) what a ring cannot do yet --------------------------------------------


@pytest.mark.parametrize("serving, reason", [
    (dict(prefix_caching=True), "ring holds the last W positions"),
    (dict(speculation="ngram", spec_gamma=2), "ring has written"),
    (dict(prefill_chunk=12), "whole number of prompt chunks"),
])
def test_serving_refuses_what_a_ring_cannot_do(serving, reason):
    with pytest.raises(ValueError, match=reason):
        ServingEngine(CONFIG, ServingConfig(**{**SERVING, **serving}),
                      _mesh(), seed=5, verbose=False)


def test_int8_and_tp_are_refused_and_the_gate_prices_the_rings():
    with pytest.raises(ValueError, match="int8"):
        validate_serving(CONFIG, 4, 128, 8, kv_quantization="int8")
    with pytest.raises(ValueError, match="sliding_attention"):
        validate_serving(CONFIG, 4, 128, 8, tp=2)
    rings = window_cache_bytes(CONFIG, 4)
    with pytest.raises(ValueError, match="window rings"):
        validate_serving(CONFIG, 4, 128, 8, hbm_budget_bytes=rings)


@pytest.mark.parametrize("change, reason", [
    (dict(sliding_window=0), "sliding_window >= 1"),
    (dict(rope_layout=[0, 1, 1]), "rope_layout"),
    (dict(rope_layout=[0, 1, 1, 1], rope_theta=0), "rope_layout"),
    (dict(scoring_func="softmax", routed_scaling_factor=2.0),
     "routed_scaling_factor must be 1"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(norm_placement="post"), "router_before_attention"),
    (dict(layer_types=["full_attention"], rope_layout=None), "without a"),
])
def test_model_config_refuses(change, reason):
    with pytest.raises(ValueError, match=reason):
        ModelConfig.from_dict({**TOY, **change})
