"""Latent attention (MLA) with rotary positions and routed + shared
experts in the ``layer_types`` family (kanana-2-30b-a3b's block) at toy
widths on the CPU, against the plain float32 reference
``benchmarks/reference/kanana2.py`` on seeded weights.

(a) ``models.forward`` and its routing; (b) prefill in chunks, then
decode through the latent cache, per-step and fused, for an unused and a
recycled slot; (c) the absorbed form equals the expanded one, the decode
kernel a dense softmax; (d) rotary scores depend on the distance alone;
(e) the expert layer against a plain loop over experts, under uniform
and fully skewed routing, the bias, the shared expert, the gates; (f)
the leading dense layer and the scanned expert layers in one stack; (g)
what the family does not serve yet is refused with its reason.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models import forward, hybrid, init_params, num_parameters
from dlbb_tpu.models.configs import (
    ModelConfig,
    latent_cache_bytes,
    validate_expert_parallelism,
)
from dlbb_tpu.ops import routed_experts as moe
from dlbb_tpu.ops.latent_attention import latent_decode_attention
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine, family_for
from dlbb_tpu.serve.traffic import Request, TrafficTrace

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import kanana2 as reference          # noqa: E402

TOY = dict(
    hidden_size=64, num_layers=3, num_heads=4, ffn_intermediate=96,
    dtype="float32", norm="rmsnorm", mlp="swiglu", bias=False,
    qk_norm=False, norm_placement="pre", rms_norm_eps=1e-6, vocab_size=256,
    layer_types=["latent_attention"], kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
    first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=2, moe_intermediate_size=24,
    routed_scaling_factor=2.448)
CONFIG = ModelConfig.from_dict(TOY)
# float32 system against float32 reference: what is left is the order of
# the sums (absorbed against expanded, grouped against looped, cached
# against whole-sequence)
TIGHT = 2e-4
SERVING = dict(max_batch=4, max_seq=128, block_size=8, prefill_chunk=16,
               queue_capacity=64)
# prompts that end inside a chunk, on a chunk edge, on a block edge and
# inside a block; with 4 slots the last three requests take recycled ones
LENGTHS = [(37, 9), (16, 5), (50, 12), (8, 3), (33, 7), (21, 6), (70, 10)]


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


def _probed_against_reference(engine, results):
    """Each probed request's logits (last prompt position and every
    decode step) against the reference's forward over the prompt and the
    tokens the engine committed, and whether its chosen experts are the
    reference's, as sets."""
    errors, same = {}, {}
    for rid, rec in results.items():
        ids = list(rec["prompt_ids"]) + rec["tokens"][:-1]
        first = len(rec["prompt_ids"]) - 1
        ref, _select, chosen, _gates = reference.forward_logits(
            engine.params, ids, TOY, positions=list(range(first, len(ids))),
            with_routing=True)
        errors[rid] = _relative(np.stack(rec["logits"]), np.asarray(ref))
        same[rid] = (np.sort(np.stack(rec["experts"]), axis=-1)
                     == np.sort(np.asarray(chosen), axis=-1)).all()
    return errors, same


# -- (a) the whole-sequence forward --------------------------------------------


@pytest.mark.parametrize("seq", [3, 64, 150])
def test_forward_logits_and_routing_match_the_reference(seq):
    params = init_params(CONFIG, jax.random.key(3))
    ids = np.random.default_rng(seq).integers(0, 256, size=(2, seq))
    logits, chosen = hybrid.forward(params, jnp.asarray(ids), CONFIG,
                                    with_routing=True)
    logits = np.asarray(logits)
    assert logits.shape == (2, seq, 256) and logits.dtype == np.float32
    assert chosen.shape == (2, 2 * seq, 2)
    for row in range(2):
        ref, _select, want, _gates = reference.forward_logits(
            params, ids[row], TOY, with_routing=True)
        assert _relative(logits[row], np.asarray(ref)) < TIGHT
        got = np.asarray(chosen)[:, row * seq:(row + 1) * seq]
        assert (np.sort(got, -1)
                == np.sort(np.asarray(want).transpose(1, 0, 2), -1)).all()
    # the plain entry point gives the same logits
    np.testing.assert_array_equal(
        logits, np.asarray(forward(params, jnp.asarray(ids), CONFIG)))


def test_parameter_count_matches_the_tree_and_the_published_arithmetic():
    params = init_params(CONFIG, jax.random.key(0))
    assert set(params) == {"embed", "lead", "periods", "ln_f", "lm_head"}
    assert num_parameters(CONFIG) == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    assert reference.weight_faults(params, TOY) == []
    # ISSUE 31's reckoning at the published widths: 26.35M of attention,
    # 640.0M an expert layer, 64.1M the dense layer, 5,069M in all
    full = CONFIG.with_(
        hidden_size=2048, num_layers=8, num_heads=32, ffn_intermediate=6144,
        vocab_size=128256, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=128,
        num_experts_per_tok=6, moe_intermediate_size=768, dtype="bfloat16")
    assert num_parameters(full) == pytest.approx(5069e6, rel=1e-3)
    assert full.latent_width == 576 and full.latent_row == 640
    assert latent_cache_bytes(full, 64, 4608, held=False) == \
        8 * 64 * 4608 * 576 * 2
    assert latent_cache_bytes(full, 64, 4608) == 8 * 64 * 4608 * 640 * 2


@pytest.mark.parametrize("fault, said", [
    ("none", None),
    ("bias_large", "periods[0].router_bias spans"),
    ("expert_doubled", "periods[0].exp_up has mean"),
    ("norm_missing", "lead[0].kv_norm is missing"),
    ("latent_scale_ones", "periods[0].kv_norm spans"),
    ("latent_transposed", "periods[0].wkv_b has shape"),
])
def test_the_reference_judges_the_weights_it_is_handed(fault, said):
    params = init_params(CONFIG, jax.random.key(3))
    lead, periods = dict(params["lead"][0]), dict(params["periods"][0])
    if fault == "bias_large":
        periods["router_bias"] = 10 * periods["router_bias"]
    elif fault == "expert_doubled":
        periods["exp_up"] = 2 * periods["exp_up"]
    elif fault == "norm_missing":
        del lead["kv_norm"]
    elif fault == "latent_scale_ones":
        periods["kv_norm"] = jnp.ones_like(periods["kv_norm"])
    elif fault == "latent_transposed":
        periods["wkv_b"] = jnp.swapaxes(periods["wkv_b"], 1, 2)
    faults = reference.weight_faults(
        {**params, "lead": (lead,), "periods": (periods,)}, TOY)
    if said is None:
        assert faults == []
    else:
        assert len(faults) == 1 and said in faults[0], faults


# -- (b) prefill in chunks, then decode through the latent cache ----------------


@pytest.mark.parametrize("horizon, window", [(1, 1), (4, 2)],
                         ids=["per-step", "fused-k4"])
@pytest.mark.parametrize("rids", [(0, 5), (2, 6), (1, 4)],
                         ids=lambda r: f"rids{r[0]}-{r[1]}")
def test_cached_logits_and_routing_match_the_reference(horizon, window,
                                                       rids):
    engine = _engine(decode_horizon=horizon, inflight_window=window)
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
        assert np.stack(rec["experts"]).shape == (LENGTHS[rid][1], 2, 2)
    errors, same = _probed_against_reference(engine, results)
    assert max(errors.values()) < TIGHT, errors
    assert all(same.values()), same
    # what the programs counted, beside the tokens they returned
    raw = report["raw_samples"]
    steps = sum(raw["unit_slot_steps"])
    assert steps == report["generated_tokens"] - len(LENGTHS)
    # 2 expert layers x 2 experts a token: no assignment dropped, none
    # made for an empty slot or a chunk's padding
    assert sum(raw["moe_unit_assignments"]) == steps * 2 * 2
    assert sum(raw["moe_chunk_assignments"]) == \
        sum(p for p, _ in LENGTHS) * 2 * 2
    assert len(raw["moe_unit_touched"]) == report["decode_units"]
    assert len(raw["moe_chunk_touched"]) == \
        report["fast_path"]["prefill_chunks"]
    assert 0.0 < report["experts_touched_share"] <= 1.0
    assert report["expert_load_max_over_mean"] >= 1.0
    assert 0.0 < report["latent_live_share"] <= 1.0
    reg = engine.registry
    assert reg.get("serve_latent_tiles_live") > 0
    assert reg.get("serve_latent_bytes") == \
        latent_cache_bytes(CONFIG, 4, 128) == 3 * 4 * 128 * 128 * 4
    assert reg.get("serve_kv_bytes") == 0 == reg.get("serve_state_bytes")
    assert reg.get("serve_moe_assignments") >= \
        sum(raw["moe_unit_assignments"]) + sum(raw["moe_chunk_assignments"])
    # live tokens: every step of a slot attends its length plus itself
    assert sum(raw["unit_live_tokens"]) == sum(
        sum(p + i + 1 for i in range(o - 1)) for p, o in LENGTHS)


def test_recycled_slot_gives_a_fresh_engines_logits():
    lengths = [(37, 9), (21, 6)]
    engine = _engine(max_batch=1)
    engine.probe([1])
    engine.run_trace(_trace(lengths))
    reused = engine.probe_results()[1]
    assert reused["slot"] == 0 and reused["recycled"]
    only = _trace(lengths).requests[1:]
    engine.run_trace(TrafficTrace(kind="test", seed=0, params={},
                                  requests=only))
    fresh = engine.probe_results()[1]
    assert not fresh["recycled"]
    assert fresh["tokens"] == reused["tokens"]
    np.testing.assert_allclose(np.stack(reused["logits"]),
                               np.stack(fresh["logits"]), atol=1e-5)


# -- (c) absorbed == expanded; the kernel == a dense softmax -------------------


def _draw(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("lengths", [[0, 5, 23, 64], [63, 64, 65, 127]])
def test_decode_kernel_reads_each_row_once_for_all_heads(lengths):
    """The absorbed scores and values over the plane equal a dense
    softmax over the rows each slot holds; an inactive slot gives
    zeros."""
    rng = np.random.default_rng(sum(lengths))
    b, n, layers, nb, bs, w, r = 4, 4, 2, 16, 8, 128, 32
    plane = _draw(rng, layers, b, nb, bs, w)
    q = _draw(rng, b, n, w)
    lens = jnp.asarray(lengths, jnp.int32)
    active = jnp.asarray([True, True, False, True])
    out = latent_decode_attention(q, plane, jnp.int32(1), lens, active,
                                  _mesh(), r, 0.25)
    rows = np.asarray(plane[1]).reshape(b, nb * bs, w)
    for slot in range(b):
        if not bool(active[slot]):
            assert not np.asarray(out[slot]).any()
            continue
        held = rows[slot, :lengths[slot] + 1]
        s = np.asarray(q[slot]) @ held.T * 0.25
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ held[:, :r]
        np.testing.assert_allclose(out[slot], want, atol=2e-5)


def test_absorbed_form_equals_the_expanded_form():
    """One query against cached tokens: ``q_nope W^K . c'`` and ``(sum p
    c') W^V`` give what per-head expanded keys and values give."""
    rng = np.random.default_rng(7)
    n, dn, dr, dv, r, s = 4, 16, 8, 16, 32, 21
    wkv_b = _draw(rng, r, n, dn + dv) / np.sqrt(r)
    c, k_rope = _draw(rng, s, r), _draw(rng, s, dr)
    q_nope, q_rope = _draw(rng, n, dn), _draw(rng, n, dr)
    scale = (dn + dr) ** -0.5
    k_nope, v = hybrid.expand_latent(c, wkv_b, CONFIG)
    scores = (jnp.einsum("nd,snd->ns", q_nope, k_nope)
              + jnp.einsum("nd,sd->ns", q_rope, k_rope)) * scale
    expanded = jnp.einsum("ns,snd->nd", jax.nn.softmax(scores, -1), v)
    q_abs = jnp.einsum("nd,rnd->nr", q_nope, wkv_b[..., :dn])
    absorbed_scores = (q_abs @ c.T + q_rope @ k_rope.T) * scale
    np.testing.assert_allclose(absorbed_scores, scores, atol=1e-5)
    o = jax.nn.softmax(absorbed_scores, -1) @ c
    np.testing.assert_allclose(
        jnp.einsum("nr,rnd->nd", o, wkv_b[..., dn:]), expanded, atol=1e-5)


# -- (d) rotary positions -------------------------------------------------------


def test_rotary_scores_depend_on_the_distance_alone():
    rng = np.random.default_rng(11)
    q, k = _draw(rng, 8), _draw(rng, 8)

    def score(tq, tk):
        return float(hybrid.rope(q, jnp.int32(tq), 1e6)
                     @ hybrid.rope(k, jnp.int32(tk), 1e6))

    assert score(5, 2) == pytest.approx(score(4003, 4000), abs=1e-4)
    assert score(5, 2) == pytest.approx(score(103, 100), abs=1e-5)
    assert abs(score(5, 2) - score(5, 3)) > 1e-3
    # position 0 is the identity, and a rotation keeps the length
    np.testing.assert_allclose(hybrid.rope(q, jnp.int32(0), 1e6), q)
    assert float(jnp.linalg.norm(hybrid.rope(q, jnp.int32(77), 1e6))) == \
        pytest.approx(float(jnp.linalg.norm(q)), rel=1e-6)
    # adjacent pairs (2i, 2i+1), pair i by t x theta^(-2i/d): pair 0 of
    # a token at position 1 turns by one radian whatever theta
    one = hybrid.rope(jnp.asarray([1.0, 0.0, 1.0, 0.0]), jnp.int32(1), 1e6)
    np.testing.assert_allclose(one[:2], [np.cos(1.0), np.sin(1.0)],
                               atol=1e-6)
    np.testing.assert_allclose(one[2:], [np.cos(1e-3), np.sin(1e-3)],
                               atol=1e-6)
    # ... and it is the reference's rotation
    x = _draw(rng, 5, 3, 8)
    np.testing.assert_allclose(
        hybrid.rope(x, jnp.arange(5)[:, None], 1e6),
        reference._rope(x, jnp.arange(5), 1e6), atol=1e-6)


# -- (e) the expert layer -------------------------------------------------------


def _expert_weights(rng, h=16, e=8, f=12, shared=2):
    w = {"router": _draw(rng, h, e),
         "router_bias": jnp.asarray(rng.uniform(-0.01, 0.01, e), jnp.float32),
         "exp_gate": _draw(rng, e, h, f) / 4, "exp_up": _draw(rng, e, h, f) / 4,
         "exp_down": _draw(rng, e, f, h) / 4}
    if shared:
        w.update(shared_gate=_draw(rng, h, shared * f) / 4,
                 shared_up=_draw(rng, h, shared * f) / 4,
                 shared_down=_draw(rng, shared * f, h) / 4)
    return w


def _loop_over_experts(u, w, top_k, scale):
    """Every expert on every token, one after another, the gate as the
    mask; the shared expert once."""
    s = 1 / (1 + np.exp(-(np.asarray(u) @ np.asarray(w["router"]))))
    chosen = np.argsort(-(s + np.asarray(w["router_bias"])), axis=-1,
                        kind="stable")[:, :top_k]
    y = np.zeros_like(np.asarray(u))
    for t in range(u.shape[0]):
        total = s[t, chosen[t]].sum()
        for e in chosen[t]:
            x = np.asarray(u[t])
            act = (jax.nn.silu(x @ w["exp_gate"][e]) * (x @ w["exp_up"][e]))
            y[t] += scale * s[t, e] / total * np.asarray(
                act @ w["exp_down"][e])
    if "shared_gate" in w:
        y += np.asarray((jax.nn.silu(u @ w["shared_gate"])
                         * (u @ w["shared_up"])) @ w["shared_down"])
    return y, chosen


@pytest.mark.parametrize("skew", ["uniform", "one_expert", "two_experts"])
def test_expert_layer_equals_a_plain_loop_and_drops_nothing(skew):
    rng = np.random.default_rng(5)
    w = _expert_weights(rng)
    u = _draw(rng, 40, 16)
    if skew != "uniform":
        # every token's largest scores at the same experts, whatever the
        # token: all 40 x 2 assignments go to 2 experts (or, with k = 1,
        # all 40 to one)
        w["router_bias"] = w["router_bias"].at[jnp.asarray([3, 6])].add(
            jnp.asarray([20.0, 10.0]))
    top_k = 1 if skew == "one_expert" else 2
    y, routing, counts = moe.expert_layer(u, w, top_k, 2.448)
    want, chosen = _loop_over_experts(u, w, top_k, 2.448)
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_array_equal(np.sort(routing.experts, -1),
                                  np.sort(chosen, -1))
    assigned, touched, fullest = (int(c) for c in counts)
    assert assigned == 40 * top_k           # no capacity, nothing dropped
    if skew == "uniform":
        assert touched > 4
    else:
        assert touched == top_k and fullest == 40
        assert set(np.asarray(routing.experts).ravel()) <= {3, 6}


def test_the_bias_chooses_and_does_not_weight_and_the_gates_sum_to_the_scale():
    rng = np.random.default_rng(9)
    w = _expert_weights(rng)
    u = _draw(rng, 12, 16)
    plain = moe.route(u, w["router"], jnp.zeros(8), 2, 2.448)
    np.testing.assert_allclose(plain.gates.sum(-1), 2.448, rtol=1e-6)
    # a bias that lifts experts 1 and 4 over all others: they are chosen,
    # and weighted by their OWN sigmoid scores, not by score + bias
    lifted = moe.route(u, w["router"], jnp.zeros(8).at[
        jnp.asarray([1, 4])].set(5.0), 2, 2.448)
    assert set(np.asarray(lifted.experts).ravel()) == {1, 4}
    np.testing.assert_allclose(lifted.scores, plain.scores)
    s = np.take_along_axis(np.asarray(plain.scores),
                           np.asarray(lifted.experts), axis=-1)
    np.testing.assert_allclose(lifted.gates,
                               2.448 * s / s.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_the_shared_expert_is_counted_once_and_invalid_tokens_take_no_expert():
    rng = np.random.default_rng(13)
    w = _expert_weights(rng)
    u = _draw(rng, 10, 16)
    both, _, _ = moe.expert_layer(u, w, 2, 2.448)
    routed_only, _, _ = moe.expert_layer(
        u, {k: v for k, v in w.items() if not k.startswith("shared")}, 2,
        2.448)
    shared = (jax.nn.silu(u @ w["shared_gate"]) * (u @ w["shared_up"])) \
        @ w["shared_down"]
    np.testing.assert_allclose(both - routed_only, shared, atol=2e-5)
    valid = jnp.arange(10) < 6
    y, _, counts = moe.expert_layer(u, w, 2, 2.448, valid=valid)
    np.testing.assert_allclose(y[:6], both[:6], atol=2e-5)
    assert int(counts[0]) == 6 * 2


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_layer", "layer_of_a_stack"])
def test_grouped_products_are_the_chips_kernel_row_for_row(stacked):
    """The path the chip runs (``megablox.gmm``, interpreted here): 100
    live rows padded to the kernel's row tile, and a layer's experts as
    groups of a whole stack's, against each row's own expert."""
    rng = np.random.default_rng(17)
    stack = [_expert_weights(rng, h=128, e=4, f=128, shared=0)
             for _ in range(3 if stacked else 1)]
    w = stack[-2 if stacked else 0]
    u = _draw(rng, 64, 128)
    routing = moe.route(u, w["router"], w["router_bias"], 2, 1.0)
    d = moe.dispatch(u, routing.experts, 4, jnp.arange(64) < 50)
    if stacked:
        out = moe.grouped_products(
            d, *(jnp.stack([layer[name] for layer in stack])
                 for name in ("exp_gate", "exp_up", "exp_down")),
            layer=jnp.int32(1))
    else:
        out = moe.grouped_products(d, w["exp_gate"], w["exp_up"],
                                   w["exp_down"])
    flat = np.where(np.repeat(np.arange(64) < 50, 2),
                    np.asarray(routing.experts).ravel(), 4)
    for pos, a in enumerate(np.asarray(d.order)):
        if flat[a] == 4:
            want = np.zeros(128)
        else:
            x, e = np.asarray(u[a // 2]), flat[a]
            gate, up = x @ np.asarray(w["exp_gate"][e]), \
                x @ np.asarray(w["exp_up"][e])
            want = (gate / (1 + np.exp(-gate)) * up) \
                @ np.asarray(w["exp_down"][e])
        np.testing.assert_allclose(out[pos], want, atol=1e-4)


# -- (f) one stack: the leading dense layer, then the scanned expert layers ----


def test_leading_dense_layer_and_expert_layers_share_one_cache_plane():
    from dlbb_tpu.serve.kvcache import create_hybrid_cache

    cache = create_hybrid_cache(CONFIG, 4, 16, 8)
    # all three layers' rows in one plane, 32 + 8 values in 128 lanes;
    # the kinds the model has no layer of hold nothing
    assert cache.latent.shape == (3, 4, 16, 8, 128)
    assert cache.k.size == cache.state.size == cache.conv.size == 0
    params = init_params(CONFIG, jax.random.key(1))
    assert params["lead"][0]["mlp_up"].shape == (1, 64, 96)
    assert params["periods"][0]["exp_up"].shape == (2, 8, 64, 24)
    assert "router" not in params["lead"][0]
    # two leading layers, one expert layer: the same seam
    other = CONFIG.with_(first_k_dense_replace=2)
    p2 = init_params(other, jax.random.key(1))
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 20))
    ref = reference.forward_logits(
        p2, ids[0], {**TOY, "first_k_dense_replace": 2})
    assert _relative(np.asarray(forward(p2, jnp.asarray(ids), other))[0],
                     np.asarray(ref)) < TIGHT


# -- (g) what is refused, and why ----------------------------------------------


@pytest.mark.parametrize("serving, reason", [
    (dict(speculation="greedy"), "absorbed attention for several positions"),
    (dict(prefix_caching=True), "latent plane has no attach program"),
    (dict(kv_quantization="int8"), "fp K/V layout only"),
    (dict(prefill_chunk=None), "prefilled in chunks"),
])
def test_serving_refuses_what_the_family_lacks(serving, reason):
    sv = ServingConfig(**{**SERVING, **serving})
    with pytest.raises(ValueError, match=reason):
        family_for(CONFIG).check_serving(CONFIG, sv)
        sv.validate(CONFIG)


def test_tp_and_ep_are_refused_and_the_gate_prices_the_latents():
    with pytest.raises(ValueError, match="no head dim to shard"):
        ServingConfig(**SERVING).validate(CONFIG, tp=2)
    with pytest.raises(ValueError, match="no expert-parallel share"):
        validate_expert_parallelism(CONFIG, 2)
    with pytest.raises(ValueError, match="GiB of latents"):
        ServingConfig(**{**SERVING, "hbm_budget_gb": 1e-4}).validate(CONFIG)
    with pytest.raises(ValueError, match="dense FFN in the GPT block"):
        ServingConfig(**SERVING).validate(ModelConfig(
            hidden_size=64, num_layers=2, num_heads=4, ffn_intermediate=128,
            num_experts=4))


@pytest.mark.parametrize("change, reason", [
    (dict(qk_norm=True), "model family not implemented"),
    (dict(norm_placement="both"), "unknown norm_placement"),
    (dict(kv_lora_rank=0), "latent_attention layers need"),
    (dict(qk_rope_head_dim=7), "latent_attention layers need"),
    (dict(num_experts_per_tok=9), "routed experts need"),
    (dict(n_routed_experts=0), "without n_routed_experts"),
    (dict(first_k_dense_replace=4), "whole number of periods"),
])
def test_model_config_refuses(change, reason):
    with pytest.raises(ValueError, match=reason):
        ModelConfig.from_dict({**TOY, **change})
