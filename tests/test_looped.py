"""The looped stack of the ``layer_types`` family (Ouro: the same layers
run ``total_ut_steps`` times a token, K/V planes per (pass, layer),
sandwich norms, half-split rotary, an exit gate after every pass) on the
CPU at a small size, float32, seeded weights, against the plain
reference ``benchmarks/reference/ouro.py``: the whole-sequence forward;
prefill in two chunks then decode through the cache, on logits AND on
the four exit gates of every probed position; each fault of the loop
(``scripts/ouro_controls.py``) read by the comparison; what the cache
holds and what the family refuses."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.models import hybrid
from dlbb_tpu.models.configs import (
    ModelConfig,
    kv_cache_bytes,
    kv_cache_bytes_per_device,
)
from dlbb_tpu.models.hybrid import init_params, num_parameters
from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine, family_for
from dlbb_tpu.serve.traffic import Request, TrafficTrace

ROOT = Path(__file__).resolve().parents[1]
for path in (ROOT, ROOT / "scripts"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import ouro_controls as controls                                # noqa: E402
from benchmarks.reference import ouro as reference              # noqa: E402

TOY = dict(
    hidden_size=64, num_layers=3, num_heads=4, ffn_intermediate=96,
    dtype="float32", norm="rmsnorm", mlp="swiglu", bias=False,
    qk_norm=False, norm_placement="sandwich", rms_norm_eps=1e-6,
    vocab_size=256, layer_types=["full_attention"], rope_theta=1e6,
    total_ut_steps=4, early_exit_threshold=1.0)
CONFIG = ModelConfig.from_dict(TOY)
# float32 system against float32 reference: what is left is the order of
# the sums (cached against whole-sequence, the kernel's online softmax)
TIGHT = 2e-4
SERVING = dict(max_batch=4, max_seq=128, block_size=8, prefill_chunk=16,
               queue_capacity=64)
# prompts of two chunks and more that end inside a chunk, on a chunk edge
# and inside a block; with 4 slots the last three requests take recycled
# ones
LENGTHS = [(27, 9), (16, 5), (50, 12), (8, 3), (33, 7), (21, 6), (70, 10)]


def _relative(system, ref):
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


def _probed_against_reference(engine, results, model=TOY):
    """Each probed request's logits and exit gates (last prompt position
    and every decode step) against the reference's forward over the
    prompt and the tokens the engine committed: ``{rid: (logits'
    largest relative error, gates' largest absolute error)}``."""
    errors = {}
    for rid, rec in results.items():
        ids = list(rec["prompt_ids"]) + rec["tokens"][:-1]
        first = len(rec["prompt_ids"]) - 1
        ref, gates = reference.forward_logits(
            engine.params, ids, model, positions=list(range(first, len(ids))),
            with_gates=True)
        errors[rid] = (
            _relative(np.stack(rec["logits"]), np.asarray(ref)),
            float(np.abs(np.stack(rec["exit_gates"])
                         - np.asarray(gates)).max()))
    return errors


# -- (a) the whole-sequence forward --------------------------------------------


@pytest.mark.parametrize("seq", [3, 64, 150])
def test_forward_logits_and_gates_match_the_reference(seq):
    params = init_params(CONFIG, jax.random.key(3))
    ids = np.random.default_rng(seq).integers(0, 256, size=(2, seq))
    logits, gates = hybrid.forward(params, jnp.asarray(ids), CONFIG,
                                   with_gates=True)
    assert logits.shape == (2, seq, 256) and logits.dtype == jnp.float32
    assert gates.shape == (4, 2, seq) and gates.dtype == jnp.float32
    for row in range(2):
        ref, want = reference.forward_logits(params, ids[row], TOY,
                                             with_gates=True)
        assert _relative(np.asarray(logits)[row], np.asarray(ref)) < TIGHT
        np.testing.assert_allclose(np.asarray(gates)[:, row].T, want,
                                   atol=1e-5)
    # the gates spread over (0, 1), and differ from pass to pass
    assert 0.05 < float(gates.min()) and float(gates.max()) < 0.99
    assert float(jnp.abs(gates[0] - gates[3]).mean()) > 0.02


@pytest.mark.parametrize("passes", [1, 2, 3])
def test_fewer_passes_are_another_function_and_one_pass_is_the_plain_stack(
        passes):
    """``total_ut_steps`` 1 is the unlooped stack (no gate, the final
    norm once, ``scan_stack`` and no loop around it) and equals the
    reference run for one pass; every count of passes equals the
    reference's for that count and differs from four's."""
    params = init_params(CONFIG, jax.random.key(3))
    config = CONFIG.with_(total_ut_steps=passes)
    if passes == 1:
        params = {name: a for name, a in params.items()
                  if not name.startswith("exit_gate")}
        assert set(init_params(config, jax.random.key(3))) == set(params)
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 40))
    logits = np.asarray(hybrid.forward(params, jnp.asarray(ids), config))[0]
    model = {**TOY, "total_ut_steps": passes}
    assert _relative(logits, np.asarray(
        reference.forward_logits(params, ids[0], model))) < TIGHT
    four = np.asarray(reference.forward_logits(
        init_params(CONFIG, jax.random.key(3)), ids[0], TOY))
    assert _relative(logits, four) > 0.1


def test_parameter_count_matches_the_tree_and_the_published_arithmetic():
    params = init_params(CONFIG, jax.random.key(0))
    assert set(params) == {"embed", "periods", "ln_f", "lm_head",
                           "exit_gate_w", "exit_gate_b"}
    assert set(params["periods"][0]) == {
        "ln1", "ln1_out", "ln2", "ln2_out", "wq", "wk", "wv", "wo",
        "mlp_gate", "mlp_up", "mlp_down"}
    assert num_parameters(CONFIG) == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    assert reference.weight_faults(params, TOY) == []
    # ISSUE 33's reckoning at the published widths: 51,388,416 a layer,
    # 2,466.6M in 48, 201.3M of embedding and head, 4,097 of final norm
    # and gate: 2,668.0M
    full = CONFIG.with_(hidden_size=2048, num_layers=48, num_heads=16,
                        ffn_intermediate=5632, vocab_size=49152,
                        dtype="bfloat16")
    assert num_parameters(full) == 48 * 51_388_416 + 2 * 49152 * 2048 + 4097
    assert num_parameters(full) == pytest.approx(2668.0e6, rel=1e-4)
    # ... and a token's K and V in every (pass, layer): 1,572,864 B
    assert full.kv_planes == 192
    assert kv_cache_bytes(full, 1, 1) == 1_572_864
    assert kv_cache_bytes(full, 8, 640) == pytest.approx(8.05e9, rel=1e-3)


@pytest.mark.parametrize("fault, said", [
    ("none", None),
    ("scale_not_ones", "periods[0].ln1_out is not all ones"),
    ("gate_doubled", "exit_gate_w has mean"),
    ("bias_large", "exit_gate_b is"),
    ("norm_missing", "periods[0].ln2_out is missing"),
    ("qk_norm_left_in", "periods[0].q_norm is not expected"),
    ("heads_transposed", "periods[0].wo has shape"),
])
def test_the_reference_judges_the_weights_it_is_handed(fault, said):
    params = init_params(CONFIG, jax.random.key(3))
    layer = dict(params["periods"][0])
    if fault == "scale_not_ones":
        layer["ln1_out"] = 0.5 * layer["ln1_out"]
    elif fault == "gate_doubled":
        params["exit_gate_w"] = 2 * params["exit_gate_w"]
    elif fault == "bias_large":
        params["exit_gate_b"] = params["exit_gate_b"] + 3.0
    elif fault == "norm_missing":
        del layer["ln2_out"]
    elif fault == "qk_norm_left_in":
        layer["q_norm"] = jnp.ones((3, 4, 16))
    elif fault == "heads_transposed":
        layer["wo"] = jnp.swapaxes(layer["wo"], 1, 2)
    faults = reference.weight_faults({**params, "periods": (layer,)}, TOY)
    if said is None:
        assert faults == []
    else:
        assert len(faults) == 1 and said in faults[0], faults


def test_the_references_exit_rule_is_the_published_one():
    gates = jnp.asarray([[0.5, 0.5, 0.5, 0.5], [0.0, 0.0, 0.0, 0.0],
                         [1.0, 0.3, 0.3, 0.3]])
    p = reference.exit_distribution(gates)
    np.testing.assert_allclose(p[0], [0.5, 0.25, 0.125, 0.125])
    np.testing.assert_allclose(p[1], [0.0, 0.0, 0.0, 1.0])
    np.testing.assert_allclose(p.sum(-1), 1.0)
    # at the published threshold of 1 every token runs every pass, unless
    # a gate saturates; at 0.7 the first leaves after pass 2
    assert reference.exit_pass(gates, 1.0).tolist() == [4, 4, 1]
    assert reference.exit_pass(gates, 0.7).tolist() == [2, 4, 1]


def test_a_looped_stacks_residual_stream_is_float32_whatever_the_weights(
        monkeypatch):
    """bfloat16 weights, bfloat16 into and out of every sub-layer, and a
    float32 stream between them from the embedding to the head; a plain
    stack keeps the weights' dtype."""
    seen = []

    class Recording(hybrid.SequenceMixer):
        def attention(self, q, k, v, l, state):
            seen.append(("attention", q.dtype, k.dtype, v.dtype))
            return super().attention(q, k, v, l, state)

    mlp = hybrid._mlp

    def recording_mlp(u, *rest):
        y = mlp(u, *rest)
        seen.append(("mlp", u.dtype, y.dtype))
        return y

    monkeypatch.setattr(hybrid, "_mlp", recording_mlp)

    def stream(config):
        params = jax.eval_shape(lambda: init_params(config,
                                                    jax.random.key(0)))

        def run(params, ids):
            h = hybrid.embed_tokens(params, ids, config)
            return hybrid.run_stack(h, params, config,
                                    lambda _xs: Recording(config, 8), None)[0]
        return jax.eval_shape(run, params,
                              jax.ShapeDtypeStruct((1, 8), jnp.int32)).dtype

    bf16 = CONFIG.with_(dtype="bfloat16")
    assert stream(bf16) == jnp.float32
    assert seen and all(dtype == jnp.bfloat16 for _, *dtypes in seen
                        for dtype in dtypes), seen
    assert stream(bf16.with_(total_ut_steps=1)) == jnp.bfloat16


# -- (b) rotary positions on half-split pairs ----------------------------------


def test_half_split_rotary_is_the_references_and_not_the_adjacent_one():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((5, 3, 8)), jnp.float32)
    pos = jnp.arange(5)[:, None]
    half = hybrid.rope(x, pos, 1e6, half_split=True)
    np.testing.assert_allclose(half, reference._rotate(x, 1e6), atol=1e-6)
    assert float(jnp.abs(half - hybrid.rope(x, pos, 1e6)).max()) > 0.1
    # pair i is (i, i + d/2) and turns by t x theta^(-2i/d): pair 0 of a
    # token at position 1 by one radian, pair 1 by 1e6^(-1/2)
    one = hybrid.rope(jnp.asarray([1.0, 1.0, 0.0, 0.0]), jnp.int32(1), 1e6,
                      half_split=True)
    np.testing.assert_allclose(
        one, [np.cos(1.0), np.cos(1e-3), np.sin(1.0), np.sin(1e-3)],
        atol=1e-6)
    q, k = x[0, 0], x[1, 1]

    def score(tq, tk):
        return float(hybrid.rope(q, jnp.int32(tq), 1e6, half_split=True)
                     @ hybrid.rope(k, jnp.int32(tk), 1e6, half_split=True))

    assert score(5, 2) == pytest.approx(score(103, 100), abs=1e-5)
    assert abs(score(5, 2) - score(5, 3)) > 1e-3


# -- (c) prefill in chunks, then decode through the (pass, layer) planes -------


@pytest.mark.parametrize("horizon, window", [(1, 1), (4, 2)],
                         ids=["per-step", "fused-k4"])
@pytest.mark.parametrize("rids", [(0, 5), (2, 6), (1, 4)],
                         ids=lambda r: f"rids{r[0]}-{r[1]}")
def test_cached_logits_and_gates_match_the_reference(horizon, window, rids):
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
        assert np.stack(rec["exit_gates"]).shape == (LENGTHS[rid][1], 4)
        assert rec["experts"] is None and rec["gates"] is None
    errors = _probed_against_reference(engine, results)
    assert max(e for e, _ in errors.values()) < TIGHT, errors
    assert max(g for _, g in errors.values()) < 1e-5, errors
    # what the programs counted: four passes a decode step and a chunk
    raw = report["raw_samples"]
    steps = sum(raw["unit_slot_steps"])
    assert steps == report["generated_tokens"] - len(LENGTHS)
    reg = engine.registry
    assert report["exit_pass_mean"] == 4.0
    assert reg.get("serve_loop_passes") >= 4 * (
        report["decode_steps"] + report["fast_path"]["prefill_chunks"])
    assert sum(raw["unit_live_tokens"]) == sum(
        sum(p + i + 1 for i in range(o - 1)) for p, o in LENGTHS)
    # the tile counters count a layer's planes of all four passes
    fast = report["fast_path"]
    assert fast["kv_tiles_held"] % 4 == 0 and fast["kv_tiles_live"] % 4 == 0
    assert 0.0 < report["kv_live_share"] <= 1.0
    assert reg.get("serve_kv_bytes") == kv_cache_bytes(CONFIG, 4, 128)


def test_recycled_slot_gives_a_fresh_engines_logits_and_gates():
    lengths = [(37, 9), (21, 6)]
    engine = _sound(max_batch=1)
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
    np.testing.assert_allclose(np.stack(reused["exit_gates"]),
                               np.stack(fresh["exit_gates"]), atol=1e-6)


# request 0 takes an unused slot with a prompt of two chunks, request 5 a
# recycled one; which of them a fault must show in, and where
@pytest.mark.parametrize("fault, shows_in", [
    ("three_passes", "both"),
    ("loop_norm_left_out", "both"),
    ("previous_pass_planes", "both"),
    ("rope_off", "both"),
    ("rope_adjacent", "both"),
    ("sandwich_outputs_left_out", "both"),
    ("stale_last_pass", "both"),
])
def test_every_fault_of_the_loop_is_read_by_the_comparison(fault, shows_in,
                                                          monkeypatch):
    controls.apply(fault, monkeypatch.setattr, TOY)
    engine = _engine(decode_horizon=4, inflight_window=2)
    engine.probe((0, 5))
    report = engine.run_trace(_trace(), collect_raw=True)
    # every request was served, and wrongly
    assert report["requests"]["completed"] == len(LENGTHS)
    results = engine.probe_results()
    assert [results[r]["recycled"] for r in (0, 5)] == [False, True]
    errors = _probed_against_reference(engine, results)
    for rid in (0, 5):
        logits, gates = errors[rid]
        assert logits > 100 * TIGHT or gates > 1e-2, (fault, errors)


def test_a_recycled_slot_that_keeps_a_plane_reads_its_previous_request(
        monkeypatch):
    """One slot, two requests: with the last pass's planes left as they
    were by a prompt's chunks, the SECOND request attends in pass four to
    what the first wrote there, and its decode steps read it; served
    alone into the unused slot the same fault reads otherwise (zeros
    there), so what the first request left is what was read."""
    controls.apply("stale_last_pass", monkeypatch.setattr, TOY)
    # the first request's decode steps append at positions 5 to 23, under
    # the second one's prompt
    lengths = [(5, 20), (21, 6)]
    engine = _engine(max_batch=1)
    engine.probe([1])
    engine.run_trace(_trace(lengths))
    reused = engine.probe_results()[1]
    assert reused["recycled"]
    (logits, gates), = _probed_against_reference(
        engine, {1: reused}).values()
    assert logits > 100 * TIGHT
    engine.run_trace(TrafficTrace(kind="test", seed=0, params={},
                                  requests=_trace(lengths).requests[1:]))
    fresh = engine.probe_results()[1]
    assert not fresh["recycled"]
    assert float(np.abs(np.stack(reused["logits"])[1:]
                        - np.stack(fresh["logits"])[1:]).max()) > 1e-2


def test_dp2_tp2_mesh_equals_the_single_device_logits_and_gates():
    """Slots over ``dp``, whole heads over ``tp`` (``validate_serving``
    refuses neither): the planes of every pass shard as a plain stack's,
    and the exit gate is whole on every device."""
    mesh = build_parallelism_mesh(2, 1, 1, 2, 1, devices=jax.devices()[:4])
    meshed = ServingEngine(CONFIG, ServingConfig(**SERVING), mesh, seed=5,
                           verbose=False, capture_tokens=True)
    results = []
    for engine in (_sound(), meshed):
        engine.probe((0, 5))
        engine.run_trace(_trace())
        results.append(engine.probe_results())
    for rid in (0, 5):
        assert results[0][rid]["tokens"] == results[1][rid]["tokens"]
        np.testing.assert_allclose(np.stack(results[0][rid]["logits"]),
                                   np.stack(results[1][rid]["logits"]),
                                   atol=1e-4)
    errors = _probed_against_reference(meshed, results[1])
    assert max(e for e, _ in errors.values()) < TIGHT, errors
    assert max(g for _, g in errors.values()) < 1e-5, errors


# -- (d) the cache: a plane for every (pass, layer) ----------------------------


def test_the_cache_holds_a_plane_a_pass_and_layer_and_is_priced_so():
    from dlbb_tpu.serve.kvcache import create_hybrid_cache

    cache = create_hybrid_cache(CONFIG, 4, 16, 8)
    # 4 passes x 3 layers; 4 heads held as 8 (whole tiles of heads)
    assert cache.k.shape == cache.v.shape == (12, 4, 16, 8, 8, 16)
    assert cache.state.size == cache.conv.size == cache.latent.size == 0
    assert kv_cache_bytes(CONFIG, 4, 128) == cache.k.nbytes + cache.v.nbytes
    assert kv_cache_bytes_per_device(CONFIG, 4, 128) == \
        cache.k.nbytes + cache.v.nbytes
    # the gate that prices a serving configuration says so
    with pytest.raises(ValueError, match="3 layers x 4 passes"):
        ServingConfig(**{**SERVING, "hbm_budget_gb": 1e-4}).validate(CONFIG)
    # ... and a chunk's carried prefix is per (pass, layer) too
    from dlbb_tpu.serve import hybrid as serve_hybrid
    prefix = serve_hybrid.create_prefix(CONFIG, _mesh())
    assert prefix[0].shape == prefix[1].shape == (12, 0, 4, 16)
    # the plain stack of the same layers: a quarter
    plain = CONFIG.with_(total_ut_steps=1)
    assert kv_cache_bytes(plain, 4, 128) * 4 == kv_cache_bytes(CONFIG, 4, 128)


def test_every_pass_writes_planes_of_its_own():
    """After one prompt of one chunk into slot 1, every (pass, layer)
    plane of the slot holds keys, all twelve differ (a pass's input is
    the pass before's output), and the other slots' planes are
    untouched."""
    from dlbb_tpu.serve import hybrid as serve_hybrid

    mesh = _mesh()
    serving = ServingConfig(**SERVING)
    params = init_params(CONFIG, jax.random.key(5))
    cache, _tok = serve_hybrid.fresh_carry(CONFIG, serving, mesh)
    request = _trace([(12, 2)]).requests[0]
    ids = serve_hybrid.prompt_input(CONFIG, request, 16, jnp.float32)
    cache, prefix, last = serve_hybrid.build_prefill_chunk(
        CONFIG, mesh, 16, 0)(cache, serve_hybrid.create_prefix(CONFIG, mesh),
                             params, ids, np.int32(1), np.int32(12))
    k = np.asarray(cache.k)                       # [12, 4, 16, 8, 8, 16]
    assert not k[:, [0, 2, 3]].any()
    held = k[:, 1, :2, :, :4].reshape(12, -1)
    assert held.any(axis=-1).all()
    for a in range(12):
        for b in range(a + 1, 12):
            assert np.abs(held[a] - held[b]).max() > 1e-3, (a, b)
    assert int(cache.lengths[1]) == 12
    # what the chunk hands the next one is per (pass, layer) too, and
    # ``last`` holds the four gates of the prompt's last position
    assert prefix[0].shape == (12, 16, 4, 16)
    logits, gates, passes = last
    assert logits.shape == (256,) and gates.shape == (4,)
    assert int(passes) == 4


# -- (e) what is refused, and why ----------------------------------------------


def test_leaving_the_loop_early_is_refused_by_its_mechanism():
    early = CONFIG.with_(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="groups slots by the pass"):
        family_for(early).check_serving(early, ServingConfig(**SERVING))
    with pytest.raises(ValueError, match="groups slots by the pass"):
        _engine(config=early)


@pytest.mark.parametrize("change, reason", [
    (dict(total_ut_steps=0), "total_ut_steps >= 1"),
    (dict(early_exit_threshold=1.5), "early_exit_threshold <= 1"),
    (dict(norm_placement="both"), "unknown norm_placement"),
    # (a full_attention layer with neither QK-norm nor rotary was
    # refused until PR 37; it is Granite's ``nope``, and is taken: below)
    (dict(mlp="gelu"), "model family not implemented"),
    (dict(layer_types=["full_attention", "linear_attention"],
          num_layers=4, linear_num_key_heads=4, linear_num_value_heads=4,
          linear_key_head_dim=8, linear_value_head_dim=8,
          linear_conv_kernel_dim=4), "only the K/V planes are laid out"),
    (dict(layer_types=None, norm="layernorm", mlp="gelu", bias=True,
          vocab_size=0, rope_theta=0.0, norm_placement="post"),
     "model family not implemented"),
])
def test_model_config_refuses(change, reason):
    with pytest.raises(ValueError, match=reason):
        ModelConfig.from_dict({**TOY, **change})


def test_the_refusal_says_what_the_family_takes():
    with pytest.raises(ValueError) as said:
        ModelConfig.from_dict({**TOY, "norm": "layernorm"})
    for word in ("sandwich", "rope_theta > 0, both or neither",
                 "total_ut_steps"):
        assert word in str(said.value)
    # full-attention layers WITHOUT positions and without QK-norm are a
    # model of the family (the recurrent layers beside them, or here
    # nothing, carry position), and another function than the rotary one
    nope = ModelConfig.from_dict({**TOY, "rope_theta": 0.0})
    params = init_params(nope, jax.random.key(0))
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 9)))
    assert float(jnp.abs(hybrid.forward(params, ids, nope)
                         - hybrid.forward(params, ids, CONFIG)).max()) > 1e-3
    assert "sandwich" in family_for(CONFIG).check_serving.__doc__
    assert "total_ut_steps" in family_for(CONFIG).check_serving.__doc__


def test_qk_norm_and_rotary_may_stand_together_or_apart():
    """The full-attention layers take QK-norm (Olmo-Hybrid), rotary
    positions (this stack), or both; the tree holds ``q_norm`` only
    with the first."""
    both = CONFIG.with_(qk_norm=True, total_ut_steps=1,
                        norm_placement="post")
    params = init_params(both, jax.random.key(0))
    assert {"q_norm", "k_norm"} <= set(params["periods"][0])
    assert "ln1_out" not in params["periods"][0]
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 9)))
    with_rope = hybrid.forward(params, ids, both)
    without = hybrid.forward(params, ids, both.with_(rope_theta=0.0))
    assert float(jnp.abs(with_rope - without).max()) > 1e-3
