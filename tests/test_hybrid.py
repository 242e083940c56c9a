"""The hybrid block family (``ModelConfig.layer_types``, Olmo-Hybrid) at
toy widths on the CPU: everything against the plain float32 reference
``benchmarks/reference/olmo_hybrid.py`` (the one reference) on seeded weights.

(a) ``models.forward``; (b) prefill then decode through the cache,
per-step and fused, with prompts that cross chunk and block boundaries;
(c) the chunked gated delta rule equals the token recurrence; (d) a
recycled slot gives a fresh engine's logits; (f) a dp 2 x tp 2 mesh
equals one device; (g) what the family does not run yet is refused with
its reason.
"""

from __future__ import annotations

import glob
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models import forward, init_params
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.ops import gated_delta
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine, family_for
from dlbb_tpu.serve.traffic import Request, TrafficTrace

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import olmo_hybrid as reference     # noqa: E402

TOY = dict(
    hidden_size=64, num_layers=8, num_heads=4, ffn_intermediate=96,
    dtype="float32", norm="rmsnorm", mlp="swiglu", bias=False, qk_norm=True,
    vocab_size=256,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True)
CONFIG = ModelConfig.from_dict(TOY)
# float32 system against float32 reference: what is left is the order of
# the sums (chunked against recurrent, cached against whole-sequence)
TIGHT = 2e-4
SERVING = dict(max_batch=4, max_seq=128, block_size=8, prefill_chunk=16,
               queue_capacity=64)
# prompts that end inside a chunk, on a chunk edge, on a block edge and
# inside a block; with 4 slots the last three requests take recycled ones
LENGTHS = [(37, 9), (16, 5), (50, 12), (8, 3), (33, 7), (21, 6), (70, 10)]


def _reference_logits(params, ids, positions=None, state_at=None):
    out = reference.forward_logits(
        params, ids, CONFIG.layer_types,
        linear_key_head_dim=CONFIG.linear_key_head_dim,
        linear_allow_neg_eigval=CONFIG.linear_allow_neg_eigval,
        rms_norm_eps=CONFIG.rms_norm_eps, positions=positions,
        state_at=state_at)
    return jax.tree.map(np.asarray, out)


def _relative(system, ref):
    """Largest per-position ``|system - ref| / |ref|`` (L2 over the
    vocabulary)."""
    return float(np.max(np.linalg.norm(system - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


def _mesh(dp=1, tp=1):
    return build_parallelism_mesh(dp, 1, 1, tp, 1,
                                  devices=jax.devices()[:dp * tp])


def _trace(lengths=LENGTHS):
    return TrafficTrace(kind="test", seed=0, params={}, requests=tuple(
        Request(rid=i, arrival_s=0.0, prompt_len=p, output_len=o,
                seed=100 + i) for i, (p, o) in enumerate(lengths)))


_ENGINES: dict = {}


def _engine(dp=1, tp=1, **serving):
    """One engine per mesh and serving envelope for the whole file: a
    ``run_trace`` starts from a fresh cache, and building one compiles
    its programs anew."""
    key = (dp, tp, tuple(sorted(serving.items())))
    if key not in _ENGINES:
        _ENGINES[key] = ServingEngine(
            CONFIG, ServingConfig(**{**SERVING, **serving}), _mesh(dp, tp),
            seed=5, verbose=False, capture_tokens=True)
    return _ENGINES[key]


def _probed_against_reference(engine, results):
    """Each probed request's logits (last prompt position and every
    decode step) and its slot's recurrent state (after the prompt and
    after the last step) against the reference's forward over the prompt
    and the tokens the engine committed: the larger of the two errors."""
    errors = {}
    for rid, rec in results.items():
        ids = list(rec["prompt_ids"]) + rec["tokens"][:-1]
        first = len(rec["prompt_ids"]) - 1
        ref, states = _reference_logits(
            engine.params, ids, positions=list(range(first, len(ids))),
            state_at=[first, len(ids) - 1])
        kept = np.stack([rec["prompt_state"], rec["end_state"]])
        flat = (kept.shape[0], kept.shape[1], -1)
        errors[rid] = max(
            _relative(np.stack(rec["logits"]), ref),
            _relative(kept.reshape(flat), states.reshape(flat)))
    return errors


# -- (a) the whole-sequence forward --------------------------------------------


@pytest.mark.parametrize("seq", [3, 64, 150])
def test_forward_logits_match_the_reference(seq):
    params = init_params(CONFIG, jax.random.key(3))
    ids = np.random.default_rng(seq).integers(0, 256, size=(2, seq))
    logits = np.asarray(forward(params, jnp.asarray(ids), CONFIG))
    assert logits.shape == (2, seq, 256) and logits.dtype == np.float32
    for row in range(2):
        assert _relative(logits[row],
                         _reference_logits(params, ids[row])) < TIGHT


def test_parameter_count_matches_the_tree():
    from dlbb_tpu.models import num_parameters

    params = init_params(CONFIG, jax.random.key(0))
    assert num_parameters(CONFIG) == sum(
        leaf.size for leaf in jax.tree.leaves(params))


# -- (c) chunked form == recurrence --------------------------------------------


@pytest.mark.parametrize("seq, chunk", [(1, 64), (63, 64), (64, 64),
                                        (150, 64), (150, 16)])
def test_chunked_delta_rule_equals_the_token_recurrence(seq, chunk):
    rng = np.random.default_rng(seq + chunk)
    b, h, dk, dv = 2, 3, 8, 16

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = gated_delta.l2_normalise(draw(b, seq, h, dk), dk ** -0.5)
    k = gated_delta.l2_normalise(draw(b, seq, h, dk))
    v = draw(b, seq, h, dv)
    log_alpha = -jnp.asarray(rng.uniform(0.0, 1.5, (b, seq, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (b, seq, h)), jnp.float32)
    state0 = draw(b, h, dv, dk)
    out, state = gated_delta.gated_delta_chunked(q, k, v, log_alpha, beta,
                                                 state0, chunk=chunk)
    want, st = [], state0
    for t in range(seq):
        o_t, st = gated_delta.gated_delta_step(
            q[:, t], k[:, t], v[:, t], jnp.exp(log_alpha[:, t]), beta[:, t],
            st)
        want.append(o_t)
    np.testing.assert_allclose(out, jnp.stack(want, 1), atol=2e-5)
    np.testing.assert_allclose(state, st, atol=2e-5)


# -- (b) prefill, then decode through the cache --------------------------------


@pytest.mark.parametrize("horizon, window", [(1, 1), (4, 2)],
                         ids=["per-step", "fused-k4"])
@pytest.mark.parametrize("rids", [(0, 5), (2, 6), (1, 4)],
                         ids=lambda r: f"rids{r[0]}-{r[1]}")
def test_cached_logits_match_the_reference(horizon, window, rids):
    engine = _engine(decode_horizon=horizon, inflight_window=window)
    engine.probe(rids)
    report = engine.run_trace(_trace())
    assert report["requests"]["completed"] == len(LENGTHS)
    if horizon > 1:
        assert report["fast_path"]["fused_scans"] > 0
    results = engine.probe_results()
    assert sorted(results) == sorted(rids)
    for rid, rec in results.items():
        assert rec["tokens"] == report["completed_tokens"][str(rid)]
        assert len(rec["tokens"]) == LENGTHS[rid][1]
    errors = _probed_against_reference(engine, results)
    assert max(errors.values()) < TIGHT, errors


# -- (d) a recycled slot starts from nothing -----------------------------------


def test_recycled_slot_gives_a_fresh_engines_logits(tmp_path):
    from dlbb_tpu.obs import spans

    lengths = [(37, 9), (21, 6)]
    engine = _engine(max_batch=1)
    engine.probe([1])
    tracer = spans.start(tmp_path / "spans.json")
    try:
        engine.run_trace(_trace(lengths))
        events = tracer.events()
    finally:
        spans.stop()
    reused = engine.probe_results()[1]
    assert reused["slot"] == 0 and reused["recycled"]
    resets = [e for e in events if e["name"] == "state-reset"]
    assert [(e["args"]["rid"], e["args"]["slot"]) for e in resets] == [(1, 0)]
    assert engine.registry.get("serve_state_resets") == 1

    # the same request alone, so into a slot nobody has used
    only = _trace(lengths).requests[1:]
    engine.run_trace(TrafficTrace(kind="test", seed=0, params={},
                                  requests=only))
    fresh = engine.probe_results()[1]
    assert not fresh["recycled"]
    assert fresh["tokens"] == reused["tokens"]
    np.testing.assert_allclose(np.stack(reused["logits"]),
                               np.stack(fresh["logits"]), atol=1e-5)
    assert _probed_against_reference(engine, {1: reused})[1] < TIGHT


# -- (f) a simulated mesh ------------------------------------------------------


def test_dp2_tp2_mesh_equals_the_single_device_logits():
    single, meshed = _engine(), _engine(dp=2, tp=2)
    results = []
    for engine in (single, meshed):
        engine.probe([0, 6])
        engine.run_trace(_trace())
        results.append(engine.probe_results())
    for rid in (0, 6):
        assert results[0][rid]["tokens"] == results[1][rid]["tokens"]
        np.testing.assert_allclose(np.stack(results[0][rid]["logits"]),
                                   np.stack(results[1][rid]["logits"]),
                                   atol=1e-4)
    assert max(_probed_against_reference(meshed, results[1]).values()) < TIGHT


def test_cache_holds_two_kinds_and_the_gate_prices_both():
    from dlbb_tpu.models.configs import kv_cache_bytes, state_cache_bytes
    from dlbb_tpu.serve.kvcache import create_hybrid_cache

    cache = create_hybrid_cache(CONFIG, 4, 16, 8)
    # L_full = 2 of 8 layers; the 4 heads held as one whole tile of 8
    assert cache.k.shape == (2, 4, 16, 8, 8, 16)
    assert cache.state.shape == (6, 4, 4, 16, 8)
    assert cache.state.dtype == jnp.float32
    assert cache.conv.shape == (6, 4, 3, 4, 32)
    assert kv_cache_bytes(CONFIG, 4, 128) == cache.k.nbytes + cache.v.nbytes
    assert state_cache_bytes(CONFIG, 4) == \
        cache.state.nbytes + cache.conv.nbytes
    with pytest.raises(ValueError, match="recurrent state"):
        ServingConfig(**{**SERVING, "hbm_budget_gb": 1e-4}).validate(CONFIG)


# -- (g) what is refused, and why ----------------------------------------------


@pytest.mark.parametrize("serving, reason", [
    (dict(speculation="greedy"), "state rolled back"),
    (dict(speculation="ngram", spec_gamma=2), "state rolled back"),
    (dict(speculation="draft-model", spec_gamma=2, prefill_chunk=None),
     "state rolled back"),
    (dict(prefix_caching=True), "state as it was at the block boundary"),
    (dict(kv_quantization="int8"), "fp K/V layout only"),
    (dict(prefill_chunk=None), "prefilled in chunks"),
])
def test_serving_refuses_what_the_family_lacks(serving, reason):
    # what an engine checks when it is built: the family's own
    # refusals, then the envelope against the model
    sv = ServingConfig(**{**SERVING, **serving})
    with pytest.raises(ValueError, match=reason):
        family_for(CONFIG).check_serving(CONFIG, sv)
        sv.validate(CONFIG)


def test_linear_heads_must_divide_over_tp():
    with pytest.raises(ValueError, match="linear_num_value_heads"):
        ServingConfig(**SERVING).validate(
            CONFIG.with_(linear_num_key_heads=3, linear_num_value_heads=3,
                         num_heads=4), tp=4)


def test_train_step_and_pipeline_and_sequence_parallel_are_refused():
    import optax

    from dlbb_tpu.models.configs import validate_attention_parallelism
    from dlbb_tpu.parallel.pipeline import validate_pipeline
    from dlbb_tpu.train.loop import make_train_step

    with pytest.raises(ValueError, match="no backward pass"):
        make_train_step(CONFIG, _mesh(), optax.sgd(0.1), params=None)
    with pytest.raises(ValueError, match="period of mixed layers"):
        validate_pipeline(CONFIG, 2, 4, None)
    with pytest.raises(ValueError, match="sequence shard"):
        validate_attention_parallelism(CONFIG, 2)


# -- the configuration ---------------------------------------------------------


@pytest.mark.parametrize("change, reason", [
    (dict(layer_typs=["full_attention"]), "unknown model key"),
    (dict(norm="layernorm"), "model family not implemented"),
    (dict(vocab_size=0), "model family not implemented"),
    (dict(layer_types=["window_attention"]), "non-empty pattern"),
    (dict(num_layers=6), "whole number of periods"),
    (dict(linear_key_head_dim=0), "linear_attention layers need"),
    (dict(linear_num_value_heads=8), "grouped value heads"),
])
def test_model_config_refuses(change, reason):
    with pytest.raises(ValueError, match=reason):
        ModelConfig.from_dict({**TOY, **change})


def test_gpt_block_with_a_misspelt_key_is_an_error_too():
    with pytest.raises(ValueError, match="unknown model key"):
        ModelConfig.from_dict(dict(hidden_size=64, num_layers=2, num_heads=4,
                                   ffn_intermediate=128, atention="full"))


def _model_sections():
    files = sorted(glob.glob(str(ROOT / "dlbb_tpu/configs/*.yaml")))
    files += sorted(glob.glob(str(ROOT / "benchmarks/configs/*.json")))
    return files


@pytest.mark.parametrize("path", _model_sections(),
                         ids=lambda p: Path(p).name)
def test_every_shipped_model_section_still_loads(path):
    with open(path) as f:
        data = json.load(f) if path.endswith(".json") else yaml.safe_load(f)
    model = data["program"]["model"] if "program" in data else data["model"]
    config = ModelConfig.from_dict(model)
    assert config.hidden_size > 0
    assert config.is_hybrid == ("layer_types" in model)
