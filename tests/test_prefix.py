"""Shared-prefix KV cache + int8-quantized KV planes
(``docs/serving.md``, "Prefix cache & quantized KV").

The load-bearing contract is EQUIVALENCE: the prefix-cached engine (fp
planes) must produce completed-token sequences IDENTICAL to the
no-sharing engine on the same trace — an attach copies the exact block
values the skipped chunks would have computed, so reuse buys prefill
dispatches, never different results.  Around that: the host-side radix
trie's refcount/copy-on-write/free semantics, the rollback snapshot
covering trie + refcounts (a replayed dispatch never double-frees or
leaks a shared block), the int8 codec's fp32 round-trip stability, the
quantized-layout footprint formula, and the config validation fences
(prefix caching is a dp=1 + chunked-prefill + no-speculation feature)."""

import json

import numpy as np
import pytest

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models.configs import (
    ModelConfig,
    kv_cache_bytes_per_device,
)
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.kvcache import (
    BlockLedger,
    CacheOverflow,
    PrefixTrie,
    dequantize_kv_blocks,
    quantize_kv_blocks,
)
from dlbb_tpu.serve.traffic import generate_trace

TINY = dict(hidden_size=64, num_layers=2, num_heads=4,
            ffn_intermediate=128, dtype="float32", attention="full")
MODEL = ModelConfig(**TINY)
SERVE = dict(max_batch=4, block_size=8, max_seq=96, hbm_budget_gb=None,
             prefill_chunk=16)


def _prefix_trace(num=8, seed=3, groups=2, prefix_len=64):
    return generate_trace("poisson", num, seed=seed, rate=100.0,
                          prompt_range=(65, 80), output_range=(4, 8),
                          prefix_groups=groups, prefix_len=prefix_len)


@pytest.fixture(scope="module")
def mesh_tp4():
    """dp=1 x tp=4 — the prefix/quant serving envelope."""
    return build_parallelism_mesh(tensor_parallel=4)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_prefix_caching_validation_fences():
    # prefix caching rides the chunked-prefill machinery
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingConfig(**SERVE | {"prefill_chunk": None},
                      prefix_caching=True).validate(MODEL)
    # dp=1 only: a donor copy must be shard-local
    with pytest.raises(ValueError, match="dp=1"):
        ServingConfig(**SERVE, prefix_caching=True).validate(MODEL, dp=2)
    # speculation's token-feedback bookkeeping is out of envelope
    with pytest.raises(ValueError, match="speculation"):
        ServingConfig(**SERVE, prefix_caching=True, speculation="greedy",
                      ).validate(MODEL)
    ServingConfig(**SERVE, prefix_caching=True).validate(MODEL, dp=1)


def test_kv_quantization_validation_fences():
    with pytest.raises(ValueError, match="kv_quantization"):
        ServingConfig(**SERVE, kv_quantization="fp4").validate(MODEL)
    with pytest.raises(ValueError, match="speculation"):
        ServingConfig(**SERVE, kv_quantization="int8",
                      speculation="ngram", spec_gamma=2).validate(MODEL)
    # the int8 layout has its own fused-scan programs
    ServingConfig(**SERVE, kv_quantization="int8",
                  decode_horizon=8).validate(MODEL)
    sv = ServingConfig(**SERVE, prefix_caching=True,
                       kv_quantization="int8")
    sv.validate(MODEL, dp=1)
    # both knobs round-trip the config dict (report/manifest identity)
    back = ServingConfig.from_dict(sv.to_dict())
    assert back.prefix_caching and back.kv_quantization == "int8"


def test_quantized_footprint_formula():
    """int8 layout: one byte per element + one fp32 scale per
    (block, kv-head) per plane — strictly between 1/4 and 1/3 of the
    fp32 footprint at block_size=8, and the per-device split divides
    exactly like the fp path."""
    fp = kv_cache_bytes_per_device(MODEL, 8, 64, dp=1, tp=4)
    q = kv_cache_bytes_per_device(MODEL, 8, 64, dp=1, tp=4,
                                  kv_quantization="int8", block_size=8)
    assert fp / 4 < q < fp / 3
    whole = kv_cache_bytes_per_device(MODEL, 8, 64,
                                      kv_quantization="int8",
                                      block_size=8)
    assert whole == 4 * q  # tp divides kv-heads; scales shard with them


# ---------------------------------------------------------------------------
# int8 codec
# ---------------------------------------------------------------------------


def test_int8_roundtrip_is_fp32_stable():
    """quantize -> dequantize(fp32) -> quantize is a fixed point: the
    second pass reproduces the first bit-exactly (|q*s/s - q| well under
    0.5 ulp of the int grid), so requantizing an untouched block in the
    decode step never walks its values."""
    rng = np.random.default_rng(0)
    blocks = rng.standard_normal((2, 4, 3, 8, 4, 16)).astype(np.float32)
    q, s = quantize_kv_blocks(blocks)
    assert str(q.dtype) == "int8" and str(s.dtype) == "float32"
    deq = dequantize_kv_blocks(q, s, np.float32)
    q2, s2 = quantize_kv_blocks(deq)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))
    # max quantization error bounded by half a step per (block, head)
    step = np.asarray(s)[..., None, :, None]
    assert np.max(np.abs(np.asarray(deq) - blocks) / step) <= 0.5 + 1e-6


def test_int8_all_zero_block_uses_unit_scale():
    q, s = quantize_kv_blocks(np.zeros((1, 1, 2, 4, 2, 8), np.float32))
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(s) == 1.0)


# ---------------------------------------------------------------------------
# trie + refcounted ledger semantics (pure host, no device)
# ---------------------------------------------------------------------------


def _chain(*vals):
    return [tuple(range(v * 10, v * 10 + 4)) for v in vals]


def test_trie_match_attach_release_refcounts():
    trie = PrefixTrie()
    assert trie.match(_chain(1, 2)) == (0, None)
    created, newly = trie.extend(0, _chain(1, 2))
    assert created == 2 and newly == 2 and trie.num_nodes == 2
    depth, donor = trie.match(_chain(1, 2, 3))
    assert depth == 2 and donor == 0
    trie.attach(1, _chain(1, 2), 2)
    assert trie.total_refs() == 4 and trie.shared_depth(1) == 2
    # divergent extend: slot 1 adds its own third block (copy-on-write
    # edge) — the shared spine keeps both refs
    created, newly = trie.extend(1, _chain(1, 2, 9))
    assert created == 1 and trie.num_nodes == 3
    # release the donor: spine survives (slot 1 still refs it), only
    # nodes that lose their LAST ref prune
    assert trie.release(0) == 0
    assert trie.num_nodes == 3 and trie.shared_depth(1) == 3
    assert trie.release(1) == 3
    assert trie.num_nodes == 0 and trie.total_refs() == 0
    # idempotent: releasing a slot with no refs is a no-op, never a
    # double-free
    assert trie.release(1) == 0


def test_ledger_shared_blocks_counted_once():
    """Two slots holding the same 2-block prefix reserve it ONCE
    fleet-wide: dedup at register() refunds the private reservation, so
    a third request that would not fit privately still admits."""
    led = BlockLedger(total_blocks=8, block_size=4, prefix_caching=True)
    chain = _chain(1, 2)
    led.reserve(0, total_tokens=12, chain=None, attach_blocks=0)
    led.register(0, chain)
    assert led.blocks_reserved == 3  # 2 shared + 1 private
    assert led.shared_blocks == 2
    depth, donor = led.match_prefix(_chain(1, 2, 5))
    assert (depth, donor) == (2, 0)
    # second request attaches: only its private tail is new budget
    assert led.can_reserve(12, shared_blocks=2)
    led.reserve(1, total_tokens=12, chain=chain, attach_blocks=2)
    led.register(1, chain)
    assert led.blocks_reserved == 4  # 2 shared + 2 private tails
    # free slot 0: the shared spine survives under slot 1's refs
    led.append(0, 8)
    led.append(1, 8)
    assert led.free(0) == 1
    assert led.shared_blocks == 2 and led.blocks_reserved == 3
    assert led.free(1) == 3
    assert led.blocks_reserved == 0 and led.shared_blocks == 0
    assert led.stats()["prefix_refs"] == 0


def test_ledger_register_overflow_fails_closed():
    led = BlockLedger(total_blocks=4, block_size=4, prefix_caching=True)
    led.reserve(0, total_tokens=4)
    with pytest.raises(CacheOverflow):
        led.register(0, _chain(1, 2))  # 2 new shared > 1 reserved


def test_ledger_snapshot_restores_trie_and_refcounts():
    """The pre-dispatch rollback covers the trie: a torn attach (or a
    torn free) replayed from the snapshot neither leaks a node nor
    double-frees a shared block."""
    led = BlockLedger(total_blocks=16, block_size=4, prefix_caching=True)
    chain = _chain(1, 2)
    led.reserve(0, 12), led.register(0, chain)
    snap = led.snapshot()
    # torn mutation: a second slot attaches AND the donor frees
    led.reserve(1, 12, chain=chain, attach_blocks=2)
    led.register(1, chain)
    led.free(0)
    led.restore(snap)
    assert led.blocks_reserved == 3 and led.shared_blocks == 2
    assert led.trie.total_refs() == 2 and led.trie.shared_depth(0) == 2
    # replay applies cleanly on the restored state
    led.reserve(1, 12, chain=chain, attach_blocks=2)
    led.register(1, chain)
    led.free(0), led.free(1)
    assert led.blocks_reserved == 0 and led.trie.num_nodes == 0


# ---------------------------------------------------------------------------
# traffic: seeded shared-prefix groups
# ---------------------------------------------------------------------------


def test_prefix_trace_groups_share_seeds_and_roundtrip(tmp_path):
    trace = _prefix_trace()
    seeds = {r.prefix_seed for r in trace.requests}
    assert len(seeds) == 2 and None not in seeds
    assert all(r.prefix_len == 64 for r in trace.requests)
    assert all(r.prefix_len < r.prompt_len for r in trace.requests)
    path = tmp_path / "t.json"
    trace.save(path)
    replay = type(trace).load(path)
    assert replay.requests == trace.requests


def test_plain_trace_bytes_unchanged(tmp_path):
    """The prefix draws happen strictly AFTER the original rng
    consumption, so traces without prefix_groups are byte-identical to
    the pre-prefix schema (saved replay traces stay valid)."""
    plain = generate_trace("poisson", 4, seed=7, rate=50.0,
                           prompt_range=(4, 16), output_range=(2, 6))
    assert all(r.prefix_len is None and r.prefix_seed is None
               for r in plain.requests)
    plain.save(tmp_path / "p.json")
    payload = json.loads((tmp_path / "p.json").read_text())
    assert all("prefix_len" not in r for r in payload["requests"])


# ---------------------------------------------------------------------------
# engine equivalence + accounting (the prefix_smoke gate)
# ---------------------------------------------------------------------------


@pytest.mark.prefix_smoke
def test_prefix_and_int8_engines_token_identical(mesh_tp4):
    """The gate: on a seeded 2-group shared-prefix trace, the
    prefix-cached fp engine is TOKEN-IDENTICAL to the no-sharing
    engine (attach copies the exact chunk values), the int8 engine
    completes every request (argmax-identical on this model), the trie
    registers real hits, and every shared block drains to zero."""
    trace = _prefix_trace()

    def run(**extra):
        eng = ServingEngine(MODEL, ServingConfig(**SERVE, **extra),
                            mesh_tp4, verbose=False, capture_tokens=True)
        return eng.run_trace(trace), eng

    base, _ = run()
    pfx, eng = run(prefix_caching=True)
    assert pfx["completed_tokens"] == base["completed_tokens"]
    # group members admitted AFTER their group's first registration
    # attach (the exact count depends on admission timing; with
    # max_batch=4 and a fast trace at least the trailing arrivals hit)
    hits = pfx["prefix"]["hits"]
    assert hits >= 2
    assert pfx["prefix"]["tokens_reused"] == hits * 64
    assert pfx["prefix"]["hit_rate"] == pytest.approx(hits / 8)
    assert pfx["cache"]["peak_shared_blocks"] > 0
    assert pfx["cache"]["shared_blocks"] == 0  # drained
    assert pfx["cache"]["prefix_refs"] == 0
    assert pfx["cache"]["blocks_reserved"] == 0
    assert int(eng.registry.get("serve_prefix_hits")) == hits
    assert len(pfx["timeseries"]["shared_blocks"]) == len(
        pfx["timeseries"]["t_s"])

    quant, _ = run(prefix_caching=True, kv_quantization="int8")
    assert quant["requests"]["completed"] == len(trace)
    assert quant["prefix"]["hits"] >= 2
    assert quant["completed_tokens"] == base["completed_tokens"]


@pytest.mark.prefix_smoke
def test_prefix_run_artifacts_and_metrics(tmp_path):
    """serve/bench.py + obs surface end to end: journal carries
    prefix-attach events, journal_to_trace renders them as
    prefix-cache instants, metrics.prom exports the hit counters and
    the quantized HBM record prices the int8 layout."""
    from dlbb_tpu.obs import spans
    from dlbb_tpu.resilience.journal import read_journal
    from dlbb_tpu.serve.bench import run_serving

    config = {
        "experiment": {"name": "pfx"},
        "model": dict(TINY),
        "parallelism": {"data_parallel": 1, "world_size": 4},
        "serving": dict(SERVE, prefix_caching=True,
                        kv_quantization="int8"),
    }
    trace = _prefix_trace(num=6, groups=2)
    report = run_serving(config, trace, str(tmp_path), verbose=False)
    assert report["requests"]["completed"] == 6
    hits = report["prefix"]["hits"]
    assert hits >= 1

    events, torn = read_journal(tmp_path)
    assert torn == 0
    attaches = [e for e in events if e["event"] == "prefix-attach"]
    assert len(attaches) == hits
    assert all(e["tokens"] == 64 and e["blocks"] == 8 for e in attaches)
    timeline, _n, _t = spans.journal_to_trace(tmp_path,
                                              tmp_path / "tl.json")
    rebuilt = spans.load_trace(timeline)
    pre = [e for e in rebuilt["traceEvents"]
           if e.get("cat") == "prefix-cache"]
    assert len(pre) == hits and all(e["ph"] == "i" for e in pre)

    text = (tmp_path / "metrics.prom").read_text()
    assert f"dlbb_serve_prefix_hits_total {hits}" in text
    assert (f"dlbb_serve_prefix_tokens_reused_total {hits * 64}"
            in text)
    assert "dlbb_serve_prefix_hit_rate" in text
    assert 'dlbb_serve_cache_blocks{stat="peak_shared_blocks"}' in text

    result = json.loads((tmp_path / "serving_pfx.json").read_text())
    hbm = result["hbm"]
    fp = kv_cache_bytes_per_device(MODEL, SERVE["max_batch"],
                                   SERVE["max_seq"], dp=1, tp=4)
    assert hbm["kv_cache_bytes_per_device"] < fp / 3


@pytest.mark.prefix_smoke
def test_degraded_attach_after_carry_reset_stays_correct(mesh_tp4):
    """A carry reset between plan and prefill (a permanent decode
    failure mid-trace) invalidates every planned attach: the prefill
    degrades to the full computation (copying a fresh carry's zeroed
    blocks would serve garbage) and the completed requests still match
    the no-sharing engine under the same fault plan."""
    trace = _prefix_trace()

    def run(**extra):
        eng = ServingEngine(
            MODEL, ServingConfig(**SERVE, max_dispatch_retries=0,
                                 **extra),
            mesh_tp4, verbose=False, capture_tokens=True)
        return eng.run_trace(trace, collect_raw=False)

    import dlbb_tpu.resilience.inject as inject
    with inject.plan_scope("serve-decode-fail:@2"):
        base = run()
    with inject.plan_scope("serve-decode-fail:@2"):
        pfx = run(prefix_caching=True)
    done = {k for k, v in base["requests"]["outcomes"].items()
            if v == "completed"}
    for rid in done:
        assert (pfx["completed_tokens"].get(rid)
                == base["completed_tokens"].get(rid)), rid
    assert pfx["cache"]["blocks_reserved"] == 0
    assert pfx["cache"]["shared_blocks"] == 0
