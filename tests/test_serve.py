"""Serving subsystem tests: traffic traces, the paged KV-cache ledger,
the build-time serving validation (HBM budget gate), prefill/decode
equivalence against the full-sequence forward pass, and the
continuous-batching engine end to end (``serve_smoke``)."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models.configs import (
    ModelConfig,
    kv_cache_bytes,
    validate_serving,
)
from dlbb_tpu.models.transformer import forward, init_params_sharded
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.gpt import (
    build_decode_fused,
    build_decode_step,
    build_prefill,
    inject_token,
)
from dlbb_tpu.serve.kvcache import (
    BlockLedger,
    CacheOverflow,
    KVCache,
    cache_shardings,
    create_kv_cache,
)
from dlbb_tpu.serve.traffic import TrafficTrace, generate_trace

TINY = dict(hidden_size=64, num_layers=2, num_heads=4,
            ffn_intermediate=128, dtype="float32", attention="full")


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["poisson", "bursty", "diurnal"])
def test_trace_deterministic_and_replayable(kind, tmp_path):
    a = generate_trace(kind, 40, seed=11, rate=20.0)
    b = generate_trace(kind, 40, seed=11, rate=20.0)
    assert a == b
    c = generate_trace(kind, 40, seed=12, rate=20.0)
    assert a != c
    # arrivals sorted, lengths within bounds, seeds present
    arr = [r.arrival_s for r in a]
    assert arr == sorted(arr) and arr[0] > 0
    assert all(8 <= r.prompt_len <= 96 for r in a)
    assert all(4 <= r.output_len <= 48 for r in a)
    # JSON round trip through the atomic writer
    path = tmp_path / "trace.json"
    a.save(path)
    loaded = TrafficTrace.load(path)
    assert loaded == a


def test_trace_rejects_bad_args(tmp_path):
    with pytest.raises(ValueError, match="unknown trace kind"):
        generate_trace("constant", 10)
    with pytest.raises(ValueError, match="num_requests"):
        generate_trace("poisson", 0)
    with pytest.raises(ValueError, match="rate"):
        generate_trace("poisson", 10, rate=0.0)
    with pytest.raises(ValueError, match="1 <= lo <= hi"):
        generate_trace("poisson", 10, prompt_range=(0, 96))
    (tmp_path / "bad.json").write_text(json.dumps({"schema": "nope"}))
    with pytest.raises(ValueError, match="not a serving trace"):
        TrafficTrace.load(tmp_path / "bad.json")


def test_bursty_is_burstier_than_poisson():
    """The MMPP trace's inter-arrival coefficient of variation must
    exceed the Poisson trace's (CV 1) — the property the generator
    exists to provide."""
    def cv(trace):
        gaps = np.diff([0.0] + [r.arrival_s for r in trace])
        return gaps.std() / gaps.mean()

    poisson = generate_trace("poisson", 400, seed=3, rate=50.0)
    bursty = generate_trace("bursty", 400, seed=3, rate=50.0,
                            burst_factor=10.0, dwell_s=0.5)
    assert cv(bursty) > cv(poisson)


# ---------------------------------------------------------------------------
# ledger + config validation
# ---------------------------------------------------------------------------


def test_block_ledger_accounting():
    led = BlockLedger(total_blocks=8, block_size=4)
    assert led.blocks_for(1) == 1 and led.blocks_for(4) == 1
    assert led.blocks_for(5) == 2
    assert led.reserve(0, 9) == 3          # ceil(9/4); 12-token capacity
    assert led.blocks_reserved == 3 and led.blocks_free == 5
    led.append(0, 5)                       # prompt: 2 blocks in use
    assert led.blocks_in_use == 2
    led.append(0, 4)                       # 9 tokens -> 3rd block
    assert led.blocks_in_use == 3 and led.peak_in_use == 3
    led.append(0, 3)                       # 12 tokens: exactly full
    with pytest.raises(CacheOverflow, match="outgrew"):
        led.append(0)                      # 13th token > reservation
    assert led.free(0) == 3
    assert led.blocks_reserved == 0
    with pytest.raises(CacheOverflow):
        led.free(0)
    # all-or-nothing reservation against the budget
    led.reserve(1, 32)                     # all 8 blocks
    assert not led.can_reserve(1)
    with pytest.raises(CacheOverflow, match="cannot reserve"):
        led.reserve(2, 1)


def test_validate_serving_envelope():
    cfg = ModelConfig(**TINY)
    validate_serving(cfg, max_batch=4, max_seq=32, block_size=8,
                     dp=2, tp=4)
    with pytest.raises(ValueError, match="attention"):
        validate_serving(cfg.with_(attention="simplified"), 4, 32, 8)
    with pytest.raises(ValueError, match="multiple"):
        validate_serving(cfg, max_batch=4, max_seq=30, block_size=8)
    with pytest.raises(ValueError, match="divisible by dp"):
        validate_serving(cfg, max_batch=3, max_seq=32, block_size=8, dp=2)
    with pytest.raises(ValueError, match="kv_heads"):
        validate_serving(cfg.with_(num_kv_heads=2), 4, 32, 8, tp=4)
    with pytest.raises(ValueError, match="dense FFN"):
        validate_serving(cfg.with_(num_experts=4), 4, 32, 8)


def test_hbm_budget_gate_rejects_oversized_cache():
    """The satellite fix: an infeasible ``max_batch x max_seq`` KV-cache
    is a clear build-time error, never an OOM mid-trace."""
    cfg = ModelConfig(**TINY)
    total = kv_cache_bytes(cfg, max_batch=64, max_seq=4096)
    assert total == 2 * 2 * 64 * 4096 * 4 * 16 * 4  # K+V,L,B,S,kvh,d,f32
    # generous budget passes
    validate_serving(cfg, 64, 4096, 128, hbm_budget_bytes=total)
    with pytest.raises(ValueError, match="HBM budget"):
        validate_serving(cfg, 64, 4096, 128,
                         hbm_budget_bytes=total // 4)
    # sharding divides the per-device footprint: dp=2 x tp=4 fits in 1/8
    validate_serving(cfg, 64, 4096, 128, dp=2, tp=4,
                     hbm_budget_bytes=total // 8)
    # ServingConfig.validate wires the GiB knob through
    sv = ServingConfig(max_batch=64, max_seq=4096, block_size=128,
                       hbm_budget_gb=total / 4 / 2**30)
    with pytest.raises(ValueError, match="hbm_budget_gb"):
        sv.validate(cfg)


def test_serving_config_buckets_and_dict():
    sv = ServingConfig(max_batch=4, block_size=8, max_seq=64)
    assert sv.prefill_buckets == (8, 16, 32, 64)
    assert sv.num_blocks == 8
    assert sv.bucket_for(1) == 8 and sv.bucket_for(9) == 16
    assert sv.bucket_for(64) == 64
    with pytest.raises(ValueError, match="largest prefill bucket"):
        sv.bucket_for(65)
    round_trip = ServingConfig.from_dict(sv.to_dict())
    assert round_trip.prefill_buckets == sv.prefill_buckets
    assert round_trip.max_seq == sv.max_seq
    # explicit buckets normalise to ascending unique order (bucket_for's
    # first-match walk and the buckets[-1]-is-largest consumers rely on it)
    shuffled = ServingConfig(max_batch=4, block_size=8, max_seq=64,
                             prefill_buckets=(64, 16, 16, 32))
    assert shuffled.prefill_buckets == (16, 32, 64)
    assert shuffled.bucket_for(8) == 16
    with pytest.raises(ValueError, match="bucket"):
        ServingConfig(max_batch=4, block_size=8, max_seq=64,
                      prefill_buckets=(12,)).validate(ModelConfig(**TINY))


def test_resolved_trace_always_fits_the_envelope():
    """resolve_trace's auto length bounds must satisfy the engine's
    pre-run validation for ANY feasible envelope — including tiny
    max_seq where prompt+output once overflowed (max_out is now the
    exact remainder of max_prompt)."""
    from dlbb_tpu.serve.bench import resolve_trace

    for max_seq, block in ((8, 8), (16, 8), (24, 8), (256, 16)):
        sv = ServingConfig(max_batch=4, block_size=block,
                           max_seq=max_seq, hbm_budget_gb=None)
        trace = resolve_trace("poisson", num_requests=50, seed=5,
                              serving=sv)
        for r in trace:
            assert r.total_tokens <= sv.max_seq, (max_seq, r)
            assert r.prompt_len <= sv.prefill_buckets[-1]
            assert r.output_len >= 1


def test_default_parallelism_prefers_tp_over_single_device():
    from dlbb_tpu.serve.bench import default_parallelism

    assert default_parallelism(8, 4, 8) == (2, 4)
    assert default_parallelism(8, 8, 8) == (2, 4)
    assert default_parallelism(1, 4, 8) == (1, 1)
    # kv_heads indivisible by 4/2: tp collapses, dp takes the devices
    assert default_parallelism(8, 3, 8) == (8, 1)
    # an awkward max_batch costs dp width, never the whole tp axis
    assert default_parallelism(8, 4, 3) == (1, 4)
    assert default_parallelism(8, 4, 6) == (2, 4)


def test_plan_expected_kinds_decode():
    from dlbb_tpu.analysis.expectations import plan_expected_kinds

    # dp is pure batch parallelism at inference: no collectives at all
    assert plan_expected_kinds(dp=8, decode=True) == set()
    # tp keeps its per-token row psum; nothing gradient-shaped sneaks in,
    # and no permute realigns q, k and v (the qkv columns lie by group)
    assert plan_expected_kinds(dp=2, tp=4, decode=True) == {"all-reduce"}
    with pytest.raises(ValueError, match="dp, tp"):
        plan_expected_kinds(sp=2, decode=True)


# ---------------------------------------------------------------------------
# prefill/decode equivalence vs the full-sequence forward pass
# ---------------------------------------------------------------------------

# fp32 pin: the cached path computes the same logits over the same
# positions, but XLA fuses/partitions the [S, S] prefill and the
# per-step [1, S] decode contractions differently per mesh layout —
# observed divergence <= ~7e-7 on unit-scale layernormed outputs.
F32_TOL = 1e-5


def _equivalence_case(cfg, mesh, dp, tol):
    """Prefill P tokens, decode the rest feeding the TRUE next inputs,
    and compare every produced position against the one-shot forward."""
    params = init_params_sharded(cfg, jax.random.key(0), mesh)
    seq, prompt, slot = 24, 11, 2
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x_full = jnp.asarray(
        rng.standard_normal((1, seq, cfg.hidden_size), dtype=np.float32),
        dtype=dtype,
    )
    y_full = jax.jit(lambda p, a: forward(p, a, cfg, mesh=mesh))(
        params, x_full)

    sv = ServingConfig(max_batch=4, block_size=8, max_seq=32,
                       hbm_budget_gb=None)
    sv.validate(cfg, dp=dp, tp=mesh.shape["tp"])
    cache = create_kv_cache(cfg, sv.max_batch, sv.num_blocks,
                            sv.block_size, mesh=mesh)
    prefill = build_prefill(cfg, mesh)
    decode = build_decode_step(cfg, mesh)

    bucket = sv.bucket_for(prompt)
    xp = np.zeros((1, bucket, cfg.hidden_size), np.float32)
    xp[:, :prompt] = np.asarray(x_full[:, :prompt], np.float32)
    cache, y_last = prefill(cache, params, jnp.asarray(xp, dtype),
                            np.int32(slot), np.int32(prompt))
    errs = [float(jnp.abs(y_last - y_full[0, prompt - 1]).max())]

    x = jax.device_put(
        jnp.zeros((sv.max_batch, 1, cfg.hidden_size), dtype),
        NamedSharding(mesh, P("dp" if dp > 1 else None, None, None)),
    )
    active = np.zeros(sv.max_batch, bool)
    active[slot] = True
    active = jnp.asarray(active)
    carry = (cache, x)
    for i in range(prompt, seq):
        carry = inject_token(carry, np.int32(slot), x_full[0, i])
        carry, y = decode(carry, params, active)
        errs.append(float(jnp.abs(y[slot, 0] - y_full[0, i]).max()))
    assert max(errs) <= tol, f"max divergence {max(errs)} > {tol}"
    # the decoded slot advanced exactly seq - prompt tokens
    assert int(carry[0].lengths[slot]) == seq
    assert int(carry[0].lengths[0]) == 0  # untouched slots stay empty


def test_prefill_decode_matches_forward_dp_tp(mesh2x4):
    """(dp, tp) mesh, full MHA, fp32: exact to rounding noise."""
    _equivalence_case(ModelConfig(**TINY), mesh2x4, dp=2, tol=F32_TOL)


def test_prefill_decode_matches_forward_tp_only_gqa():
    """(tp)-only mesh with GQA (kv_heads=2 < num_heads=4): head-dim
    sharding alone, grouped cache reads at kv_heads width with a 2-way
    kv-head shard."""
    cfg = ModelConfig(**{**TINY, "num_kv_heads": 2})
    mesh = build_parallelism_mesh(tensor_parallel=2,
                                  devices=jax.devices()[:2])
    _equivalence_case(cfg, mesh, dp=1, tol=F32_TOL)


# bf16 tolerance pin: the cached path reorders nothing algebraically,
# but bf16 rounding differs between the [S, S] prefill matmuls and the
# per-step [1, S] decode contractions; 0.05 absolute on unit-scale
# layernormed outputs holds with ~6x headroom (observed max ~8e-3).
BF16_TOL = 0.05


def test_prefill_decode_matches_forward_bf16(mesh2x4):
    cfg = ModelConfig(**{**TINY, "dtype": "bfloat16"})
    _equivalence_case(cfg, mesh2x4, dp=2, tol=BF16_TOL)


@pytest.mark.parametrize("program", ["decode_step", "decode_k4"])
def test_decode_append_writes_only_the_active_slots_own_rows(mesh2x4,
                                                             program):
    """The in-place append's contract, on a cache full of noise: a
    decode step and a fused scan leave an inactive slot's K/V rows bit
    for bit as they were, and change in an active slot exactly the rows
    ``lengths[slot] .. lengths[slot] + steps`` and nothing else — also
    for a slot whose next token lands on its last row."""
    cfg = ModelConfig(**TINY)
    sv = ServingConfig(max_batch=4, block_size=8, max_seq=32,
                       hbm_budget_gb=None)
    params = init_params_sharded(cfg, jax.random.key(0), mesh2x4)
    rng = np.random.default_rng(3)
    shape = (cfg.num_layers, sv.max_batch, sv.num_blocks, sv.block_size,
             cfg.kv_heads, cfg.head_dim)
    before = {"k": rng.standard_normal(shape, dtype=np.float32),
              "v": rng.standard_normal(shape, dtype=np.float32)}
    lengths = np.array([5, 17, sv.max_seq - 1, 9], np.int32)
    active = np.array([True, False, True, False])
    cache = jax.device_put(
        KVCache(jnp.asarray(before["k"]), jnp.asarray(before["v"]),
                jnp.asarray(lengths)),
        cache_shardings(mesh2x4))
    x = jax.device_put(
        jnp.asarray(rng.standard_normal(
            (sv.max_batch, 1, cfg.hidden_size), dtype=np.float32)),
        NamedSharding(mesh2x4, P("dp", None, None)))
    if program == "decode_step":
        steps = np.where(active, 1, 0)
        (cache, _), _ = build_decode_step(cfg, mesh2x4)(
            (cache, x), params, jnp.asarray(active))
    else:
        # slot 0 runs 3 of the scan's 4 trips; slot 2 has one row left
        steps = np.array([3, 0, 1, 0])
        (cache, _), _ = build_decode_fused(cfg, mesh2x4, 4)(
            (cache, x), params, jnp.asarray(active),
            jnp.asarray(steps, jnp.int32))
    np.testing.assert_array_equal(np.asarray(cache.lengths),
                                  lengths + steps)
    flat = (cfg.num_layers, sv.max_batch, sv.max_seq, cfg.kv_heads,
            cfg.head_dim)
    for name in ("k", "v"):
        was = before[name].reshape(flat)
        now = np.asarray(getattr(cache, name)).reshape(flat)
        changed = (was != now).any(axis=(0, 3, 4))          # [B, S]
        want = np.zeros_like(changed)
        for slot, n in enumerate(steps):
            want[slot, lengths[slot]:lengths[slot] + n] = True
        np.testing.assert_array_equal(changed, want, err_msg=name)


# ---------------------------------------------------------------------------
# the engine end to end
# ---------------------------------------------------------------------------

SMOKE_MODEL = ModelConfig(**TINY)
SMOKE_SERVING = ServingConfig(max_batch=8, block_size=8, max_seq=64,
                              queue_capacity=64, hbm_budget_gb=None)


def _smoke_trace(n=30, seed=7):
    return generate_trace("poisson", n, seed=seed, rate=200.0,
                          prompt_range=(4, 16), output_range=(2, 8))


@pytest.fixture(scope="module")
def smoke_engine(mesh2x4):
    """One compiled engine shared by the module's trace-running tests
    (fresh cache per run_trace; the request counters accumulate, so only
    the FIRST trace-running test may assert absolute counts)."""
    return ServingEngine(SMOKE_MODEL, SMOKE_SERVING, mesh2x4,
                         verbose=False)


@pytest.mark.serve_smoke
def test_engine_serves_poisson_trace_clean(smoke_engine, tmp_path):
    """The serve_smoke gate: a seeded 30-request Poisson mini-trace on
    the simulated mesh completes with ZERO rejected-by-bug requests, a
    valid span-trace file, journaled request lifecycle, live registry
    counters + metrics.prom export, and finite metrics (queue capacity
    >= trace size, so any rejection here is an engine bug, not load)."""
    from dlbb_tpu.obs import spans
    from dlbb_tpu.obs.export import serving_metrics
    from dlbb_tpu.resilience.journal import SweepJournal, read_journal

    engine = smoke_engine
    trace = _smoke_trace()
    span_path = tmp_path / "serve_trace.json"
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        with spans.tracing(span_path):
            report = engine.run_trace(trace)
    finally:
        engine.journal = None
        journal.close()

    req = report["requests"]
    assert req["arrived"] == 30 and req["completed"] == 30
    assert req["rejected"] == 0 and req["rejected_rids"] == []
    assert report["goodput_tokens_per_s"] > 0
    assert math.isfinite(report["goodput_tokens_per_s"])
    for block in ("ttft", "per_token_latency", "prefill_time",
                  "decode_step_time", "e2e_latency"):
        for q in ("median", "p95", "p99", "p999"):
            assert math.isfinite(report[block][q]), (block, q)
    assert report["ttft"]["count"] == 30
    assert report["completed_output_tokens"] == sum(
        r.output_len for r in trace)
    # queue-depth/occupancy timeseries present and consistent
    series = report["timeseries"]
    n = len(series["t_s"])
    assert n > 0 and all(len(v) == n for v in series.values())
    assert series["t_s"] == sorted(series["t_s"])
    assert max(series["blocks_in_use"]) <= SMOKE_SERVING.total_blocks
    # every block freed at the end
    assert report["cache"]["blocks_reserved"] == 0
    # span trace: schema-valid trace-event JSON with the serving phases
    payload = spans.load_trace(span_path)
    assert spans.validate_trace_events(payload["traceEvents"]) == []
    names = {e["name"] for e in payload["traceEvents"]}
    assert {"serve-prefill", "serve-decode"} <= names
    # journal: full request lifecycle, fsync'd
    events, torn = read_journal(tmp_path)
    assert torn == 0
    kinds = {e["event"] for e in events}
    assert {"request-arrived", "request-admitted", "request-prefill",
            "request-completed"} <= kinds
    completed = [e for e in events if e["event"] == "request-completed"]
    assert len(completed) == 30
    # journal -> Perfetto timeline: each request's arrived->completed
    # pair becomes one end-to-end X span (cli obs trace on a serving dir)
    timeline, n_events, torn2 = spans.journal_to_trace(
        tmp_path, tmp_path / "timeline.json")
    assert torn2 == 0
    rebuilt = spans.load_trace(timeline)
    req_spans = [e for e in rebuilt["traceEvents"] if e["ph"] == "X"]
    assert len(req_spans) == 30
    assert all(e["cat"] == "config-completed" for e in req_spans)
    # the MetricsRegistry satellite: counters live in the registry and
    # export to the Prometheus textfile
    reg = engine.registry
    done_total = int(reg.get("serve_requests", outcome="completed"))
    assert done_total >= 30  # cumulative across the shared engine's runs
    prom_path = serving_metrics(report, registry=reg).write_textfile(
        tmp_path / "metrics.prom")
    text = prom_path.read_text()
    assert (f'dlbb_serve_requests_total{{outcome="completed"}} '
            f"{done_total}") in text
    assert "dlbb_serve_goodput_tokens_per_second" in text
    assert 'dlbb_serve_ttft_seconds{quantile="p999"}' in text
    assert 'dlbb_serve_cache_blocks{stat="peak_blocks_in_use"}' in text


def test_engine_bounded_queue_rejects_under_overload(smoke_engine):
    """Admission control: a queue bound of 1 under a burst MUST shed
    load — rejections counted, journaled as queue-full, and the rest of
    the trace still completes.  Only queue_capacity changes (host-side
    scheduling state), so the shared engine's compiles are reused."""
    from dataclasses import replace

    engine = smoke_engine
    trace = generate_trace("poisson", 12, seed=3, rate=5000.0,
                           prompt_range=(4, 16), output_range=(4, 8))
    original = engine.serving
    engine.serving = replace(original, queue_capacity=1)
    try:
        report = engine.run_trace(trace)
    finally:
        engine.serving = original
    req = report["requests"]
    assert req["rejected"] > 0
    assert req["completed"] == 12 - req["rejected"]
    assert len(req["rejected_rids"]) == req["rejected"]
    assert max(report["timeseries"]["queue_depth"]) <= 1


def test_engine_rejects_infeasible_trace_upfront(smoke_engine):
    """A request that cannot fit the serving envelope fails BEFORE the
    run (and before any compile) with a clear error, not mid-trace."""
    engine = smoke_engine
    bad = generate_trace("poisson", 4, seed=1, rate=10.0,
                         prompt_range=(40, 60), output_range=(30, 40))
    with pytest.raises(ValueError, match="max_seq"):
        engine.run_trace(bad)
    with pytest.raises(ValueError, match="empty trace"):
        engine.run_trace(TrafficTrace(kind="poisson", seed=0, params={}))


@pytest.mark.serve_smoke
def test_serving_bench_writes_artifact_set(tmp_path):
    """serve/bench.py end to end: result JSON + replayable trace +
    manifest + metrics.prom + journal, all parseable."""
    from dlbb_tpu.serve.bench import run_serving

    config = {
        "experiment": {"name": "smoke"},
        "model": dict(TINY),
        "parallelism": {"data_parallel": 2, "world_size": 4},
        "serving": {"max_batch": 8, "block_size": 8, "max_seq": 32,
                    "prefill_buckets": [16], "hbm_budget_gb": None},
    }
    trace = generate_trace("poisson", 4, seed=7, rate=200.0,
                           prompt_range=(4, 16), output_range=(2, 6))
    report = run_serving(config, trace, str(tmp_path), verbose=False)
    assert report["requests"]["completed"] == 4
    result = json.loads((tmp_path / "serving_smoke.json").read_text())
    assert result["schema"] == "dlbb_serving_report_v1"
    assert result["mesh"] == {"dp": 2, "sp": 1, "pp": 1, "ep": 1, "tp": 4}
    manifest = json.loads(
        (tmp_path / "serving_manifest.json").read_text())
    assert manifest["schema"] == "dlbb_serving_manifest_v1"
    assert manifest["requests"]["completed"] == 4
    assert "topology" in manifest
    replay = TrafficTrace.load(tmp_path / "trace_smoke.json")
    assert len(replay) == 4
    assert "dlbb_serve_requests_total" in (
        tmp_path / "metrics.prom").read_text()
    assert (tmp_path / "sweep_journal.jsonl").exists()


def test_serving_report_writer(tmp_path):
    from dlbb_tpu.stats.serving_report import write_serving_report
    from dlbb_tpu.utils.config import save_json

    fake = {
        "schema": "dlbb_serving_report_v1",
        "trace": {"kind": "poisson", "num_requests": 10},
        "requests": {"completed": 9, "rejected": 1},
        "mesh": {"dp": 2, "tp": 4, "sp": 1, "pp": 1, "ep": 1},
        "serving": {"max_batch": 8, "block_size": 16, "max_seq": 256},
        "goodput_tokens_per_s": 123.4,
        "throughput_tokens_per_s": 150.0,
        "ttft": {"median": 0.01, "p99": 0.02, "p999": 0.03},
        "per_token_latency": {"median": 0.001, "p99": 0.002,
                              "p999": 0.003},
        "cache": {"peak_blocks_in_use": 12},
        "timeseries": {"queue_depth": [0, 3, 1]},
        "decode_steps": 42,
        "wall_seconds": 1.5,
    }
    results = tmp_path / "results"
    save_json(fake, results / "serving_run1.json")
    rows = write_serving_report(results, tmp_path / "stats")
    assert len(rows) == 1
    row = rows[0]
    assert row["name"] == "run1" and row["mesh"] == "dp2xtp4"
    assert row["ttft_p999_ms"] == 30.0 and row["peak_queue_depth"] == 3
    md = (tmp_path / "stats" / "SERVING.md").read_text()
    assert "run1" in md and "poisson" in md
    csv_text = (tmp_path / "stats" / "serving.csv").read_text()
    assert csv_text.startswith("name,trace,")
    # an empty dir produces no report (and clobbers nothing)
    assert write_serving_report(tmp_path / "nothing",
                                tmp_path / "stats2") == []
    assert not (tmp_path / "stats2").exists()
