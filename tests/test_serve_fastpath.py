"""Decode fast-path tests (``docs/serving.md``): fused multi-step
decode / chunked prefill / host-overlap window.

The load-bearing contract is EQUIVALENCE: every fast-path configuration
must produce the identical completed-token sequences (argmax over each
generated output) as the PR-9 per-step engine on the same trace — the
fast path buys dispatches, never different results.  On top of that,
the scheduler edge cases the fast path makes reachable: completion
mid-fused-scan (masked slot stays dead, blocks free at scan exit),
admission arriving during an in-flight window, and a K horizon that
overshoots every remaining output length.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.traffic import Request, TrafficTrace, generate_trace

TINY = dict(hidden_size=64, num_layers=2, num_heads=4,
            ffn_intermediate=128, dtype="float32", attention="full")
MODEL = ModelConfig(**TINY)
SERVE = dict(max_batch=8, block_size=8, max_seq=64, hbm_budget_gb=None)


def _trace(reqs):
    return TrafficTrace(kind="poisson", seed=0, params={},
                        requests=tuple(reqs))


@pytest.fixture(scope="module")
def baseline_engine(mesh2x4):
    """The per-step PR-9 engine — every equivalence test's oracle."""
    return ServingEngine(MODEL, ServingConfig(**SERVE), mesh2x4,
                         verbose=False, capture_tokens=True)


@pytest.fixture(scope="module")
def fast_engine(mesh2x4):
    """The full fast path: fused scans (K<=16), in-flight window 2,
    chunked prefill (8-token chunks)."""
    return ServingEngine(
        MODEL,
        ServingConfig(**SERVE, decode_horizon=16, inflight_window=2,
                      prefill_chunk=8),
        mesh2x4, verbose=False, capture_tokens=True,
    )


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_fastpath_config_validation():
    with pytest.raises(ValueError, match="decode_horizon"):
        ServingConfig(**SERVE, decode_horizon=0).validate(MODEL)
    with pytest.raises(ValueError, match="inflight_window"):
        ServingConfig(**SERVE, inflight_window=0).validate(MODEL)
    # a window without fused scans would be a silent no-op (k=1 units
    # never stay in flight)
    with pytest.raises(ValueError, match="inflight_window"):
        ServingConfig(**SERVE, inflight_window=2).validate(MODEL)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingConfig(**SERVE, prefill_chunk=12).validate(MODEL)
    # the chunk must divide max_seq: chunk-rounding a near-max_seq
    # prompt must never overrun the slot's block ring
    with pytest.raises(ValueError, match="divide"):
        ServingConfig(max_batch=8, block_size=8, max_seq=40,
                      hbm_budget_gb=None,
                      prefill_chunk=16).validate(MODEL)
    # 25 knobs; a key this envelope does not have (an older artifact's
    # knob, since removed) is ignored by from_dict, as any unknown key
    # is, and refused by the constructor
    assert len(dataclasses.fields(ServingConfig)) == 25
    assert ServingConfig.from_dict({**SERVE, "removed_knob": 0.5}) == \
        ServingConfig(**SERVE)
    with pytest.raises(TypeError, match="removed_knob"):
        ServingConfig(**SERVE, removed_knob=0.5)
    # a fused ladder on a dp-sharded slot dim is a legal envelope
    ServingConfig(**SERVE, decode_horizon=16).validate(MODEL, dp=2, tp=4)
    # the power-of-two fused bucket ladder
    assert ServingConfig(**SERVE).fused_horizons == ()
    assert ServingConfig(**SERVE,
                         decode_horizon=16).fused_horizons == (2, 4, 8, 16)
    # round trip keeps the fast-path knobs
    sv = ServingConfig(**SERVE, decode_horizon=4, prefill_chunk=8,
                       reject_infeasible=True)
    rt = ServingConfig.from_dict(sv.to_dict())
    assert rt.decode_horizon == 4 and rt.prefill_chunk == 8
    assert rt.reject_infeasible is True


# ---------------------------------------------------------------------------
# the equivalence contract (serve_fastpath_smoke)
# ---------------------------------------------------------------------------


@pytest.mark.serve_fastpath_smoke
def test_fused_engine_matches_per_step_tokens(baseline_engine,
                                              fast_engine):
    """The CI gate: the full fast path (fused scans + window + chunked
    prefill) serves the seeded mini-trace with completed-token
    sequences IDENTICAL to the per-step engine's, token for token."""
    trace = generate_trace("poisson", 24, seed=7, rate=500.0,
                           prompt_range=(4, 20), output_range=(2, 12))
    base = baseline_engine.run_trace(trace)
    fast = fast_engine.run_trace(trace)
    assert base["requests"]["completed"] == 24
    assert fast["requests"]["completed"] == 24
    assert base["completed_tokens"] == fast["completed_tokens"]
    # every request produced exactly its output_len tokens
    for r in trace:
        assert len(fast["completed_tokens"][str(r.rid)]) == r.output_len
    # the fast path actually engaged
    fp = fast["fast_path"]
    assert fp["enabled"] and fp["fused_scans"] > 0
    assert fp["prefill_chunks"] > 0
    assert fast["decode_units"] < fast["decode_steps"]
    # per-step engine: one dispatch per step, nothing fused
    assert base["fast_path"]["fused_scans"] == 0
    assert base["decode_units"] == base["decode_steps"]


@pytest.mark.serve_fastpath_smoke
def test_fastpath_artifact_set_schema_valid(tmp_path):
    """serve/bench.py with fast-path overrides: the artifact set stays
    schema-valid and records the fast-path counters."""
    from dlbb_tpu.serve.bench import run_serving

    config = {
        "experiment": {"name": "fastsmoke"},
        "model": dict(TINY),
        "parallelism": {"data_parallel": 2, "world_size": 4},
        "serving": {**SERVE, "decode_horizon": 8, "inflight_window": 2},
    }
    trace = generate_trace("poisson", 6, seed=9, rate=500.0,
                           prompt_range=(4, 16), output_range=(4, 10))
    report = run_serving(config, trace, str(tmp_path), verbose=False)
    assert report["requests"]["completed"] == 6
    result = json.loads((tmp_path / "serving_fastsmoke.json").read_text())
    assert result["schema"] == "dlbb_serving_report_v1"
    assert result["fast_path"]["decode_horizon"] == 8
    assert result["serving"]["decode_horizon"] == 8
    prom = (tmp_path / "metrics.prom").read_text()
    assert "dlbb_serve_decode_steps_total" in prom
    assert "dlbb_serve_fused_scan_steps_total" in prom
    assert "dlbb_serve_prefill_chunks_total" in prom
    # batch occupancy over time is the report's ``timeseries.
    # active_slots`` (a gauge written once after the drain read 0)
    assert "dlbb_serve_decode_batch_occupancy" not in prom
    assert "dlbb_serve_active_slots" in prom


# ---------------------------------------------------------------------------
# scheduler edge cases the fast path makes reachable
# ---------------------------------------------------------------------------


def test_completion_mid_fused_scan(baseline_engine, mesh2x4):
    """A slot whose request completes mid-scan is masked inactive for
    the remaining trips: it receives EXACTLY output_len tokens, its
    cache stops advancing, and its blocks free at scan exit."""
    engine = ServingEngine(
        MODEL, ServingConfig(**SERVE, decode_horizon=8), mesh2x4,
        verbose=False, capture_tokens=True,
    )
    # both resident from t=0; nothing pending/queued after admission, so
    # the horizon is max(remaining) and the scan overshoots rid 0
    trace = _trace([
        Request(rid=0, arrival_s=0.0, prompt_len=6, output_len=3,
                seed=11),
        Request(rid=1, arrival_s=0.0, prompt_len=6, output_len=12,
                seed=12),
    ])
    report = engine.run_trace(trace)
    base = baseline_engine.run_trace(trace)
    assert report["completed_tokens"] == base["completed_tokens"]
    assert len(report["completed_tokens"]["0"]) == 3
    assert len(report["completed_tokens"]["1"]) == 12
    # a fused scan ran past rid 0's completion
    assert report["fast_path"]["fused_steps"] >= 8
    # scan exit freed everything
    assert report["cache"]["blocks_reserved"] == 0
    assert report["requests"]["completed"] == 2


def test_admission_during_inflight_window(baseline_engine, mesh2x4):
    """An arrival landing while decode units are in flight is admitted
    at the next scan boundary (the engine drains the window before the
    prefill, keeping TTFT honest) and the tokens stay identical."""
    engine = ServingEngine(
        MODEL, ServingConfig(**SERVE, decode_horizon=4,
                             inflight_window=3),
        mesh2x4, verbose=False, capture_tokens=True,
    )
    trace = _trace([
        Request(rid=0, arrival_s=0.0, prompt_len=8, output_len=24,
                seed=21),
        Request(rid=1, arrival_s=0.0, prompt_len=8, output_len=24,
                seed=22),
        # lands mid-decode: the per-step run takes ~24 steps to drain
        Request(rid=2, arrival_s=0.05, prompt_len=8, output_len=8,
                seed=23),
    ])
    report = engine.run_trace(trace)
    base = baseline_engine.run_trace(trace)
    assert report["requests"]["completed"] == 3
    assert report["completed_tokens"] == base["completed_tokens"]
    assert report["fast_path"]["fused_scans"] > 0


def test_k_horizon_overshoots_every_remaining_length(mesh2x4):
    """decode_horizon far beyond every remaining output: the fused
    bucket clamps to the drain horizon, masked trips never generate
    tokens past output_len, and the ledger never overflows."""
    engine = ServingEngine(
        MODEL, ServingConfig(**SERVE, decode_horizon=64), mesh2x4,
        verbose=False, capture_tokens=True,
    )
    trace = _trace([
        Request(rid=0, arrival_s=0.0, prompt_len=4, output_len=3,
                seed=31),
        Request(rid=1, arrival_s=0.0, prompt_len=4, output_len=5,
                seed=32),
    ])
    report = engine.run_trace(trace)
    assert report["requests"]["completed"] == 2
    assert len(report["completed_tokens"]["0"]) == 3
    assert len(report["completed_tokens"]["1"]) == 5
    # the scan ladder never dispatched more trips than the longest
    # remaining output (prefill already produced token 1 of each)
    assert report["decode_steps"] == 4
    assert report["cache"]["blocks_reserved"] == 0


# ---------------------------------------------------------------------------
# chunked prefill: program-level equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["programs", "engine-interleaved",
                                  "engine-admission-behind-a-step"])
def test_chunked_prefill_matches_monolithic(case, mesh2x4, request):
    """Chunk-by-chunk prefill writes the identical cache and returns
    the identical last-token output as the monolithic bucketed
    prefill (the offset-causal prefix-carry attention is the same
    math).  Through the engine: a chunked run whose interleaved steps
    are waited for behind the next chunk completes the same tokens as
    the run with bucketed prefills, which drains before every one."""
    if case != "programs":
        # the traces whose launch order ``test_launch_spans.py`` pins:
        # prompts of one to four chunks admitted beside a resident
        # batch, and more requests than slots, where an admission's
        # first chunk goes out behind the loop's own step
        import test_launch_spans

        trace = (test_launch_spans._trace() if case == "engine-interleaved"
                 else test_launch_spans._backlog())
        mono = request.getfixturevalue("baseline_engine").run_trace(trace)
        chunked = request.getfixturevalue("fast_engine").run_trace(trace)
        assert mono["requests"]["completed"] == len(trace)
        assert chunked["completed_tokens"] == mono["completed_tokens"]
        assert mono["fast_path"]["prefill_chunks"] == 0
        assert mono["decode_units_overlapped"] == 0
        fast = chunked["fast_path"]
        assert fast["prefill_chunks"] > len(trace)
        assert 0 < chunked["decode_units_overlapped"] <= fast["single_steps"]
        return
    from dlbb_tpu.models.transformer import init_params_sharded
    from dlbb_tpu.serve.gpt import (
        build_prefill,
        build_prefill_chunk,
        create_prefix,
    )
    from dlbb_tpu.serve.kvcache import create_kv_cache

    params = init_params_sharded(MODEL, jax.random.key(0), mesh2x4)
    prompt, slot, chunk = 19, 1, 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (1, 24, MODEL.hidden_size)).astype(np.float32))

    sv = ServingConfig(**SERVE)
    cache_a = create_kv_cache(MODEL, sv.max_batch, sv.num_blocks,
                              sv.block_size, mesh=mesh2x4)
    bucket = sv.bucket_for(prompt)
    xa = jnp.zeros((1, bucket, MODEL.hidden_size),
                   jnp.float32).at[:, :prompt].set(x[:, :prompt])
    cache_a, ya = build_prefill(MODEL, mesh2x4)(
        cache_a, params, xa, np.int32(slot), np.int32(prompt))

    cache_b = create_kv_cache(MODEL, sv.max_batch, sv.num_blocks,
                              sv.block_size, mesh=mesh2x4)
    prefix = create_prefix(MODEL, mesh2x4)
    n_chunks = -(-prompt // chunk)
    xb = jnp.zeros((1, n_chunks * chunk, MODEL.hidden_size),
                   jnp.float32).at[:, :prompt].set(x[:, :prompt])
    for ci in range(n_chunks):
        jit = build_prefill_chunk(MODEL, mesh2x4, chunk, ci * chunk)
        cache_b, prefix, yb = jit(
            cache_b, prefix, params, xb[:, ci * chunk:(ci + 1) * chunk],
            np.int32(slot), np.int32(prompt))

    assert float(jnp.abs(ya - yb).max()) <= 1e-5
    ka = np.asarray(cache_a.k)[:, slot].reshape(
        MODEL.num_layers, -1, MODEL.kv_heads, MODEL.head_dim)[:, :prompt]
    kb = np.asarray(cache_b.k)[:, slot].reshape(
        MODEL.num_layers, -1, MODEL.kv_heads, MODEL.head_dim)[:, :prompt]
    assert float(np.abs(ka - kb).max()) <= 1e-5
    assert int(cache_b.lengths[slot]) == prompt
    assert int(cache_b.lengths[0]) == 0


# ---------------------------------------------------------------------------
# rejection detail + journal reasons (admission-tuning satellite)
# ---------------------------------------------------------------------------


def test_rejection_detail_and_shed_rate(baseline_engine):
    """Queue-full rejections carry the queue head's wait time (how
    backed up admission was when load was shed) and the report exposes
    the shed rate."""
    from dataclasses import replace

    engine = baseline_engine
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
    detail = req["rejected_detail"]
    assert len(detail) == req["rejected"]
    assert all(d["reason"] == "queue-full" for d in detail)
    assert all(d["queue_wait_s"] >= 0.0 for d in detail)
    assert req["shed_rate"] == pytest.approx(
        req["rejected"] / req["arrived"])
    assert req["rejected_rids"] == [d["rid"] for d in detail]


def test_infeasible_rejected_and_journaled_distinctly(mesh2x4, tmp_path):
    """reject_infeasible: an unservable request is shed at arrival with
    reason="infeasible" — a DISTINCT journal event from queue-full —
    while the feasible rest of the trace completes."""
    from dlbb_tpu.obs import spans
    from dlbb_tpu.resilience.journal import SweepJournal, read_journal

    engine = ServingEngine(
        MODEL, ServingConfig(**SERVE, reject_infeasible=True), mesh2x4,
        verbose=False,
    )
    trace = _trace([
        Request(rid=0, arrival_s=0.0, prompt_len=8, output_len=4,
                seed=1),
        # prompt + output outgrows max_seq: infeasible, not load
        Request(rid=1, arrival_s=0.0, prompt_len=40, output_len=30,
                seed=2),
    ])
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        report = engine.run_trace(trace)
    finally:
        engine.journal = None
        journal.close()
    req = report["requests"]
    assert req["completed"] == 1 and req["rejected"] == 1
    assert req["rejected_detail"][0]["reason"] == "infeasible"
    assert "max_seq" in req["rejected_detail"][0]["detail"]
    # infeasible is a config mismatch, never LOAD: not in the shed rate
    assert req["shed_rate"] == 0.0
    events, torn = read_journal(tmp_path)
    assert torn == 0
    kinds = {e["event"] for e in events}
    assert "request-infeasible" in kinds
    assert "request-rejected" not in kinds  # no load was shed
    # the reason-labelled counter split the two paths
    assert engine.registry.get("serve_rejections",
                               reason="infeasible") >= 1
    # journal -> timeline: the infeasible rejection still closes the
    # request's arrived->end span
    timeline, _n, torn2 = spans.journal_to_trace(
        tmp_path, tmp_path / "timeline.json")
    assert torn2 == 0
    rebuilt = spans.load_trace(timeline)
    infeasible_spans = [e for e in rebuilt["traceEvents"]
                        if e["ph"] == "X"
                        and e["cat"] == "config-infeasible"]
    assert len(infeasible_spans) == 1
    # the strict default still fails the whole trace up front
    with pytest.raises(ValueError, match="max_seq"):
        ServingEngine(MODEL, ServingConfig(**SERVE), mesh2x4,
                      verbose=False).run_trace(trace)


# ---------------------------------------------------------------------------
# span-trace fidelity (one span per scan) + journal timelines
# ---------------------------------------------------------------------------


def test_fused_scan_emits_one_span_with_steps_attr(mesh2x4, tmp_path):
    """A fused K-step scan is ONE ``serve-decode`` span carrying a
    ``steps`` attribute — not K fake per-step spans — and the journal
    timeline stays correct when several requests complete inside one
    host iteration."""
    from dlbb_tpu.obs import spans
    from dlbb_tpu.resilience.journal import SweepJournal, read_journal

    engine = ServingEngine(
        MODEL, ServingConfig(**SERVE, decode_horizon=8), mesh2x4,
        verbose=False,
    )
    trace = _trace([
        Request(rid=i, arrival_s=0.0, prompt_len=6, output_len=6,
                seed=40 + i)
        for i in range(4)
    ])
    span_path = tmp_path / "trace.json"
    journal = SweepJournal(tmp_path, meta={"mode": "serve"},
                           sink=spans.journal_sink)
    engine.journal = journal
    try:
        with spans.tracing(span_path):
            report = engine.run_trace(trace)
    finally:
        engine.journal = None
        journal.close()
    payload = spans.load_trace(span_path)
    assert spans.validate_trace_events(payload["traceEvents"]) == []
    decode_begins = [e for e in payload["traceEvents"]
                     if e["ph"] == "B" and e["name"] == "serve-decode"]
    # one span per dispatched unit, scans included
    assert len(decode_begins) == report["decode_units"]
    fused = [e for e in decode_begins if e["args"]["steps"] > 1]
    assert len(fused) == report["fast_path"]["fused_scans"]
    assert sum(e["args"]["steps"] for e in decode_begins) == \
        report["decode_steps"]
    # all four requests completed in ONE host iteration (same scan);
    # the journal still pairs every lifecycle span
    events, torn = read_journal(tmp_path)
    assert torn == 0
    completed = [e for e in events if e["event"] == "request-completed"]
    assert len(completed) == 4
    timeline, _n, torn2 = spans.journal_to_trace(
        tmp_path, tmp_path / "timeline.json")
    assert torn2 == 0
    rebuilt = spans.load_trace(timeline)
    req_spans = [e for e in rebuilt["traceEvents"] if e["ph"] == "X"]
    assert len(req_spans) == 4
    assert all(e["cat"] == "config-completed" for e in req_spans)


# ---------------------------------------------------------------------------
# names that survive a recompile: program names, phase scopes, the span
# tree inside serve-admission and the request identifier (PR 25)
# ---------------------------------------------------------------------------


def _decode_args(mesh, sv):
    from dlbb_tpu.models.transformer import init_params_sharded
    from dlbb_tpu.serve.kvcache import create_kv_cache

    params = init_params_sharded(MODEL, jax.random.key(0), mesh)
    cache = create_kv_cache(MODEL, sv.max_batch, sv.num_blocks,
                            sv.block_size, mesh=mesh)
    x = jnp.zeros((sv.max_batch, 1, MODEL.hidden_size), jnp.float32)
    active = jnp.zeros((sv.max_batch,), bool)
    return params, cache, x, active


def _lowered(jitted, *args):
    return jitted.lower(*args).as_text(debug_info=True)


def test_serve_blocks_share_the_one_phase_tuple():
    """Both block definitions unpack ``models.transformer.BLOCK_PHASES``:
    a phase renamed there is renamed in training and serving alike."""
    from dlbb_tpu.models import transformer
    from dlbb_tpu.serve import attend, gpt

    names = ("LN1", "ATTN_QKV", "ATTN_CORE", "ATTN_OUT",
             "LN2", "MLP_UP", "MLP_ACT", "MLP_DOWN")
    assert tuple(getattr(transformer, n) for n in names) == \
        transformer.BLOCK_PHASES
    assert tuple(getattr(gpt, n) for n in names) == \
        transformer.BLOCK_PHASES
    assert (attend.KV_UPDATE, attend.KV_ATTEND) == \
        transformer.SERVE_PHASES == ("kv_update", "kv_attend")


@pytest.mark.parametrize("program", ["serve_decode_k4",
                                     "serve_prefill_chunk_o8"])
def test_serving_program_lowers_with_its_name_and_every_phase(
        mesh2x4, program):
    """The lowered text of a decode scan and of a prefill chunk is
    called what it is (the module name the profile prints) and carries
    every phase scope of the block plus the two cache phases."""
    from dlbb_tpu.models.transformer import BLOCK_PHASES, SERVE_PHASES
    from dlbb_tpu.serve.gpt import (
        build_decode_fused,
        build_prefill_chunk,
        create_prefix,
    )

    sv = ServingConfig(**SERVE)
    params, cache, x, active = _decode_args(mesh2x4, sv)
    if program.startswith("serve_decode"):
        text = _lowered(build_decode_fused(MODEL, mesh2x4, 4),
                        (cache, x), params, active,
                        jnp.zeros((sv.max_batch,), jnp.int32))
    else:
        text = _lowered(build_prefill_chunk(MODEL, mesh2x4, 8, 8),
                        cache, create_prefix(MODEL, mesh2x4), params,
                        jnp.zeros((1, 8, MODEL.hidden_size), jnp.float32),
                        np.int32(0), np.int32(12))
    assert f"module @jit_{program}" in text
    for phase in BLOCK_PHASES + SERVE_PHASES:
        assert f"{phase}/" in text, phase


# ---------------------------------------------------------------------------
# a cache write touches only the rows it writes, in place (PR 26): read
# from the optimised HLO, on the simulated mesh and compiled for the v5e
# ---------------------------------------------------------------------------

_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
# what may hold one whole cache plane: the program's arguments, the layer
# loop's carry, and the update that writes into that carry's buffer
_PLANE_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while",
                   "bitcast"}
_PLANE_UPDATES = {"scatter", "dynamic-update-slice"}


def _hlo_instructions(hlo: str):
    """``(computation, line, name, result type, op)`` of every
    instruction of an optimised HLO module."""
    computation = ""
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            computation = head.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m:
            yield (computation, line, *m.groups())


def _plane_producers(hlo: str, plane: tuple, dtype: str) -> dict:
    """``{instruction name: op}`` of every instruction of an optimised
    HLO module whose result holds one whole (per-device) cache plane,
    plumbing left out; a fusion is given as ``fusion:<its root's op>``.
    Listed too: a ``select`` over one whole layer of the plane (the
    masked-select append this replaced), and, outside fused
    computations, any other instruction that writes one whole layer out
    (``layer:<op>``: the attention read is to take its slice of the
    plane as a prologue, not from a copy of the layer)."""
    l, b, nb, bs, kvh, d = plane
    full = f"{dtype}[{','.join(map(str, plane))}]"
    layers = {f"[{','.join(map(str, dims))}]"
              for dims in ((b, nb, bs, kvh, d), (1, b, nb, bs, kvh, d),
                           (b, nb * bs, kvh, d), (1, b, nb * bs, kvh, d))}
    roots, found = {}, {}
    for computation, line, name, result, op in _hlo_instructions(hlo):
        if line.lstrip().startswith("ROOT "):
            roots[computation] = op
        shape = re.sub(r"^[a-z0-9]+|\{.*$", "", result)
        if full in result and op not in _PLANE_PLUMBING:
            called = re.search(r"calls=%([\w.\-]+)", line)
            found[name] = (op, called.group(1) if called else None)
        elif shape in layers and op == "select":
            found[name] = ("select-over-a-layer", None)
        elif (shape in layers and op not in _PLANE_PLUMBING
              and "fused_computation" not in computation):
            found[name] = (f"layer:{op}", None)
    return {name: f"fusion:{roots.get(called)}" if called else op
            for name, (op, called) in found.items()}


def _assert_writes_in_place(hlo: str, plane: tuple, dtype: str) -> None:
    producers = _plane_producers(hlo, plane, dtype)
    allowed = _PLANE_UPDATES | {f"fusion:{op}" for op in _PLANE_UPDATES}
    extra = {n: op for n, op in producers.items() if op not in allowed}
    assert not extra, (
        f"a whole plane or layer written beside the in-place write: {extra}")
    # K and V each written at least once: the shapes matched something
    assert len(producers) >= 2, producers


def _cache_writing_program(program, cfg, mesh, cache, params, x, chunk):
    """The jitted program and its arguments (arrays or shapes)."""
    from dlbb_tpu.serve import gpt as E

    b = cache.k.shape[1]
    like = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=NamedSharding(mesh, P()))
    active, i32 = like((b,), jnp.bool_), like((), jnp.int32)
    if program == "serve_decode_step":
        return E.build_decode_step(cfg, mesh), ((cache, x), params, active)
    if program == "serve_decode_k4":
        return (E.build_decode_fused(cfg, mesh, 4),
                ((cache, x), params, active, like((b,), jnp.int32)))
    pre = jax.ShapeDtypeStruct(
        (cfg.num_layers, chunk, cfg.kv_heads, cfg.head_dim), x.dtype,
        sharding=NamedSharding(mesh, E.prefix_spec(mesh)))
    return (E.build_prefill_chunk(cfg, mesh, chunk, chunk),
            (cache, (pre, pre), params,
             like((1, chunk, cfg.hidden_size), x.dtype), i32, i32))


CACHE_WRITERS = ["serve_decode_step", "serve_decode_k4",
                 "serve_prefill_chunk"]


@pytest.mark.parametrize("program", CACHE_WRITERS)
def test_cache_writes_are_in_place_on_the_simulated_mesh(mesh2x4, program):
    """On dp=2 x tp=4 no instruction of the compiled program but the
    in-place write produces a whole cache plane (per device): no
    ``copy``, no ``select``, no other fusion."""
    sv = ServingConfig(**SERVE)
    params, cache, x, _ = _decode_args(mesh2x4, sv)
    jitted, args = _cache_writing_program(program, MODEL, mesh2x4, cache,
                                          params, x, chunk=8)
    hlo = jitted.lower(*args).compile().as_text()
    _assert_writes_in_place(
        hlo, cache.k.sharding.shard_shape(cache.k.shape), "f32")


@pytest.fixture(scope="module")
def v5e_tray():
    """The four described (not attached) chips of a v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def v5e_chip(v5e_tray):
    """One described v5e chip as a 1 x 1 (dp, tp) mesh."""
    return build_parallelism_mesh(1, 1, 1, 1, 1, devices=v5e_tray[:1])


@pytest.fixture()
def as_on_the_chip(monkeypatch):
    """The code that asks which backend it runs on takes its TPU branch
    (the decode kernel compiled by Mosaic, not interpreted), as in a
    process that holds the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _gpt7b_decode_shapes(mesh):
    """The 7B serving cell's decode carry and parameters as shapes (16
    slots x 1024 tokens, bf16; 2 layers: the layer loop's body does not
    depend on their number)."""
    from dlbb_tpu.models.transformer import init_params
    from dlbb_tpu.serve.kvcache import KVCache, cache_shardings

    cfg = ModelConfig(hidden_size=4096, num_layers=2, num_heads=32,
                      ffn_intermediate=16384, dtype="bfloat16",
                      attention="full")
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: init_params(cfg, jax.random.key(0))))
    plane = (cfg.num_layers, 16, 64, 16, cfg.kv_heads, cfg.head_dim)
    sh = cache_shardings(mesh)
    cache = KVCache(
        jax.ShapeDtypeStruct(plane, jnp.bfloat16, sharding=sh.k),
        jax.ShapeDtypeStruct(plane, jnp.bfloat16, sharding=sh.v),
        jax.ShapeDtypeStruct((16,), jnp.int32, sharding=sh.lengths))
    x = jax.ShapeDtypeStruct((16, 1, cfg.hidden_size), jnp.bfloat16,
                             sharding=rep)
    return cfg, params, cache, x, plane


@pytest.mark.parametrize("program", CACHE_WRITERS)
def test_cache_writes_are_in_place_compiled_for_the_v5e(v5e_chip, program,
                                                        as_on_the_chip):
    """The same, as the TPU's own compiler leaves the program at the
    benchmark cell's widths: the parent had a whole-plane ``select``
    fusion and two whole-plane ``copy`` a program run here."""
    mesh = v5e_chip
    cfg, params, cache, x, plane = _gpt7b_decode_shapes(mesh)
    jitted, args = _cache_writing_program(program, cfg, mesh, cache,
                                          params, x, chunk=128)
    hlo = jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    _assert_writes_in_place(hlo, plane, "bf16")


@pytest.mark.parametrize("program", CACHE_WRITERS)
def test_qkv_kernel_is_read_where_it_lies_compiled_for_the_v5e(
        v5e_chip, program, as_on_the_chip):
    """The fused projection reads its layer's kernel out of the stack,
    inside the matmul's fusion: no instruction of the layer loop writes
    a kernel-sized buffer.  Without the barrier in ``split_qkv`` the
    compiler folds the split's reshape into the matmul and re-lays the
    kernel out for it, a 100 MB copy a layer in every decode step and
    chunk: ``serve7b_backlog`` read 328 tokens/s for 474 (``PERF.md``
    §6, PR 32)."""
    mesh = v5e_chip
    cfg, params, cache, x, _ = _gpt7b_decode_shapes(mesh)
    jitted, args = _cache_writing_program(program, cfg, mesh, cache,
                                          params, x, chunk=128)
    hlo = jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    kernel = f"{cfg.hidden_size},{cfg.qkv_width}]"
    written = {
        name: f"{op} {result}"
        for computation, _, name, result, op in _hlo_instructions(hlo)
        if "fused_computation" not in computation
        and op not in _PLANE_PLUMBING
        and re.sub(r"\{.*$", "", result).endswith(kernel)}
    assert not written, written


def _hybrid_decode_step(mesh):
    """``serve_decode_step`` of the Olmo-Hybrid cell (32 slots x 2048
    tokens, 30 heads held as 32) at two periods of its four."""
    from benchmarks.harness import cells
    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid
    from dlbb_tpu.serve.kvcache import HybridCache, hybrid_cache_shardings

    model = dict(cells.resolve_cell("olmohyb_longgen_backlog")
                 .config["program"]["model"], num_layers=8)
    cfg = ModelConfig.from_dict(model)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.key(0))))
    plane = (2, 32, 128, 16, 32, cfg.head_dim)
    heads = cfg.linear_num_value_heads
    shapes = HybridCache(
        (plane, jnp.bfloat16), (plane, jnp.bfloat16),
        ((6, 32, heads, cfg.linear_value_head_dim,
          cfg.linear_key_head_dim), jnp.float32),
        ((6, 32, cfg.linear_conv_kernel_dim - 1, heads,
          cfg.linear_conv_channels // heads), jnp.bfloat16),
        ((0, 32, 128, 16, 0), jnp.bfloat16), ((32,), jnp.int32))
    cache = HybridCache(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
        for (shape, dtype), sh in zip(shapes, hybrid_cache_shardings(mesh))))
    like = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=rep)
    return (serve_hybrid.build_decode_step(cfg, mesh),
            ((cache, like((32,), jnp.int32)), params, like((32,), jnp.bool_),
             like((serve_hybrid.PROBES,), jnp.int32)), plane)


def test_latent_decode_step_compiled_for_the_v5e_copies_no_plane_and_no_expert(
        v5e_chip, as_on_the_chip):
    """``serve_decode_step`` of the kanana-2 cell at its published widths
    (its 64 slots and max_seq, one leading dense layer and two expert
    layers: the layer loops' bodies do not depend on their number):
    Mosaic takes the latent decode kernel and the three grouped expert
    products of the loop's body, the carried latent plane is handed over
    whole (no instruction but the in-place append produces a plane or a
    layer of it), and no expert's weights are sliced out of their stack
    in front of a kernel (``ops/routed_experts.py::grouped_products``)."""
    from benchmarks.harness import cells
    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid
    from dlbb_tpu.serve.kvcache import create_hybrid_cache

    mesh = v5e_chip
    program = cells.resolve_cell("kanana_serve_longctx_backlog") \
        .config["program"]
    cfg = ModelConfig.from_dict(dict(program["model"], num_layers=3))
    sv = ServingConfig.from_dict(program["serving"])
    rep = NamedSharding(mesh, P())

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), tree)

    params = shaped(jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.key(0))))
    cache = shaped(jax.eval_shape(lambda: create_hybrid_cache(
        cfg, sv.max_batch, sv.num_blocks, sv.block_size)))
    like = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=rep)
    b = sv.max_batch
    compiled = serve_hybrid.build_decode_step(cfg, mesh).trace(
        (cache, like((b,), jnp.int32)), params, like((b,), jnp.bool_),
        like((serve_hybrid.PROBES,), jnp.int32)).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    calls = re.findall(r"%(\S+) = \S+ custom-call\(.*tpu_custom_call", hlo)
    assert sorted(c.split(".")[0] for c in calls) == [
        "gmm", "gmm", "gmm", "latent_attend_decode", "latent_attend_decode"]
    layers, _, nb, bs, row = cache.latent.shape
    assert (layers, nb * bs, row) == (3, sv.max_seq, 640)
    whole = {f"[{','.join(map(str, dims))}]" for dims in (
        (layers, b, nb, bs, row), (b, nb, bs, row), (1, b, nb, bs, row),
        (b, nb * bs, row), (128, 2048, 768), (128, 768, 2048),
        (2, 128, 2048, 768), (2, 128, 768, 2048))}
    left = {}
    for line in hlo.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if (m and m.group(3) not in _PLANE_PLUMBING | {"scatter", "fusion"}
                and re.sub(r"^[a-z0-9]+|\{.*$", "", m.group(2)) in whole):
            left[m.group(1)] = m.group(3)
    assert not left, f"ops of a whole plane's or expert stack's shape: {left}"
    # ... and what the program holds beside its arguments is small
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("program", ["serve_decode_step", "serve_decode_k16",
                                     "serve_prefill_chunk_o128"])
def test_looped_programs_compiled_for_the_v5e_hold_one_layer_body_and_copy_no_plane(
        v5e_chip, as_on_the_chip, program):
    """The Ouro cell's programs at its published widths and its 8 slots
    of 640 tokens (2 of its 48 layers, all 4 passes: the loops' bodies
    do not depend on the number of layers): the passes are a LOOP around
    the layer loop, so a decode program holds ONE ``kv_attend_decode``
    kernel and not four; every K/V plane ``[passes x layers, ...]`` is
    written in place (the decode append's scatter, or the chunk's one
    update of every plane after its loops) and nothing else of a whole
    plane's or a plane's layer's shape is produced; and what a program
    holds beside its arguments is the re-tiled q, k, v kernels and no
    more (a plane is 168 MB here, 4.03 GB at 48 layers)."""
    from benchmarks.harness import cells
    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid
    from dlbb_tpu.serve.kvcache import create_hybrid_cache

    mesh = v5e_chip
    cell = cells.resolve_cell("ouro_serve_reason_backlog").config["program"]
    cfg = ModelConfig.from_dict(dict(cell["model"], num_layers=2))
    sv = ServingConfig.from_dict(cell["serving"])
    rep = NamedSharding(mesh, P())

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), tree)

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = shaped(jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.key(0))))
    cache = shaped(jax.eval_shape(lambda: create_hybrid_cache(
        cfg, sv.max_batch, sv.num_blocks, sv.block_size)))
    b = sv.max_batch
    plane = cache.k.shape
    assert plane == (4 * 2, b, 40, 16, 16, 128)
    carry = (cache, like((b,), jnp.int32))
    masks = (like((b,), jnp.bool_),)
    probe = like((serve_hybrid.PROBES,), jnp.int32)
    if program == "serve_decode_step":
        traced = serve_hybrid.build_decode_step(cfg, mesh).trace(
            carry, params, *masks, probe)
    elif program == "serve_decode_k16":
        traced = serve_hybrid.build_decode_fused(cfg, mesh, 16).trace(
            carry, params, *masks, like((b,), jnp.int32), probe)
    else:
        prefix = tuple(
            like((t.shape[0], 128) + t.shape[2:], t.dtype) if i < 2 else t
            for i, t in enumerate(shaped(jax.eval_shape(
                lambda: serve_hybrid.create_prefix(cfg, mesh)))))
        traced = serve_hybrid.build_prefill_chunk(cfg, mesh, 128, 128).trace(
            cache, prefix, params, like((1, 128), jnp.int32),
            like((), jnp.int32), like((), jnp.int32))
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    calls = re.findall(r"%(\S+) = \S+ custom-call\(.*tpu_custom_call", hlo)
    if program.startswith("serve_decode"):
        assert len(calls) == 1 and calls[0].startswith("kv_attend_decode"), \
            calls
    else:
        assert calls == []
    _assert_writes_in_place(hlo, plane, "bf16")
    # the q, k and v kernels of both layers, re-tiled once a call (3 x
    # 16.8 MB), and the chunk's scores; never a plane
    assert compiled.memory_analysis().temp_size_in_bytes < 96 * 2**20


@pytest.mark.parametrize("program", ["serve_decode_step", "serve_decode_k16",
                                     "serve_prefill_chunk_o256"])
def test_state_space_programs_compiled_for_the_v5e_update_the_state_in_place(
        v5e_chip, as_on_the_chip, program):
    """The granite-4.0-h-micro cell's programs at its published widths
    and its slots (one period of its four: nine state-space layers and
    one attention layer; the loop's body does not depend on their
    number).  The float32 state plane ``[9, slots, 64, 64, 128]`` (1.2 GB
    here, 4.8 GB at 40 layers) is stepped in place by a Mosaic kernel of
    its own in the decode programs (``ssm_state_step``, once a
    state-space layer of the period, its result aliased to the plane it
    is handed): no fusion, no ``copy`` and no other op of the plane's or
    of a plane's layer's shape is left there (the nine
    ``select_dynamic-update-slice`` writers are gone); a chunk writes one
    slot's state by plain ``dynamic-update-slice``s.  The K/V planes of
    whole rows ``[1, slots, 80, 16, 512]`` and the convolution inputs are
    written in place; Mosaic takes the row-layout ``kv_attend_decode``
    (heads of 64), once in the decode loop's body; and what a program
    holds beside its arguments is small (with the in-projection held
    whole, 8512 columns, the fused scan re-laid all its kernels out:
    1.19 GiB at 40 layers, ``PERF.md`` §6, PR 37)."""
    from benchmarks.harness import cells
    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid
    from dlbb_tpu.serve.kvcache import create_hybrid_cache

    mesh = v5e_chip
    cell = cells.resolve_cell("granite4h_serve_chat_backlog") \
        .config["program"]
    cfg = ModelConfig.from_dict(dict(cell["model"], num_layers=10))
    sv = ServingConfig.from_dict(cell["serving"])
    rep = NamedSharding(mesh, P())

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), tree)

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = shaped(jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.key(0))))
    cache = shaped(jax.eval_shape(lambda: create_hybrid_cache(
        cfg, sv.max_batch, sv.num_blocks, sv.block_size)))
    b, nb = sv.max_batch, sv.num_blocks
    assert cache.k.shape == (1, b, nb, 16, 512)
    assert cache.state.shape == (9, b, 64, 64, 128)
    assert cache.conv.shape == (9, b, 3 * 4352)
    carry = (cache, like((b,), jnp.int32))
    masks = (like((b,), jnp.bool_),)
    probe = like((serve_hybrid.PROBES,), jnp.int32)
    if program == "serve_decode_step":
        traced = serve_hybrid.build_decode_step(cfg, mesh).trace(
            carry, params, *masks, probe)
    elif program == "serve_decode_k16":
        traced = serve_hybrid.build_decode_fused(cfg, mesh, 16).trace(
            carry, params, *masks, like((b,), jnp.int32), probe)
    else:
        prefix = tuple(
            like((t.shape[0], 256) + t.shape[2:], t.dtype) if i < 2 else t
            for i, t in enumerate(shaped(jax.eval_shape(
                lambda: serve_hybrid.create_prefix(cfg, mesh)))))
        traced = serve_hybrid.build_prefill_chunk(cfg, mesh, 256, 256).trace(
            cache, prefix, params, like((1, 256), jnp.int32),
            like((), jnp.int32), like((), jnp.int32))
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    decode = program.startswith("serve_decode")
    calls = [(name, line) for _, line, name, _, op in _hlo_instructions(hlo)
             if op == "custom-call" and "tpu_custom_call" in line]
    kernels = sorted(re.sub(r"[.\d]+$", "", name) for name, _ in calls)
    # two kernels in the loop's body: the attention layer's, and the
    # state step once a state-space layer of the period
    assert kernels == (["kv_attend_decode"] + 9 * ["ssm_state_step"]
                       if decode else []), kernels
    plane = f"f32[{','.join(map(str, cache.state.shape))}]"
    for name, line in calls:
        if name.startswith("ssm_state_step"):
            # the plane is the call's last operand and its first result
            operands = line.split("custom-call(")[1].split(")")[0]
            last = operands.count("%") - 1
            assert line.split(" = ")[1].startswith(f"({plane}"), line[:200]
            assert f"output_to_operand_aliasing={{{{0}}: ({last}, {{}})" \
                in line, line[-300:]
    state = {f"[{','.join(map(str, dims))}]" for dims in (
        cache.state.shape, cache.state.shape[1:],
        (1,) + cache.state.shape[1:])}
    whole = state | {f"[{','.join(map(str, dims))}]" for dims in (
        cache.k.shape, cache.k.shape[1:], (b, nb * 16, 512),
        cache.conv.shape)}
    left = {}
    for computation, _, name, result, op in _hlo_instructions(hlo):
        shape = re.sub(r"^[a-z0-9]+|\{.*$", "", result)
        if "fused_computation" in computation or op in _PLANE_PLUMBING:
            continue
        if (shape in whole and op not in _PLANE_UPDATES | {"fusion"}
                or decode and shape in state):
            left[name] = op
    assert not left, f"ops of a whole plane's or layer's shape: {left}"
    # every fusion that gives a whole state plane is a chunk's in-place
    # write of one slot's state
    writers = {name: line for _, line, name, result, op
               in _hlo_instructions(hlo)
               if op == "fusion" and "fused_computation" not in _
               and re.sub(r"^[a-z0-9]+|\{.*$", "", result)
               == f"[{','.join(map(str, cache.state.shape))}]"}
    assert all("dynamic-update-slice" in name
               or "dynamic_update_slice" in line
               for name, line in writers.items()), writers
    assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2**20


@pytest.mark.parametrize("family", ["gpt", "hybrid"])
def test_decode_step_attends_through_the_kernel_compiled_for_the_v5e(
        v5e_chip, as_on_the_chip, family):
    """``serve_decode_step`` of both families at their cells' widths: ONE
    Mosaic kernel, ``kv_attend_decode``, is handed the carried planes
    whole, and nothing of a layer's shape (``[B, S_max, kvh, d]``, flat
    or in blocks: the dense path's fp32 ``convert``, a ``copy`` in front
    of the custom call) is left in the program."""
    mesh = v5e_chip
    if family == "gpt":
        cfg, params, cache, x, plane = _gpt7b_decode_shapes(mesh)
        jitted, args = _cache_writing_program(
            "serve_decode_step", cfg, mesh, cache, params, x, chunk=128)
    else:
        jitted, args, plane = _hybrid_decode_step(mesh)
    hlo = jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    calls = re.findall(r"%(\S+) = \S+ custom-call\(.*tpu_custom_call", hlo)
    assert len(calls) == 1 and calls[0].startswith("kv_attend_decode"), calls
    _, b, nb, bs, kvh, d = plane
    layer = {f"[{','.join(map(str, dims))}]" for dims in (
        (b, nb, bs, kvh, d), (1, b, nb, bs, kvh, d), (b, nb * bs, kvh, d),
        (1, b, nb * bs, kvh, d), (b, kvh, nb * bs, d), (b, nb * bs, kvh))}
    left = {}
    for line in hlo.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if (m and m.group(3) not in _PLANE_PLUMBING
                and re.sub(r"^[a-z0-9]+|\{.*$", "", m.group(2)) in layer):
            left[m.group(1)] = m.group(3)
    assert not left, f"ops of a whole layer's shape: {left}"


def test_tp4_forward_compiled_for_the_v5e_hops_beside_its_matmuls(
        v5e_tray, as_on_the_chip):
    """The 13B forward of the four-chip cell (``fwd13b_tp4``; two layers:
    the layer loop's body does not depend on their number), with nothing
    said about ``tp_overlap``, as the TPU's own compiler schedules it.
    This file holds it because every test that describes a TPU lives in
    one file.  The ``tp`` axis goes round the tray (0, 1, 3, 2: in the
    plain order two hops of four are diagonals); every ring hop of the
    layer body has a partial matmul between its start and its done; the
    all-gather rings' block writes are folded into those matmuls (no
    ``dynamic-update-slice`` of its own, which on the chip copied the
    whole output at every visit: 38.5 ms of a 209 ms step, ``PERF.md``
    §6, PR 34); no instruction writes a kernel-sized buffer; and no
    all-reduce is left."""
    from conftest import abstract_forward

    mesh = build_parallelism_mesh(tensor_parallel=4, devices=v5e_tray)
    assert [d.id for d in mesh.devices.reshape(-1)] == [0, 1, 3, 2]
    cfg = ModelConfig(hidden_size=5120, num_layers=2, num_heads=40,
                      ffn_intermediate=20480, dtype="bfloat16",
                      attention="full")
    fn, args = abstract_forward(cfg, mesh, (8, 512, 5120), jnp.bfloat16)
    hlo = fn.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()

    body = [(line, name, result, op)
            for computation, line, name, result, op in _hlo_instructions(hlo)
            if computation.startswith("wide.region_0")]
    assert body, "no layer loop body found"
    ops = [op for _, _, _, op in body]
    assert "all-reduce" not in ops and "all-reduce-start" not in ops
    assert "dynamic-update-slice" not in ops
    kernels = {"5120,3840]", "1280,5120]", "5120,5120]"}
    written = [f"{op} {result}" for _, _, result, op in body
               if op not in _PLANE_PLUMBING | {"slice-start", "slice-done",
                                               "custom-call"}
               and re.sub(r"\{.*$", "", result)[-10:] in kernels]
    assert not written, written
    open_hops, bare = {}, []
    for line, name, _result, op in body:
        if op == "collective-permute-start":
            assert "ring_hop_" in line, line
            open_hops[name] = 0
        elif op == "collective-permute-done":
            start = re.search(r"\(%?(collective-permute-start[\w.]*)\)",
                              line).group(1)
            if not open_hops.pop(start):
                bare.append(start)
        elif op == "fusion" and "dot_general" in line:
            for hop in open_hops:
                open_hops[hop] += 1
    assert not bare, f"hops with no matmul beside them: {bare}"


def test_toy_widths_are_refused_on_the_chip_when_the_engine_is_built(
        mesh2x4, as_on_the_chip):
    """Heads of 16 floats are no whole tiles for the kernel's copies, and
    no dense path stands behind the kernel: on the chip an engine of
    such a model says so when it is built (``tests/test_tpu.py`` serves
    real head widths there); the int8 layout, which reads its layers
    whole, is built as ever."""
    with pytest.raises(ValueError, match="1 kv-heads of 16"):
        ServingEngine(MODEL, ServingConfig(**SERVE), mesh2x4,
                      verbose=False)
    engine = ServingEngine(
        MODEL, ServingConfig(kv_quantization="int8", **SERVE), mesh2x4,
        verbose=False)
    assert engine._kv_tile == 0


def test_every_serving_program_has_a_stable_name(mesh2x4):
    """Program names say what the program is and which static shape it
    was built for; they are the jitted function's own name."""
    from dlbb_tpu.serve import gpt as E

    def name(jitted):
        return jitted.__wrapped__.__name__

    assert name(E.build_decode_step(MODEL, mesh2x4)) == "serve_decode_step"
    assert name(E.build_decode_fused(MODEL, mesh2x4, 16)) == \
        "serve_decode_k16"
    assert name(E.build_prefill_chunk(MODEL, mesh2x4, 8, 24)) == \
        "serve_prefill_chunk_o24"
    assert name(E.build_prefill(MODEL, mesh2x4)) == "serve_prefill"
    assert name(E.build_prefix_attach(MODEL, mesh2x4, 8, 8)) == \
        "serve_prefix_attach"
    assert name(E.build_decode_token_step(MODEL, mesh2x4)) == \
        "serve_decode_token_step"
    assert name(E.build_decode_fused_token(MODEL, mesh2x4, 4)) == \
        "serve_decode_token_k4"
    assert name(E.build_verify_step(MODEL, mesh2x4, 2)) == \
        "serve_spec_verify_g2"
    assert name(E.build_verify_probs(MODEL, mesh2x4, 2)) == \
        "serve_spec_probs_g2"
    assert name(E.build_spec_commit(MODEL, mesh2x4)) == "serve_spec_commit"
    assert name(E.build_draft_scan(MODEL, mesh2x4, 2)) == \
        "serve_spec_draft_g2"
    # the engine's own: one prefill jit per bucket, and the inject
    eng = ServingEngine(MODEL, ServingConfig(**SERVE), mesh2x4,
                        verbose=False)
    assert name(eng._prefill_jit(16)) == "serve_prefill_b16"
    assert eng._prefill_jit(16) is eng._prefill_jit(16)
    assert name(eng._inject) == "serve_inject"


def test_traced_chunked_run_gives_admission_a_span_tree_and_requests_a_rid(
        fast_engine, tmp_path):
    """A traced serving run with chunked prefill: the file validates,
    every ``serve-admit-*`` span lies inside a ``serve-admission`` and
    carries ``rid`` and ``slot``, the decode unit's dispatch and sync
    are children of ``serve-decode``, no program span uses a prefix the
    benchmark keeps for itself, and every completed request has its
    four lifecycle instants under ONE ``rid``."""
    from dlbb_tpu.obs import spans

    trace = _trace([
        Request(rid=i, arrival_s=0.0, prompt_len=10 + 7 * i,
                output_len=5 + i, seed=70 + i)
        for i in range(5)
    ])
    span_path = tmp_path / "spans.json"
    with spans.tracing(span_path):
        report = fast_engine.run_trace(trace)
    assert report["requests"]["completed"] == 5
    events = spans.load_trace(span_path)["traceEvents"]
    assert spans.validate_trace_events(events) == []

    stack, parents = [], {}      # one scheduler thread: a plain stack
    for ev in events:
        if ev["ph"] == "B":
            parents.setdefault(ev["name"], []).append(
                [name for name, _a in stack])
            stack.append((ev["name"], ev.get("args", {})))
        elif ev["ph"] == "E":
            stack.pop()
    steps = ("serve-admit-plan", "serve-admit-embed",
             "serve-admit-inject", "serve-admit-book")
    for step in steps:
        assert len(parents[step]) == 5, step
        assert all("serve-admission" in above
                   for above in parents[step]), step
    begins = [e for e in events if e["ph"] == "B"]
    for ev in begins:
        assert not ev["name"].startswith(("bench-", "step-"))
        if ev["name"].startswith(("serve-admit-", "serve-prefill")):
            assert "rid" in ev["args"], ev["name"]
        if ev["name"].startswith("serve-admit-"):
            assert "slot" in ev["args"], ev["name"]
    assert all(above[-1:] == ["serve-decode"]
               for above in parents["serve-decode-dispatch"])
    assert len(parents["serve-decode-dispatch"]) == \
        len(parents["serve-decode"]) == len(parents["serve-decode-plan"])
    # every dispatched unit is waited for exactly once, inside the
    # decode span at a window boundary or under a drain
    assert len(parents["serve-decode-sync"]) == report["decode_units"]
    assert parents["serve-drain"]
    syncs = [e for e in begins if e["name"] == "serve-decode-sync"]
    assert all(e["args"]["k"] >= 1 for e in syncs)

    by_rid = {}
    for ev in events:
        if ev["ph"] == "i" and ev.get("cat") == "request":
            by_rid.setdefault(ev["args"]["rid"], []).append(ev["name"])
    assert sorted(by_rid) == [0, 1, 2, 3, 4]
    for rid, names in by_rid.items():
        assert names == ["request-arrived", "request-admitted",
                         "request-prefill", "request-completed"], rid


def test_event_emits_nothing_without_a_tracer(baseline_engine):
    """No tracer: ``_event`` is a global load and the span entry point
    still hands back the one shared null context."""
    from dlbb_tpu.obs import spans

    assert spans.active() is None
    assert spans.span("serve-admit-plan", rid=0, slot=0) is \
        spans.span("serve-decode")
    baseline_engine._event("request-arrived", 0)     # must not raise
    assert spans.active() is None


# ---------------------------------------------------------------------------
# report writers
# ---------------------------------------------------------------------------


def test_serving_report_shed_columns(tmp_path):
    from dlbb_tpu.stats.serving_report import write_serving_report
    from dlbb_tpu.utils.config import save_json

    fake = {
        "schema": "dlbb_serving_report_v1",
        "trace": {"kind": "poisson", "num_requests": 10},
        "requests": {"arrived": 10, "completed": 8, "rejected": 2,
                     "shed_rate": 0.2,
                     "rejected_detail": [
                         {"rid": 4, "reason": "queue-full",
                          "queue_depth": 3, "queue_wait_s": 0.05},
                         {"rid": 7, "reason": "queue-full",
                          "queue_depth": 3, "queue_wait_s": 0.15},
                     ]},
        "mesh": {"dp": 2, "tp": 4},
        "serving": {"max_batch": 8, "block_size": 16, "max_seq": 256},
        "fast_path": {"fused_steps": 64, "prefill_chunks": 5},
        "goodput_tokens_per_s": 100.0,
        "ttft": {"median": 0.01, "p99": 0.02, "p999": 0.03},
        "per_token_latency": {"median": 0.001, "p99": 0.002,
                              "p999": 0.003},
        "cache": {"peak_blocks_in_use": 12},
        "timeseries": {"queue_depth": [0, 3]},
        "decode_steps": 42,
        "wall_seconds": 1.5,
    }
    results = tmp_path / "results"
    save_json(fake, results / "serving_fastrun.json")
    rows = write_serving_report(results, tmp_path / "stats")
    assert len(rows) == 1
    row = rows[0]
    assert row["shed_rate"] == 0.2
    assert row["rej_queue_wait_ms"] == 100.0  # mean of 50 and 150
    assert row["fused_steps"] == 64
    md = (tmp_path / "stats" / "SERVING.md").read_text()
    assert "20%" in md and "100.0" in md


def test_fastpath_report_writer(tmp_path):
    from dlbb_tpu.stats.serving_report import write_fastpath_report
    from dlbb_tpu.utils.config import save_json

    bench = {
        "schema": "dlbb_bench_serve_v1",
        "baseline": "per_step",
        "settings": {
            "per_step": {
                "decode_horizon": 1,
                "output_tokens_per_s": {"median": 100.0, "min": 95.0,
                                        "max": 105.0},
                "per_token_p50_ms": 10.0, "decode_units": 200,
            },
            "fused_k16": {
                "decode_horizon": 16,
                "output_tokens_per_s": {"median": 250.0, "min": 240.0,
                                        "max": 260.0},
                "per_token_p50_ms": 4.0, "decode_units": 20,
            },
        },
    }
    path = tmp_path / "BENCH_serve.json"
    save_json(bench, path)
    rows = write_fastpath_report(path, tmp_path / "stats")
    assert len(rows) == 2
    by_name = {r["setting"]: r for r in rows}
    assert by_name["fused_k16"]["speedup_vs_baseline"] == 2.5
    assert by_name["per_step"]["speedup_vs_baseline"] == 1.0
    md = (tmp_path / "stats" / "FASTPATH.md").read_text()
    assert "2.50x" in md and "fused_k16" in md
    # missing artifact: no rows, nothing clobbered
    assert write_fastpath_report(tmp_path / "nope.json",
                                 tmp_path / "stats2") == []
