"""The launch sequence of the serving engine (``TraceRun._launch``,
``docs/observability.md`` §1): every call of a jitted serving program on
the scheduler's path is a numbered span, every wait of the scheduler
thread for a device value names the launch it waits for, and with no
tracer the helper costs a count and the shared null context.  Since
PR 36 a single decode step is waited for only before the next program
that donates the whole carry: a prompt chunk goes out behind it.
"""

from __future__ import annotations

import re

import pytest

import test_obs
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.obs import spans
from dlbb_tpu.resilience.preempt import PreemptionGuard
from dlbb_tpu.serve import run as run_module
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.lookahead import InputLookahead
from dlbb_tpu.serve.traffic import Request, TrafficTrace

GPT = ModelConfig(hidden_size=64, num_layers=2, num_heads=4,
                  ffn_intermediate=128, dtype="float32", attention="full")
HYBRID = ModelConfig.from_dict(dict(
    hidden_size=64, num_layers=4, num_heads=4, ffn_intermediate=96,
    dtype="float32", norm="rmsnorm", mlp="swiglu", bias=False, qk_norm=True,
    vocab_size=256,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True))
SERVE = dict(max_batch=8, block_size=8, max_seq=64, hbm_budget_gb=None)
# chunked prefill, fused scans, two units in flight: the cells' path
FAST = dict(decode_horizon=16, inflight_window=2, prefill_chunk=8)
# (model, envelope, probed requests); ``gpt-ngram`` is the token-feedback
# path of ``cli serve --speculation ngram``: a bucketed prefill, the
# greedy inject whose token the host reads, draft-and-verify units
ENGINES = {
    "gpt": (GPT, FAST, ()),
    "hybrid": (HYBRID, FAST, (0, 3)),
    "gpt-ngram": (GPT, dict(speculation="ngram", spec_gamma=4,
                            decode_horizon=16), ()),
}
LAUNCH_SPANS = ("serve-launch", "serve-decode-dispatch",
                "serve-prefill-chunk", "serve-prefix-attach")
SYNC = re.compile(r"^serve-[a-z]+-sync$")

_BUILT: dict = {}


def _engine(kind, mesh):
    if kind not in _BUILT:
        model, envelope, probed = ENGINES[kind]
        _BUILT[kind] = ServingEngine(
            model, ServingConfig(**SERVE, **envelope), mesh, verbose=False,
            capture_tokens=True)
        if probed:
            _BUILT[kind].probe(probed)
    return _BUILT[kind]


def _trace(n=5):
    """All due at once and fewer than the slots: once the last is
    admitted nothing waits, so the decode units fuse and two stay in
    flight; the prompts take one to four chunks of 8."""
    return TrafficTrace(kind="test", seed=0, params={}, requests=tuple(
        Request(rid=i, arrival_s=0.0, prompt_len=6 + 6 * i,
                output_len=20 + 3 * i, seed=40 + i, prompt_period=3)
        for i in range(n)))


def _backlog(n=14):
    """More requests than the 8 slots, all due at once.  The first
    eight fill the slots with one-chunk prompts, two of them one token
    from their end: the loop's first unit is a single step that
    completes them, and the admissions of the six that waited (prompts
    of two to four chunks) begin with that step still in flight."""
    return TrafficTrace(kind="test", seed=0, params={}, requests=tuple(
        Request(rid=i, arrival_s=0.0,
                prompt_len=6 if i < 8 else 6 + 5 * (i % 6),
                output_len=2 + 3 * (i % 4) if i < 8 else 5 + 2 * (i % 5),
                seed=40 + i, prompt_period=3)
        for i in range(n)))


TRACES = {"few": _trace, "backlog": _backlog}
# the programs of ``_trace()`` by launch number, as the tree before
# PR 36 launched them: ``c<offset>`` a prompt chunk, ``s`` a single
# decode step, ``i`` the inject, ``p`` a probed slot's state copied,
# ``k<K>`` a fused scan; a bar where an admission ends
SEQUENCES = {
    "gpt": "c0 i | c0 s c8 i | c0 s c8 s c16 i | c0 s c8 s c16 i | "
           "c0 s c8 s c16 s c24 i | k16 k8 k4 k2 s",
    "hybrid": "c0 i p | c0 s c8 i | c0 s c8 s c16 i | c0 s c8 s c16 i p | "
              "c0 s c8 s c16 s c24 i | k16 p k8 k4 p k2 s",
}
# every jit built with the whole carry as its donated argument 0
DONATES_CARRY = re.compile(r"^jit_serve_(decode|inject|spec_)")


def _programs(sequence):
    names = {"s": "jit_serve_decode_step", "i": "jit_serve_inject",
             "p": "jit_serve_probe_state"}
    return [names.get(t) or (f"jit_serve_prefill_chunk_o{t[1:]}"
                             if t[0] == "c" else f"jit_serve_decode_{t}")
            for t in sequence.replace("|", " ").split()]


def _compiled(engine) -> set[str]:
    """The names, as a profile prints them, of every program the engine
    holds a jit of."""
    programs = [engine._decode, engine._inject,
                engine._family.slot_state if engine._probes else None,
                engine._decode_token,
                getattr(engine, "_inject_greedy", None),
                *engine._decode_fused.values(),
                *engine._decode_fused_token.values(),
                *engine._prefill_jits.values(),
                *engine._prefill_chunk_jits.values(),
                *engine._verify.values()]
    return {f"jit_{p.__name__}" for p in programs if p is not None}


def _begins(engine, path, trace=None):
    trace = trace or _trace()
    with spans.tracing(path):
        report = engine.run_trace(trace)
    events = spans.load_trace(path)["traceEvents"]
    assert spans.validate_trace_events(events) == []
    assert report["requests"]["completed"] == len(trace)
    return report, [ev for ev in events if ev["ph"] == "B"]


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_launches_are_one_sequence_without_hole_or_repeat(
        kind, mesh2x4, tmp_path):
    engine = _engine(kind, mesh2x4)
    report, begins = _begins(engine, tmp_path / "spans.json")
    launches = [ev for ev in begins if "program" in ev.get("args", {})]
    numbers = [ev["args"]["launch"] for ev in launches]
    # in the file's order, which is the scheduler thread's
    assert numbers == list(range(len(numbers)))
    assert len(numbers) == report["launches"] > report["decode_units"]
    assert {ev["name"] for ev in launches} <= set(LAUNCH_SPANS)
    programs = {ev["args"]["program"] for ev in launches}
    assert programs <= _compiled(engine), programs - _compiled(engine)
    assert any(p.startswith("jit_serve_inject") for p in programs)
    if engine._probes:
        assert "jit_serve_probe_state" in programs
    # the arguments four readers use are what they were
    for ev in launches:
        if ev["name"] == "serve-decode-dispatch":
            assert ev["args"]["k"] >= 1
        if ev["name"] == "serve-prefill-chunk":
            assert {"rid", "chunk", "seq"} <= set(ev["args"])


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_every_wait_names_a_launch_made_before_it(kind, mesh2x4, tmp_path):
    engine = _engine(kind, mesh2x4)
    report, begins = _begins(engine, tmp_path / "spans.json")
    program_of: dict[int, str] = {}
    span_of: dict[int, str] = {}
    waits = []
    older_than_newest = 0
    for ev in begins:
        args = ev.get("args", {})
        if "program" in args:
            program_of[args["launch"]] = args["program"]
            span_of[args["launch"]] = ev["name"]
        elif SYNC.match(ev["name"]):
            assert args["launch"] in program_of, ev
            waits.append(ev)
            older_than_newest += args["launch"] < max(program_of)
    names = {ev["name"] for ev in waits}
    assert "serve-decode-sync" in names and "serve-prefill-sync" in names
    decode_waits = [ev for ev in waits if ev["name"] == "serve-decode-sync"]
    # every unit is waited for once, and the wait names a decode or
    # verify program's launch with that unit's ``k``
    assert len(decode_waits) == report["decode_units"]
    for ev in decode_waits:
        program = program_of[ev["args"]["launch"]]
        assert re.match(r"jit_serve_(decode|spec_verify)_", program), program
        assert ev["args"]["k"] >= 1
    for ev in waits:
        if ev["name"] == "serve-prefill-sync":
            assert program_of[ev["args"]["launch"]].startswith(
                "jit_serve_prefill"), ev
            assert "rid" in ev["args"]
    if kind == "gpt-ngram":
        # the host reads the greedy inject's token: a wait of its own
        injects = [ev for ev in waits if ev["name"] == "serve-inject-sync"]
        assert len(injects) == 5
        assert {program_of[ev["args"]["launch"]] for ev in injects} == \
            {"jit_serve_inject_greedy"}
    else:
        assert report["fast_path"]["fused_scans"] > 0
        # two units in flight: some wait is for an older launch than the
        # newest
        assert older_than_newest > 0
        assert all(span_of[ev["args"]["launch"]] == "serve-decode-dispatch"
                   for ev in decode_waits)


@pytest.mark.parametrize("kind", sorted(SEQUENCES))
def test_interleaved_step_is_waited_for_behind_the_next_chunk(
        kind, mesh2x4, tmp_path):
    """Chunk c, step c, chunk c + 1, step c + 1: the programs go out in
    the order they always did, and the wait for step c begins after the
    call of chunk c + 1 and ends before step c + 1 (or the inject) is
    called."""
    engine = _engine(kind, mesh2x4)
    report, begins = _begins(engine, tmp_path / "spans.json")
    at = {id(ev): i for i, ev in enumerate(begins)}
    launches = [ev for ev in begins if "program" in ev.get("args", {})]
    assert [ev["args"]["program"] for ev in launches] == \
        _programs(SEQUENCES[kind])
    wait_of = {ev["args"]["launch"]: at[id(ev)] for ev in begins
               if ev["name"] == "serve-decode-sync"}
    interleaved = 0
    for step, chunk, after in zip(launches, launches[1:], launches[2:]):
        if (step["name"], chunk["name"]) != (
                "serve-decode-dispatch", "serve-prefill-chunk"):
            continue
        interleaved += 1
        assert step["args"]["k"] == 1
        assert at[id(chunk)] < wait_of[step["args"]["launch"]] < \
            at[id(after)], step
    # one a non-final chunk dispatched with slots resident
    assert interleaved == SEQUENCES[kind].count("s c") == 8
    assert report["decode_units_overlapped"] == interleaved


@pytest.mark.parametrize("kind,traffic", [
    ("gpt", "few"), ("hybrid", "few"), ("gpt-ngram", "few"),
    ("gpt", "backlog"), ("hybrid", "backlog"), ("gpt-ngram", "backlog")])
def test_no_carry_is_donated_under_a_single_step_in_flight(
        kind, traffic, mesh2x4, tmp_path):
    """The CPU honours no donation, so a held ``ys`` deleted by a later
    launch would pass here unseen: the spans show instead that between
    the call of a single step and the wait for it no program is called
    that donates the whole carry (a decode or verify program, an
    inject).  The units so overlapped are what the report and the
    registry count."""
    engine = _engine(kind, mesh2x4)
    counted = engine.registry.get("serve_decode_units_overlapped")
    report, begins = _begins(engine, tmp_path / "spans.json",
                             TRACES[traffic]())
    single = None             # launch of the single step not waited for
    behind = 0                # launches made since it was called
    overlapped = heads = 0
    at_head = False           # an admission began with it in flight
    for ev in begins:
        args = ev.get("args", {})
        if "program" in args:
            if single is not None:
                assert not DONATES_CARRY.match(args["program"]), (single, ev)
                behind += 1
            if ev["name"] == "serve-decode-dispatch" and args["k"] == 1:
                single, behind, at_head = args["launch"], 0, False
        elif ev["name"] == "serve-admission":
            at_head = single is not None
        elif ev["name"] == "serve-decode-sync" and \
                args["launch"] == single:
            overlapped += behind > 0
            heads += at_head and behind > 0
            single = None
    assert single is None
    assert report["decode_units_overlapped"] == overlapped
    assert engine.registry.get("serve_decode_units_overlapped") == \
        counted + overlapped
    if engine.serving.prefill_chunk is None:
        # a bucketed prefill drains at the admission's head
        assert overlapped == 0
    elif traffic == "backlog":
        # the interleaved steps, and the loop's own step where an
        # admission follows a completion: its first chunk goes out
        # behind that step
        assert 0 < heads < overlapped <= report["fast_path"]["single_steps"]


def test_without_a_tracer_the_helper_takes_the_null_path_and_still_counts(
        mesh2x4, monkeypatch):
    """Off means off: the pin of ``tests/test_obs.py`` holds, and on top
    of it the helper and the waits ask ``spans.span`` for a name alone,
    no argument built, and get the one shared null context; the report
    counts the launches all the same."""
    test_obs.test_disabled_span_is_shared_singleton()
    engine = _engine("gpt", mesh2x4)
    null = spans.span("anything")
    asked = []
    real = spans.span

    def recording(name, cat="harness", **args):
        got = real(name, cat, **args)
        asked.append((name, args, got))
        return got

    monkeypatch.setattr(run_module.spans, "span", recording)
    report = engine.run_trace(_trace())
    mine = [(name, args, got) for name, args, got in asked
            if name in LAUNCH_SPANS or SYNC.match(name)]
    assert len([m for m in mine if m[0] in LAUNCH_SPANS]) == \
        report["launches"] > 0
    assert all(args == {} and got is null for _n, args, got in mine)
    assert {name for name, _a, _g in mine} >= {
        "serve-launch", "serve-decode-dispatch", "serve-prefill-chunk",
        "serve-decode-sync", "serve-prefill-sync", "serve-inject-sync"}
    # the same trace traced launches as often
    assert spans.active() is None


def test_the_helper_numbers_any_call_and_runs_it_under_a_guard(mesh2x4):
    engine = _engine("gpt", mesh2x4)
    run = run_module.TraceRun(engine, _trace(), PreemptionGuard(),
                              InputLookahead(engine._request_input))

    def serve_toy(a, b):
        return a + b

    guarded = []

    def guard(call):
        guarded.append(True)
        return call()

    assert run._launch(serve_toy, 1, 2) == 3
    assert run._launch(serve_toy, 3, 4, span="serve-decode-dispatch",
                       fields=lambda: {"k": 1}, via=guard) == 7
    assert run.stats.launches == 2 and guarded == [True]


def test_traced_and_untraced_runs_launch_alike_and_compile_alike(
        mesh2x4, tmp_path):
    """The launch number never reaches a traced value: a traced run
    launches as many programs as an untraced one and compiles no more
    (a run builds its fresh cache in a jit of its own, nothing else)."""
    import jax

    engine = _engine("hybrid", mesh2x4)
    engine.run_trace(_trace())
    compiled = []

    def listener(event, _secs, **_kw):
        if event.endswith("backend_compile_duration"):
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        plain = engine.run_trace(_trace())
        untraced = len(compiled)
        with spans.tracing(tmp_path / "spans.json"):
            traced = engine.run_trace(_trace())
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert traced["launches"] == plain["launches"]
    assert traced["completed_tokens"] == plain["completed_tokens"]
    assert len(compiled) - untraced == untraced
