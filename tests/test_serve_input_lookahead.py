"""Input look-ahead (``serve/lookahead.py``, ``docs/serving.md``): the
scheduler prepares the inputs of queued requests on one worker thread
while the device works, and takes them at admission.

What is pinned, over both block families and both prefill paths (the
hybrid has no monolithic prefill): the same inputs and tokens as the
inline path, bit for bit; nothing prepared for a request that has not
arrived or before the clock starts; a bounded number outstanding; every
way out of the queue drops what was prepared; a worker's exception fails
its request alone; no thread outlives a run; the span file stays one
thread's; and the counters add up to the admissions.
"""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness.feed import DueFeed, Observer          # noqa: E402
from dlbb_tpu.comm.mesh import build_parallelism_mesh          # noqa: E402
from dlbb_tpu.models.configs import ModelConfig                # noqa: E402
from dlbb_tpu.obs import spans                                 # noqa: E402
from dlbb_tpu.resilience import inject                         # noqa: E402
from dlbb_tpu.serve import engine as engine_module             # noqa: E402
from dlbb_tpu.serve import lookahead as lookahead_module       # noqa: E402
from dlbb_tpu.serve.config import ServingConfig                # noqa: E402
from dlbb_tpu.serve.engine import ServingEngine                # noqa: E402
from dlbb_tpu.serve.lookahead import InputLookahead            # noqa: E402
from dlbb_tpu.serve.traffic import Request, TrafficTrace       # noqa: E402

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
# two slots, so that most of a trace waits in the queue
ENVELOPE = dict(max_batch=2, block_size=8, max_seq=64, queue_capacity=64,
                hbm_budget_gb=None, retry_backoff_s=0.01)
PATHS = {
    "gpt-chunked": (GPT, dict(prefill_chunk=8)),
    "gpt-monolithic": (GPT, dict()),
    "hybrid-chunked": (HYBRID, dict(prefill_chunk=16)),
}
# prompts of one to four chunks, ending inside and on a chunk's edge
LENGTHS = [(20, 4), (9, 3), (17, 5), (8, 3), (23, 4), (12, 6), (30, 3)]
WORKER = "serve-input"

every_path = pytest.mark.parametrize("path", list(PATHS))
chunked_paths = pytest.mark.parametrize(
    "path", [p for p, (_m, knobs) in PATHS.items() if knobs])

_ENGINES: dict = {}


def _engine(path: str) -> ServingEngine:
    """One engine a path for the whole file: a ``run_trace`` starts from
    a fresh cache, and building one compiles its programs anew."""
    if path not in _ENGINES:
        model, knobs = PATHS[path]
        mesh = build_parallelism_mesh(1, 1, 1, 1, 1,
                                      devices=jax.devices()[:1])
        _ENGINES[path] = ServingEngine(
            model, ServingConfig(**ENVELOPE, **knobs), mesh, seed=3,
            verbose=False, capture_tokens=True)
    return _ENGINES[path]


def _trace(lengths=LENGTHS, gap_s=0.0, **fields) -> TrafficTrace:
    return TrafficTrace(kind="test", seed=0, params={}, requests=tuple(
        Request(rid=i, arrival_s=gap_s * i, prompt_len=p, output_len=o,
                seed=100 + i, **fields)
        for i, (p, o) in enumerate(lengths)))


def _workers() -> list[str]:
    return [t.name for t in threading.enumerate()
            if t.name.startswith(WORKER)]


class Watch:
    """Everything a test may look at: each input the family made (by
    rid: the array, the thread that made it, what ``before`` said at the
    time), and each run's look-ahead with the most it ever held and the
    requests it dropped."""

    def __init__(self, monkeypatch, engine, before=None, fail_rid=None,
                 slow_s=0.0):
        self.inputs: dict[int, list[np.ndarray]] = {}
        self.threads: dict[int, list[str]] = {}
        self.before: dict[int, list] = {}
        self.lookaheads: list[InputLookahead] = []
        family = engine._family
        make = family.prompt_input
        watch = self

        def prompt_input(config, req, pad_to, dtype):
            if req.rid < 0:                       # the warm-up's dummy
                return make(config, req, pad_to, dtype)
            if before is not None:
                watch.before.setdefault(req.rid, []).append(before(req))
            watch.threads.setdefault(req.rid, []).append(
                threading.current_thread().name)
            if req.rid == fail_rid:
                raise ValueError(f"no input for request {req.rid}")
            time.sleep(slow_s)
            x = make(config, req, pad_to, dtype)
            watch.inputs.setdefault(req.rid, []).append(np.asarray(x))
            return x

        class Watched(InputLookahead):
            def __init__(self, prepare):
                super().__init__(prepare)
                self.peak, self.dropped = 0, []
                watch.lookaheads.append(self)

            def top_up(self, queue):
                super().top_up(queue)
                self.peak = max(self.peak, len(self._ahead))
                head = [r.rid for r in queue][
                    :lookahead_module.INPUT_LOOKAHEAD]
                assert set(self._ahead) <= set(head)

            def drop(self, rid):
                self.dropped.append(rid)
                super().drop(rid)

        monkeypatch.setattr(family, "prompt_input", prompt_input)
        monkeypatch.setattr(engine_module, "InputLookahead", Watched)

    def left_nothing_behind(self) -> bool:
        return (not _workers()
                and all(not la._ahead and la._pool is None
                        for la in self.lookaheads))


def _baseline(path: str) -> dict:
    """The unfaulted run's report, once a path."""
    key = ("baseline", path)
    if key not in _ENGINES:
        _ENGINES[key] = _engine(path).run_trace(_trace())
        assert _ENGINES[key]["requests"]["completed"] == len(LENGTHS)
    return _ENGINES[key]


# -- (1) the same inputs, tokens and outcomes as the inline path -------------


@every_path
def test_lookahead_serves_what_the_inline_path_serves_bit_for_bit(
        path, monkeypatch):
    engine = _engine(path)
    with monkeypatch.context() as patch:
        ahead = Watch(patch, engine)
        with_lookahead = engine.run_trace(_trace())
    with monkeypatch.context() as patch:
        patch.setattr(lookahead_module, "INPUT_LOOKAHEAD", 0)
        inline = Watch(patch, engine)
        without = engine.run_trace(_trace())

    assert with_lookahead["completed_tokens"] == without["completed_tokens"]
    assert (with_lookahead["requests"]["outcomes"]
            == without["requests"]["outcomes"])
    assert with_lookahead["requests"]["completed"] == len(LENGTHS)
    # each request's input was made once, and is the parent's array:
    # the family's own function on the same arguments
    model = engine.config
    for req in _trace():
        (a,), (b,) = ahead.inputs[req.rid], inline.inputs[req.rid]
        direct = np.asarray(engine._family.prompt_input(
            model, req, engine._padded_len(req), engine._dtype))
        for x in (a, b):
            assert x.dtype == direct.dtype and x.shape == direct.shape
            assert x.tobytes() == direct.tobytes()
    # the look-ahead did engage, and the inline run is the parent's path
    made_on = {name for names in ahead.threads.values() for name in names}
    assert any(name.startswith(WORKER) for name in made_on)
    assert not any(name.startswith(WORKER)
                   for names in inline.threads.values() for name in names)
    assert without["input_ready_share"] == 0.0
    assert ahead.left_nothing_behind() and inline.left_nothing_behind()


# -- (2) only requests that have arrived, and only after the clock starts ----


@every_path
def test_nothing_is_prepared_before_a_request_arrives_or_the_clock_starts(
        path, monkeypatch):
    engine = _engine(path)
    _baseline(path)                  # compiled: the run below is all serving
    observer = Observer()

    def before(req):
        admitted = observer.at.get("request-admitted", {}).get(req.rid)
        return observer.t0 is not None, admitted, observer.now()

    watch = Watch(monkeypatch, engine, before=before)
    trace = _trace(LENGTHS + LENGTHS[:3], gap_s=0.03)
    feed = DueFeed(trace.requests, observer.now)
    report = engine.run_trace(trace, feed=feed, control=observer)
    assert report["requests"]["completed"] == len(trace)
    assert sorted(watch.before) == [r.rid for r in trace]
    for rid, seen in watch.before.items():
        for clock_started, admitted_at, now in seen:
            assert clock_started, rid
            assert admitted_at is not None and admitted_at <= now, rid
            assert admitted_at >= trace.requests[rid].arrival_s, rid
    assert watch.left_nothing_behind()


# -- (3) bounded ---------------------------------------------------------------


@every_path
def test_never_more_prepared_inputs_outstanding_than_the_constant(
        path, monkeypatch):
    engine = _engine(path)
    watch = Watch(monkeypatch, engine)
    report = engine.run_trace(_trace(LENGTHS * 2))
    assert report["requests"]["completed"] == 2 * len(LENGTHS)
    (la,) = watch.lookaheads
    assert 2 <= lookahead_module.INPUT_LOOKAHEAD <= 4
    assert 1 <= la.peak <= lookahead_module.INPUT_LOOKAHEAD
    assert not hasattr(engine.serving, "input_lookahead")


# -- (4) every other way out of the queue, and every retry --------------------


class _Cancels(Observer):
    """A control plane that cancels ``rid`` at the loop's second turn,
    when it waits in the queue with its input handed to the worker."""

    def __init__(self, rid: int) -> None:
        super().__init__()
        self._rid, self._turn = rid, 0

    def take_cancels(self):
        self._turn += 1
        return ((self._rid, "test"),) if self._turn == 2 else ()


@every_path
def test_a_canceled_request_has_its_prepared_input_dropped(
        path, monkeypatch):
    engine, base = _engine(path), _baseline(path)
    watch = Watch(monkeypatch, engine)
    report = engine.run_trace(_trace(), control=_Cancels(3))
    outcomes = report["requests"]["outcomes"]
    assert outcomes.pop("3") == "canceled[test]"
    assert set(outcomes.values()) == {"completed"}
    (la,) = watch.lookaheads
    assert la.dropped == [3] and "3" not in report["completed_tokens"]
    for rid in outcomes:
        assert report["completed_tokens"][rid] == \
            base["completed_tokens"][rid]
    assert watch.left_nothing_behind()


@every_path
def test_a_shed_queue_head_has_its_prepared_input_dropped(
        path, monkeypatch):
    engine = _engine(path)
    # all due at once under a 20 ms deadline, an input taking 30 ms: the
    # first two are admitted within microseconds of arriving, the heads
    # left behind are looked at again after those prefills and shed,
    # three of them with an input made or in the making
    watch = Watch(monkeypatch, engine, slow_s=0.03)
    report = engine.run_trace(_trace(deadline_s=0.02))
    req = report["requests"]
    assert req["completed"] == 2 and req["deadline_shed"] == 5
    shed = {d["rid"] for d in req["rejected_detail"]}
    (la,) = watch.lookaheads
    assert set(la.dropped) == shed
    assert not shed & set(report["completed_tokens"])
    assert watch.left_nothing_behind()


@every_path
def test_a_retried_prefill_reuses_the_prepared_input(path, monkeypatch):
    engine, base = _engine(path), _baseline(path)
    watch = Watch(monkeypatch, engine)
    with inject.plan_scope("serve-prefill-fail:@3"):
        report = engine.run_trace(_trace())
    assert report["requests"]["completed"] == len(LENGTHS)
    assert report["resilience"]["retries"] == 1
    assert report["completed_tokens"] == base["completed_tokens"]
    # one input a request, the retried one too
    assert all(len(made) == 1 for made in watch.inputs.values())
    assert watch.left_nothing_behind()


@chunked_paths
def test_a_carry_reset_mid_prefill_restarts_over_the_same_input(
        path, monkeypatch):
    """The resident batch fails for real while a prompt's chunks
    interleave with it: the carry is replaced, the prefill starts again
    from its first chunk, over the input it already holds.  (No
    interleave without chunks: the monolithic path has nothing to reset
    mid-prefill.)"""
    engine = _engine(path)
    watch = Watch(monkeypatch, engine)
    decode, calls = engine._decode, []

    def failing_once(carry, params, active):
        if np.asarray(active).any():         # not the warm-up's step
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("the device lost the step")
        return decode(carry, params, active)

    monkeypatch.setattr(engine, "_decode", failing_once)
    # request 0 (one chunk with the hybrid's, three with the GPT's) is
    # resident when request 1's prefill interleaves the first decode
    report = engine.run_trace(_trace([(8, 6), (40, 4), (17, 5), (9, 3)]))
    outcomes = report["requests"]["outcomes"]
    assert outcomes.pop("0") == "failed[dispatch-failed]"
    assert set(outcomes.values()) == {"completed"}
    assert report["resilience"]["retries"] >= 1       # the restart
    assert all(len(made) == 1 for made in watch.inputs.values())
    # and what it served is what an unfaulted engine serves
    monkeypatch.setattr(engine, "_decode", decode)
    clean = engine.run_trace(_trace([(8, 6), (40, 4), (17, 5), (9, 3)]))
    for rid in outcomes:
        assert report["completed_tokens"][rid] == \
            clean["completed_tokens"][rid]
    assert watch.left_nothing_behind()


# -- (5) a worker's exception ---------------------------------------------------


@every_path
def test_a_worker_exception_fails_that_request_alone(path, monkeypatch):
    engine, base = _engine(path), _baseline(path)
    watch = Watch(monkeypatch, engine, fail_rid=4)
    report = engine.run_trace(_trace())
    outcomes = report["requests"]["outcomes"]
    assert outcomes.pop("4") == "failed[dispatch-failed]"
    # the residents at the time lived: the cache was never touched
    assert set(outcomes.values()) == {"completed"}
    (failure,) = report["resilience"]["failed"]
    assert failure["rids"] == [4]
    assert "no input for request 4" in failure["error"]
    assert any(name.startswith(WORKER) for name in watch.threads[4])
    for rid in outcomes:
        assert report["completed_tokens"][rid] == \
            base["completed_tokens"][rid]
    assert watch.left_nothing_behind()


# -- (6) lifetime ---------------------------------------------------------------


class _Dies(Observer):
    def __init__(self) -> None:
        super().__init__()
        self._turn = 0

    def check(self) -> None:
        self._turn += 1
        if self._turn == 3:
            raise RuntimeError("replica killed")


@every_path
@pytest.mark.parametrize("way_out", ["returns", "preempts", "raises"])
def test_no_thread_outlives_a_run_and_the_next_run_starts_clean(
        path, way_out, monkeypatch):
    engine, base = _engine(path), _baseline(path)
    watch = Watch(monkeypatch, engine)
    assert not _workers()
    if way_out == "returns":
        report = engine.run_trace(_trace())
        assert report["requests"]["completed"] == len(LENGTHS)
    elif way_out == "preempts":
        with inject.plan_scope("serve-preempt:@3"):
            report = engine.run_trace(_trace())
        assert report["preempted"] and report["remaining_rids"]
    else:
        with pytest.raises(RuntimeError, match="replica killed"):
            engine.run_trace(_trace(), control=_Dies())
    assert watch.left_nothing_behind()
    # the worker did run in the run that ended early
    assert any(name.startswith(WORKER)
               for names in watch.threads.values() for name in names)
    again = engine.run_trace(_trace())
    assert again["completed_tokens"] == base["completed_tokens"]
    assert len(watch.lookaheads) == 2 and watch.left_nothing_behind()


# -- (7) the span file is one thread's -------------------------------------------


@every_path
def test_the_worker_emits_no_span_and_the_take_keeps_its_own(
        path, monkeypatch, tmp_path):
    engine = _engine(path)
    watch = Watch(monkeypatch, engine)
    span_path = tmp_path / "spans.json"
    with spans.tracing(span_path):
        report = engine.run_trace(_trace())
    assert report["requests"]["completed"] == len(LENGTHS)
    assert any(name.startswith(WORKER)
               for names in watch.threads.values() for name in names)
    events = spans.load_trace(span_path)["traceEvents"]
    assert spans.validate_trace_events(events) == []
    assert len({ev["tid"] for ev in events if "tid" in ev}) == 1
    stack, embeds = [], []
    for ev in events:
        if ev["ph"] == "B":
            if ev["name"] == "serve-admit-embed":
                embeds.append((ev["args"], [name for name in stack]))
            stack.append(ev["name"])
        elif ev["ph"] == "E":
            stack.pop()
    # once an admission, with the request and its slot, under the loop
    assert sorted(args["rid"] for args, _above in embeds) == \
        [r.rid for r in _trace()]
    assert all("slot" in args and above[-1:] == ["serve-admission"]
               for args, above in embeds)


# -- (8) the counters -------------------------------------------------------------


@every_path
def test_ready_and_waited_add_up_to_the_admissions(path, monkeypatch):
    engine = _engine(path)
    names = ("serve_input_ready", "serve_input_waited",
             "serve_input_wait_seconds")
    base = {name: engine.registry.get(name) for name in names}
    report = engine.run_trace(_trace())
    ready, waited, wait_s = (engine.registry.get(name) - base[name]
                             for name in names)
    admissions = report["requests"]["completed"]
    assert admissions == len(LENGTHS)
    assert ready + waited == admissions
    assert waited >= 1 and wait_s > 0.0     # the first admission's, inline
    assert report["input_ready_share"] == pytest.approx(ready / admissions)
    prom = engine.registry.to_prometheus()
    for name in names:
        assert f"dlbb_{name}_total " in prom


# -- the hand-over itself, without an engine --------------------------------------


def test_every_take_gets_its_own_request_under_a_short_switch_interval():
    """The scheduler's side alone touches the table of prepared inputs;
    a future is the only thing both threads hold.  Many admissions,
    drops and inline takes with the interpreter switching threads every
    few microseconds: each take returns its own request's input, and
    every take is counted once."""
    import random
    from collections import deque

    def prepare(req):
        time.sleep(random.random() * 2e-4)
        return ("input", req.rid)

    requests = _trace([(8, 2)] * 600).requests
    queue, taken, dropped = deque(requests), 0, 0
    rng = random.Random(7)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    deadline = time.monotonic() + 60.0
    try:
        with InputLookahead(prepare) as la:
            while queue:
                assert time.monotonic() < deadline
                la.top_up(queue)
                assert len(la._ahead) <= lookahead_module.INPUT_LOOKAHEAD
                req = queue.popleft()
                if rng.random() < 0.2:
                    la.drop(req.rid)
                    dropped += 1
                else:
                    assert la.take(req) == ("input", req.rid)
                    taken += 1
            assert la.ready + la.waited == taken and la.ready > 0
            assert taken + dropped == len(requests)
    finally:
        sys.setswitchinterval(interval)
    assert not la._ahead and la._pool is None and not _workers()
