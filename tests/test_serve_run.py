"""The phases of one serving run (``serve/run.py``), each driven alone on
a run that compiled nothing: arrivals into the bounded queue, the
deadline shed at its head, and a supervisor's cancel of a queued
request.  Building a ``TraceRun`` touches no program and starts no
clock, so each phase is a host step with its outcomes, counters and
journal events in hand."""

from __future__ import annotations

import pytest

from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.resilience.preempt import PreemptionGuard
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.lookahead import InputLookahead
from dlbb_tpu.serve.run import TraceRun
from dlbb_tpu.serve.traffic import Request, TrafficTrace

MODEL = ModelConfig(hidden_size=64, num_layers=2, num_heads=4,
                    ffn_intermediate=128, dtype="float32", attention="full")
# a queue of two, and a prompt over the largest bucket (32) is refused
# at its arrival rather than before the run
SERVING = ServingConfig(max_batch=2, block_size=8, max_seq=32,
                        hbm_budget_gb=None, queue_capacity=2,
                        reject_infeasible=True)


class _Journal:
    def __init__(self) -> None:
        self.events: list[tuple[str, dict]] = []

    def event(self, name: str, **fields) -> None:
        self.events.append((name, fields))


@pytest.fixture(scope="module")
def engine(mesh2x4):
    return ServingEngine(MODEL, SERVING, mesh2x4, verbose=False)


def _run(engine, requests) -> TraceRun:
    engine.journal = _Journal()
    trace = TrafficTrace(kind="test", seed=0, params={},
                         requests=tuple(requests))
    return TraceRun(engine, trace, PreemptionGuard(),
                    InputLookahead(engine._request_input))


def _counted(engine, run, *outcomes):
    return {k: engine._requests[k] - run.counts_base[k] for k in outcomes}


def test_arrivals_are_queued_shed_when_full_or_refused_as_infeasible(
        engine):
    run = _run(engine, [
        Request(0, 0.0, 8, 4, seed=0),
        Request(1, 0.01, 8, 4, seed=1),
        Request(2, 0.02, 8, 4, seed=2),
        Request(3, 0.0, 40, 4, seed=3),     # prompt over every bucket
        Request(4, 1.0, 8, 4, seed=4),      # not yet arrived
    ])
    infeasible = engine._rejections["infeasible"]
    run.arrive(0.5)

    assert [r.rid for r in run.queue] == [0, 1]
    assert [r.rid for r in run.pending] == [4]
    assert run.outcomes == {2: "rejected[queue-full]",
                            3: "rejected[infeasible]"}
    assert _counted(engine, run, "arrived", "admitted", "rejected") == {
        "arrived": 4, "admitted": 2, "rejected": 2}
    assert engine._rejections["queue-full"] - run.shed_base == 1
    assert engine._rejections["infeasible"] - infeasible == 1
    refused, shed = run.rejected_detail
    assert refused["rid"] == 3 and refused["reason"] == "infeasible"
    assert "prompt_len=40" in refused["detail"]
    assert shed == {"rid": 2, "reason": "queue-full", "queue_depth": 2,
                    "queue_wait_s": 0.5}
    # arrival order, each arrival journaled before its fate
    assert [(name, f["config"], f.get("reason"))
            for name, f in engine.journal.events] == [
        ("request-arrived", "request-0", None),
        ("request-admitted", "request-0", None),
        ("request-arrived", "request-3", None),
        ("request-infeasible", "request-3", "infeasible"),
        ("request-arrived", "request-1", None),
        ("request-admitted", "request-1", None),
        ("request-arrived", "request-2", None),
        ("request-rejected", "request-2", "queue-full"),
    ]


def test_a_queue_head_past_its_deadline_is_shed_and_the_next_kept(engine):
    run = _run(engine, [Request(0, 0.0, 8, 4, seed=0, deadline_s=0.1),
                        Request(1, 0.0, 8, 4, seed=1, deadline_s=5.0)])
    deadline = engine._rejections["deadline"]
    shed_queued = engine._deadline_counter["shed-queued"]
    run.arrive(0.0)
    run.shed_expired(0.05)
    assert [r.rid for r in run.queue] == [0, 1] and not run.outcomes

    run.shed_expired(0.25)
    assert [r.rid for r in run.queue] == [1]
    assert run.outcomes == {0: "rejected[deadline]"}
    assert run.stats.deadline_shed == 1
    assert _counted(engine, run, "rejected") == {"rejected": 1}
    assert engine._rejections["deadline"] - deadline == 1
    assert engine._deadline_counter["shed-queued"] - shed_queued == 1
    assert run.rejected_detail == [{
        "rid": 0, "reason": "deadline", "queue_depth": 1,
        "queue_wait_s": 0.25, "deadline_s": 0.1}]
    assert engine.journal.events[-1] == ("request-rejected", {
        "config": "request-0", "reason": "deadline",
        "queue_wait_s": 0.25, "deadline_s": 0.1})


def test_a_cancel_drops_a_queued_request_and_ignores_an_unknown_one(
        engine):
    run = _run(engine, [Request(0, 0.0, 8, 4, seed=0),
                        Request(1, 0.0, 8, 4, seed=1)])
    run.arrive(0.0)
    run.cancel_request(0, "hedge-lost")
    assert [r.rid for r in run.queue] == [1]
    assert run.outcomes == {0: "canceled[hedge-lost]"}
    assert _counted(engine, run, "canceled") == {"canceled": 1}
    assert engine.journal.events[-1] == ("request-canceled", {
        "config": "request-0", "reason": "hedge-lost", "tokens_done": 0})

    # a rid that completed between the decision and the boundary
    run.cancel_request(7, "hedge-lost")
    assert [r.rid for r in run.queue] == [1]
    assert run.outcomes == {0: "canceled[hedge-lost]"}
    assert _counted(engine, run, "canceled") == {"canceled": 1}
