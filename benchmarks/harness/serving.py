"""Runner of the serving kinds (``backlog``, ``paced``): one
``ServingEngine``, built as ``serve/bench.py::run_serving`` builds it,
serves a short warm-up trace of the cell's own shapes and then the
measured trace.

The benchmark takes the end-to-end numbers itself.  The engine is driven
through ``run_trace(trace, feed=, control=)`` (``harness/feed.py``): the
feed shows a request only once it is due, and the observer stamps the
run's start and every request's first token and completion on the
benchmark's ``perf_counter``.  The program's report is read only for
correctness counts and for the per-layer readers.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Optional

import numpy as np

from benchmarks.harness import trace_reduce, traffic as traffic_gen
from benchmarks.harness.cells import Cell, program_seed
from benchmarks.harness.device import CompileCounter, device_record
from benchmarks.harness.feed import DueFeed, Observer
from benchmarks.harness.result import Run


def to_trace(records: list[dict[str, Any]], kind: str, seed: int) -> Any:
    """The generated records as the program's own trace type."""
    from dlbb_tpu.serve.traffic import Request, TrafficTrace

    return TrafficTrace(
        kind=kind, seed=seed, params={},
        requests=tuple(Request(**r) for r in records),
    )


def build_engine(cell: Cell, seed: int) -> Any:
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.parallel.plan import ParallelismPlan
    from dlbb_tpu.serve.engine import ServingConfig, ServingEngine

    program = cell.config["program"]
    model_cfg = ModelConfig.from_dict(program["model"])
    serving_cfg = ServingConfig.from_dict(program["serving"])
    plan = ParallelismPlan.from_config(program, model_cfg)
    return ServingEngine(model_cfg, serving_cfg, plan.mesh,
                         seed=program_seed(seed), verbose=False)


def warmup_trace(cell: Cell, seed: int) -> Any:
    """A few requests, all due at once, that touch every shape of the
    cell's traffic: prompts from the longest down in steps of
    ``warmup_prompt_stride`` (the engine pads a prompt to whole prefill
    chunks and compiles per chunk offset and per padded length), outputs
    from the cell's range cut short."""
    t = cell.traffic
    lo, hi = t["prompt_range"]
    prompts = list(range(int(hi), int(lo) - 1,
                         -int(t["warmup_prompt_stride"])))
    records = traffic_gen.generate(
        {**t, "kind": "backlog"}, seed + 1, len(prompts),
        output_range=tuple(t["warmup_output_range"]))
    for record, prompt in zip(records, prompts):
        record["prompt_len"] = prompt
    return to_trace(records, "warmup", seed + 1)


def _span_intervals(events: list[dict[str, Any]]
                    ) -> tuple[list[trace_reduce.Span], Optional[float]]:
    """The program's span events (``obs/spans.py``: B/E pairs per thread,
    microseconds on the tracer's clock) as intervals in seconds, and the
    tracer-clock time of the ``bench-sync`` instant."""
    open_: dict[Any, list[tuple[str, float]]] = {}
    out: list[trace_reduce.Span] = []
    sync_at = None
    for ev in events:
        ts = ev["ts"] * 1e-6
        if ev["ph"] == "B":
            open_.setdefault(ev["tid"], []).append((ev["name"], ts))
        elif ev["ph"] == "E" and open_.get(ev["tid"]):
            name, start = open_[ev["tid"]].pop()
            out.append((name, start, ts))
        elif ev["ph"] == "i" and ev["name"] == trace_reduce.SYNC_SPAN:
            sync_at = ts
    return out, sync_at


class _SliceProfiler(threading.Thread):
    """Traces ``seconds`` of the serving window from ``start_s`` after
    the engine's clock starts; gives up if the run ends first."""

    def __init__(self, observer: Observer, start_s: float, seconds: float,
                 log_dir: str) -> None:
        super().__init__(name="bench-profiler", daemon=True)
        self._observer = observer
        self._start_s, self._seconds, self._dir = start_s, seconds, log_dir
        self.finished = threading.Event()
        self.traced = False

    def run(self) -> None:
        import jax

        from dlbb_tpu.obs import spans

        while self._observer.now() < self._start_s:
            if self.finished.wait(0.05):
                return
        with trace_reduce.profiling(self._dir):
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC_SPAN):
                spans.instant(trace_reduce.SYNC_SPAN)
            self.traced = True
            self.finished.wait(self._seconds)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: CompileCounter, scratch: str) -> Run:
    from dlbb_tpu.obs import spans

    t = cell.traffic
    engine = build_engine(cell, seed)
    phases = {"built": time.perf_counter()}
    engine.run_trace(warmup_trace(cell, seed))
    phases["warm"] = time.perf_counter()

    n = traffic_gen.request_count(t, seconds)
    records = traffic_gen.generate(t, seed, n)
    measured = to_trace(records, t["kind"], seed)
    observer = Observer(mark=lambda: compiles.count)
    feed = DueFeed(measured.requests, observer.now)

    profiler = None
    if trace:
        spans.start(os.path.join(scratch, "spans.json"))
        profiler = _SliceProfiler(observer, float(t["trace_start_s"]),
                                  float(t["trace_seconds"]), scratch)
        profiler.start()
    try:
        report = engine.run_trace(measured, collect_raw=True, feed=feed,
                                  control=observer)
    finally:
        if profiler is not None:
            profiler.finished.set()
            profiler.join()
    compiled = compiles.count - observer.mark_at_start

    profile: dict[str, Any] = {}
    if profiler is not None:
        tracer = spans.active()
        host_spans, sync_at = _span_intervals(tracer.events())
        spans.stop()
        if profiler.traced:
            profile = trace_reduce.reduce_profile(scratch, host_spans,
                                                  sync_at)

    due = {r["rid"]: r["arrival_s"] for r in records}
    out_len = {r["rid"]: r["output_len"] for r in records}
    first = observer.at.get("request-prefill", {})
    done = observer.at.get("request-completed", {})
    taken = observer.at.get("request-arrived", {})
    wall = max(done.values()) if done else float("nan")
    out_tokens = sum(out_len[rid] for rid in done)
    ttft = [first[rid] - due[rid] for rid in sorted(first)]
    tpot = [(done[rid] - first[rid]) / (out_len[rid] - 1)
            for rid in sorted(done) if rid in first and out_len[rid] > 1]
    # the report's prefill samples are in admission order, which is the
    # order of the first tokens
    raw = report["raw_samples"]
    admitted = sorted(first, key=first.get)
    queue = [first[rid] - due[rid] - dt
             for rid, dt in zip(admitted, raw["prefill_s"])]

    req = report["requests"]
    faults = []
    if compiled:
        faults.append(f"{compiled} program(s) compiled inside the window")
    if len(done) != n:
        faults.append(f"{n - len(done)} of {n} requests did not complete")
    for key in ("rejected", "failed", "preempted", "canceled"):
        if req[key]:
            faults.append(f"{req[key]} request(s) {key}")
    res = report["resilience"]
    if res["retries"] or res["hung_dispatches"]:
        faults.append(f"{res['retries']} retries, "
                      f"{res['hung_dispatches']} hung dispatches")
    if report["generated_tokens"] != sum(out_len.values()):
        faults.append(f"{report['generated_tokens']} tokens generated, "
                      f"{sum(out_len.values())} asked for")

    def ms(values: list[float], q: float) -> float:
        return float(np.percentile(values, q)) * 1e3 if values \
            else float("nan")

    return Run(
        cell=cell, seconds=seconds, started_at=observer.t0,
        correct=not faults, attempted=n, failed=n - len(done),
        values={"out_tokens_per_s": out_tokens / wall,
                "tpot_ms_p50": ms(tpot, 50), "ttft_ms_p50": ms(ttft, 50),
                "ttft_ms_p90": ms(ttft, 90)},
        samples={"ttft_s": ttft, "tpot_s": tpot, "queue_s": queue,
                 "arrival_late_s": [taken[rid] - due[rid]
                                    for rid in sorted(taken)],
                 "per_token_s": raw["per_token_s"],
                 "prefill_s": raw["prefill_s"],
                 "decode_unit_s": raw["decode_step_s"],
                 "active_slots": report["timeseries"]["active_slots"]},
        scalars={"wall_s": wall, "requests": n},
        profile=profile, device=device_record(), faults=faults,
        phases=phases,
    )
