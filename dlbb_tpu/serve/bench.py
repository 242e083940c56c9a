"""Trace-driven serving benchmark harness (``cli serve``).

Composes the serving level out of the machinery every other level
already uses: the :class:`~dlbb_tpu.parallel.plan.ParallelismPlan`
resolves and validates the mesh, the resilience journal records request
lifecycle events (fsync'd, reconstructable into a Perfetto timeline via
``cli obs trace``), obs spans wrap the admission/prefill/decode phases,
and every artifact is an atomic write:

- ``serving_<name>.json``   — the full report (``docs/serving.md``);
- ``trace_<name>.json``     — the exact trace served, replayable;
- ``serving_manifest.json`` — run summary + topology fingerprint;
- ``metrics.prom``          — Prometheus textfile
  (``obs.export.serving_metrics``);
- ``sweep_journal.jsonl``   — request lifecycle audit trail.

Graceful drain + deterministic resume (docs/resilience.md): a SIGTERM
mid-trace stops admission, drains the in-flight window, and writes
``serving_resume.json`` — the queue/trace-cursor checkpoint (remaining
rids + the partial report with raw latency samples) next to the full
replayable trace.  ``cli serve --resume`` replays the remaining
requests (arrivals rebased, original gaps preserved) and MERGES the two
sessions into the final artifact set, so it matches an uninterrupted
run: same artifact names, same report schema, and the same per-request
outcomes for every non-preempted request — the invariant
``cli chaos --plan serve`` pins.  The checkpoint is deleted once the
merged artifacts land; an incomplete session never writes
``serving_<name>.json``.
"""

from __future__ import annotations

import json
import time
from dataclasses import replace
from pathlib import Path
from typing import Any, Optional, Sequence

from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.serve.config import ServingConfig
from dlbb_tpu.serve.engine import ServingEngine
from dlbb_tpu.serve.traffic import TRACE_KINDS, TrafficTrace, generate_trace

SERVING_MANIFEST_SCHEMA = "dlbb_serving_manifest_v1"
SERVING_RESUME_SCHEMA = "dlbb_serving_resume_v1"
RESUME_CHECKPOINT = "serving_resume.json"

# The CLI's default model when no --config YAML is given: small enough
# that a 100-request trace serves in seconds on the CPU-simulated mesh,
# GQA (kv_heads < num_heads) so the grouped cache path is always the one
# exercised, exact attention as serving requires.
DEFAULT_SERVE_MODEL = dict(
    hidden_size=128, num_layers=4, num_heads=8, num_kv_heads=4,
    ffn_intermediate=256, dtype="float32", attention="full",
)


def _hbm_record(model_cfg: ModelConfig, serving_cfg: ServingConfig,
                plan) -> dict:
    """The HBM envelope a run was admitted under: the analytic
    per-device cache footprint ``validate_serving`` priced (the number
    the static memory audit pins against the compiled decode carry —
    docs/memory_audit.md) next to the configured budget, recorded in
    both the result report and the serving manifest (fresh runs and
    resumed merges alike)."""
    from dlbb_tpu.models.configs import kv_cache_bytes_per_device

    cache_dev = kv_cache_bytes_per_device(
        model_cfg, serving_cfg.max_batch, serving_cfg.max_seq,
        dp=plan.dp, tp=plan.tp,
        kv_quantization=serving_cfg.kv_quantization,
        block_size=serving_cfg.block_size)
    budget = (None if serving_cfg.hbm_budget_gb is None
              else int(serving_cfg.hbm_budget_gb * 2**30))
    return {
        "kv_cache_bytes_per_device": cache_dev,
        "budget_bytes": budget,
        "headroom_bytes": (None if budget is None
                           else budget - cache_dev),
    }


def _stamp_report(report: dict, config: dict, plan, engine: ServingEngine,
                  model_cfg: ModelConfig, serving_cfg: ServingConfig
                  ) -> None:
    """What a session's report says about where it ran — the same fields
    for a fresh run and a resumed one (the merge keeps the latter's)."""
    from dlbb_tpu.utils.sysinfo import collect_system_info, device_spread

    report["experiment"] = config.get("experiment", {})
    report["backend"] = "xla_tpu"
    report["config"] = config
    report["mesh"] = plan.mesh_dict()
    # devices the weights ended up spread over
    report["param_devices"] = device_spread(engine.params)
    report["system_info"] = collect_system_info()
    report["timestamp"] = time.time()
    report["hbm"] = _hbm_record(model_cfg, serving_cfg, plan)


def default_parallelism(n_devices: int, kv_heads: int,
                        max_batch: int) -> tuple[int, int]:
    """Auto (dp, tp) for ``n_devices``: the largest tp in {4, 2, 1} that
    divides the device count AND the kv-head count, then the largest dp
    that divides ``max_batch`` within the remaining devices — both
    serving cache axes populated whenever the mesh allows it, and an
    awkward max_batch costs dp width, never the whole tp axis."""
    for tp in (4, 2, 1):
        if n_devices % tp or kv_heads % tp:
            continue
        for dp in range(n_devices // tp, 0, -1):
            if max_batch % dp == 0:
                return dp, tp
    return 1, 1


def resolve_trace(
    trace: str,
    num_requests: int = 100,
    seed: int = 42,
    rate: Optional[float] = None,
    serving: Optional[ServingConfig] = None,
    deadline_s: Optional[float] = None,
    **params: Any,
) -> TrafficTrace:
    """``--trace`` semantics: a known kind generates a seeded trace
    (lengths bounded to fit the serving envelope); anything else is a
    path to a saved trace JSON."""
    if trace not in TRACE_KINDS:
        return TrafficTrace.load(trace)
    kw: dict[str, Any] = dict(params)
    if rate is not None:
        kw["rate"] = rate
    if deadline_s is not None:
        kw["deadline_s"] = deadline_s
    if serving is not None and "prompt_range" not in kw:
        # bound sampled lengths so every request fits the envelope:
        # prompt within the largest bucket, and max_prompt + max_out <=
        # max_seq BY CONSTRUCTION (max_out is the exact remainder), so
        # the engine's pre-run validation can never reject a generated
        # trace
        max_prompt = min(serving.prefill_buckets[-1],
                         max(1, serving.max_seq // 2))
        max_out = serving.max_seq - max_prompt
        if max_out < 1:
            raise ValueError(
                f"serving.max_seq={serving.max_seq} leaves no room for "
                "output tokens; raise max_seq or pass explicit "
                "prompt_range/output_range"
            )
        kw["prompt_range"] = (min(8, max_prompt), max_prompt)
        kw["output_range"] = (min(4, max_out), min(48, max_out))
    return generate_trace(trace, num_requests, seed=seed, **kw)


def run_serving(
    config: dict[str, Any],
    trace: TrafficTrace,
    output_dir: Optional[str] = None,
    devices: Optional[Sequence] = None,
    journal: bool = True,
    verbose: bool = True,
    fault_plan: Optional[str] = None,
    collect_raw: bool = False,
    device_trace: Optional[str] = None,
    capture_tokens: bool = False,
) -> dict[str, Any]:
    """Run one trace-driven serving benchmark.

    ``config`` follows the experiment-YAML schema with a ``serving:``
    section next to ``model:`` and ``parallelism:`` (world_size = tp,
    data_parallel = dp).  Returns the report dict; when ``output_dir``
    is set, writes the artifact set listed in the module docstring.

    ``fault_plan`` activates the chaos harness for the run (an
    explicit plan wins; else an already-active plan is left alone;
    else ``DLBB_FAULT_PLAN`` — the sweep engine's contract).  A
    SIGTERM mid-trace (or the ``serve-preempt`` site) drains
    gracefully and writes the ``serving_resume.json`` checkpoint
    instead of the result artifact — see :func:`resume_serving`.

    ``device_trace`` (``--device-trace`` / ``DLBB_DEVICE_TRACE``)
    routes through the same ``obs/capture`` gate as sweeps: one
    captured prefill + one captured decode scan per run, AFTER the
    trace has been served (strictly outside timed regions), contained
    failures counted in ``obs_device_capture_failures_total``."""
    import os

    from dlbb_tpu.obs import capture as obs_capture
    from dlbb_tpu.obs import spans
    from dlbb_tpu.obs.export import serving_metrics
    from dlbb_tpu.parallel.plan import ParallelismPlan
    from dlbb_tpu.resilience import inject
    from dlbb_tpu.resilience.journal import SweepJournal
    from dlbb_tpu.resilience.preempt import PreemptionGuard
    from dlbb_tpu.utils.config import save_json
    from dlbb_tpu.utils.simulate import topology_record

    model_cfg = ModelConfig.from_dict(config.get("model",
                                                 DEFAULT_SERVE_MODEL))
    serving_cfg = ServingConfig.from_dict(config.get("serving", {}))
    plan = ParallelismPlan.from_config(config, model_cfg, devices)
    if plan.sp > 1 or plan.pp > 1 or plan.ep > 1:
        raise ValueError(
            f"serving supports (dp, tp) meshes only (got sp={plan.sp}, "
            f"pp={plan.pp}, ep={plan.ep}); the decode step's length-1 "
            "sequence cannot shard over sp/pp, and MoE is outside the "
            "serving envelope"
        )

    # chaos-harness activation (mirrors bench/runner.py): explicit arg
    # wins; else an already-active plan is left alone; else the env
    fault_spec = fault_plan
    if fault_spec is None and inject.active() is None:
        fault_spec = os.environ.get(inject.ENV_VAR, "").strip() or None

    name = config.get("experiment", {}).get("name") or (
        f"{trace.kind}_{len(trace)}req_seed{trace.seed}"
    )
    out = Path(output_dir) if output_dir is not None else None
    jrn = None
    if out is not None and journal:
        jrn = SweepJournal(
            out,
            meta={"mode": "serve", "name": name, "trace_kind": trace.kind,
                  "num_requests": len(trace), "fault_plan": fault_spec},
            sink=spans.journal_sink,
        )
    topology = topology_record()
    try:
        with inject.plan_scope(fault_spec), PreemptionGuard() as guard:
            engine = ServingEngine(
                model_cfg, serving_cfg, plan.mesh,
                journal=jrn,
                seed=config.get("input", {}).get("seed", 0),
                verbose=verbose,
                capture_tokens=capture_tokens,
            )
            if jrn is not None:
                jrn.event("topology", **topology)
            report = engine.run_trace(trace, guard=guard,
                                      collect_raw=collect_raw)
    finally:
        if jrn is not None:
            jrn.close()

    _stamp_report(report, config, plan, engine, model_cfg, serving_cfg)

    # serving capture parity (docs/observability.md): the gated device
    # capture runs AFTER the trace has been served — never inside a
    # timed region — on fresh state, one prefill + one decode scan
    capture_dir = device_trace or obs_capture.default_capture_dir()
    if capture_dir and not report.get("preempted"):
        with spans.span("device-capture", cat="capture", label="serve"):
            metas = engine.capture_device_traces(capture_dir)
        for m in metas:
            if "error" in m:
                engine.registry.inc(
                    "obs_device_capture_failures",
                    reason=m.get("error_kind", "unknown"),
                    help="contained device-capture failures "
                         "(error recorded in the capture metadata)",
                )
        report["observability"] = {
            "device_trace_dir": str(capture_dir),
            "device_captures": metas,
        }
        if verbose:
            ok = sum(1 for m in metas if "error" not in m)
            print(f"[serve] device capture: {ok}/{len(metas)} phase "
                  f"capture(s) under {capture_dir}")

    if out is not None:
        trace_path = trace.save(out / f"trace_{name}.json")
        if report["preempted"]:
            # graceful-drain checkpoint: the full replayable trace is
            # on disk, this records the cursor (remaining rids) + the
            # partial report with raw samples for the resume merge.
            # The result artifact is NOT written — an incomplete
            # session must never masquerade as a run
            ckpt = {
                "schema": SERVING_RESUME_SCHEMA,
                "name": name,
                "trace_file": trace_path.name,
                "config": config,
                "remaining_rids": report["remaining_rids"],
                "partial": report,
            }
            save_json(ckpt, out / RESUME_CHECKPOINT)
            if verbose:
                print(f"[serve] preempted — checkpoint written to "
                      f"{out / RESUME_CHECKPOINT}; finish with "
                      "`cli serve --resume --output "
                      f"{out}`")
            return report
        result_path = save_json(report, out / f"serving_{name}.json")
        registry = serving_metrics(report, registry=engine.registry)
        prom_path = registry.write_textfile(out / "metrics.prom")
        manifest = {
            "schema": SERVING_MANIFEST_SCHEMA,
            "name": name,
            "result": result_path.name,
            "trace_file": trace_path.name,
            "metrics": prom_path.name,
            "requests": report["requests"],
            "goodput_tokens_per_s": report["goodput_tokens_per_s"],
            "wall_seconds": report["wall_seconds"],
            "compile_time_s": report["compile_time_s"],
            "decode_steps": report["decode_steps"],
            "mesh": report["mesh"],
            "hbm": report["hbm"],
            "topology": topology,
            # replica id -> device ids for fleet runs (serve/fleet.py
            # writes its own manifest); None marks a single-replica run
            # so overlays never silently aggregate across the two
            "fault_domains": topology.get("fault_domains"),
            "journal": (None if jrn is None else jrn.path.name),
        }
        save_json(manifest, out / "serving_manifest.json")
        if verbose:
            print(f"[serve] report written to {result_path}")
    return report


def merge_reports(partial: dict[str, Any],
                  resumed: dict[str, Any]) -> dict[str, Any]:
    """Merge a preempted session's partial report with its resumed
    session into one report equivalent (names + schema + per-request
    outcomes for non-preempted requests) to an uninterrupted run.

    Counters sum across sessions (a preempted-then-replayed request
    therefore counts in both — ``requests.sessions`` records how many
    sessions merged); latency summaries are re-summarized over BOTH
    sessions' raw samples, never faked from two percentile sets; the
    resumed session's outcome for a rid overrides the partial one (a
    ``preempted`` marker resolves to its replayed outcome)."""
    from dlbb_tpu.utils.metrics import summarize

    merged = dict(resumed)
    merged["trace"] = partial["trace"]  # the FULL trace identity
    req_a = partial["requests"]
    req_b = resumed["requests"]
    req: dict[str, Any] = {
        k: req_a.get(k, 0) + req_b.get(k, 0)
        for k in ("arrived", "admitted", "rejected", "completed",
                  "failed", "preempted", "canceled", "deadline_shed",
                  "completed_past_deadline")
    }
    req["rejected_detail"] = (list(req_a.get("rejected_detail", []))
                              + list(req_b.get("rejected_detail", [])))
    req["rejected_rids"] = [d["rid"] for d in req["rejected_detail"]]
    outcomes = dict(req_a.get("outcomes", {}))
    outcomes.update(req_b.get("outcomes", {}))
    req["outcomes"] = {k: outcomes[k]
                       for k in sorted(outcomes, key=int)}
    arrived = req["arrived"]
    queue_full = sum(1 for d in req["rejected_detail"]
                     if d.get("reason") == "queue-full")
    req["shed_rate"] = (queue_full / arrived) if arrived else 0.0
    req["sessions"] = req_a.get("sessions", 1) + req_b.get("sessions", 1)
    merged["requests"] = req

    raw: dict[str, list] = {}
    for key in ("ttft_s", "per_token_s", "prefill_s", "decode_step_s",
                "e2e_latency_s"):
        raw[key] = (list(partial.get("raw_samples", {}).get(key, []))
                    + list(resumed.get("raw_samples", {}).get(key, [])))
    merged["ttft"] = summarize(raw["ttft_s"])
    merged["per_token_latency"] = summarize(raw["per_token_s"])
    merged["e2e_latency"] = summarize(raw["e2e_latency_s"])
    merged["prefill_time"] = summarize(raw["prefill_s"])
    merged["decode_step_time"] = summarize(raw["decode_step_s"])

    for key in ("completed_output_tokens", "generated_tokens",
                "decode_steps", "decode_units", "decode_units_overlapped",
                "launches",
                "wall_seconds",
                "compile_time_s"):
        merged[key] = partial.get(key, 0) + resumed.get(key, 0)
    wall = merged["wall_seconds"]
    merged["goodput_tokens_per_s"] = (
        merged["completed_output_tokens"] / wall if wall > 0 else 0.0)
    merged["throughput_tokens_per_s"] = (
        merged["generated_tokens"] / wall if wall > 0 else 0.0)

    fast = dict(resumed.get("fast_path", {}))
    for key in ("fused_scans", "fused_steps", "single_steps",
                "prefill_chunks", "kv_tiles_live", "kv_tiles_held"):
        fast[key] = (partial.get("fast_path", {}).get(key, 0)
                     + resumed.get("fast_path", {}).get(key, 0))
    merged["fast_path"] = fast
    merged["kv_live_share"] = (fast["kv_tiles_live"] / fast["kv_tiles_held"]
                               if fast["kv_tiles_held"] else 0.0)

    res_a = partial.get("resilience", {})
    res_b = resumed.get("resilience", {})
    merged["resilience"] = {
        "retries": res_a.get("retries", 0) + res_b.get("retries", 0),
        "hung_dispatches": (res_a.get("hung_dispatches", 0)
                            + res_b.get("hung_dispatches", 0)),
        "failed_requests": (res_a.get("failed_requests", 0)
                            + res_b.get("failed_requests", 0)),
        "failed": (list(res_a.get("failed", []))
                   + list(res_b.get("failed", []))),
    }

    cache = dict(resumed.get("cache", {}))
    for key in ("peak_blocks_reserved", "peak_blocks_in_use",
                "peak_shared_blocks"):
        cache[key] = max(partial.get("cache", {}).get(key, 0),
                         resumed.get("cache", {}).get(key, 0))
    cache["cow_blocks"] = (partial.get("cache", {}).get("cow_blocks", 0)
                           + resumed.get("cache", {}).get("cow_blocks", 0))
    merged["cache"] = cache

    if "prefix" in partial or "prefix" in resumed:
        pre_a = partial.get("prefix", {})
        pre_b = resumed.get("prefix", {})
        prefix = dict(pre_b) or dict(pre_a)
        for key in ("hits", "tokens_reused", "cow_blocks"):
            prefix[key] = pre_a.get(key, 0) + pre_b.get(key, 0)
        prefills = len(raw["prefill_s"])
        prefix["hit_rate"] = (prefix.get("hits", 0) / prefills
                              if prefills else 0.0)
        merged["prefix"] = prefix

    # timeseries: the resumed session re-anchored its clock, so its
    # samples are offset by the partial session's wall
    offset = partial.get("wall_seconds", 0.0)
    series_a = partial.get("timeseries", {})
    series_b = resumed.get("timeseries", {})
    series = {}
    for key in series_a:
        vals_b = series_b.get(key, [])
        if key == "t_s":
            vals_b = [round(t + offset, 6) for t in vals_b]
        series[key] = list(series_a.get(key, [])) + list(vals_b)
    merged["timeseries"] = series

    # a resumed session preempted AGAIN keeps its raw samples so the
    # next resume can merge honestly; a completed merge drops them
    if resumed.get("preempted"):
        merged["raw_samples"] = raw
    else:
        merged.pop("raw_samples", None)
    if "completed_tokens" in partial or "completed_tokens" in resumed:
        toks = dict(partial.get("completed_tokens", {}))
        toks.update(resumed.get("completed_tokens", {}))
        merged["completed_tokens"] = toks
    return merged


def resume_serving(
    output_dir: str,
    devices: Optional[Sequence] = None,
    verbose: bool = True,
) -> dict[str, Any]:
    """Finish a preempted serving run (``cli serve --resume``).

    Loads ``serving_resume.json`` + the saved full trace, replays the
    remaining requests (arrivals rebased to the resume instant with
    their original gaps preserved), merges both sessions, and writes
    the final artifact set — identical names + schema (and per-request
    outcomes for non-preempted requests) to an uninterrupted run.  The
    checkpoint is deleted on success; a session preempted AGAIN
    rewrites it with the merged partial instead."""
    from dlbb_tpu.utils.config import save_json

    out = Path(output_dir)
    ckpt_path = out / RESUME_CHECKPOINT
    if not ckpt_path.exists():
        raise FileNotFoundError(
            f"nothing to resume: no {RESUME_CHECKPOINT} under {out} "
            "(either the run completed, or it was never preempted)"
        )
    ckpt = json.loads(ckpt_path.read_text())
    if ckpt.get("schema") != SERVING_RESUME_SCHEMA:
        raise ValueError(
            f"{ckpt_path} is not a serving resume checkpoint "
            f"(schema={ckpt.get('schema')!r})"
        )
    full = TrafficTrace.load(out / ckpt["trace_file"])
    remaining = set(ckpt["remaining_rids"])
    reqs = [r for r in full if r.rid in remaining]
    if not reqs:
        raise ValueError(
            f"checkpoint names no servable remaining requests "
            f"({len(remaining)} rids, none found in {ckpt['trace_file']})"
        )
    # rebase arrivals to the resume instant, preserving the original
    # inter-arrival gaps so the replayed load keeps its shape
    t0 = min(r.arrival_s for r in reqs)
    sub = TrafficTrace(
        kind=full.kind, seed=full.seed,
        params={**full.params, "resumed_from": ckpt["name"]},
        requests=tuple(replace(r, arrival_s=r.arrival_s - t0)
                       for r in sorted(reqs, key=lambda r: (r.arrival_s,
                                                            r.rid))),
    )
    if verbose:
        print(f"[serve] resuming {ckpt['name']}: {len(sub)} remaining "
              f"request(s) of {len(full)}")

    from dlbb_tpu.obs import spans
    from dlbb_tpu.obs.export import serving_metrics
    from dlbb_tpu.parallel.plan import ParallelismPlan
    from dlbb_tpu.resilience.journal import SweepJournal
    from dlbb_tpu.resilience.preempt import PreemptionGuard
    from dlbb_tpu.utils.simulate import topology_record

    config = ckpt["config"]
    name = ckpt["name"]
    model_cfg = ModelConfig.from_dict(config.get("model",
                                                 DEFAULT_SERVE_MODEL))
    serving_cfg = ServingConfig.from_dict(config.get("serving", {}))
    plan = ParallelismPlan.from_config(config, model_cfg, devices)
    # the journal is append-only across sessions: the resume appends a
    # new session marker + its own lifecycle after the preempted one's
    jrn = SweepJournal(
        out,
        meta={"mode": "serve", "name": name, "resume": True,
              "remaining": len(sub)},
        sink=spans.journal_sink,
    )
    try:
        with PreemptionGuard() as guard:
            engine = ServingEngine(
                model_cfg, serving_cfg, plan.mesh, journal=jrn,
                seed=config.get("input", {}).get("seed", 0),
                verbose=verbose,
            )
            resumed = engine.run_trace(sub, guard=guard,
                                       collect_raw=True)
    finally:
        jrn.close()
    _stamp_report(resumed, config, plan, engine, model_cfg, serving_cfg)

    merged = merge_reports(ckpt["partial"], resumed)
    if merged.get("preempted"):
        # preempted AGAIN mid-resume: refresh the checkpoint with the
        # merged partial; the final artifacts wait for the next resume
        save_json({
            "schema": SERVING_RESUME_SCHEMA,
            "name": name,
            "trace_file": ckpt["trace_file"],
            "config": config,
            "remaining_rids": merged["remaining_rids"],
            "partial": merged,
        }, ckpt_path)
        if verbose:
            print("[serve] preempted again mid-resume — checkpoint "
                  "refreshed")
        return merged
    result_path = save_json(merged, out / f"serving_{name}.json")
    registry = serving_metrics(merged, registry=engine.registry)
    prom_path = registry.write_textfile(out / "metrics.prom")
    manifest = {
        "schema": SERVING_MANIFEST_SCHEMA,
        "name": name,
        "result": result_path.name,
        "trace_file": ckpt["trace_file"],
        "metrics": prom_path.name,
        "requests": merged["requests"],
        "goodput_tokens_per_s": merged["goodput_tokens_per_s"],
        "wall_seconds": merged["wall_seconds"],
        "compile_time_s": merged["compile_time_s"],
        "decode_steps": merged["decode_steps"],
        "mesh": merged["mesh"],
        "hbm": merged.get("hbm"),
        "topology": topology_record(),
        "journal": jrn.path.name,
    }
    save_json(manifest, out / "serving_manifest.json")
    ckpt_path.unlink()
    if verbose:
        print(f"[serve] resumed run merged into {result_path}")
    return merged


def run_serve_from_config(
    config_path: Optional[str],
    trace: str = "poisson",
    num_requests: int = 100,
    seed: int = 42,
    rate: Optional[float] = None,
    output_dir: Optional[str] = None,
    overrides: Optional[dict[str, Any]] = None,
    devices: Optional[Sequence] = None,
    verbose: bool = True,
    resume: bool = False,
    fault_plan: Optional[str] = None,
    slo: Optional[float] = None,
    device_trace: Optional[str] = None,
    prefix_groups: Optional[int] = None,
    prefix_len: Optional[int] = None,
    replicas: Optional[int] = None,
) -> dict[str, Any]:
    """CLI entry: optional experiment YAML + flag overrides (including
    the decode fast-path knobs — decode_horizon / inflight_window /
    prefill_chunk — and the resilience knobs,
    docs/serving.md).  ``--resume`` finishes a preempted run from its
    ``serving_resume.json`` checkpoint; ``--slo SEC`` stamps generated
    requests with a per-request deadline; ``--fault-plan`` activates
    the chaos harness; ``--prefix-groups``/``--prefix-len`` generate a
    shared-prefix trace (docs/serving.md, "Prefix cache & quantized
    KV") — the traffic shape the ``prefix_caching`` engine exploits.

    Without ``--config`` the default small GQA model serves on an
    auto-planned (dp, tp) mesh over the available devices.

    ``--replicas N`` (or a ``fleet:`` config section) routes the trace
    through the replica-level fleet supervisor instead — N failure
    domains, each its own engine, with health-fencing / failover /
    hedging / the degradation ladder (docs/fleet.md); the
    ``parallelism:`` section then describes ONE replica's mesh."""
    import jax

    from dlbb_tpu.utils.config import load_config

    if resume:
        out = output_dir or "results/serving"
        return resume_serving(out, devices=devices, verbose=verbose)
    if config_path is not None:
        config = load_config(config_path)
    else:
        config = {"model": dict(DEFAULT_SERVE_MODEL)}
    config.setdefault("serving", {})
    if overrides:
        for key, value in sorted(overrides.items()):
            if value is not None:
                config["serving"][key] = value
    serving_cfg = ServingConfig.from_dict(config["serving"])
    if replicas is not None and replicas > 1:
        config.setdefault("fleet", {})["replicas"] = replicas
    fleet = bool(config.get("fleet"))
    if "parallelism" not in config:
        model_cfg = ModelConfig.from_dict(config.get("model",
                                                     DEFAULT_SERVE_MODEL))
        n = len(devices) if devices is not None else len(jax.devices())
        if fleet:
            # fleet parallelism is PER REPLICA: auto-plan within one
            # failure domain's device share
            n //= max(1, int(config["fleet"].get("replicas", 2)))
        dp, tp = default_parallelism(n, model_cfg.kv_heads,
                                     serving_cfg.max_batch)
        config["parallelism"] = {"data_parallel": dp, "world_size": tp}
    trace_kw: dict[str, Any] = {}
    if prefix_groups is not None:
        trace_kw["prefix_groups"] = prefix_groups
    if prefix_len is not None:
        trace_kw["prefix_len"] = prefix_len
    resolved = resolve_trace(trace, num_requests=num_requests, seed=seed,
                             rate=rate, serving=serving_cfg,
                             deadline_s=slo, **trace_kw)
    out = output_dir or config.get("experiment", {}).get(
        "output_dir", "results/serving")
    if fleet:
        from dlbb_tpu.serve.fleet import run_fleet

        return run_fleet(config, resolved, output_dir=out,
                         devices=devices, verbose=verbose,
                         fault_plan=fault_plan)
    return run_serving(config, resolved, output_dir=out, devices=devices,
                       verbose=verbose, fault_plan=fault_plan,
                       device_trace=device_trace)
