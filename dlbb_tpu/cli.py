"""Command-line interface.

Replaces the reference's launch layer (L7): ``mpirun -np N python
collectives/1d/openmpi.py`` with edit-the-file constants becomes
``python -m dlbb_tpu.cli bench1d --ranks 2 4 8 --variant ring``; the
rank-count sweep loops of ``collectives/launch_{openmpi,intelmpi,dsccl}.sh``
become the ``--ranks`` flag; the CCL_* env tuning matrix becomes
``--variant`` (see ``dlbb_tpu.comm.variants``).

``--simulate N`` stands up the N-device CPU-simulated mesh (the dev path,
analogue of running N ranks on localhost) — it must act before the JAX
backend initialises, which is why it is handled first in ``main``.
Without it a device command needs an accelerator: on a CPU backend it
exits non-zero before it measures or writes anything
(``utils/simulate.require_accelerator``).
"""

from __future__ import annotations

import argparse
import os
import sys

# subcommands that measure on the device: they get the persistent compile
# cache and the no-chip rule; the rest are file processing, or (analyze,
# obs, chaos) reach a backend only through code that checks for itself
DEVICE_COMMANDS = ("bench1d", "bench3d", "e2e", "train", "serve", "plan")


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--impl", default="xla_tpu", help="implementation name recorded in results")
    p.add_argument("--variant", default="default", help="named tuning variant")
    p.add_argument("--ranks", type=int, nargs="+", default=None, help="rank counts to sweep")
    p.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float16", "float32"])
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--output", default=None, help="output directory for result JSONs")
    p.add_argument("--simulate", type=int, default=0, metavar="N",
                   help="use an N-device CPU-simulated mesh (dev path)")
    p.add_argument("--resume", action="store_true",
                   help="skip configs whose result JSON already exists in the "
                        "output dir (pick an interrupted sweep back up)")
    p.add_argument("--no-pipeline", action="store_true",
                   help="disable the compile-ahead thread and compile each "
                        "config inline (serial debug mode; identical result "
                        "schema and timing semantics)")
    p.add_argument("--pipeline", action="store_true",
                   help="force the compile-ahead thread on (default: auto — "
                        "enabled only on hosts with spare cores)")
    p.add_argument("--prefetch", type=int, default=2, metavar="K",
                   help="configs compiled ahead of the one measuring "
                        "(pipelined mode; default 2)")
    p.add_argument("--compile-cache", default="auto", choices=("auto", "off"),
                   help="persistent XLA compilation cache for this sweep "
                        "('auto' = on, in JAX_COMPILATION_CACHE_DIR or "
                        "<checkout>/.jax_cache; 'off' = real compiles, "
                        "the chaos gate's setting)")
    p.add_argument("--fault-plan", default=None, metavar="PLAN",
                   help="deterministic fault-injection plan (chaos "
                        "harness, e.g. 'exec-transient:2,seed=7'; "
                        "DLBB_FAULT_PLAN env is the default; see "
                        "docs/resilience.md)")
    p.add_argument("--deadline", type=float, default=None, metavar="SEC",
                   dest="unit_deadline",
                   help="wall-clock watchdog per work unit (compile + "
                        "measurement); an overrun is abandoned and "
                        "quarantined (DLBB_UNIT_DEADLINE env default)")
    p.add_argument("--max-retries", type=int, default=2, metavar="N",
                   help="bounded retries with exponential backoff for "
                        "transient per-config failures (default 2; "
                        "retried configs recompute from scratch and "
                        "record `retries` in the artifact)")
    p.add_argument("--no-journal", action="store_true",
                   help="disable the append-only sweep_journal.jsonl "
                        "(crash audit trail; on by default)")
    p.add_argument("--device-trace", default=None, metavar="DIR",
                   dest="device_trace",
                   help="capture a jax.profiler device trace per config on "
                        "a DEDICATED profile rep (excluded from the stats "
                        "series) under DIR; DLBB_DEVICE_TRACE env is the "
                        "default (docs/observability.md)")
    _add_trace(p)


def _add_trace(p: argparse.ArgumentParser) -> None:
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="write an XLA profiler trace (xplane) to DIR; "
                        "DLBB_TRACE_DIR env is the default")
    p.add_argument("--span-trace", default=None, metavar="FILE",
                   dest="span_trace",
                   help="write a host-side span trace (Chrome trace-event "
                        "JSON, Perfetto-loadable) of the whole run to FILE; "
                        "DLBB_SPANS env is the default "
                        "(docs/observability.md)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="dlbb_tpu", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    b1 = sub.add_parser("bench1d", help="1D collective microbenchmark sweep")
    _add_common(b1)
    b1.add_argument("--ops", nargs="+", default=None, help="collectives to benchmark")
    b1.add_argument("--sizes", nargs="+", default=None,
                    help="size labels (1KB 64KB 1MB 16MB 64MB 256MB 1GB) or 'extended'")

    b3 = sub.add_parser("bench3d", help="3D (batch, seq, hidden) tensor collective sweep")
    _add_common(b3)
    b3.add_argument("--ops", nargs="+", default=None)
    b3.add_argument("--batch", type=int, nargs="+", default=None)
    b3.add_argument("--seq", type=int, nargs="+", default=None)
    b3.add_argument("--hidden", type=int, nargs="+", default=None)

    s1 = sub.add_parser("stats1d", help="process 1D result JSONs to stats + CSV")
    s1.add_argument("--input", required=True)
    s1.add_argument("--output", required=True)
    s1.add_argument("--algorithm-bandwidth", action="store_true",
                    help="use per-op bus-bandwidth factors instead of the "
                         "reference's uniform formula")

    s3 = sub.add_parser("stats3d", help="process 3D result JSONs to standard+transposed CSVs")
    s3.add_argument("--input", required=True)
    s3.add_argument("--output", required=True)
    s3.add_argument("--impl", default="xla_tpu")

    cp = sub.add_parser(
        "compare",
        help="reference-vs-dlbb_tpu head-to-head comparison report "
             "(CSV + markdown, per-config match/beat/lose verdicts)",
    )
    cp.add_argument("--reference", default="/root/reference",
                    help="reference repo root (holds collectives/{1d,3d}/results)")
    cp.add_argument("--own-1d", default="results/1d/xla_tpu")
    cp.add_argument("--own-3d", default="results/3d/xla_tpu")
    cp.add_argument("--output", default="stats/compare")

    e2 = sub.add_parser("e2e", help="end-to-end TP transformer forward benchmark")
    e2.add_argument("--config", required=True, help="YAML experiment config")
    e2.add_argument("--simulate", type=int, default=0, metavar="N")
    e2.add_argument("--output", default=None)
    e2.add_argument("--tp-overlap", default=None,
                    choices=("off", "ring", "bidir"), dest="tp_overlap",
                    help="force model.tp_overlap: off = GSPMD fused TP "
                         "collectives, ring/bidir = ring-decomposed "
                         "collective matmuls overlapping comm with compute; "
                         "without it the config's word holds, by default "
                         "'auto': the shapes decide (docs/overlap.md)")
    _add_trace(e2)

    rp = sub.add_parser(
        "reports",
        help="regenerate the derived comparison reports (variant tuning "
             "1D + 3D winners, parallelism families) from committed "
             "results/ + stats/ — pure file processing, no backend",
    )
    rp.add_argument("--stats", default="stats", help="stats tree root")
    rp.add_argument("--results", default="results",
                    help="results tree root (parallelism artifacts)")

    an = sub.add_parser(
        "analyze",
        help="comm-lint: static HLO collective audit, α–β schedule audit, "
             "and source lint (verifies benchmarks match their "
             "parallelism plan, no TPU needed — runs on the --simulate "
             "mesh).  Exit codes are a pinned contract: 0 clean / "
             "1 findings / 2 crash (docs/schedule_audit.md)",
    )
    an.add_argument("which", nargs="?", default="all",
                    choices=("hlo", "lint", "schedule", "memory",
                             "numerics", "all", "snapshot", "diff"),
                    help="pass to run: hlo = collective byte audit, "
                         "schedule = α–β critical-path/overlap audit, "
                         "memory = buffer-liveness peak-HBM audit, "
                         "numerics = dtype-flow precision audit, "
                         "lint = AST source lint, all = every pass "
                         "(default); snapshot = (re)write the "
                         "regression baselines (schedule + memory + "
                         "numerics axes), diff = fail on unexplained "
                         "drift from the committed baselines")
    an.add_argument("--simulate", type=int, default=0, metavar="N",
                    help="use an N-device CPU-simulated mesh for the HLO "
                         "audit (targets needing more devices than "
                         "available are skipped)")
    an.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable findings report here")
    an.add_argument("--root", default=".",
                    help="repo root for the source lint (default: cwd)")
    an.add_argument("--strict-warnings", action="store_true",
                    help="exit nonzero on warnings too")
    an.add_argument("--baselines", default=None, metavar="DIR",
                    help="schedule-baseline directory for snapshot/diff "
                         "(default: stats/analysis/baselines)")
    an.add_argument("--tier", default=None, metavar="TIER",
                    help="cost-model link tier for the schedule audit "
                         "(cpu-sim, tpu-v5lite, tpu-v5lite-dcn; default: "
                         "auto from the backend — see "
                         "analysis/costmodel.py)")
    an.add_argument("--model", default="cm1", choices=("cm1", "cm2"),
                    help="cost model the schedule audit prices with: cm1 "
                         "= analytic seed constants, cm2 = coefficients "
                         "fitted from the sweep corpus "
                         "(stats/analysis/costmodel_fit/; falls back to "
                         "cm1 with a fit-missing warning)")
    an.add_argument("--output", default=None, metavar="DIR",
                    help="observability surface for the memory + "
                         "numerics audits: write memory_audit.json / "
                         "numerics_audit.json under DIR, merge the "
                         "per-target peak_live_bytes and numerics gate "
                         "keys into DIR/sweep_manifest.json, and fold "
                         "analysis_peak_live_bytes{target} / "
                         "analysis_numerics_* / per-pass "
                         "analysis_findings{pass,severity} gauges into "
                         "DIR/metrics.prom (docs/memory_audit.md, "
                         "docs/numerics.md)")

    ob = sub.add_parser(
        "obs",
        help="runtime observability: journal->trace reconstruction "
             "(trace), the predicted-vs-measured cost-model calibration "
             "report (calibrate), and the calibration regression gate "
             "(diff) — exit codes pinned 0 clean / 1 findings / 2 crash "
             "(docs/observability.md)",
    )
    ob.add_argument("which", choices=("trace", "calibrate", "diff",
                                      "fit", "attribute", "devtrace"),
                    help="trace = rebuild a Perfetto timeline from a "
                         "sweep's journal; calibrate = measure every "
                         "committed schedule-baseline target and report "
                         "signed predicted-vs-measured error; diff = fail "
                         "when the model error regressed past the "
                         "committed calibration baseline; fit = regress "
                         "cm2 (α, β, peak, per-dispatch γ) from the "
                         "sweep-artifact corpus into the versioned "
                         "fitted DB; attribute = join a run's span "
                         "trace/journal against the cost model into a "
                         "per-phase 'where did the time go' report "
                         "(MD+CSV under stats/analysis/attribution/); "
                         "devtrace = parse the run's device captures "
                         "into per-op measured timelines, report "
                         "measured overlap beside the static proof, and "
                         "mine the op-level cm2 fit samples (MD+CSV+JSON "
                         "under stats/analysis/devtrace/)")
    ob.add_argument("--journal", default=None, metavar="DIR",
                    help="sweep output directory holding "
                         "sweep_journal.jsonl (obs trace)")
    ob.add_argument("--output", default=None,
                    help="output path (trace JSON) or report directory "
                         "(calibrate/diff; default results/obs)")
    ob.add_argument("--baselines", default=None, metavar="DIR",
                    help="schedule-baseline directory to calibrate "
                         "against (default: stats/analysis/baselines)")
    ob.add_argument("--calibration", default=None, metavar="DIR",
                    help="committed calibration baseline for diff "
                         "(default: stats/analysis/calibration)")
    ob.add_argument("--report", default=None, metavar="JSON",
                    help="diff an existing calibration report instead of "
                         "re-measuring")
    ob.add_argument("--simulate", type=int, default=0, metavar="N")
    ob.add_argument("--tier", default=None, metavar="TIER",
                    help="cost-model tier (default: auto from the "
                         "backend; must match the committed baselines)")
    ob.add_argument("--reps", type=int, default=30,
                    help="timed reps per target (default 30)")
    ob.add_argument("--warmup", type=int, default=5)
    ob.add_argument("--targets", nargs="+", default=None,
                    help="substring filter on baseline target names "
                         "(calibrate/diff subset runs)")
    ob.add_argument("--strict-warnings", action="store_true",
                    help="exit nonzero on warnings too")
    ob.add_argument("--model", default="cm1", choices=("cm1", "cm2"),
                    help="cost model for calibrate/diff/attribute: cm1 "
                         "analytic constants, cm2 the fitted DB "
                         "(docs/observability.md)")
    ob.add_argument("--fit-dir", default=None, metavar="DIR",
                    dest="fit_dir",
                    help="fitted-DB directory (default "
                         "stats/analysis/costmodel_fit; obs fit writes "
                         "here, cm2 pricing reads here)")
    ob.add_argument("--results", nargs="+", default=None, metavar="DIR",
                    help="results tree(s) the fit ingests (obs fit; "
                         "default: results)")
    ob.add_argument("--span-trace-file", default=None, metavar="FILE",
                    dest="span_trace_file",
                    help="explicit span-trace JSON for obs attribute "
                         "(default: auto-detect in --journal DIR)")
    ob.add_argument("--min-samples", type=int, default=None,
                    dest="min_samples",
                    help="minimum corpus samples per tier before the fit "
                         "refuses (obs fit; default 16)")
    ob.add_argument("--host", default=None, dest="host_filter",
                    help="substring filter on the corpus host "
                         "fingerprint (obs fit): fit the tier for the "
                         "host you will predict on")

    ch = sub.add_parser(
        "chaos",
        help="chaos gate: mini-sweep/mini-train under each injected fault "
             "class, asserting the resilience invariants (no corrupt "
             "artifact survives, resume completes the grid, hangs are "
             "quarantined — docs/resilience.md)",
    )
    ch.add_argument("--plan", default="all",
                    help="fault class to exercise (compile, transient, "
                         "nan, torn, hang, ckpt, preempt, kill, serve, "
                         "fleet) or 'all'")
    ch.add_argument("--simulate", type=int, default=8, metavar="N",
                    help="CPU-simulated mesh size (default 8; the gate "
                         "needs no TPU)")
    ch.add_argument("--output", default=None,
                    help="workdir for the gate's artifacts (default: a "
                         "fresh temp dir, kept on failure)")

    sv = sub.add_parser(
        "serve",
        help="continuous-batching serving benchmark: a synthetic traffic "
             "trace served through the paged-KV-cache inference engine; "
             "reports goodput, TTFT / per-token latency p50/p99/p99.9, "
             "queue depth and cache occupancy (docs/serving.md)",
    )
    sv.add_argument("--config", default=None,
                    help="experiment YAML with model/parallelism/serving "
                         "sections (default: a small GQA model on an "
                         "auto-planned (dp, tp) mesh)")
    sv.add_argument("--trace", default="poisson",
                    help="arrival process (poisson, bursty, diurnal) or a "
                         "path to a saved trace JSON (replay)")
    sv.add_argument("--requests", type=int, default=100,
                    help="requests to generate (generated traces only)")
    sv.add_argument("--rate", type=float, default=None,
                    help="mean arrival rate in req/s (default 32)")
    sv.add_argument("--seed", type=int, default=42,
                    help="trace seed (arrivals, lengths, embeddings)")
    sv.add_argument("--max-batch", type=int, default=None,
                    dest="max_batch", help="decode slots (default 8)")
    sv.add_argument("--block-size", type=int, default=None,
                    dest="block_size",
                    help="KV-cache tokens per block (default 16)")
    sv.add_argument("--max-seq", type=int, default=None, dest="max_seq",
                    help="per-slot prompt+output ceiling (default 256)")
    sv.add_argument("--queue-capacity", type=int, default=None,
                    dest="queue_capacity",
                    help="admission-control queue bound (default 64)")
    sv.add_argument("--decode-horizon", type=int, default=None,
                    dest="decode_horizon",
                    help="fused-scan horizon cap K: fuse up to K decode "
                         "steps into one on-device lax.scan dispatch "
                         "(default 1 = per-step; docs/serving.md)")
    sv.add_argument("--inflight-window", type=int, default=None,
                    dest="inflight_window",
                    help="bounded in-flight decode dispatch window "
                         "(default 1 = sync every unit; 2 overlaps "
                         "dispatch N+1 with N's compute)")
    sv.add_argument("--prefill-chunk", type=int, default=None,
                    dest="prefill_chunk",
                    help="chunked prefill: tokens per chunk (a "
                         "block-size multiple), interleaved with decode "
                         "steps so long prompts stop head-of-line "
                         "blocking the batch (default: monolithic)")
    sv.add_argument("--speculation", default=None,
                    choices=["off", "greedy", "ngram", "draft-model"],
                    help="decode feedback / drafting mode: off = legacy "
                         "continuous feedback, greedy = token feedback "
                         "without drafting, ngram = prompt-lookup "
                         "self-speculation, draft-model = shallow draft "
                         "transformer on the same mesh "
                         "(docs/serving.md, 'Speculative decoding')")
    sv.add_argument("--spec-gamma", type=int, default=None,
                    dest="spec_gamma",
                    help="draft tokens proposed per verify step (the γ "
                         "of draft-and-verify; required by ngram / "
                         "draft-model)")
    sv.add_argument("--spec-adaptive", action="store_true", default=None,
                    dest="spec_adaptive",
                    help="per-request adaptive γ: back off to a smaller "
                         "verify width on low acceptance EMA")
    sv.add_argument("--temperature", type=float, default=None,
                    help="sampled decode: softmax temperature of the "
                         "residual-sampling verify path (requires a "
                         "drafting speculation mode and "
                         "decode_horizon=1; default 0 = greedy argmax)")
    sv.add_argument("--sample-seed", type=int, default=None,
                    dest="sample_seed",
                    help="host RNG seed for the sampled (temperature "
                         "> 0) path — makes sampled runs replayable")
    sv.add_argument("--prefix-caching", action="store_true", default=None,
                    dest="prefix_caching",
                    help="shared-prefix KV reuse: content-address full "
                         "blocks in a host-side radix trie, attach new "
                         "admissions to a donor's matched blocks (one "
                         "masked copy replaces the matched chunks' "
                         "prefill — requires --prefill-chunk, dp=1; "
                         "docs/serving.md, 'Prefix cache & quantized "
                         "KV')")
    sv.add_argument("--kv-quantization", default=None,
                    dest="kv_quantization", choices=["none", "int8"],
                    help="KV-cache plane dtype: int8 stores K/V blocks "
                         "quantized with per-(block, kv-head) fp32 "
                         "scales — ~4x smaller cache under the same "
                         "hbm_budget_gb (docs/serving.md)")
    sv.add_argument("--prefix-groups", type=int, default=None,
                    dest="prefix_groups", metavar="G",
                    help="generated traces only: split requests into G "
                         "seeded populations sharing a common prompt "
                         "prefix (the system-prompt traffic shape the "
                         "prefix cache exploits)")
    sv.add_argument("--prefix-len", type=int, default=None,
                    dest="prefix_len", metavar="TOKENS",
                    help="shared-prefix length for --prefix-groups "
                         "(clamped per request to prompt_len - 1; "
                         "default: the prompt-range midpoint)")
    sv.add_argument("--slo", type=float, default=None, metavar="SEC",
                    help="per-request deadline (SLO) stamped on every "
                         "generated request: queued requests whose wait "
                         "already blew it are shed "
                         "(request-rejected[reason=deadline]) and "
                         "completions past it are counted "
                         "(docs/serving.md)")
    sv.add_argument("--dispatch-retries", type=int, default=None,
                    dest="max_dispatch_retries",
                    help="bounded retries for a transiently-failed "
                         "prefill/decode dispatch (default 2; host "
                         "state rolls back to the pre-dispatch "
                         "snapshot before each retry)")
    sv.add_argument("--dispatch-deadline-factor", type=float,
                    default=None, dest="dispatch_deadline_factor",
                    help="arm the in-flight dispatch watchdog: abandon "
                         "a decode unit exceeding FACTOR x K x the "
                         "per-step EMA (requests journaled "
                         "request-failed[reason=hung-dispatch]; "
                         "default: off)")
    sv.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="serve through the replica-level fleet "
                         "supervisor: N failure domains, each its own "
                         "engine, with health-fencing / failover / "
                         "hedging / the overload degradation ladder; "
                         "the parallelism section (or auto-plan) then "
                         "describes ONE replica's mesh (docs/fleet.md)")
    sv.add_argument("--hedge-factor", type=float, default=None,
                    dest="hedge_factor", metavar="F",
                    help="fleet hedging: duplicate a request still "
                         "resident past F x the observed p99 latency "
                         "onto another replica — first completion "
                         "wins, the loser is canceled (needs "
                         "--replicas >= 2; docs/fleet.md)")
    sv.add_argument("--fault-plan", default=None, metavar="PLAN",
                    help="deterministic fault-injection plan for the "
                         "serving chaos harness (e.g. "
                         "'serve-decode-fail:1'; DLBB_FAULT_PLAN env "
                         "is the default; docs/resilience.md)")
    sv.add_argument("--resume", action="store_true",
                    help="finish a preempted serving run from the "
                         "serving_resume.json checkpoint in --output: "
                         "replays the remaining trace and merges both "
                         "sessions into the final artifact set")
    sv.add_argument("--output", default=None,
                    help="output directory (default results/serving)")
    sv.add_argument("--simulate", type=int, default=0, metavar="N")
    # --trace names the TRAFFIC here, so the xplane flag gets a
    # serve-specific name (main() routes it into maybe_trace)
    sv.add_argument("--xplane-trace", default=None, metavar="DIR",
                    dest="xplane_trace",
                    help="write an XLA profiler trace (xplane) to DIR "
                         "(the --trace flag of the other levels; "
                         "DLBB_TRACE_DIR env is the default)")
    sv.add_argument("--span-trace", default=None, metavar="FILE",
                    dest="span_trace",
                    help="write a host-side span trace (Chrome "
                         "trace-event JSON) of the run to FILE; "
                         "DLBB_SPANS env is the default "
                         "(docs/observability.md)")
    sv.add_argument("--device-trace", default=None, metavar="DIR",
                    dest="device_trace",
                    help="capture one prefill + one decode scan through "
                         "the obs/capture gate AFTER the trace is served "
                         "(outside every timed region) under DIR; "
                         "DLBB_DEVICE_TRACE env is the default; parsed "
                         "by `obs devtrace` (docs/observability.md)")

    pl = sub.add_parser(
        "plan",
        help="cm2-driven parallelism-plan autotuner: enumerate the full "
             "plan space, statically prune (validate_*/HBM, every pruned "
             "point journaled with its reason), rank by the fitted cost "
             "model, measure the top-k through the real engines "
             "(--auto); or price a fleet capacity curve over a traffic "
             "trace + SLO (--capacity) (docs/autotune.md)",
    )
    mode = pl.add_mutually_exclusive_group(required=True)
    mode.add_argument("--auto", action="store_true",
                      help="run the predict-prune-measure plan search")
    mode.add_argument("--capacity", action="store_true",
                      help="run the fleet capacity planner (predicted vs "
                           "measured goodput/TTFT per plan + replicas-"
                           "for-N-users curve, published to SERVING.md)")
    pl.add_argument("--target", default="serving",
                    choices=("serving", "train"),
                    help="which engine's plan space to search (--auto)")
    pl.add_argument("--top-k", type=int, default=2, dest="top_k",
                    help="cm2-ranked plans to validate with real "
                         "measured runs (the default heuristic plan is "
                         "always measured too)")
    pl.add_argument("--no-measure", action="store_true",
                    dest="no_measure",
                    help="static search only: enumerate, prune, rank — "
                         "skip the measured validation runs")
    pl.add_argument("--no-mesh-champions", action="store_true",
                    dest="no_mesh_champions",
                    help="measure only the overall top-k (default: also "
                         "measure the predicted-best plan of every "
                         "surviving mesh factorization, so a mesh the "
                         "model mis-ranks still reaches the agreement "
                         "table)")
    pl.add_argument("--trace", default="poisson",
                    help="traffic kind for the measured serving runs "
                         "(poisson, bursty, diurnal) or a saved trace")
    pl.add_argument("--requests", type=int, default=24,
                    help="requests per measured serving run")
    pl.add_argument("--rate", type=float, default=None,
                    help="mean arrival rate in req/s (default 32)")
    pl.add_argument("--seed", type=int, default=42,
                    help="trace seed (shared by every measured run)")
    pl.add_argument("--prompt-range", type=int, nargs=2, default=None,
                    dest="prompt_range", metavar=("MIN", "MAX"),
                    help="generated traces only: prompt-length bounds")
    pl.add_argument("--output-range", type=int, nargs=2, default=None,
                    dest="output_range", metavar=("MIN", "MAX"),
                    help="generated traces only: output-length bounds "
                         "(the committed reference workload saturates "
                         "decode with --rate 1e5 --prompt-range 8 16 "
                         "--output-range 240 240)")
    pl.add_argument("--slo", type=float, default=30.0,
                    help="TTFT SLO in seconds (--capacity; stamps the "
                         "trace's deadline_s)")
    pl.add_argument("--user-rate", type=float, default=0.2,
                    dest="user_rate",
                    help="req/s one user issues (--capacity curve)")
    pl.add_argument("--users", type=int, nargs="+",
                    default=(4, 8, 16, 32, 64),
                    help="N-user points on the capacity curve")
    pl.add_argument("--fit-dir", default=None, dest="fit_dir",
                    help="cm2 fitted-coefficient DB directory (default "
                         "stats/analysis/costmodel_fit; a missing fit "
                         "fails the search closed: every point is "
                         "journaled cm2-fit-missing)")
    pl.add_argument("--tier", default=None,
                    help="cost-model tier (default cpu-sim)")
    pl.add_argument("--output", default=None,
                    help="output directory (default results/autotune or "
                         "results/capacity)")
    pl.add_argument("--bench-out", default=None, dest="bench_out",
                    help="also write the repo-root bench artifact "
                         "(BENCH_autotune.json; --auto only)")
    pl.add_argument("--simulate", type=int, default=0, metavar="N")

    tr = sub.add_parser("train", help="DDP/ZeRO-{1,2,3} training-loop benchmark")
    tr.add_argument("--config", required=True, help="YAML experiment config")
    tr.add_argument("--simulate", type=int, default=0, metavar="N")
    tr.add_argument("--zero1", action="store_true", help="shard optimizer state (ZeRO-1)")
    tr.add_argument("--zero", type=int, default=None, choices=(0, 1, 2, 3),
                    metavar="STAGE", dest="zero_stage",
                    help="ZeRO stage: 0=DDP, 1=opt-state sharding, "
                         "2=+grad reduce-scatter, 3=FSDP param sharding")
    tr.add_argument("--output", default=None)
    tr.add_argument("--tp-overlap", default=None,
                    choices=("off", "ring", "bidir"), dest="tp_overlap",
                    help="force model.tp_overlap (see the e2e flag)")
    tr.add_argument("--grad-compression", default=None,
                    choices=("none", "int8", "fp8"), dest="grad_compression",
                    help="override training.grad_compression: quantise "
                         "the dp gradient reduction to an int8/fp8 wire "
                         "with an error-feedback residual "
                         "(docs/compression.md)")
    _add_trace(tr)

    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if getattr(args, "simulate", 0):
        from dlbb_tpu.utils.simulate import force_cpu_simulation

        force_cpu_simulation(args.simulate)
    elif (
        os.environ.get("DLBB_DISTRIBUTED") == "auto"
        and args.cmd in DEVICE_COMMANDS
    ):
        # pod launcher path (launch/launch_tpu_pod.sh): stand up
        # jax.distributed across hosts before any backend use; stats
        # subcommands are pure file processing and skip the handshake
        from dlbb_tpu.comm.mesh import initialize_distributed

        ctx = initialize_distributed(auto=True)
        print(
            f"[distributed] process {ctx.process_id}/{ctx.num_processes}, "
            f"{ctx.num_devices} devices"
        )

    if args.cmd in DEVICE_COMMANDS:
        from dlbb_tpu.utils.compile_cache import configure_compile_cache
        from dlbb_tpu.utils.simulate import (
            NoAcceleratorError,
            require_accelerator,
        )

        configure_compile_cache()
        try:
            require_accelerator()
        except NoAcceleratorError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    if getattr(args, "variant", None) is not None:
        from dlbb_tpu.comm.variants import get_variant

        try:
            get_variant(args.variant)
        except KeyError as e:
            print(f"error: {e.args[0]}")
            return 2

    if args.cmd in ("bench1d", "bench3d", "e2e", "train", "serve"):
        # stats subcommands are pure numpy file processing — no backend,
        # no profiler, and no jax import even when DLBB_TRACE_DIR is set
        from dlbb_tpu.obs import spans
        from dlbb_tpu.utils.profiling import maybe_trace

        span_path = getattr(args, "span_trace", None) \
            or spans.default_span_path()
        # serve's --trace selects the traffic; its xplane dir rides the
        # dedicated --xplane-trace flag
        profile_dir = (getattr(args, "xplane_trace", None)
                       if args.cmd == "serve"
                       else getattr(args, "trace", None))
        with spans.tracing(span_path, meta={"cmd": args.cmd}) as tracer, \
                maybe_trace(profile_dir) as trace_dir:
            rc = _dispatch(args)
        if trace_dir:
            print(f"[trace] xplane trace written to {trace_dir}")
        if tracer is not None:
            print(f"[obs] span trace written to {tracer.path} "
                  "(load in https://ui.perfetto.dev)")
        return rc
    return _dispatch(args)


def _pipeline_arg(args):
    """--no-pipeline > --pipeline > None (host-auto)."""
    if args.no_pipeline:
        return False
    if args.pipeline:
        return True
    return None


def _dispatch(args) -> int:
    if args.cmd == "bench1d":
        from dlbb_tpu.bench import (
            DATA_SIZES_1D,
            EXTENDED_DATA_SIZES_1D,
            OPERATIONS_1D,
            Sweep1D,
            run_sweep,
        )

        if args.sizes == ["extended"]:
            sizes = tuple(EXTENDED_DATA_SIZES_1D.items())
        elif args.sizes:
            table = EXTENDED_DATA_SIZES_1D
            unknown = [s for s in args.sizes if s not in table]
            if unknown:
                print(f"unknown size labels {unknown}; known: {list(table)}")
                return 2
            sizes = tuple((s, table[s]) for s in args.sizes)
        else:
            sizes = tuple(DATA_SIZES_1D.items())
        sweep = Sweep1D(
            implementation=args.impl,
            variant=args.variant,
            operations=tuple(args.ops) if args.ops else OPERATIONS_1D,
            data_sizes=sizes,
            rank_counts=tuple(args.ranks) if args.ranks else (2, 4, 8),
            dtype=args.dtype,
            warmup_iterations=args.warmup,
            measurement_iterations=args.iters,
            output_dir=args.output or "results/1d",
            resume=args.resume,
            pipeline=_pipeline_arg(args),
            prefetch=args.prefetch,
            compile_cache=args.compile_cache,
            fault_plan=args.fault_plan,
            unit_deadline_seconds=args.unit_deadline,
            max_retries=args.max_retries,
            journal=not args.no_journal,
            span_trace=args.span_trace,
            device_trace_dir=args.device_trace,
        )
        files = run_sweep(sweep)
        # resume mode counts pre-existing artifacts too — don't claim writes
        print(f"{len(files)} result artifacts in {sweep.output_dir}")
        return 0

    if args.cmd == "bench3d":
        from dlbb_tpu.bench import GRID_3D, OPERATIONS_3D, Sweep3D, run_sweep

        sweep = Sweep3D(
            implementation=args.impl,
            variant=args.variant,
            operations=tuple(args.ops) if args.ops else OPERATIONS_3D,
            batch_sizes=tuple(args.batch) if args.batch else tuple(GRID_3D["batch_sizes"]),
            seq_lengths=tuple(args.seq) if args.seq else tuple(GRID_3D["seq_lengths"]),
            hidden_dims=tuple(args.hidden) if args.hidden else tuple(GRID_3D["hidden_dims"]),
            rank_counts=tuple(args.ranks) if args.ranks else (4, 8),
            dtype=args.dtype,
            warmup_iterations=args.warmup,
            measurement_iterations=args.iters,
            output_dir=args.output or "results/3d",
            resume=args.resume,
            pipeline=_pipeline_arg(args),
            prefetch=args.prefetch,
            compile_cache=args.compile_cache,
            fault_plan=args.fault_plan,
            unit_deadline_seconds=args.unit_deadline,
            max_retries=args.max_retries,
            journal=not args.no_journal,
            span_trace=args.span_trace,
            device_trace_dir=args.device_trace,
        )
        files = run_sweep(sweep)
        print(f"{len(files)} result artifacts in {sweep.output_dir}")
        return 0

    if args.cmd == "stats1d":
        from dlbb_tpu.stats import process_1d_results

        results = process_1d_results(
            args.input, args.output,
            algorithm_bandwidth=args.algorithm_bandwidth,
        )
        print(f"processed {len(results)} result files")
        return 0

    if args.cmd == "stats3d":
        from dlbb_tpu.stats import process_3d_results

        results = process_3d_results(args.input, args.output, args.impl)
        print(f"processed {len(results)} result files")
        return 0

    if args.cmd == "compare":
        from pathlib import Path

        from dlbb_tpu.stats import write_comparison

        summary = write_comparison(
            Path(args.reference), Path(args.own_1d), Path(args.own_3d),
            Path(args.output), repo_root=Path.cwd(),
        )
        for dim in ("1d", "3d"):
            s = summary[dim]
            print(f"{dim}: {s['configs']} configs — {s['beat']} beat, "
                  f"{s['match']} match, {s['lose']} lose")
        print(f"report written to {args.output}/COMPARISON.md")
        return 0

    if args.cmd == "reports":
        from pathlib import Path

        from dlbb_tpu.stats import write_variants_report
        from dlbb_tpu.stats.parallelism_report import (
            DEFAULT_FAMILIES,
            write_parallelism_report,
        )
        from dlbb_tpu.stats.variants_report import write_variants3d_report

        stats_root, results_root = Path(args.stats), Path(args.results)
        produced = 0
        summary = write_variants_report(stats_root / "variants")
        if summary["winners"]:
            produced += 1
            print(f"variants: {len(summary['winners'])} sizes across rank "
                  f"counts {sorted(summary.get('ranks', {}))} -> "
                  f"{stats_root / 'variants' / 'VARIANTS.md'}")
        else:
            print(f"variants: no stats under {stats_root / 'variants'} — "
                  "skipped")
        rows3d = write_variants3d_report(stats_root / "variants3d")
        if rows3d:
            produced += 1
            print(f"variants3d: {len(rows3d)} joined configs -> "
                  f"{stats_root / 'variants3d' / 'VARIANTS3D.md'}")
        else:
            print(f"variants3d: no stats under "
                  f"{stats_root / 'variants3d'} — skipped")
        # only (re)write the parallelism report when its input artifacts
        # exist: a typo'd --results must not clobber the committed report
        # with an all-null table
        par_dir = results_root / "parallelism"
        if any(par_dir.glob("train_*.json")):
            rows = write_parallelism_report(
                par_dir, stats_root / "parallelism", DEFAULT_FAMILIES,
            )
            measured = [
                r for r in rows if r["step_time_mean_s"] is not None
            ]
            produced += 1
            print(f"parallelism: {len(measured)} measured members -> "
                  f"{stats_root / 'parallelism' / 'PARALLELISM.md'}")
        else:
            print(f"parallelism: no train_*.json under {par_dir} — "
                  "skipped")
        cp_dir = par_dir / "cp_scaling"
        if any(cp_dir.glob("train_ddp_cp_s*.json")):
            from dlbb_tpu.stats.parallelism_report import (
                write_cp_scaling_report,
            )

            cp_rows = write_cp_scaling_report(
                cp_dir, stats_root / "parallelism",
            )
            produced += 1
            print(f"cp_scaling: {len(cp_rows)} (S, sp) cells -> "
                  f"{stats_root / 'parallelism' / 'CP_SCALING.md'}")
        else:
            print(f"cp_scaling: no train_ddp_cp_s*.json under {cp_dir} — "
                  "skipped")
        serve_dir = results_root / "serving"
        if any(p.name != "serving_manifest.json"
               for p in serve_dir.rglob("serving_*.json")) or \
                any(serve_dir.rglob("fleet_*.json")):
            from dlbb_tpu.stats.serving_report import write_serving_report

            srows = write_serving_report(serve_dir, stats_root / "serving")
            if srows:
                produced += 1
                print(f"serving: {len(srows)} run(s) -> "
                      f"{stats_root / 'serving' / 'SERVING.md'}")
        else:
            print(f"serving: no serving_*.json under {serve_dir} — "
                  "skipped")
        bench_fleet = Path("BENCH_fleet.json")
        if bench_fleet.exists():
            from dlbb_tpu.stats.serving_report import write_fleet_report

            flrows = write_fleet_report(bench_fleet,
                                        stats_root / "serving")
            if flrows:
                produced += 1
                print(f"fleet: {len(flrows)} setting(s) -> "
                      f"{stats_root / 'serving' / 'FLEET.md'}")
        else:
            print("fleet: no BENCH_fleet.json at the repo root — "
                  "skipped")
        bench_serve = Path("BENCH_serve.json")
        if bench_serve.exists():
            from dlbb_tpu.stats.serving_report import write_fastpath_report

            frows = write_fastpath_report(bench_serve,
                                          stats_root / "serving")
            if frows:
                produced += 1
                print(f"fastpath: {len(frows)} setting(s) -> "
                      f"{stats_root / 'serving' / 'FASTPATH.md'}")
        else:
            print("fastpath: no BENCH_serve.json at the repo root — "
                  "skipped")
        bench_autotune = Path("BENCH_autotune.json")
        if bench_autotune.exists():
            from dlbb_tpu.stats.parallelism_report import (
                write_autotune_report,
            )

            arows = write_autotune_report(bench_autotune,
                                          stats_root / "parallelism")
            if arows:
                produced += 1
                print(f"autotune: {len(arows)} measured plan(s) -> "
                      f"{stats_root / 'parallelism' / 'AUTOTUNE.md'}")
        else:
            print("autotune: no BENCH_autotune.json at the repo root — "
                  "skipped")
        from dlbb_tpu.stats.northstar import (
            default_stats_1d_csv,
            write_northstar_report,
        )

        ns = write_northstar_report(
            default_stats_1d_csv(stats_root), stats_root / "northstar",
        )
        if ns:
            produced += 1
            print(f"northstar: {sum(ns.values())} size rows across "
                  f"{list(ns)} -> {stats_root / 'northstar' / 'NORTHSTAR.md'}")
        else:
            print(f"northstar: no north-star rows in "
                  f"{default_stats_1d_csv(stats_root)} — skipped")
        if produced == 0:
            print("error: nothing to report — check --stats/--results "
                  "point at the committed trees")
            return 1
        return 0

    if args.cmd == "analyze":
        from dlbb_tpu.analysis import run_analysis

        return run_analysis(
            which=args.which, root=args.root, json_path=args.json,
            strict_warnings=args.strict_warnings,
            baselines=args.baselines, tier=args.tier, model=args.model,
            output=args.output,
        )

    if args.cmd == "obs":
        from dlbb_tpu.obs import run_obs

        return run_obs(
            which=args.which, journal=args.journal, output=args.output,
            baselines=args.baselines, calibration=args.calibration,
            report=args.report, tier=args.tier, reps=args.reps,
            warmup=args.warmup, targets=args.targets,
            strict_warnings=args.strict_warnings, model=args.model,
            fit_dir=args.fit_dir, results=args.results,
            trace=args.span_trace_file, min_samples=args.min_samples,
            host_filter=args.host_filter,
        )

    if args.cmd == "chaos":
        from dlbb_tpu.resilience.chaos import run_chaos

        return run_chaos(plan=args.plan, output=args.output)

    if args.cmd == "e2e":
        try:
            from dlbb_tpu.bench.e2e import run_e2e_from_config
        except ImportError:
            print("error: the e2e benchmark module is not available in this build")
            return 2

        result = run_e2e_from_config(args.config, output_dir=args.output,
                                     tp_overlap=args.tp_overlap)
        print(f"forward mean {result['forward_time']['mean'] * 1e3:.2f} ms")
        return 0

    if args.cmd == "serve":
        from dlbb_tpu.serve.bench import run_serve_from_config

        result = run_serve_from_config(
            args.config,
            trace=args.trace,
            num_requests=args.requests,
            seed=args.seed,
            rate=args.rate,
            output_dir=args.output,
            overrides={
                "max_batch": args.max_batch,
                "block_size": args.block_size,
                "max_seq": args.max_seq,
                "queue_capacity": args.queue_capacity,
                "decode_horizon": args.decode_horizon,
                "inflight_window": args.inflight_window,
                "prefill_chunk": args.prefill_chunk,
                "speculation": args.speculation,
                "spec_gamma": args.spec_gamma,
                "spec_adaptive": args.spec_adaptive,
                "max_dispatch_retries": args.max_dispatch_retries,
                "dispatch_deadline_factor":
                    args.dispatch_deadline_factor,
                "prefix_caching": args.prefix_caching,
                "kv_quantization": args.kv_quantization,
                "temperature": args.temperature,
                "sample_seed": args.sample_seed,
                "hedge_factor": args.hedge_factor,
            },
            resume=args.resume,
            fault_plan=args.fault_plan,
            slo=args.slo,
            device_trace=args.device_trace,
            prefix_groups=args.prefix_groups,
            prefix_len=args.prefix_len,
            replicas=args.replicas,
        )
        req = result["requests"]
        if "failovers" in result:
            live = sum(1 for r in result["replicas"]
                       if r["status"] == "ok")
            print(
                f"fleet: {live}/{len(result['replicas'])} replica(s) "
                f"healthy, {result['failovers']['total']} failover(s), "
                f"{result['hedges']['issued']} hedge(s) issued, "
                f"degrade level {result['degrade']['level']} "
                f"({result['degrade']['name']})"
            )
        if result.get("prefix", {}).get("enabled"):
            pre = result["prefix"]
            print(
                f"prefix cache: {pre['hits']} hit(s), "
                f"{pre['tokens_reused']} token(s) reused "
                f"(hit rate {pre['hit_rate']:.2f})"
            )
        if result.get("preempted"):
            print(
                f"preempted after {req['completed']} completed "
                f"request(s); {len(result['remaining_rids'])} remain — "
                "finish with `serve --resume`"
            )
            return 0
        print(
            f"goodput {result['goodput_tokens_per_s']:.0f} tok/s over "
            f"{req['completed']} completed / {req['rejected']} rejected "
            f"request(s)"
        )
        from dlbb_tpu.resilience import inject

        if req.get("failed") and not (
                args.fault_plan or os.environ.get(inject.ENV_VAR)):
            # the engine contains a failed dispatch and serves on (the
            # resilience contract); with no fault plan asking for one, a
            # failed request is a real failure and the run says so
            print(f"error: {req['failed']} request(s) failed with no "
                  "fault plan active (see resilience.failed in the "
                  "report)", file=sys.stderr)
            return 1
        return 0

    if args.cmd == "plan":
        from dlbb_tpu.analysis.costmodel import DEFAULT_TIER

        tier_name = args.tier or DEFAULT_TIER
        n_dev = args.simulate
        if not n_dev:
            import jax

            n_dev = len(jax.devices())
        trace_params = {}
        if args.prompt_range:
            trace_params["prompt_range"] = tuple(args.prompt_range)
        if args.output_range:
            trace_params["output_range"] = tuple(args.output_range)
        if args.capacity:
            from dlbb_tpu.plan.autotune import run_capacity_plan

            run_capacity_plan(
                n_devices=n_dev, slo=args.slo, users=tuple(args.users),
                user_rate=args.user_rate, trace=args.trace,
                num_requests=args.requests, seed=args.seed,
                rate=args.rate, trace_params=trace_params or None,
                output_dir=args.output or "results/capacity",
                tier_name=tier_name, fit_dir=args.fit_dir,
            )
            return 0
        from dlbb_tpu.plan.autotune import run_plan_search

        result = run_plan_search(
            target=args.target, n_devices=n_dev, top_k=args.top_k,
            output_dir=args.output or "results/autotune",
            trace=args.trace, num_requests=args.requests,
            seed=args.seed, rate=args.rate,
            trace_params=trace_params or None, tier_name=tier_name,
            fit_dir=args.fit_dir, measure=not args.no_measure,
            mesh_champions=not args.no_mesh_champions,
            bench_out=args.bench_out,
        )
        return 1 if result.get("error") else 0

    if args.cmd == "train":
        try:
            from dlbb_tpu.train.loop import run_train_from_config
        except ImportError:
            print("error: the train module is not available in this build")
            return 2

        result = run_train_from_config(
            args.config, zero1=args.zero1, zero_stage=args.zero_stage,
            output_dir=args.output, tp_overlap=args.tp_overlap,
            grad_compression=args.grad_compression,
        )
        if result.get("preempted") and "step_time" not in result:
            print(f"preempted at step {result['preempted_at_step']}; "
                  "checkpoint saved — resume to continue")
            return 0
        print(f"step mean {result['step_time']['mean'] * 1e3:.2f} ms")
        return 0

    return 2


if __name__ == "__main__":
    sys.exit(main())
