#!/usr/bin/env bash
# comm-lint CI gate: both static passes, no TPU needed.
#
#   scripts/run_static_analysis.sh [report.json]
#
# Runs the AST source lint over dlbb_tpu/ + scripts/ and the HLO collective
# audit on an 8-device CPU-simulated mesh (the same surface as
# `python -m dlbb_tpu.cli analyze all --simulate 8`), then the fast tier-1
# analyzer tests.  Exit nonzero on any finding or test failure.
set -euo pipefail
cd "$(dirname "$0")/.."

REPORT="${1:-results/analysis/comm_lint.json}"

JAX_PLATFORMS=cpu python -m dlbb_tpu.cli analyze all --simulate 8 \
    --strict-warnings --json "$REPORT"

JAX_PLATFORMS=cpu python -m pytest tests/test_analysis.py -q -m 'not slow' \
    -p no:cacheprovider

# schedule_smoke (docs/schedule_audit.md): the α–β schedule audit runs
# INSIDE `analyze all` above (one lowering serves the byte + schedule
# passes: every ring hop must be hidden behind a straddling matmul, no
# divergent-branch collective sequences).  `analyze diff` re-audits once
# for the regression-baseline gate against the committed
# stats/analysis/baselines/ snapshots (fails on >10% critical-path /
# wire growth or any new collective kind; `analyze snapshot` regenerates
# after an intended change).  Exit-code contract pinned at 0 clean /
# 1 findings / 2 crash so this composes with the chaos and compression
# stages below.
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli analyze diff --simulate 8
JAX_PLATFORMS=cpu python -m pytest tests/test_schedule_audit.py -q \
    -m schedule_smoke -p no:cacheprovider

# memory_smoke (docs/memory_audit.md): the buffer-liveness memory audit
# runs INSIDE `analyze all` above (per-target peak_live_bytes against
# the analytic ceilings, donation aliasing, the transient-replicated
# gate and the serving-cache cross-check), and `analyze diff` above
# regression-gates the committed peak/transient snapshots (>10% growth
# on the memory axis alone fails).  The pytest marker pins the donation
# proof on real serving/train targets AND the seeded violations
# (dropped donation, fat replicated intermediate) exiting 1; the CLI
# run below exercises the observability surface — memory_audit.json +
# sweep_manifest merge + analysis_peak_live_bytes gauges — over the
# default registry, clean with zero suppressions.
JAX_PLATFORMS=cpu python -m pytest tests/test_memory_audit.py -q \
    -m memory_smoke -p no:cacheprovider
MEM_TMP="$(mktemp -d)"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli analyze memory --simulate 8 \
    --strict-warnings --output "$MEM_TMP"
grep -q 'dlbb_analysis_peak_live_bytes' "$MEM_TMP/metrics.prom" \
    || { echo "memory_smoke: metrics.prom lost the peak gauges"; exit 1; }
grep -q '"memory_audit"' "$MEM_TMP/sweep_manifest.json" \
    || { echo "memory_smoke: manifest lost the memory-audit record"; \
         exit 1; }
rm -rf "$MEM_TMP"

# numerics_smoke (docs/numerics.md): the dtype-flow numerics audit runs
# INSIDE `analyze all` above (low-precision accumulators priced with
# Higham sequential/tree error bounds, silent upcasts against the
# declared policy dtype, quantise round trips without intervening
# arithmetic, convert churn across fusion boundaries, bitwise-
# reproducibility claims vs multi-replica reduction order), and
# `analyze diff` above regression-gates the committed numerics axis
# (>2x error-bound growth, >1.25x convert churn, or ANY new
# low-precision accumulation site fails).  The pytest marker pins the
# seeded-violation fixtures tripping every rule, real targets staying
# clean, and the fp64 shadow cross-check; the CLI run below exercises
# the observability surface — numerics_audit.json + manifest merge +
# analysis_numerics_* and per-pass analysis_findings gauges — over the
# default registry (the pass fails closed on an empty target surface),
# clean with zero suppressions.  The standalone shadow run then
# re-confirms the analytic bounds empirically against fp64 references.
JAX_PLATFORMS=cpu python -m pytest tests/test_numerics_audit.py -q \
    -m numerics_smoke -p no:cacheprovider
NUM_TMP="$(mktemp -d)"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli analyze numerics --simulate 8 \
    --strict-warnings --output "$NUM_TMP"
grep -q 'dlbb_analysis_numerics_max_rel_error_bound' "$NUM_TMP/metrics.prom" \
    || { echo "numerics_smoke: metrics.prom lost the error-bound gauges"; \
         exit 1; }
grep -q 'dlbb_analysis_findings{' "$NUM_TMP/metrics.prom" \
    || { echo "numerics_smoke: metrics.prom lost the per-pass finding gauges"; \
         exit 1; }
grep -q '"numerics_audit"' "$NUM_TMP/sweep_manifest.json" \
    || { echo "numerics_smoke: manifest lost the numerics-audit record"; \
         exit 1; }
JAX_PLATFORMS=cpu python -m dlbb_tpu.analysis.numerics_shadow \
    --output "$NUM_TMP/shadow"
grep -q '"refuted": 0' "$NUM_TMP/shadow/shadow_report.json" \
    || { echo "numerics_smoke: shadow cross-check refuted a static bound"; \
         exit 1; }
rm -rf "$NUM_TMP"

# obs_smoke (docs/observability.md): a span-traced + device-captured
# mini-sweep must publish stats equivalent to an untraced serial run
# (dedicated profile reps never enter the stats series; the span trace
# is valid Perfetto-loadable trace-event JSON), then the
# predicted-vs-measured calibration loop — `cli obs calibrate` on a
# micro-op subset joined against the committed α–β schedule baselines,
# and `cli obs diff` against the committed sim-tier calibration
# baseline (stats/analysis/calibration/), failing when the cost-model
# error regresses past the slack.  The profiler-in-timed-region lint
# rule gating captures runs in `analyze all` above.  Exit codes pinned
# 0 clean / 1 findings / 2 crash, like every other gate here.
JAX_PLATFORMS=cpu python -m pytest tests/test_obs.py -q \
    -m obs_smoke -p no:cacheprovider
OBS_TMP="$(mktemp -d)"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli obs diff --simulate 8 \
    --output "$OBS_TMP" --targets "::allgather" "::alltoall" "::barrier" \
    --reps 15 --warmup 5
rm -rf "$OBS_TMP"

# fit_smoke (docs/observability.md, "Fitting & attribution"): the cm2
# loop — (1) the fit pipeline proves out on the committed mini corpus
# (results/fit_corpus) into a THROWAWAY DB: seeded-coefficient recovery
# + degenerate-corpus refusal run in the pytest marker; (2) `obs
# calibrate --model cm2` prices a micro-op subset from the COMMITTED
# fitted DB (stats/analysis/costmodel_fit/) and `obs diff --model cm2`
# gates the joined-subset geomean against the committed cm2 calibration
# baseline (stats/analysis/calibration/calibration_baseline_cm2.json);
# (3) the calibrate run's sweep_manifest.json must record the fitted-DB
# version it priced with.
JAX_PLATFORMS=cpu python -m pytest tests/test_costmodel_fit.py -q \
    -m fit_smoke -p no:cacheprovider
FIT_TMP="$(mktemp -d)"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli obs fit \
    --results results/fit_corpus --tier cpu-sim --fit-dir "$FIT_TMP/db"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli obs diff --model cm2 --simulate 8 \
    --output "$FIT_TMP/cal" --targets "::allgather" "::alltoall" \
    "::barrier" --reps 15 --warmup 5
grep -q '"fit_version"' "$FIT_TMP/cal/sweep_manifest.json" \
    || { echo "fit_smoke: calibrate manifest lost the fitted-DB version"; \
         exit 1; }
rm -rf "$FIT_TMP"

# devtrace_smoke (docs/observability.md, "Device-trace analysis"): the
# captured pipeline end-to-end — the pytest marker runs a
# device-captured overlap-variant mini-sweep that must publish stats
# byte-equivalent to an uncaptured run (same proof style as obs_smoke)
# with `obs devtrace` green over it (measured overlap beside the
# committed static value, op-level fit samples mined); the unit tests
# in the same file pin bucket classification, warmup exclusion, the
# fail-closed contract and the serialized-ring gate on the committed
# golden capture.  Then the committed capture corpus re-parses
# BACKEND-FREE (exit 0: serialized-ring findings downgrade to warnings
# on the single-stream cpu-sim runtime by contract), and the
# β-identification round trip proves out into a THROWAWAY DB: fitting
# the program corpus + the committed devtrace report must identify β
# from the device-timed op samples — no pinned-from-cm1 marker (the
# committed-DB `obs fit` + `obs diff --model cm2` gate runs in
# fit_smoke above).  Zero suppressions.
JAX_PLATFORMS=cpu python -m pytest tests/test_devtrace.py -q \
    -m devtrace_smoke -p no:cacheprovider
DT_TMP="$(mktemp -d)"
python -m dlbb_tpu.cli obs devtrace \
    --journal results/fit_corpus/devtrace/sim8 --output "$DT_TMP"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli obs fit \
    --results results/fit_corpus stats/analysis/devtrace/sim8.json \
    --tier cpu-sim --host calibration --fit-dir "$DT_TMP/db"
python - "$DT_TMP/db/cm2_cpu-sim.json" <<'PY'
import json, sys
v = json.load(open(sys.argv[1]))["versions"][-1]
beta = v["coefficients"]["beta_bytes_per_us"]
assert "pinned" not in beta, f"devtrace_smoke: beta still pinned: {beta}"
assert v.get("device_samples"), "devtrace_smoke: no device samples used"
PY
rm -rf "$DT_TMP"

# compile-ahead sweep-engine smoke (bench/schedule.py is covered by the
# lint pass above; this exercises the pipelined path end-to-end on the
# simulated mesh — 2-op mini-sweep, compile accounting, manifest)
JAX_PLATFORMS=cpu python -m pytest tests/test_bench.py -q \
    -m pipeline_smoke -p no:cacheprovider

# overlapped collective-matmul smoke (docs/overlap.md): tp_overlap
# ring/bidir forward must match the GSPMD fused path on the simulated
# dp2 x tp4 mesh (the HLO-side decomposition contract is enforced by the
# audit above via the overlap targets in the default registry)
JAX_PLATFORMS=cpu python -m pytest tests/test_collective_matmul.py -q \
    -m overlap_smoke -p no:cacheprovider

# chaos smoke (docs/resilience.md): the fault matrix through the real
# sweep engine — transient retry, NaN-stat refusal, torn-write resume
# re-validation, hung-unit watchdog quarantine, SIGTERM journaled stop,
# corrupted-checkpoint fallback — each injection deterministic, each
# invariant asserted (no corrupt artifact survives; resume completes the
# grid).  The subprocess SIGKILL class runs in the slow tier
# (tests/test_resilience.py::test_chaos_gate_kill_class).
JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q \
    -m chaos_smoke -p no:cacheprovider

# serving smoke (docs/serving.md): a seeded 30-request Poisson
# mini-trace through the continuous-batching engine on the simulated
# dp2 x tp4 mesh — zero rejected-by-bug requests (queue capacity covers
# the whole trace, so any rejection is an engine bug), a schema-valid
# span-trace file, journaled request lifecycle, metrics.prom export,
# and the bench artifact set.  The HLO-side serving contract (decode =
# tiny tp collectives only, activation byte ceiling proving no
# KV-cache regather, donated cache carry) is enforced by `analyze all`
# above via the serve/engine.py targets in the default registry, and
# regression-gated by `analyze diff` against the committed baselines —
# zero suppressions.
JAX_PLATFORMS=cpu python -m pytest tests/test_serve.py -q \
    -m serve_smoke -p no:cacheprovider

# serve_fastpath_smoke (docs/serving.md): the decode fast path's
# equivalence contract — the per-step and fused-K engines must produce
# IDENTICAL completed-token sequences on a seeded mini-trace (fused
# scans, in-flight window, chunked prefill all engaged), with
# schema-valid artifacts and the fast-path metrics counters present.
# The HLO-side contract for the two fast-path jit families (fused-scan
# decode: trip-count-weighted tiny tp psums only; chunked prefill:
# prefix-carry attention with zero cache reads across the slot shard)
# is enforced by `analyze all` above via the
# serve/engine.py::{decode_fused,prefill_chunk} targets,
# and `analyze diff` against the committed baselines makes a cache
# regather inside the scan body a CI failure — zero suppressions.
JAX_PLATFORMS=cpu python -m pytest tests/test_serve_fastpath.py -q \
    -m serve_fastpath_smoke -p no:cacheprovider

# prefix_smoke (docs/serving.md, "Prefix cache & quantized KV"): the
# shared-prefix / quantized-KV equivalence contract — the prefix-cached
# fp engine must produce IDENTICAL completed-token sequences to the
# no-sharing engine on a seeded shared-prefix mini-trace (an attach
# copies the exact block values the skipped chunks would have
# computed), the int8 engine completes the same trace, the trie's
# refcount/CoW accounting drains to zero shared blocks, and the bench
# artifacts carry prefix-attach journal events + hit counters + the
# quantized HBM record.  The HLO-side contract (shared-prefix attach =
# ZERO collectives; int8 decode's donated carry priced from the
# quantized layout) is enforced by `analyze all` above via the
# serve/engine.py::{prefix_attach,decode_step[int8]} targets, and
# `analyze diff` against the committed baselines — zero suppressions.
JAX_PLATFORMS=cpu python -m pytest tests/test_prefix.py -q \
    -m prefix_smoke -p no:cacheprovider

# serve_chaos_smoke (docs/resilience.md, serving faults): the serving
# fault matrix through the real continuous-batching engine on the
# simulated mesh — seeded mini-trace per serving fault class asserting
# transient prefill/decode dispatch failures retry after rolling the
# host ledger/slot state back to the pre-dispatch snapshot, exhausted
# retries fail only the affected requests (journaled request-failed
# with exception chains, never the run), the EMA-scaled watchdog
# abandons a hung dispatch and the engine continues on a fresh carry,
# torn bookkeeping replays, blown-SLO queue heads shed with
# reason=deadline, no corrupt artifact survives, and SIGTERM-mid-trace
# + `cli serve --resume` reproduces the uninterrupted artifact set
# (names + schema + per-request outcomes for non-preempted requests).
# The decode hot path stays provably injection-free: the static
# zero-instruction pin on the fused-scan body runs in this same file.
JAX_PLATFORMS=cpu python -m pytest tests/test_serve_resilience.py -q \
    -m serve_chaos_smoke -p no:cacheprovider

# spec_smoke (docs/serving.md, "Speculative decoding"): draft-and-verify
# multi-token decode — n-gram and draft-model drafters, per-step and
# fused, must stay TOKEN-IDENTICAL to the per-step greedy oracle on a
# seeded repeating-structure mini-trace (speculation buys forwards,
# never different results), with spec-verify journal events and
# acceptance counters exported.  The HLO-side contract (one fused
# (γ+1)-wide verify forward with per-layer psums only — NO per-draft-
# token collectives or trip-weighted loops — and the 1-layer draft
# plane's own donated cache) is enforced by `analyze all` above via the
# serve/engine.py::{verify_step,draft_scan,decode_fused_token} targets,
# and `analyze diff` against the committed baselines makes a per-token
# collective inside the verify body a CI failure — zero suppressions.
JAX_PLATFORMS=cpu python -m pytest tests/test_speculative.py -q \
    -m spec_smoke -p no:cacheprovider

# fleet_smoke (docs/fleet.md): replica-level fault tolerance — the
# 2-replica fleet supervisor on the simulated mesh must route
# deterministically (least-loaded with prefix affinity), survive a
# mid-trace replica kill with every resident failed over and the
# completed tokens byte-identical to the single-engine oracle, walk
# the degradation ladder monotonically with every transition journaled
# and counted, and write the full fleet artifact family (fleet report
# + manifest fault_domains + per-replica journal tracks + the
# failover/hedge/degrade metric families).  The supervisor stays
# provably host-side: the zero-injection pin asserts serve/fleet.py
# builds no device program at all, so a fleet (or a fault plan) can
# never change the jitted prefill/decode HLO the audits above pin.
JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q \
    -m fleet_smoke -p no:cacheprovider
FLEET_TMP="$(mktemp -d)"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli serve --simulate 8 \
    --requests 8 --rate 80 --seed 11 --replicas 2 \
    --output "$FLEET_TMP" >/dev/null
grep -q 'dlbb_serve_failovers_total' "$FLEET_TMP/metrics.prom" \
    || { echo "fleet_smoke: metrics.prom lost the failover counters"; \
         exit 1; }
grep -q '"fault_domains"' "$FLEET_TMP/serving_manifest.json" \
    || { echo "fleet_smoke: manifest lost the fault_domains record"; \
         exit 1; }
rm -rf "$FLEET_TMP"

# autotune_smoke (docs/autotune.md): the cm2-driven plan autotuner —
# full-grid accounting (searched == pruned + ranked, every pruned point
# journaled with a vocabulary reason), deterministic tie-broken ranking,
# fail-closed on a missing cm2 fit, the pinned calibration-grid
# agreement regression (top-2 contains the measured winner for >= 70%
# of the committed baseline families), and one measured top-1 vs
# default-heuristic run through the real serving engine.  The CLI run
# below exercises the static observability surface end-to-end:
# sweep_manifest.json search accounting + the plan_search_points /
# plan_agreement_ratio series in metrics.prom.
JAX_PLATFORMS=cpu python -m pytest tests/test_autotune.py -q \
    -m autotune_smoke -p no:cacheprovider
PLAN_TMP="$(mktemp -d)"
JAX_PLATFORMS=cpu python -m dlbb_tpu.cli plan --auto --simulate 8 \
    --no-measure --output "$PLAN_TMP"
grep -q 'dlbb_plan_search_points_total{outcome="searched"}' \
    "$PLAN_TMP/metrics.prom" \
    || { echo "autotune_smoke: metrics.prom lost the search counters"; \
         exit 1; }
grep -q 'dlbb_plan_agreement_ratio{scope="calibration-grid"}' \
    "$PLAN_TMP/metrics.prom" \
    || { echo "autotune_smoke: metrics.prom lost the agreement gauge"; \
         exit 1; }
grep -q '"searched"' "$PLAN_TMP/sweep_manifest.json" \
    || { echo "autotune_smoke: manifest lost the search accounting"; \
         exit 1; }
rm -rf "$PLAN_TMP"

# compressed-collective smoke (docs/compression.md): int8/fp8 allreduce_q
# mini-sweep through the real engine + one compressed train step whose
# losses track the uncompressed run — the HLO-side compression proof
# (pure quantised ring, total wire <= 0.55x the bf16 baseline, scale side
# channel included) is enforced by the audit above via the compressed
# targets in the default registry, with zero suppressions
JAX_PLATFORMS=cpu python -m pytest tests/test_compression.py -q \
    -m compression_smoke -p no:cacheprovider

echo "comm-lint: clean (report: $REPORT)"
