#!/usr/bin/env python
"""Shared-prefix / quantized-KV evidence: prefix cache vs full prefill.

Measures the serving engine's refcounted shared-prefix KV cache and the
int8 KV wire layout (docs/serving.md, "Prefix cache & quantized KV")
through the engine's own trace replay and writes ``BENCH_prefix.json``
at the repo root:

- **equivalence gate first** — every prefix-cached and int8-KV setting
  replays its bench trace with token capture on and is compared
  per-request against the no-sharing fp engine on the same trace; a
  gate failure aborts the bench before any number is published.  fp
  prefix attach must be BIT-EXACT (the donor blocks hold the same K/V
  the skipped prefill would recompute — any mismatch is a bug).  int8
  is gated within tolerance: at least ``INT8_MIN_IDENTICAL`` of the
  requests must be fully token-identical (one flipped argmax diverges
  the rest of that request's greedy feedback, so per-position rates
  are meaningless after the flip; the per-request identity fraction is
  the honest scalar, and it is published per row).
- **TTFT/goodput grid** — {prefix off, prefix on} x {fp, int8 KV} over
  TWO seeded shared-prefix traces (~85% and ~60% shared prompt
  tokens, both above the >=50%-shared bar the TTFT acceptance claim
  needs; the claim is made on the LOWER one).  TTFT is
  arrival-to-first-token (queueing included), so the
  prefix cache's skipped prefill chunks show up both directly (the
  attached request computes only its unmatched suffix) and through
  faster queue drain.  The acceptance bars — prefix-on TTFT p50 >=
  1.3x the prefix-off engine on the >=50%-shared trace, and int8
  admitting >= 1.8x resident requests under the SAME ``hbm_budget_gb``
  (static, priced by ``kv_cache_bytes_per_device`` — the formula the
  memory audit pins against the compiled decode carry) — are recorded
  as checked claims, not prose.

Methodology follows ``scripts/bench_serving.py``: one warmup replay per
engine absorbs compiles, settings are INTERLEAVED within each timed
repetition so host drift cancels, and medians of per-rep throughput are
reported with min/max spread.

On this image the mesh is CPU-simulated: prefill-chunk dispatches pay
host sync, which the attach path skips — the regime the prefix cache
targets — but the int8 rows pay the dequant/requant FLOPs at real CPU
cost rather than the bandwidth win a chip's HBM gives them, so the
int8 THROUGHPUT rows undersell; the capacity ratio is
regime-independent static arithmetic.  On the chip: not measured.

Usage: python scripts/bench_prefix.py [--requests N] [--reps R] [--chip]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from dlbb_tpu.utils.config import atomic_write_text  # noqa: E402

CHIP = "--chip" in sys.argv[1:]
if not CHIP:
    from dlbb_tpu.utils.simulate import force_cpu_simulation  # noqa: E402

    force_cpu_simulation(8)

import jax  # noqa: E402

from dlbb_tpu.comm.mesh import build_parallelism_mesh  # noqa: E402
from dlbb_tpu.models.configs import (  # noqa: E402
    ModelConfig,
    kv_cache_bytes_per_device,
)
from dlbb_tpu.serve.config import ServingConfig  # noqa: E402
from dlbb_tpu.serve.engine import ServingEngine  # noqa: E402
from dlbb_tpu.serve.traffic import generate_trace  # noqa: E402
from dlbb_tpu.stats.serving_report import write_prefix_report  # noqa: E402
from dlbb_tpu.utils.simulate import topology_record  # noqa: E402

# prefix attach requires dp=1 (the donor->slot copy is shard-local);
# tp=4 keeps the collective geometry the prefix_attach audit target pins
MESH = dict(data_parallel=1, tensor_parallel=4)

SERVE = dict(max_batch=8, block_size=8, max_seq=160, queue_capacity=64,
             prefill_chunk=16, hbm_budget_gb=None)

BENCH_MODEL = dict(hidden_size=64, num_layers=2, num_heads=4,
                   ffn_intermediate=128, dtype="float32",
                   attention="full")

# two shared-prefix populations per trace (two "system prompts"):
# share80 attaches 64 of ~80 prompt tokens (8 full blocks), share60
# attaches 48 (6 full blocks) — both above the >=50%-shared bar the
# TTFT acceptance claim is made on (the LOWER one carries the claim)
TRACES = {
    "share80": dict(seed=11, prefix_groups=2, prefix_len=64),
    "share60": dict(seed=13, prefix_groups=2, prefix_len=48),
}
PROMPTS = (65, 96)
OUTPUTS = (16, 32)

MODES = {
    "off_none": dict(prefix_caching=False, kv_quantization="none"),
    "on_none": dict(prefix_caching=True, kv_quantization="none"),
    "on_int8": dict(prefix_caching=True, kv_quantization="int8"),
}
BASELINE_MODE = "off_none"
# int8 tolerance: fraction of requests whose completed sequences must
# be fully identical to the fp oracle's (greedy feedback diverges a
# whole request on one flipped argmax, so this is the honest unit)
INT8_MIN_IDENTICAL = 0.7
# static capacity comparison: ~1 MiB/device of KV budget — small enough
# that resident-request counts are tangible, and the RATIO is
# budget-independent (bytes/request is linear in max_batch)
CAPACITY_BUDGET_GB = 0.001
ACCEPT_TTFT = {"setting": "share60/on_none",
               "baseline": "share60/off_none", "min_speedup": 1.3}
ACCEPT_CAPACITY = {"min_ratio": 1.8}


def _median(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2]


def _bench_trace(num_requests: int, *, seed: int, prefix_groups: int,
                 prefix_len: int):
    """Burst-ish poisson so the batch fills in one wave and the queue
    backs up — TTFT then prices both the attached request's shorter
    prefill and the faster drain of everyone behind it."""
    return generate_trace(
        "poisson", num_requests, seed=seed, rate=500.0,
        prompt_range=PROMPTS, output_range=OUTPUTS,
        prefix_groups=prefix_groups, prefix_len=prefix_len)


def _shared_share(trace) -> float:
    total = sum(r.prompt_len for r in trace.requests)
    shared = sum(r.prefix_len or 0 for r in trace.requests)
    return shared / total if total else 0.0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16,
                    help="requests per replayed trace (default 16 = "
                         "two admission waves at max_batch=8)")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved repetitions per setting (default 3)")
    ap.add_argument("--chip", action="store_true",
                    help="run on the real TPU chip instead of the "
                         "simulated mesh")
    ap.add_argument("--output", default=str(REPO / "BENCH_prefix.json"))
    args = ap.parse_args()

    model_cfg = ModelConfig.from_dict(BENCH_MODEL)
    mesh = build_parallelism_mesh(**MESH)
    traces = {
        name: _bench_trace(args.requests, **kw)
        for name, kw in TRACES.items()
    }

    # equivalence gate FIRST, on the published traces, with dedicated
    # capture engines (token capture syncs every step, so the timed
    # engines below run with it off): every prefix-cached / int8
    # setting must match the no-sharing fp engine's completed sequences
    def _captured_tokens(trace, extra):
        eng = ServingEngine(
            model_cfg, ServingConfig(**SERVE, **extra), mesh,
            verbose=False, capture_tokens=True)
        return eng.run_trace(trace)["completed_tokens"]

    identity = {}
    n_tok = 0
    for tname, trace in traces.items():
        oracle = _captured_tokens(trace, MODES[BASELINE_MODE])
        n_tok += sum(len(v) for v in oracle.values())
        for mname, extra in MODES.items():
            if mname == BASELINE_MODE:
                continue
            got = _captured_tokens(trace, extra)
            same = sum(1 for rid in oracle if got.get(rid) == oracle[rid])
            frac = same / len(oracle) if oracle else 1.0
            exact_required = extra["kv_quantization"] == "none"
            identity[f"{tname}/{mname}"] = {
                "exact": got == oracle,
                "identical_requests": same,
                "requests": len(oracle),
                "fraction": round(frac, 4),
                "gate": ("exact" if exact_required
                         else f">={INT8_MIN_IDENTICAL}"),
                "passed": (got == oracle if exact_required
                           else frac >= INT8_MIN_IDENTICAL),
            }
    if not all(v["passed"] for v in identity.values()):
        bad = {n: f"{v['identical_requests']}/{v['requests']}"
               for n, v in sorted(identity.items()) if not v["passed"]}
        raise SystemExit(
            "equivalence gate FAILED: prefix-cached/int8 serving "
            f"diverged from the no-sharing fp engine beyond its gate "
            f"for {bad} (fp must be bit-exact; int8 needs >= "
            f"{INT8_MIN_IDENTICAL} of requests identical) — refusing "
            "to publish throughput for a wrong result"
        )
    for name, v in sorted(identity.items()):
        print(f"[equivalence] {name}: {v['identical_requests']}/"
              f"{v['requests']} requests identical "
              f"(gate {v['gate']}): OK")

    # timed engines: capture off, one untimed warmup replay each to
    # absorb compiles, then interleaved timed repetitions
    engines = {
        f"{tname}/{mname}": (tname, ServingEngine(
            model_cfg, ServingConfig(**SERVE, **extra), mesh,
            verbose=False))
        for tname in traces
        for mname, extra in MODES.items()
    }
    for tname, eng in engines.values():
        eng.run_trace(traces[tname])
    per_rep: dict[str, list[dict]] = {name: [] for name in engines}
    for _ in range(args.reps):
        for name, (tname, eng) in engines.items():
            t0 = time.perf_counter()
            report = eng.run_trace(traces[tname])
            wall = time.perf_counter() - t0
            pre = report.get("prefix", {})
            per_rep[name].append({
                "tok_s": report["completed_output_tokens"] / wall,
                "ttft_p50_s": report["ttft"]["median"],
                "per_token_p50_s": report["per_token_latency"]["median"],
                "prefix_hits": pre.get("hits", 0),
                "hit_rate": pre.get("hit_rate"),
                "tokens_reused": pre.get("tokens_reused", 0),
            })

    settings_out = {}
    for name, (tname, _) in engines.items():
        mname = name.split("/", 1)[1]
        extra = MODES[mname]
        reps = per_rep[name]
        tok = [r["tok_s"] for r in reps]
        hr = [r["hit_rate"] for r in reps if r["hit_rate"] is not None]
        ident = identity.get(name)
        settings_out[name] = {
            "trace": tname,
            "prefix_caching": extra["prefix_caching"],
            "kv_quantization": extra["kv_quantization"],
            "output_tokens_per_s": {
                "median": _median(tok), "min": min(tok), "max": max(tok),
                "reps": tok,
            },
            "ttft_p50_ms": round(
                _median([r["ttft_p50_s"] for r in reps]) * 1e3, 3),
            "per_token_p50_ms": round(
                _median([r["per_token_p50_s"] for r in reps]) * 1e3, 3),
            "prefix_hits": _median([r["prefix_hits"] for r in reps]),
            "prefix_hit_rate": (round(_median(hr), 4) if hr else None),
            "tokens_reused": _median(
                [r["tokens_reused"] for r in reps]),
            "token_identical": None if ident is None else ident["exact"],
            "token_identity_fraction": (None if ident is None
                                        else ident["fraction"]),
        }
    for name in settings_out:
        tname = settings_out[name]["trace"]
        base_name = f"{tname}/{BASELINE_MODE}"
        base = settings_out[base_name]
        s = settings_out[name]
        s["baseline"] = base_name
        s["ttft_speedup_vs_baseline"] = round(
            base["ttft_p50_ms"] / s["ttft_p50_ms"], 3)
        s["goodput_speedup_vs_baseline"] = round(
            s["output_tokens_per_s"]["median"]
            / base["output_tokens_per_s"]["median"], 3)

    # static capacity: resident requests admissible under the SAME
    # budget, priced by the audited footprint formula (one request =
    # max_batch=1 slice; bytes are linear in max_batch)
    budget = int(CAPACITY_BUDGET_GB * 2**30)
    per_req = {
        kv: kv_cache_bytes_per_device(
            model_cfg, 1, SERVE["max_seq"],
            dp=MESH["data_parallel"], tp=MESH["tensor_parallel"],
            kv_quantization=kv, block_size=SERVE["block_size"])
        for kv in ("none", "int8")
    }
    resident = {kv: budget // b for kv, b in per_req.items()}
    cap_ratio = round(resident["int8"] / resident["none"], 3)
    capacity = {
        "hbm_budget_gb": CAPACITY_BUDGET_GB,
        "max_seq": SERVE["max_seq"],
        "block_size": SERVE["block_size"],
        "dp": MESH["data_parallel"],
        "tp": MESH["tensor_parallel"],
        "per_request_bytes_per_device": per_req,
        "resident_requests": resident,
        "capacity_ratio": cap_ratio,
        "min_ratio": ACCEPT_CAPACITY["min_ratio"],
        "passed": cap_ratio >= ACCEPT_CAPACITY["min_ratio"],
    }

    ttft_row = settings_out[ACCEPT_TTFT["setting"]]
    acceptance = {
        "ttft": {
            **ACCEPT_TTFT,
            "measured_speedup": ttft_row["ttft_speedup_vs_baseline"],
            "passed": (ttft_row["ttft_speedup_vs_baseline"]
                       >= ACCEPT_TTFT["min_speedup"]),
        },
        "capacity": {
            **ACCEPT_CAPACITY,
            "measured_ratio": cap_ratio,
            "passed": capacity["passed"],
        },
    }

    backend = jax.default_backend()
    payload = {
        "harness": "scripts/bench_prefix.py",
        "schema": "dlbb_bench_prefix_v1",
        "model": dict(BENCH_MODEL),
        "serving": dict(SERVE),
        "mesh": {"dp": MESH["data_parallel"],
                 "tp": MESH["tensor_parallel"]},
        "traces": {
            name: {
                "kind": trace.kind, "requests": len(trace),
                "seed": trace.seed,
                "prefix_groups": TRACES[name]["prefix_groups"],
                "prefix_len": TRACES[name]["prefix_len"],
                "prompt_range": list(PROMPTS),
                "output_range": list(OUTPUTS),
                "shared_token_share": round(_shared_share(trace), 4),
            }
            for name, trace in traces.items()
        },
        "repetitions": args.reps,
        "baseline": BASELINE_MODE,
        "methodology": (
            "identical seeded shared-prefix traces replayed through "
            "every engine; settings interleaved within each "
            "repetition; medians of per-rep completed-output-token "
            "throughput with min/max spread; completed-token identity "
            "gate (every prefix-cached / int8 setting == the "
            "no-sharing fp engine on the same trace) run on the "
            "published traces before any timing; capacity is static "
            "arithmetic over kv_cache_bytes_per_device, the formula "
            "the memory audit pins to the compiled decode carry"
        ),
        "backend": backend,
        "topology": topology_record(),
        "jax_version": jax.__version__,
        "host_cpu_count": os.cpu_count(),
        "timestamp": time.time(),
        "equivalence": {
            "checked": True,
            "oracle": f"{BASELINE_MODE} (per trace)",
            "int8_min_identical": INT8_MIN_IDENTICAL,
            "identical": dict(sorted(identity.items())),
            "tokens": n_tok,
        },
        "settings": settings_out,
        "capacity": capacity,
        "acceptance": acceptance,
        "claim": (
            "CPU-simulated mesh: every skipped prefill chunk saves a "
            "real host dispatch — the regime the attach path targets — "
            "but int8 pays dequant/requant at CPU FLOP cost with no "
            "HBM-bandwidth win, so int8 THROUGHPUT rows undersell; the "
            "capacity ratio is regime-independent."
            if backend == "cpu" else
            "chip run: walls are device-honest; the int8 rows see the "
            "HBM-bandwidth regime the quantized layout targets."
        ),
    }
    atomic_write_text(json.dumps(payload, indent=1) + "\n",
                      Path(args.output))
    write_prefix_report(Path(args.output), REPO / "stats" / "serving")
    for name, s in settings_out.items():
        tps = s["output_tokens_per_s"]
        hit = ("-" if s["prefix_hit_rate"] is None
               else f"{s['prefix_hit_rate']:.2f}")
        print(f"[{name:16s}] {tps['median']:8.1f} tok/s "
              f"({tps['min']:.1f}..{tps['max']:.1f})  "
              f"TTFT p50 {s['ttft_p50_ms']:8.1f} ms "
              f"x{s['ttft_speedup_vs_baseline']:.2f}, hit={hit}")
    ttft_acc = acceptance["ttft"]
    print(f"[acceptance] TTFT {ttft_acc['setting']} >= "
          f"{ttft_acc['min_speedup']}x vs {ttft_acc['baseline']}: "
          f"{'PASS' if ttft_acc['passed'] else 'FAIL'} "
          f"({ttft_acc['measured_speedup']:.2f}x)")
    print(f"[acceptance] int8 capacity >= "
          f"{ACCEPT_CAPACITY['min_ratio']}x residents: "
          f"{'PASS' if capacity['passed'] else 'FAIL'} "
          f"({cap_ratio:.2f}x: {resident['none']} fp -> "
          f"{resident['int8']} int8 under "
          f"{CAPACITY_BUDGET_GB} GB/device)")
    print(f"BENCH_prefix.json -> {args.output}")
    return 0 if (ttft_acc["passed"] and capacity["passed"]) else 1


if __name__ == "__main__":
    sys.exit(main())
