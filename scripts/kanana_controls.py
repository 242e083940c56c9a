"""The named controls of ``benchmarks/harness/kind_backlog_latent.py``:
the program made wrong in one stated way, run through the SAME runner,
to see which limit of the comparison with the float32 reference reads it
(``PERF.md`` §6, PR 31; ``tests/benchmark_harness/test_kanana_cell.py``
drives the same patches at toy widths).  Not part of the benchmark and
not a way to serve the model.

    python scripts/kanana_controls.py [--seconds S] [--seed N]
        [--rps R] [--chunk C] [--slots B] <control> ...

runs the cell ``kanana_serve_longctx_backlog`` once per named control
(``sound`` is the program as it is), each in a process of its own (a
chip belongs to one process), and prints one JSON line each: the
control, ``correct``, the comparison's numbers, ``out_tokens_per_s``.
``--rps`` / ``--chunk`` / ``--slots`` override the traffic's
``backlog_rps`` and the configuration's ``prefill_chunk`` and
``max_batch`` (the sweeps that set them).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "kanana_serve_longctx_backlog"


def _reroute(change: Callable[[Any, Any, Any, float], Any]):
    """A ``route`` whose gates are ``change(routing, bias, jnp, scale)``."""
    def patch(setattr_: Callable[[Any, str, Any], None],
              model: dict) -> None:
        import jax.numpy as jnp

        from dlbb_tpu.ops import routed_experts as moe

        route = moe.route

        def wrong(u, w_router, bias, top_k, scale):
            routing = route(u, w_router, bias, top_k, scale)
            return routing._replace(gates=change(routing, bias, jnp, scale))

        setattr_(moe, "route", wrong)
    return patch


def _chosen(routing, jnp):
    return jnp.take_along_axis(routing.scores, routing.experts, axis=-1)


def _rope_off_cache(setattr_, model):
    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid

    setattr_(serve_hybrid, "_cache_rows",
             lambda c, k_rope, positions, config:
             hybrid.latent_row(c, k_rope, config))


def _latent_not_normed(setattr_, model):
    from dlbb_tpu.models import hybrid

    rmsnorm = hybrid.rmsnorm

    def skip_the_latents(x, scale, eps):
        # the latent's norm is the only one of the latent's width
        if x.shape[-1] == model["kv_lora_rank"]:
            return x
        return rmsnorm(x, scale, eps)

    setattr_(hybrid, "rmsnorm", skip_the_latents)


def _one_shared_expert(setattr_, model):
    from dlbb_tpu.ops import routed_experts as moe

    shared = moe.shared_expert

    def half(u, w):
        f = w["shared_gate"].shape[-1] // 2
        return shared(u, {"shared_gate": w["shared_gate"][:, :f],
                          "shared_up": w["shared_up"][:, :f],
                          "shared_down": w["shared_down"][:f]})

    setattr_(moe, "shared_expert", half)


def _bf16(x):
    """``x`` rounded to bfloat16's 8 bits of significand and kept in its
    own dtype.  An explicit ``reduce_precision``: XLA on the TPU drops a
    convert to bfloat16 and back (a ``router_bfloat16`` written with
    ``astype`` read like the sound program to the last digit)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rmsnorm_bf16(x, scale, eps):
    """``models/hybrid.py::rmsnorm`` with its statistics and products
    rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    inv = _bf16(jax.lax.rsqrt(
        _bf16(jnp.mean(_bf16(x32 * x32), axis=-1, keepdims=True)) + eps))
    return _bf16(_bf16(x32 * inv)
                 * scale.astype(jnp.float32)).astype(x.dtype)


def chunk_attention_bf16(qh, k_all, v_all, start, scale=None):
    """``serve/attend.py::_chunk_attention`` (plain MHA) with its scores,
    its softmax and its output rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    _, _, c, d = qh.shape
    q32 = qh.astype(jnp.float32)
    k32 = k_all.transpose(1, 0, 2).astype(jnp.float32)[None]
    v32 = v_all.transpose(1, 0, 2).astype(jnp.float32)[None]
    mask = (jnp.arange(k_all.shape[0])[None, :]
            <= (start + jnp.arange(c))[:, None])
    logits = jnp.einsum("bnqd,bnkd->bnqk", q32, k32)
    logits = _bf16(logits / d ** 0.5 if scale is None else logits * scale)
    probs = _bf16(jax.nn.softmax(
        jnp.where(mask[None, None], logits, -jnp.inf), axis=-1))
    return _bf16(jnp.einsum("bnqk,bnkd->bnqd", probs, v32)).astype(
        k_all.dtype)


def _router_bfloat16(setattr_, model):
    import jax
    import jax.numpy as jnp

    from dlbb_tpu.ops import routed_experts as moe

    def route(u, w_router, bias, top_k, scale):
        # the gate's product and its sigmoid, each rounded to bfloat16
        logits = _bf16(jnp.einsum("th,he->te", u.astype(jnp.float32),
                                  w_router.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
        scores = _bf16(jax.nn.sigmoid(logits))
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        gates = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        return moe.Routing(experts.astype(jnp.int32), gates, scores)

    setattr_(moe, "route", route)


def _norms_combine_softmax_bfloat16(setattr_, model):
    """What the configuration states as float32 beside the router, in
    bfloat16: the norms' statistics and products, the weighted sum of a
    token's expert outputs, a prompt chunk's scores and softmax.  (The
    decode kernel's softmax and the SwiGLU's activation sit inside a
    kernel and a fusion, with no seam for a control.)"""
    import jax.numpy as jnp

    from dlbb_tpu.models import hybrid
    from dlbb_tpu.ops import routed_experts as moe
    from dlbb_tpu.serve import hybrid as serve_hybrid

    def combine(out, d, gates):
        t, k = gates.shape
        back = jnp.zeros_like(d.order).at[d.order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        by_token = jnp.take(out, back, axis=0).reshape(t, k, -1).astype(
            jnp.float32)
        total = jnp.zeros_like(by_token[:, 0])
        for i in range(k):
            total = _bf16(total + _bf16(by_token[:, i]
                                        * _bf16(gates[:, i, None])))
        return total

    setattr_(hybrid, "rmsnorm", rmsnorm_bf16)
    setattr_(moe, "combine", combine)
    setattr_(serve_hybrid, "_chunk_attention", chunk_attention_bf16)


def _float32_parts_bfloat16(setattr_, model):
    _router_bfloat16(setattr_, model)
    _norms_combine_softmax_bfloat16(setattr_, model)


CONTROLS: dict[str, Callable[[Callable, dict], None]] = {
    "sound": lambda setattr_, model: None,
    # rotary left off the cached k_rope (the queries are still rotated)
    "rope_off_cache": _rope_off_cache,
    # c cached, and used, without its norm
    "latent_not_normed": _latent_not_normed,
    # routed_scaling_factor left out
    "scaling_left_out": _reroute(
        lambda r, bias, jnp, scale: r.gates / scale),
    # the top-k normalisation left out: g = scale x s
    "topk_norm_left_out": _reroute(
        lambda r, bias, jnp, scale: scale * _chosen(r, jnp)),
    # the selection bias used as a weight: g ~ s + b
    "bias_as_weight": _reroute(
        lambda r, bias, jnp, scale: (lambda sel: scale * sel / jnp.sum(
            sel, axis=-1, keepdims=True))(
                _chosen(r, jnp) + jnp.take(bias.astype(jnp.float32),
                                           r.experts))),
    # one shared expert of two
    "one_shared_expert": _one_shared_expert,
    # the nearest precision below the configuration's: everything it
    # states as float32 computed in bfloat16; then the router alone, and
    # the rest without the router
    "float32_parts_bfloat16": _float32_parts_bfloat16,
    "router_bfloat16": _router_bfloat16,
    "norms_combine_softmax_bfloat16": _norms_combine_softmax_bfloat16,
}


def apply(name: str, setattr_: Callable[[Any, str, Any], None],
          model: dict) -> None:
    """Make the program wrong as ``name`` says, through ``setattr_``
    (``monkeypatch.setattr`` in a test, ``setattr`` in a process that
    ends with the run)."""
    CONTROLS[name](setattr_, model)


def run_one(name: str, seconds: float, seed: int, rps: float,
            chunk: int, trace: bool = False, cell: str = CELL,
            controls: Any = None, slots: int = 0) -> dict:
    """One run of ``cell`` made wrong as ``controls[name]`` says (this
    file's :data:`CONTROLS` by default; ``scripts/ouro_controls.py``
    hands in its own)."""
    from benchmarks.harness import device
    from benchmarks.harness.cells import resolve_cell, runner_for
    from dlbb_tpu.utils.compile_cache import configure_compile_cache

    t_start = time.perf_counter()
    cell = resolve_cell(cell)
    config = json.loads(json.dumps(cell.config))
    traffic = dict(cell.traffic)
    if rps:
        traffic["backlog_rps"] = rps
    if slots:
        config["program"]["serving"]["max_batch"] = slots
    if chunk:
        serving = config["program"]["serving"]
        serving["prefill_chunk"] = chunk
        serving["max_seq"] = -(-serving["max_seq"] // chunk) * chunk
        traffic["warmup_prompt_stride"] = chunk
    cell = dataclasses.replace(cell, config=config, traffic=traffic)
    configure_compile_cache()
    device.require_chips(cell.chips)
    (controls or CONTROLS)[name](setattr, config["program"]["model"])
    scratch = ROOT / ".bench_scratch" / f"control_{name}"
    scratch.mkdir(parents=True, exist_ok=True)
    run = runner_for(traffic["kind"])(cell, seed, seconds, trace,
                                      device.CompileCounter(), str(scratch))
    return {"control": name, "correct": run.correct, "seed": seed,
            "requests": run.attempted, "failed": run.failed,
            "wall_s": run.scalars.get("wall_s"),
            "setup_s": run.started_at - t_start,
            "total_s": time.perf_counter() - t_start,
            "out_tokens_per_s": run.values.get("out_tokens_per_s"),
            "scalars": {k: v for k, v in run.scalars.items()
                        if k not in ("wall_s", "requests")},
            # the longest decode unit and chunk: a stall of the machine
            # (PERF.md, Open question 13) shows here
            "longest_s": {key: max(run.samples[key])
                          for key in ("decode_unit_s", "prefill_s")
                          if run.samples.get(key)},
            "memory_peak_bytes": run.device.get("memory_peak_bytes"),
            "faults": run.faults}


def main(script: str = __file__, cell: str = CELL, controls: Any = None,
         log: str = "kanana_controls.jsonl", doc: str = __doc__) -> int:
    parser = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    parser.add_argument("controls", nargs="+")
    parser.add_argument("--seconds", type=float, default=12.0)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--rps", type=float, default=0.0)
    parser.add_argument("--chunk", type=int, default=0)
    parser.add_argument("--slots", type=int, default=0)
    parser.add_argument("--child", action="store_true")
    args = parser.parse_args()
    if args.child:
        (name,) = args.controls
        print("RESULT " + json.dumps(run_one(
            name, args.seconds, args.seed, args.rps, args.chunk, cell=cell,
            controls=controls, slots=args.slots)), flush=True)
        return 0
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    lines = []
    for i, name in enumerate(args.controls):
        done = subprocess.run(
            [sys.executable, script, "--child", name, "--seconds",
             str(args.seconds), "--seed", str(args.seed + i), "--rps",
             str(args.rps), "--chunk", str(args.chunk), "--slots",
             str(args.slots)],
            capture_output=True, text=True)
        found = [l[7:] for l in done.stdout.splitlines()
                 if l.startswith("RESULT ")]
        line = found[-1] if found else json.dumps(
            {"control": name, "error": done.stderr[-1500:]})
        notes = [l for l in done.stderr.splitlines()
                 if l.startswith("[benchmark]")]
        print(line, flush=True)
        print("\n".join(notes[-4:]), flush=True)
        lines.append(line)
    with open(out / log, "a") as f:
        f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
