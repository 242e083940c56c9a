#!/usr/bin/env python
"""The chip run behind ``models/transformer.py``'s overlap rule: the
tensor-parallel forward of one configuration under every candidate route,
side by side in ONE process on one machine.

Candidates, on the mesh as ``build_parallelism_mesh`` lays it and (with
``--orders``) on the devices in their plain order:

- ``off``    the fused GSPMD route: two exposed all-reduces a layer;
- ``gspmd``  the residual stream constrained to ``activation_spec(mesh)``
             and the projections left to the compiler (a reduce-scatter
             and an all-gather where each all-reduce was);
- ``ring`` / ``bidir``  ``parallel/collective_matmul.py``'s schedules.

For each: ms a step in interleaved rounds, the output's distance from
``off`` on the same input, and from a short profile the time by block
phase and the largest ops.  ``--hops`` first times bare ``ppermute``
chains of one ring chunk, which is what sets the link rate in the rule.

Usage (four chips): python scripts/overlap_candidates.py [--layers N]
    [--rounds R] [--steps N] [--orders] [--hops] [--out DIR]
Nothing here runs on a CPU backend but ``--simulate`` at toy widths.
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from dlbb_tpu.utils.config import save_json  # noqa: E402

# the source's three sizes (models/configs.py::MODEL_CONFIGS) and a toy
WIDTHS = {
    "13b": dict(hidden_size=5120, num_heads=40, ffn_intermediate=20480,
                num_layers=40),
    "7b": dict(hidden_size=4096, num_heads=32, ffn_intermediate=16384,
               num_layers=32),
    "1b": dict(hidden_size=2048, num_heads=16, ffn_intermediate=8192,
               num_layers=24),
    "toy": dict(hidden_size=256, num_heads=8, ffn_intermediate=1024,
                num_layers=2)}


def _gspmd_forward(params, x, cfg, mesh):
    """The third candidate: ``forward`` with the residual stream held to
    the sequence-sharded layout and plain matmuls (no ring)."""
    import jax
    from jax.sharding import NamedSharding

    from dlbb_tpu.models import transformer as T
    from dlbb_tpu.parallel.collective_matmul import activation_spec

    seq = NamedSharding(mesh, activation_spec(mesh))

    def pin(a):
        return jax.lax.with_sharding_constraint(a, seq)

    def body(h, layer):
        y = T._layernorm(h, layer["ln1"]["scale"], layer["ln1"]["bias"])
        with jax.named_scope(T.ATTN_QKV):
            qkv = y @ layer["qkv"]["kernel"] + layer["qkv"]["bias"]
        with jax.named_scope(T.ATTN_CORE):
            attn = T._attention(qkv, cfg, mesh)
        with jax.named_scope(T.ATTN_OUT):
            h = pin(attn @ layer["out"]["kernel"]) + layer["out"]["bias"] + h
        y = T._layernorm(h, layer["ln2"]["scale"], layer["ln2"]["bias"])
        with jax.named_scope(T.MLP_UP):
            y = y @ layer["ffn_up"]["kernel"] + layer["ffn_up"]["bias"]
        y = jax.nn.gelu(y)
        with jax.named_scope(T.MLP_DOWN):
            h = pin(y @ layer["ffn_down"]["kernel"]) \
                + layer["ffn_down"]["bias"] + h
        return h, None

    h, _ = jax.lax.scan(body, pin(x), params["layers"])
    return T._layernorm(h, params["ln_f"]["scale"], params["ln_f"]["bias"])


def _hop_times(mesh, batch, rows, hidden, dtype, reps=5, chain=6):
    """ms a hop of a chain of dependent ``ppermute``s around the tp ring,
    one way and both ways at once, for chunks of ``batch x r x hidden``,
    ``r`` from one row up to ``rows``: ``{bytes: {way: ms}}``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlbb_tpu.compat import shard_map

    p = mesh.shape["tp"]
    fwd = [(i, (i + 1) % p) for i in range(p)]
    bwd = [(i, (i - 1) % p) for i in range(p)]

    def one_way(a):
        for _ in range(chain):
            a = lax.ppermute(a, "tp", fwd)
        return a

    def both_ways(a):
        b = a
        for _ in range(chain):
            a, b = lax.ppermute(a, "tp", fwd), lax.ppermute(b, "tp", bwd)
        return a + b

    spec = P("dp", "tp", None)
    out = {}
    for r in sorted({1, max(1, rows // 16), max(1, rows // 4), rows}):
        x = jax.device_put(jnp.ones((batch, r * p, hidden), dtype),
                           NamedSharding(mesh, spec))
        size = batch * r * hidden * jnp.dtype(dtype).itemsize
        out[size] = {}
        for name, body in (("one_way", one_way), ("both_ways", both_ways)):
            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                                   out_specs=spec))
            jax.block_until_ready(fn(x))
            samples = []
            for _ in range(reps):
                t = time.perf_counter()
                jax.block_until_ready(fn(x))
                samples.append((time.perf_counter() - t) / chain * 1e3)
            out[size][name] = round(statistics.median(samples), 4)
    return out


def _profile(step, scratch, steps=3):
    """Per-step device ms by block phase and the largest ops."""
    import jax

    from benchmarks.harness import trace_reduce
    from benchmarks.readers import named_ops
    from dlbb_tpu.models.transformer import BLOCK_PHASES

    with trace_reduce.profiling(scratch):
        out = None
        for _ in range(steps):
            out = step()
        jax.block_until_ready(out)
    files = sorted(glob.glob(os.path.join(
        scratch, "plugins", "profile", "*", "*.xplane.pb")))
    with open(files[-1], "rb") as f:
        decoded = named_ops.decode(f.read())
    windows = [(s, e) for n, s, e in decoded["host"]
               if n == trace_reduce.WINDOW_SPAN]
    decoded["window"] = windows[-1]

    def phase(op):
        # the op's own scope path first, then those of the ops fused in
        return next((t for t in re.split(r"[/ ]", op[3])
                     if t in BLOCK_PHASES), "(none)")

    def ms(groups):
        return {k: round(v / steps * 1e3, 3) for k, v in
                sorted(groups.items(), key=lambda kv: -kv[1])}

    by_phase = ms(named_ops.group_seconds(decoded, phase))
    by_kind = ms(named_ops.group_seconds(
        decoded, lambda op: op[0].split(".")[0]))
    by_op = ms(named_ops.group_seconds(
        decoded, lambda op: f"{op[0]} | {op[3][-60:]}"))
    return {"phase_ms": by_phase, "kind_ms": dict(list(by_kind.items())[:12]),
            "top_ops_ms": dict(list(by_op.items())[:40])}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--widths", default="13b", choices=sorted(WIDTHS))
    ap.add_argument("--layers", type=int, default=0,
                    help="0: the size's own")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--candidates", nargs="+",
                    default=["off", "gspmd", "ring", "bidir"])
    ap.add_argument("--orders", action="store_true",
                    help="also on the devices in their plain order")
    ap.add_argument("--hops", action="store_true")
    ap.add_argument("--no-profile", action="store_true")
    ap.add_argument("--simulate", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(REPO / "chiprun_out" / "overlap"))
    args = ap.parse_args()

    if args.simulate:
        from dlbb_tpu.utils.simulate import force_cpu_simulation

        force_cpu_simulation(args.simulate)
    else:
        from dlbb_tpu.utils.simulate import require_accelerator

        require_accelerator()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from dlbb_tpu.comm.mesh import MeshSpec, build_mesh, \
        build_parallelism_mesh
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.models.sharding import batch_spec
    from dlbb_tpu.models.transformer import forward, init_params_sharded

    toy = args.widths == "toy"
    widths = dict(WIDTHS[args.widths])
    if args.layers:
        widths["num_layers"] = args.layers
    base = ModelConfig(attention="full",
                       dtype="float32" if toy else "bfloat16", **widths)
    dtype = jnp.float32 if toy else jnp.bfloat16
    devices = jax.devices()[:args.tp]
    meshes = {"laid": build_parallelism_mesh(tensor_parallel=args.tp,
                                             devices=devices)}
    if args.orders:
        meshes["plain"] = build_mesh(
            MeshSpec.grid((1, args.tp), ("dp", "tp")), devices=devices)
    report = {"device": jax.devices()[0].device_kind,
              "devices": [[d.id, list(getattr(d, "coords", ()))]
                          for d in devices],
              "shape": [args.batch, args.seq, base.hidden_size],
              "layers": base.num_layers, "meshes": {}}
    scratch = REPO / ".bench_scratch" / "overlap"
    reference = None
    for order, mesh in meshes.items():
        entry = report["meshes"][order] = {
            "tp_devices": [d.id for d in mesh.devices.reshape(-1)]}
        if args.hops:
            entry["hop_ms"] = _hop_times(
                mesh, args.batch, args.seq // args.tp, base.hidden_size,
                dtype)
            print(order, "hop_ms", entry["hop_ms"], flush=True)
        params = init_params_sharded(base, jax.random.key(args.seed), mesh)
        x = jax.device_put(
            jax.random.normal(jax.random.key(args.seed + 1),
                              (args.batch, args.seq, base.hidden_size),
                              dtype),
            NamedSharding(mesh, batch_spec(mesh)))
        out_sh = NamedSharding(mesh, batch_spec(mesh))
        steps = {}
        for cand in args.candidates:
            if cand == "gspmd":
                fn = jax.jit(lambda p, a: _gspmd_forward(p, a, base, mesh),
                             out_shardings=out_sh)
            else:
                cfg = base.with_(tp_overlap=cand)
                fn = jax.jit(
                    lambda p, a, cfg=cfg: forward(p, a, cfg, mesh=mesh),
                    out_shardings=out_sh)
            t = time.perf_counter()
            y = np.asarray(fn(params, x).astype(jnp.float32))
            compile_s = time.perf_counter() - t
            jax.block_until_ready(fn(params, x))
            steps[cand] = fn
            if reference is None:
                reference = y
            diff = np.abs(y - reference)
            entry[cand] = {
                "compile_and_first_s": round(compile_s, 2),
                "max_abs_diff_over_max_abs": float(
                    diff.max() / np.abs(reference).max()),
                "mean_abs_diff_over_mean_abs": float(
                    diff.mean() / np.abs(reference).mean()),
                "ms": []}
        for _ in range(args.rounds):
            for cand, fn in steps.items():
                t = time.perf_counter()
                out = None
                for _ in range(args.steps):
                    out = fn(params, x)
                jax.block_until_ready(out)
                entry[cand]["ms"].append(round(
                    (time.perf_counter() - t) / args.steps * 1e3, 3))
        for cand, fn in steps.items():
            if not (args.no_profile or args.simulate or order == "plain"):
                try:
                    entry[cand]["profile"] = _profile(
                        lambda fn=fn: fn(params, x),
                        str(scratch / f"{order}_{cand}"))
                except Exception as e:  # the timings above still stand
                    print(f"   profile of {cand} failed: {e!r}", flush=True)
            print(order, cand, {k: v for k, v in entry[cand].items()
                                if k != "profile"}, flush=True)
            if "profile" in entry[cand]:
                print("   phases", entry[cand]["profile"]["phase_ms"],
                      flush=True)
                print("   kinds ", entry[cand]["profile"]["kind_ms"],
                      flush=True)
        del params, x, steps
    path = os.path.join(args.out, f"candidates_{args.widths}.json")
    save_json(report, path)
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
