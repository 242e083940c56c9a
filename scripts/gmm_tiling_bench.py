"""Time the grouped expert products (``ops/routed_experts.py::_gmm``,
``megablox.gmm``) under candidate tilings at the two expert cells'
shapes: a prompt chunk's rows and a decode step's rows of
``smallthinker_longdoc_backlog`` and ``kanana_serve_longctx_backlog``,
read from their configuration files, for gate/up ``[hidden, width]`` and
down ``[width, hidden]``.

The weights, drawn on the device, are a whole stack of the cell's
scanned expert layers, and the rows go to one layer of it (``layer=``,
what the layer scan calls); the rule's choice and the fixed (128, 2048,
768) are also timed on one layer's weights alone.  Group sizes are drawn as the cells route: a
sixteenth of the experts empty, the others Zipf-like with the fullest
16 x the mean of those that got a row, in a seeded order.  Each time
is the wall over ``--reps`` calls queued back to back, outputs kept;
each output is compared with the fixed tiling's.

    python scripts/gmm_tiling_bench.py
    python scripts/gmm_tiling_bench.py --tiny      # CPU rehearsal

Writes ``chiprun_out/gmm_tiling_bench.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from benchmarks.harness import cells                            # noqa: E402
from dlbb_tpu.ops import routed_experts as moe                  # noqa: E402
from dlbb_tpu.utils.config import save_json                     # noqa: E402

CELLS = ("smallthinker_longdoc_backlog", "kanana_serve_longctx_backlog")
# the one tiling every product took before the rule
FIXED = (128, 2048, 768)
FULLEST_OVER_MEAN = 16.0


def cell_shapes(cell: str) -> dict:
    """What a cell's grouped products see: rows of a chunk and of a
    decode step, the widths, the experts and the scanned stack."""
    program = cells.resolve_cell(cell).config["program"]
    model, serving = program["model"], program["serving"]
    top_k = model["num_experts_per_tok"]
    periods = (model["num_layers"] // len(model["layer_types"])
               - model.get("first_k_dense_replace", 0))
    return {"chunk": serving["prefill_chunk"] * top_k,
            "decode": serving["max_batch"] * top_k,
            "hidden": model["hidden_size"],
            "width": model["moe_intermediate_size"],
            "experts": model["n_routed_experts"], "stack": periods}


def group_sizes(rng: np.random.Generator, m: int, e: int) -> np.ndarray:
    """``m`` rows over ``e`` experts: ``e // 16`` empty, the rest
    Zipf-like with the fullest :data:`FULLEST_OVER_MEAN` x their mean,
    experts in a random order."""
    live = e - e // 16
    rank = np.arange(1, live + 1, dtype=np.float64)
    lo, hi = 0.0, 4.0
    for _ in range(60):
        a = (lo + hi) / 2
        w = rank ** -a
        lo, hi = (a, hi) if w[0] / w.mean() < FULLEST_OVER_MEAN else (lo, a)
    share = w / w.sum() * m
    sizes = np.floor(share).astype(np.int64)
    sizes[np.argsort(sizes - share)[:m - sizes.sum()]] += 1
    sizes = np.maximum(sizes, 1)
    while sizes.sum() > m:
        sizes[np.argmax(sizes)] -= 1
    out = np.zeros(e, np.int32)
    out[rng.permutation(e)[:live]] = sizes
    return out


def candidates(m: int, k: int, n: int, itemsize: int) -> list[tuple]:
    """The fixed tiling, then every (tm, k, tn) with tm in 128 and 256 and
    tn a lane tile of ``n`` that fits the budget beside the whole
    contraction."""
    out = [(FIXED[0], min(FIXED[1], k), min(FIXED[2], n))]
    for tm in (128, 256):
        out += [(tm, k, tn) for tn in moe._lane_tiles(n)
                if moe.gmm_vmem_bytes(tm, k, tn, itemsize)
                <= moe.GMM_VMEM_BUDGET and (tm, k, tn) not in out]
    return out


def run_gmm(rows, w, sizes, tiling):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = rows.shape[0]
    pad = -m % tiling[0]
    rows = jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows
    return gmm(rows, w, sizes, preferred_element_type=rows.dtype,
               tiling=tiling, interpret=jax.default_backend() != "tpu")[:m]


def time_one(rows, w, sizes, tiling, reps):
    fn = jax.jit(lambda r, w, s: run_gmm(r, w, s, tiling))
    got = jax.block_until_ready(fn(rows, w, sizes))
    t0 = time.perf_counter()
    jax.block_until_ready([fn(rows, w, sizes) for _ in range(reps)])
    return (time.perf_counter() - t0) / reps * 1e3, got


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths, for a rehearsal off the chip")
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    dtype = jnp.bfloat16
    itemsize = jnp.dtype(dtype).itemsize
    out = {"device": jax.devices()[0].device_kind,
           "budget_bytes": moe.GMM_VMEM_BUDGET, "cases": []}
    for cell in CELLS:
        sh = cell_shapes(cell)
        if args.tiny:
            sh.update(chunk=96, decode=24, hidden=256, width=384,
                      experts=8, stack=2)
        e, layer = sh["experts"], sh["stack"] // 2
        keys = jax.random.split(jax.random.key(args.seed), 2)
        mats = {name: jax.random.normal(key, (sh["stack"] * e, k, n), dtype)
                * jnp.asarray(k ** -0.5, dtype)
                for key, (name, (k, n)) in zip(keys, (
                    ("gate_up", (sh["hidden"], sh["width"])),
                    ("down", (sh["width"], sh["hidden"]))))}
        for phase in ("chunk", "decode"):
            m = sh[phase]
            sizes_np = group_sizes(rng, m, e)
            stacked = np.zeros(sh["stack"] * e, np.int32)
            stacked[layer * e:(layer + 1) * e] = sizes_np
            live = sizes_np[sizes_np > 0]
            for name, w in mats.items():
                k, n = w.shape[1:]
                rows = jnp.asarray(rng.standard_normal((m, k), np.float32),
                                   dtype)
                rule = moe._gmm_tiling(m, k, n, itemsize)
                ref = None
                for form, ww, ss in (
                        ("stacked", w, stacked),
                        ("one_layer", w[layer * e:(layer + 1) * e],
                         sizes_np)):
                    tilings = (candidates(m, k, n, itemsize)
                               if form == "stacked" else
                               [candidates(m, k, n, itemsize)[0], rule])
                    for tiling in tilings:
                        ms, got = time_one(rows, ww, jnp.asarray(ss),
                                           tiling, args.reps)
                        got = np.asarray(got, np.float32)
                        if ref is None:
                            ref = got
                        err = float(np.max(np.abs(got - ref))
                                    / np.max(np.abs(ref)))
                        row = {"cell": cell, "phase": phase, "product": name,
                               "form": form, "m": m, "k": k, "n": n,
                               "tiling": list(tiling),
                               "rule": tiling == rule, "ms": ms,
                               "err_vs_fixed": err,
                               "fullest_over_mean": float(
                                   live.max() / live.mean())}
                        out["cases"].append(row)
                        print(cell, phase, name, form, tiling,
                              "RULE" if tiling == rule else "",
                              f"{ms:.4f} ms", f"err {err:.1e}", flush=True)
        del mats
    print(json.dumps({"device": out["device"]}))
    save_json(out, ROOT / "chiprun_out" / "gmm_tiling_bench.json")


if __name__ == "__main__":
    main()
