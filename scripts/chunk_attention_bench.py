"""Time a prompt chunk's attention kernel (``ops/chunk_attention.py``,
``chunk_attend``) at SmallThinker-21BA3B's widths: 28 query heads over 4
K/V heads of 128, chunks of 1,024, a window of 4,096.  For each block
pair of a sweep and each chunk program it reports the kernel's time a
layer call (wall over ``--reps`` calls queued back to back, the outputs
kept), and the kernel's largest error against the dense float32
attention ``serve/attend.py::_chunk_attention`` on the same boolean mask.
It also shows what precision a float32 ``P.V`` product takes at default
precision on this backend: its distance from ``P`` rounded to bfloat16
first and from ``Precision.HIGHEST``.

    python scripts/chunk_attention_bench.py --blocks 256x1024 512x512
    python scripts/chunk_attention_bench.py --tiny      # CPU rehearsal

Writes ``chiprun_out/chunk_attention_bench.json``.
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

from dlbb_tpu.ops.chunk_attention import (                     # noqa: E402
    BLOCK_K,
    BLOCK_Q,
    chunk_attend,
)
from dlbb_tpu.serve.attend import _chunk_attention              # noqa: E402
from dlbb_tpu.serve.kvcache import ring_positions               # noqa: E402
from dlbb_tpu.utils.config import save_json                     # noqa: E402


def _keys(kind: str, start: int, chunk: int, window: int) -> np.ndarray:
    own = start + np.arange(chunk)
    if kind == "global":
        return np.arange(start + chunk)
    return np.concatenate([ring_positions(start, window), own]) if start \
        else own


def _mask(keys: np.ndarray, start: int, chunk: int, window: int):
    at = (start + np.arange(chunk))[:, None]
    mask = (keys[None] >= 0) & (keys[None] <= at)
    if window:
        mask &= keys[None] > at - window
    return jnp.asarray(mask)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", nargs="+", default=[f"{BLOCK_Q}x{BLOCK_K}"],
                    help="block_q x block_k pairs")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths, for a rehearsal off the chip")
    args = ap.parse_args()
    n, kvh, d, chunk, window = ((4, 2, 16, 16, 32) if args.tiny
                                else (28, 4, 128, 1024, 4096))
    cases = [("window", chunk), ("window", 4 * chunk), ("window", 14 * chunk),
             ("global", 2 * chunk), ("global", 4 * chunk),
             ("global", 14 * chunk)]
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32),
                           jnp.bfloat16)

    out = {"device": jax.devices()[0].device_kind, "cases": {}}
    q = arr(1, n, chunk, d)
    for kind, start in cases:
        keys = _keys(kind, start, chunk, window)
        w = window if kind == "window" else 0
        k, v = arr(len(keys), kvh, d), arr(len(keys), kvh, d)
        want = np.asarray(jax.jit(_chunk_attention, static_argnums=3)(
            q, k, v, start, None, _mask(keys, start, chunk, w)),
            np.float32)
        row = out["cases"][f"{kind}_o{start}"] = {}
        for pair in args.blocks:
            bq, bk = (int(x) for x in pair.split("x"))
            fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: chunk_attend(
                q, k, v, keys, start, w, None, bq, bk))
            got = np.asarray(jax.block_until_ready(fn(q, k, v)), np.float32)
            t0 = time.perf_counter()
            jax.block_until_ready([fn(q, k, v) for _ in range(args.reps)])
            ms = (time.perf_counter() - t0) / args.reps * 1e3
            err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            row[pair] = {"ms": ms, "max_err": err}
            print(kind, start, pair, f"{ms:.3f} ms", f"err {err:.2e}",
                  flush=True)

    p = jax.nn.softmax(jnp.asarray(
        rng.standard_normal((512, 4096), dtype=np.float32)) * 4, axis=-1)
    v32 = arr(4096, 128).astype(jnp.float32)

    def pv(a, **kw):
        return np.asarray(jnp.dot(a, v32, **kw))

    default = pv(p)
    out["pv_default_vs_p_bf16"] = float(np.max(np.abs(
        default - pv(p.astype(jnp.bfloat16).astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST))))
    out["pv_default_vs_highest"] = float(np.max(np.abs(
        default - pv(p, precision=jax.lax.Precision.HIGHEST))))
    print(json.dumps(out))
    save_json(out, ROOT / "chiprun_out" / "chunk_attention_bench.json")


if __name__ == "__main__":
    main()
