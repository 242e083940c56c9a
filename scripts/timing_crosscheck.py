#!/usr/bin/env python
"""Cross-validate the chained timing estimates against forced single-
iteration completions (VERDICT r1 weak #4/#8).

The chained mode estimates per-iteration time as
``(fori_loop(M iterations) wall - fetch overhead) / M``.  The independent
check here times ONE iteration to true completion via a data-dependent
scalar fetch (enqueue cannot satisfy it), minus the calibrated fetch
overhead.  The two must agree to within the dispatch noise; the single-
iteration estimate is biased UP by one dispatch, so chained <=
single-iteration is the expected ordering.

Writes ``results/timing_crosscheck.json`` with both estimates for the
headline configs.  Run on the real TPU chip: that is the backend whose
timing the check is about.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path


REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from dlbb_tpu.utils.config import atomic_write_text  # noqa: E402


def main() -> int:
    import jax
    import jax.numpy as jnp

    from dlbb_tpu.models.configs import MODEL_CONFIGS
    from dlbb_tpu.models.transformer import forward, init_params
    from dlbb_tpu.utils.timing import (
        resolve_timing_mode,
        single_iteration_estimate,
        time_fn_chained,
    )

    checks = []
    for size, attention in (("1B", "simplified"), ("1B", "full")):
        config = MODEL_CONFIGS[size].with_(attention=attention)
        params = init_params(config, jax.random.key(42))
        batch = jax.random.normal(
            jax.random.key(0), (8, 512, config.hidden_size),
            dtype=jnp.bfloat16,
        )
        step = jax.jit(lambda p, x, c=config: forward(p, x, c))

        # the timing loop DONATES batch; the returned carry replaces it
        # for the forced-completion estimate below
        chained, meta, batch = time_fn_chained(
            step, batch, warmup=2, iterations=20, chunk_size=5,
            op_args=(params,),
        )
        chained_mean = sum(chained) / len(chained)
        single = single_iteration_estimate(
            step, batch, trials=5, op_args=(params,)
        )
        ratio = single / chained_mean if chained_mean > 0 else float("inf")
        checks.append({
            "config": f"{size}_{attention}_b8_s512",
            "chained_mean_s": chained_mean,
            "single_iteration_s": single,
            "single_over_chained": ratio,
            "fetch_overhead_s": meta["fetch_overhead_s"],
        })
        print(f"{size}/{attention}: chained {chained_mean * 1e3:.2f} ms, "
              f"single-forced {single * 1e3:.2f} ms, ratio {ratio:.3f}",
              flush=True)

    out = {
        "backend": jax.default_backend(),
        "devices": [str(d) for d in jax.devices()],
        "timing_mode_auto": resolve_timing_mode("auto"),
        "method": __doc__.strip().splitlines()[0],
        "checks": checks,
        "timestamp": time.time(),
    }
    path = REPO / "results" / "timing_crosscheck.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    atomic_write_text(json.dumps(out, indent=2) + "\n", path)
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
