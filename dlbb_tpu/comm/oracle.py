"""Numpy oracle for the reference collectives, and a one-shot check of a
real mesh against it.

:func:`expected_output` states what each op must produce from the global
host-side input (the semantics ``tests/test_collectives.py`` pins on the
simulated mesh); :func:`check_op` runs the op once on a mesh and compares.
``python -m dlbb_tpu.comm.oracle --ranks 4 --num-elements 4194304``
checks the eight reference ops on the first 4 devices and writes a JSON
report — the correctness half of the collectives level (``cli bench1d``
only times), which ``chip_smoke.py`` runs on a multi-chip host.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Optional

import numpy as np


def expected_output(op_name: str, host: np.ndarray, root: int = 0
                    ) -> np.ndarray:
    """The global ``[P, ...]`` output of ``op_name`` over ``P`` ranks for
    the global input ``host`` (``[P, *shape]``; ``[P, P, *shape]`` for the
    per-peer ops scatter / alltoall / reducescatter), in float64.
    Non-root ranks of the rooted ops hold zeros."""
    x = np.asarray(host, dtype=np.float64)
    p = x.shape[0]
    if op_name == "allreduce":
        return np.broadcast_to(x.sum(axis=0), x.shape)
    if op_name == "allgather":
        return np.broadcast_to(x, (p,) + x.shape)
    if op_name == "broadcast":
        return np.broadcast_to(x[root], x.shape)
    if op_name == "gather":
        out = np.zeros((p,) + x.shape)
        out[root] = x
        return out
    if op_name == "scatter":
        return x[root]  # rank i receives row i of the ROOT's sendbuf
    if op_name == "reduce":
        out = np.zeros(x.shape)
        out[root] = x.sum(axis=0)
        return out
    if op_name == "alltoall":
        return np.swapaxes(x, 0, 1)  # out[i][j] == in[j][i]
    if op_name == "sendrecv":
        return np.roll(x, 1, axis=0)  # rank i's buffer lands on rank i+1
    if op_name == "reducescatter":
        return x.sum(axis=0)[:, None]  # rank i: sum over senders of chunk i
    raise KeyError(f"no oracle for collective {op_name!r}")


def check_op(op_name: str, mesh, axes=("ranks",), num_elements: int = 64,
             dtype: Any = None, root: int = 0,
             rtol: Optional[float] = None, atol: Optional[float] = None
             ) -> dict[str, Any]:
    """Run ``op_name`` once on ``mesh`` and compare with the oracle.
    Raises ``AssertionError`` on a mismatch; returns what it saw.  The
    default tolerances follow the dtype (a bf16 sum over P ranks rounds
    at 2^-8 relative per add)."""
    import jax.numpy as jnp

    from dlbb_tpu.comm.ops import get_op, make_payload

    dtype = jnp.float32 if dtype is None else dtype
    low_precision = jnp.dtype(dtype).itemsize < 4
    rtol = (0.05 if low_precision else 1e-4) if rtol is None else rtol
    atol = (0.5 if low_precision else 1e-4) if atol is None else atol
    op = get_op(op_name)
    x = make_payload(op, mesh, axes, num_elements, dtype=dtype)
    out = op.build(mesh, axes, root)(x)
    got = np.asarray(out.astype(jnp.float32), dtype=np.float64)
    want = expected_output(op_name, np.asarray(x.astype(jnp.float32)), root)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=f"{op_name} disagrees with oracle")
    return {
        "operation": op_name,
        "shape": list(got.shape),
        "max_abs_error": float(np.max(np.abs(got - want))),
        "devices": len(out.sharding.device_set),
    }


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ranks", type=int, required=True)
    ap.add_argument("--num-elements", type=int, default=4_194_304,
                    help="per-rank payload elements (default: the "
                         "reference's '16MB' label, 8 MiB in bf16)")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--output", default=None, help="JSON report path")
    ap.add_argument("--simulate", type=int, default=0, metavar="N")
    args = ap.parse_args(argv)
    if args.simulate:
        from dlbb_tpu.utils.simulate import force_cpu_simulation

        force_cpu_simulation(args.simulate)
    from dlbb_tpu.utils.compile_cache import configure_compile_cache
    from dlbb_tpu.utils.simulate import require_accelerator

    configure_compile_cache()
    require_accelerator()

    import jax.numpy as jnp

    from dlbb_tpu.bench.runner import OPERATIONS_1D
    from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
    from dlbb_tpu.utils.config import save_json
    from dlbb_tpu.utils.sysinfo import collect_system_info

    mesh = build_mesh(MeshSpec.ring(args.ranks))
    checks = [
        check_op(name, mesh, num_elements=args.num_elements,
                 dtype=getattr(jnp, args.dtype))
        for name in OPERATIONS_1D
    ]
    for c in checks:
        print(f"[oracle] {c['operation']:10s} ok  max|err| "
              f"{c['max_abs_error']:.3g}  over {c['devices']} device(s)")
    report = {"ranks": args.ranks, "num_elements": args.num_elements,
              "dtype": args.dtype, "checks": checks,
              "system_info": collect_system_info()}
    if args.output:
        save_json(report, args.output)
    else:
        print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
