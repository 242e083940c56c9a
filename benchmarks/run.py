"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: resolve the cell from ``BENCHMARK.json``
by name, build the system under test from the seed, warm up the cell's
own shapes (set-up), measure for ``--seconds``, check the outputs, and
print one JSON object as the last line of standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` the per-layer metrics its readers find in a traced slice
of the same window.  No chip, or fewer than the cell asks for, is an
error and prints no result.  See ``benchmarks/README.md``.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
# profiler traces and span files of a traced run: inside the checkout,
# listed in .gitignore, emptied before each traced run
SCRATCH = ROOT / ".bench_scratch"


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from benchmarks.harness import device
    from benchmarks.harness.cells import resolve_cell, runner_for
    from benchmarks.harness.result import result_line
    from dlbb_tpu.utils.compile_cache import configure_compile_cache

    cell = resolve_cell(args.workload)
    configure_compile_cache()
    device.require_chips(cell.chips)
    chip_at = time.perf_counter()
    compiles = device.CompileCounter()

    scratch = SCRATCH / cell.name
    if args.trace:
        shutil.rmtree(scratch, ignore_errors=True)
        scratch.mkdir(parents=True)
    run = runner_for(cell.traffic["kind"])(
        cell, args.seed, args.seconds, bool(args.trace), compiles,
        str(scratch))
    run.phases["chip"] = chip_at
    print(f"[benchmark] {run.describe(T_START)}", file=sys.stderr)
    for fault in run.faults:
        print(f"[benchmark] not correct: {fault}", file=sys.stderr)
    print(result_line(run, setup_s=run.started_at - T_START,
                      trace=bool(args.trace)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
