"""1D microbenchmark statistics.

Schema parity with reference ``collectives/1d/stats.py``: per-file stats in
µs (mean/median/min/max/std/p95/p99), load-imbalance % over per-rank means
(:54-61), bus bandwidth GB/s from the *max* time (conservative choice,
:178-186), per-file ``*_stats.json`` and a consolidated
``benchmark_statistics.csv`` with the same columns (:226-241) plus one
trailing ``timing_granularity`` extension column (the 3D *standard* CSV,
whose header is asserted byte-identical to the reference's, instead puts
the marker in the transposed CSV's metadata block — see ``stats3d``).

The reference's bandwidth formula is uniform across all eight ops
(``elements x element_size x num_ranks / time / 2**30`` — :98-121, a
documented quirk, SURVEY "known quirks").  We keep it as the default for
curve comparability and offer ``algorithm_bandwidth=True`` for the standard
bus-bandwidth factors (e.g. ring allreduce moves ``2(P-1)/P`` bytes/elt).

Differences (documented, not silent):
- element size follows the recorded dtype (the reference hardcodes fp16's
  2 bytes at :93 even for other dtypes);
- per-rank timing rows are per-*host* dispatch timings under SPMD; with one
  process the load-imbalance over a single row is 0 by construction;
- a trailing ``timing_granularity`` CSV column marks rows computed from
  chunked-mode artifacts (``dlbb_tpu/utils/timing.py::time_fn_chained``),
  whose samples are chunk *means*: their p95/p99 measure the spread of
  chunk means, not per-iteration tail latencies, and must not be compared
  against per-iteration tails.  The per-file stats JSON carries the full
  ``percentile_caveat`` text.
"""

from __future__ import annotations

import csv
import io
import json
from pathlib import Path
from typing import Any, Optional

import numpy as np

from dlbb_tpu.utils.config import atomic_write_text

_DTYPE_BYTES = {
    "bfloat16": 2,
    "float16": 2,
    "float32": 4,
    "float64": 8,
    # reference records numpy repr strings like "<class 'numpy.float16'>"
    "<class 'numpy.float16'>": 2,
}

CSV_COLUMNS = [
    "mpi_implementation",
    "operation",
    "num_ranks",
    "data_size_name",
    "num_elements",
    "mean_time_us",
    "median_time_us",
    "min_time_us",
    "max_time_us",
    "std_dev_us",
    "p95_time_us",
    "p99_time_us",
    "load_imbalance_percent",
    "bandwidth_gbps",
    # extension columns (not in the reference):
    # - timing_granularity: "per_iteration" or "chunked(N)" — percentile
    #   columns of chunked rows are over chunk means, not per-iteration tails
    # - dtype: the measured element type; the corpus carries the north-star
    #   curve in BOTH bf16 and fp32 (BASELINE.json configs[1]), so rows are
    #   keyed by (op, size, ranks, dtype)
    # - bytes_on_wire: analytic per-device wire bytes of the op's
    #   implementation (dlbb_tpu.analysis.expectations.op_wire_bytes;
    #   blank for ops without a wire model).  bandwidth_gbps stays the
    #   reference's LOGICAL-payload formula, so compressed-vs-uncompressed
    #   curves normalise by logical bytes and this column shows the wire
    #   saving (docs/compression.md)
    "timing_granularity",
    "dtype",
    "bytes_on_wire",
]


def calculate_statistics(timings_2d: list[list[float]]) -> dict[str, Any]:
    """Aggregate stats (µs) + load imbalance over per-rank means
    (reference ``collectives/1d/stats.py:26-75``)."""
    arr = np.asarray(timings_2d, dtype=np.float64)
    per_rank_means = arr.mean(axis=1)
    flat = arr.ravel()
    mean_of_means = per_rank_means.mean()
    load_imbalance = (
        (per_rank_means.max() - mean_of_means) / mean_of_means * 100.0
        if mean_of_means > 0
        else 0.0
    )
    return {
        "mean_time_us": float(flat.mean() * 1e6),
        "median_time_us": float(np.median(flat) * 1e6),
        "min_time_us": float(flat.min() * 1e6),
        "max_time_us": float(flat.max() * 1e6),
        "std_dev_us": float(flat.std() * 1e6),
        "p95_time_us": float(np.percentile(flat, 95) * 1e6),
        "p99_time_us": float(np.percentile(flat, 99) * 1e6),
        "load_imbalance_percent": float(load_imbalance),
        "per_rank_means_us": (per_rank_means * 1e6).tolist(),
    }


# Logical bytes moved per element, as a multiple of (element_size), for the
# standard bus-bandwidth accounting (cf. nccl-tests bus bandwidth).
def _algo_volume_factor(operation: str, p: int) -> float:
    if operation in ("allreduce",):
        return 2.0 * (p - 1) / p * p  # 2(P-1) x elements x size total
    if operation in ("allgather", "reducescatter", "alltoall"):
        return float(p - 1)
    if operation in ("broadcast", "gather", "scatter", "reduce"):
        return float(p - 1)
    if operation == "sendrecv":
        return float(p)
    return float(p)


def calculate_bandwidth(
    num_elements: int,
    dtype: str,
    time_seconds: float,
    operation: str,
    num_ranks: int,
    algorithm_bandwidth: bool = False,
) -> Optional[float]:
    """Bus bandwidth in GB/s (GiB-based divisor, like the reference :124)."""
    if time_seconds <= 0:
        return None
    element_size = _DTYPE_BYTES.get(dtype, 2)
    if algorithm_bandwidth:
        volume = num_elements * element_size * _algo_volume_factor(
            operation, num_ranks
        )
    else:
        # reference's uniform formula (:98-121)
        volume = num_elements * element_size * num_ranks
    return float(volume / time_seconds / 2**30)


def process_file(
    json_path: Path, algorithm_bandwidth: bool = False
) -> dict[str, Any]:
    with open(json_path) as f:
        data = json.load(f)
    impl = (
        data.get("mpi_implementation")
        or data.get("implementation")
        or "unknown"
    )
    stats = calculate_statistics(data["timings"])
    bandwidth = calculate_bandwidth(
        data["num_elements"],
        data.get("dtype", "bfloat16"),
        stats["max_time_us"] / 1e6,
        data["operation"],
        data["num_ranks"],
        algorithm_bandwidth=algorithm_bandwidth,
    )
    # analytic wire volume (dlbb_tpu.analysis.expectations — jax-free, so
    # the stats path stays backend-free): lets compressed-vs-uncompressed
    # bus-bandwidth curves normalise by LOGICAL payload bytes (the
    # bandwidth column above) while still showing the wire saving
    from dlbb_tpu.analysis.expectations import op_wire_bytes

    wire = op_wire_bytes(
        data["operation"], data["num_elements"], data["num_ranks"],
        _DTYPE_BYTES.get(data.get("dtype", "bfloat16"), 2),
        compression=data.get("compression"),
    )
    out = {
        "mpi_implementation": impl,
        "operation": data["operation"],
        "num_ranks": data["num_ranks"],
        "data_size_name": data.get("data_size_name", ""),
        "num_elements": data["num_elements"],
        "dtype": data.get("dtype", ""),
        **stats,
        "bandwidth_gbps": bandwidth,
        "bytes_on_wire": wire,
        # reference artifacts (and per_iter runs) have no granularity
        # marker: their timing rows are genuine per-iteration samples
        "timing_granularity": data.get("timing_granularity",
                                       "per_iteration"),
        # measured backend ("cpu" = simulated mesh) — consumed by the
        # comparison's not_comparable(simulated) verdict; reference
        # artifacts record no system_info and get None
        "backend": (data.get("system_info") or {}).get("backend"),
    }
    if "percentile_caveat" in data:
        out["percentile_caveat"] = data["percentile_caveat"]
    return out


def process_1d_results(
    input_dir: str | Path,
    output_dir: str | Path,
    csv_name: str = "benchmark_statistics.csv",
    algorithm_bandwidth: bool = False,
    verbose: bool = True,
) -> list[dict[str, Any]]:
    """Process every result JSON in ``input_dir`` → per-file ``*_stats.json``
    + consolidated CSV in ``output_dir`` (reference
    ``collectives/1d/stats.py:135-250``).  Idempotent, like the reference's
    recompute-from-artifacts model (SURVEY §5.4)."""
    input_dir, output_dir = Path(input_dir), Path(output_dir)
    output_dir.mkdir(parents=True, exist_ok=True)
    results = []
    for json_file in sorted(input_dir.glob("*.json")):
        if json_file.name.endswith("_stats.json"):
            continue
        try:
            result = process_file(json_file, algorithm_bandwidth)
        except Exception as e:  # noqa: BLE001 — per-file resilience (:204)
            if verbose:
                print(f"  ERROR processing {json_file.name}: {e}")
            continue
        out = output_dir / (json_file.stem + "_stats.json")
        # atomic (tmp + fsync + os.replace): a killed stats pass must not
        # leave a torn *_stats.json that the next report run would parse
        atomic_write_text(json.dumps(result, indent=2), out)
        results.append(result)

    if results:
        buf = io.StringIO()
        writer = csv.DictWriter(buf, fieldnames=CSV_COLUMNS)
        writer.writeheader()
        for r in results:
            writer.writerow(
                {
                    k: v
                    for k, v in r.items()
                    if k not in ("per_rank_means_us",
                                 "percentile_caveat", "backend")
                }
            )
        atomic_write_text(buf.getvalue(), output_dir / csv_name, newline="")
        if verbose:
            print(f"Consolidated CSV saved: {output_dir / csv_name}")
    return results
