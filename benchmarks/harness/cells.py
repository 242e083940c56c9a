"""Resolve a cell of ``BENCHMARK.json`` to its data files, by name.

Nothing here knows any cell, configuration, traffic mix or metric: a
later PR adds an entry to ``BENCHMARK.json`` and the files it names
(``configs/<config>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.json``, ``readers/<reader>.py``) and edits no
file that is already here.
"""

from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"


def load_json(path: Path) -> dict[str, Any]:
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(f"{path} does not hold a JSON object")
    return data


def load_benchmark(root: Path = ROOT) -> dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    name: str
    chips: int
    config_name: str
    config: dict[str, Any]
    traffic_name: str
    traffic: dict[str, Any]
    end_to_end: tuple[dict[str, Any], ...]
    # each per-layer entry of BENCHMARK.json with the ``reader`` and
    # ``args`` of its layer_metrics/<name>.json
    per_layer: tuple[dict[str, Any], ...]


def _in_cell(metric: dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "benchmarks" / "traffic"
                        / f"{w['traffic']}.json")
    per_layer = tuple(
        {**load_json(root / "benchmarks" / "layer_metrics"
                     / f"{m['name']}.json"), **m}
        for m in bench["per_layer"] if _in_cell(m, name)
    )
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _in_cell(m, name)),
        per_layer=per_layer,
    )


def program_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; the program's seeds are 31-bit."""
    return seed % (2**31 - 1)


def runner_for(kind: str) -> Callable[..., Any]:
    """The runner of a traffic ``kind`` is ``harness/kind_<kind>.py``'s
    ``run``: a new kind of traffic is a new file."""
    return importlib.import_module(f"benchmarks.harness.kind_{kind}").run


def reader_for(reader: str) -> Callable[..., Optional[float]]:
    """A reader is ``readers/<reader>.py``'s ``read(run, **args)``."""
    return importlib.import_module(f"benchmarks.readers.{reader}").read
