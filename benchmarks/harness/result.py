"""What a runner hands back, and the one line the benchmark prints."""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Optional

from benchmarks.harness import trace_reduce
from benchmarks.harness.cells import Cell, reader_for


@dataclass
class Run:
    """One measured run of one cell.

    ``values``: the end-to-end numbers the runner took on its own clock,
    by metric name (every one the cell's ``end_to_end`` lists but
    ``setup_s``, which the entry point works out from ``started_at``).  ``samples``: lists of
    numbers for the readers (step times, latencies, series);
    ``scalars``: single numbers for them.  ``profile``: the reduced
    trace of a ``--trace 1`` run, else empty."""

    cell: Cell
    seconds: float
    # perf_counter as the measured window opened: set-up ends here
    started_at: float
    correct: bool
    attempted: int
    failed: int
    values: dict[str, float]
    samples: dict[str, list[float]] = field(default_factory=dict)
    scalars: dict[str, float] = field(default_factory=dict)
    profile: dict[str, Any] = field(default_factory=dict)
    device: dict[str, Any] = field(default_factory=dict)
    # why ``correct`` is false, for the log (stderr), never the line
    faults: list[str] = field(default_factory=list)
    # perf_counter at the ends of the set-up's phases, for the log
    phases: dict[str, float] = field(default_factory=dict)

    def describe(self, since: float) -> str:
        """Everything the run took, in one line for the log: values,
        scalars, each sample list's count, median and maximum, and when
        each phase of the set-up ended, in seconds after ``since``."""
        import numpy as np

        lists = {k: [len(v), float(np.median(v)), float(np.max(v))]
                 for k, v in self.samples.items() if len(v)}
        ends = {k: round(t - since, 3) for k, t in
                {**self.phases, "window": self.started_at}.items()}
        return json.dumps({"cell": self.cell.name, "values": self.values,
                           "scalars": self.scalars, "samples": lists,
                           "setup_phase_ends_s": ends})


def result_line(run: Run, setup_s: float, trace: bool) -> str:
    cell = run.cell
    metrics: dict[str, dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            value: Optional[float] = reader_for(m["reader"])(
                run, **m.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = {**run.values, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    line: dict[str, Any] = {
        "correct": bool(run.correct), "attempted": int(run.attempted),
        "failed": int(run.failed), "metrics": metrics,
        "device": dict(run.device),
    }
    if trace and run.profile:
        line["device"]["busy_s"] = run.profile["busy_s"]
        line["device"]["window_s"] = run.profile["window_s"]
        line["breakdown"] = {
            "device_ops": trace_reduce.top(run.profile["op_seconds"]),
            "idle_gaps": trace_reduce.top(run.profile["idle_gaps"]),
        }
    return json.dumps(line)
