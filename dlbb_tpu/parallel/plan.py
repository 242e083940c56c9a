"""Shared parallelism-plan resolution for the E2E and train harnesses.

One place that parses the YAML ``parallelism:`` section, runs every
validation (device preflight — parity with reference ``run_mpi.py:73-77`` —
attention/sp, MoE/ep, pipeline divisibility), and builds the mesh; the two
harnesses consume the resulting plan instead of duplicating the logic.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models.configs import (
    ModelConfig,
    validate_attention_parallelism,
    validate_expert_parallelism,
    validate_tp_overlap,
)
from dlbb_tpu.parallel.pipeline import validate_pipeline


@dataclass(frozen=True)
class ParallelismPlan:
    dp: int
    sp: int
    pp: int
    ep: int
    tp: int
    num_microbatches: Optional[int]
    mesh: Mesh
    # the route the model's TP projections take on this mesh for the
    # input's whole batch ("off" | "ring" | "bidir": what
    # models/transformer.py::tp_overlap_route resolves, never the
    # configuration's "auto"), so harnesses can record it next to the
    # mesh in result JSON
    tp_overlap: str = "off"

    @classmethod
    def from_config(
        cls,
        config: dict[str, Any],
        model_cfg: ModelConfig,
        devices: Optional[Sequence] = None,
    ) -> "ParallelismPlan":
        par = config.get("parallelism", {})
        tp = par.get("world_size", 1)
        dp = par.get("data_parallel", 1)
        sp = par.get("sequence_parallel", 1)
        pp = par.get("pipeline_parallel", 1)
        ep = par.get("expert_parallel", 1)
        num_microbatches = par.get("num_microbatches")

        needed = tp * dp * sp * pp * ep
        n_avail = len(devices) if devices is not None else len(jax.devices())
        if needed > n_avail:
            raise ValueError(
                f"config needs {needed} devices (tp={tp} x dp={dp} x "
                f"sp={sp} x pp={pp} x ep={ep}), only {n_avail} available"
            )

        validate_attention_parallelism(model_cfg, sp)
        validate_expert_parallelism(model_cfg, ep)
        validate_tp_overlap(
            model_cfg, tp, pp=pp, sp=sp,
            seq_len=config.get("input", {}).get("sequence_length", 0),
        )
        if pp > 1:
            num_microbatches = validate_pipeline(
                model_cfg, pp, config["input"]["batch_size"],
                num_microbatches,
            )
        elif num_microbatches is not None:
            raise ValueError(
                "parallelism.num_microbatches requires "
                "pipeline_parallel > 1 (microbatching is the pipeline's "
                "schedule; without pp it would silently be ignored)"
            )

        mesh = build_parallelism_mesh(dp, sp, pp, tp, ep, devices=devices)
        from dlbb_tpu.models.transformer import tp_overlap_route

        inp = config.get("input", {})
        return cls(dp, sp, pp, ep, tp, num_microbatches, mesh,
                   tp_overlap=tp_overlap_route(
                       model_cfg, mesh,
                       (inp.get("batch_size", 0),
                        inp.get("sequence_length", 0),
                        model_cfg.hidden_size)))

    def mesh_dict(self) -> dict[str, int]:
        """The result-JSON ``mesh`` field."""
        return {"dp": self.dp, "sp": self.sp, "pp": self.pp,
                "ep": self.ep, "tp": self.tp}
