"""Pass 1 — HLO collective auditor.

Lowers every registered benchmark computation on a (usually CPU-simulated)
mesh, compiles it, and audits the post-SPMD HLO against the analytic
expectation model (``expectations.py``): every collective instruction must
be of an allowed kind and within its byte envelope, the op's defining
primitive must actually appear, and train-step computations must donate
their state buffers.  This catches the GSPMD failure mode the framework is
most exposed to — a sharding mismatch silently inserting an all-gather (or
replicating a computation) *before* any device time is spent measuring it.

Audit targets are plain builders ``mesh_free_callable() -> (fn, args,
expectation)`` so the default registry below can be extended by tests (the
seeded-violation fixtures) and future benchmarks.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

from dlbb_tpu.analysis.expectations import (
    TargetExpectation,
    compressed_op_expectation,
    op_expectation,
    overlap_op_expectation,
    plan_expected_kinds,
    wire_bytes,
)
from dlbb_tpu.analysis.findings import (
    SEVERITY_ERROR,
    AnalysisReport,
    Finding,
)
from dlbb_tpu.analysis.hlo_parse import (
    CollectiveInstr,
    has_donation,
    parse_collectives,
)


@dataclass
class AuditTarget:
    """One computation to lower + audit.

    ``build()`` returns ``(fn, args)`` where ``fn`` is jittable (or already
    a ``jax.jit`` object) and ``args`` the example arguments to lower with.
    ``min_devices`` lets the driver skip targets the current platform
    cannot host instead of crashing mid-audit.
    """

    name: str
    build: Callable[[], tuple[Any, tuple]]
    expectation: TargetExpectation
    min_devices: int = 1


def audit_target(
    target: AuditTarget,
    passes: Sequence[str] = ("hlo",),
    tier: Optional[object] = None,
    model: str = "cm1",
) -> tuple[list[Finding], dict]:
    """Lower, compile, parse, and check one target.  Returns the findings
    plus a meta dict (instruction inventory, and — when the ``schedule``
    pass is requested — the α–β schedule report) for the JSON report.
    One lowering serves both passes: ``analyze all`` does not compile the
    30-target surface twice."""
    import jax

    from dlbb_tpu.analysis.hlo_parse import parse_module

    fn, args = target.build()
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    module = parse_module(compiled_text)
    exp = target.expectation

    findings: list[Finding] = []
    meta: dict = {}
    if "schedule" in passes:
        from dlbb_tpu.analysis.schedule_audit import analyze_schedule

        sched_findings, sched_meta = analyze_schedule(
            module, exp, target.name, tier=tier, model=model,
        )
        findings.extend(sched_findings)
        meta["schedule"] = sched_meta
    if "memory" in passes:
        from dlbb_tpu.analysis.costmodel import CostTier
        from dlbb_tpu.analysis.memory_audit import analyze_memory

        mem_findings, mem_meta = analyze_memory(
            module, exp, target.name,
            lowered_text=lowered.as_text(),
            # the TARGET's mesh size, not the host's device count: every
            # builder stands up exactly min_devices devices (a dp1 x tp4
            # prefix-attach target on an 8-device host still runs a 4-way
            # mesh, and the replicated-spike P-factor must match it)
            num_devices=max(1, target.min_devices),
            tier=tier if isinstance(tier, CostTier) else None,
        )
        findings.extend(mem_findings)
        meta["memory"] = mem_meta
    if "numerics" in passes:
        from dlbb_tpu.analysis.numerics_audit import analyze_numerics

        num_findings, num_meta = analyze_numerics(
            module, exp, target.name,
            num_devices=max(1, target.min_devices),
            # price silent-upcast carries against the memory pass's peak
            # when both passes ride the same lowering (`analyze all`)
            peak_live_bytes=meta.get("memory", {}).get("peak_live_bytes"),
        )
        findings.extend(num_findings)
        meta["numerics"] = num_meta
    if "hlo" not in passes:
        return findings, meta

    instrs = parse_collectives(module)
    for instr in instrs:
        base = _instr_details(instr, exp)
        if instr.kind not in exp.allowed:
            findings.append(Finding(
                pass_name="hlo",
                rule="unexpected-collective",
                severity=SEVERITY_ERROR,
                target=target.name,
                message=(
                    f"{instr.kind} of {instr.dtype}{list(instr.shape)} "
                    f"({instr.result_bytes} B/device) is not in the "
                    f"plan's allowed set {sorted(exp.allowed)} — likely a "
                    "sharding mismatch (GSPMD inserted a collective the "
                    "parallelism plan does not account for)"
                ),
                location=instr.source,
                details=base,
            ))
        elif (exp.max_bytes_per_instr is not None
                and instr.result_bytes > exp.max_bytes_per_instr):
            findings.append(Finding(
                pass_name="hlo",
                rule="oversized-collective",
                severity=SEVERITY_ERROR,
                target=target.name,
                message=(
                    f"{instr.kind} carries {instr.result_bytes} B/device, "
                    f"over the plan ceiling of {exp.max_bytes_per_instr} B "
                    "— a larger buffer than the benchmark claims to move"
                ),
                location=instr.source,
                details=base,
            ))
    if exp.required_any:
        # execution-weighted: a collective inside a scanned layer body
        # counts once per trip, not once per static instruction (the
        # while-body undercount fix, pinned by test_schedule_audit)
        hits = sum(
            i.execution_count for i in instrs if i.kind in exp.required_any
        )
        if hits < exp.min_required:
            findings.append(Finding(
                pass_name="hlo",
                rule="missing-collective",
                severity=SEVERITY_ERROR,
                target=target.name,
                message=(
                    f"expected >= {exp.min_required} execution(s) of "
                    f"{sorted(exp.required_any)}, found {hits} — the "
                    "benchmark does not perform the collective it claims "
                    "(XLA may have elided or replaced it)"
                ),
                details={
                    "expected_kinds": sorted(exp.required_any),
                    "expected_min_count": exp.min_required,
                    "found_count": hits,
                    "present": [i.to_dict() for i in instrs],
                },
            ))
    total_wire = sum(
        wire_bytes(i.kind, i.result_bytes, i.group_size)
        * i.execution_count
        for i in instrs
    )
    if (exp.max_total_wire_bytes is not None
            and total_wire > exp.max_total_wire_bytes):
        findings.append(Finding(
            pass_name="hlo",
            rule="wire-volume-ceiling",
            severity=SEVERITY_ERROR,
            target=target.name,
            message=(
                f"total analytic wire volume {total_wire} B/device exceeds "
                f"the ceiling of {exp.max_total_wire_bytes} B — for a "
                "compressed collective this means the quantisation did "
                "not reach the wire (XLA dequantised before the "
                "collective, or an uncompressed reduction survived)"
            ),
            details={
                "total_wire_bytes": total_wire,
                "max_total_wire_bytes": exp.max_total_wire_bytes,
                "per_instr_wire_bytes": [
                    {"kind": i.kind,
                     "execution_count": i.execution_count,
                     "wire_bytes": wire_bytes(
                         i.kind, i.result_bytes, i.group_size)}
                    for i in instrs
                ],
            },
        ))
    if exp.expect_donation and not has_donation(lowered.as_text(),
                                                compiled_text):
        findings.append(Finding(
            pass_name="hlo",
            rule="missing-donation",
            severity=SEVERITY_ERROR,
            target=target.name,
            message=(
                "no input buffer is donated (no aliasing/buffer-donor "
                "marker in the lowered module and no input_output_alias "
                "in the compiled one) — the step keeps input AND output "
                "state resident, doubling state HBM"
            ),
            details={"expected": "donate_argnums on the step jit"},
        ))
    meta.update({
        "collectives": [i.to_dict() for i in instrs],
        "num_collectives": sum(i.execution_count for i in instrs),
        "total_wire_bytes": total_wire,
    })
    return findings, meta


def _instr_details(instr: CollectiveInstr, exp: TargetExpectation) -> dict:
    d = instr.to_dict()
    d["expected_allowed_kinds"] = sorted(exp.allowed)
    d["expected_max_bytes_per_instr"] = exp.max_bytes_per_instr
    d["analytic_wire_bytes"] = wire_bytes(
        instr.kind, instr.result_bytes, instr.group_size
    )
    return d


# ---------------------------------------------------------------------------
# default target registry
# ---------------------------------------------------------------------------

_TINY_MODEL = dict(hidden_size=64, num_layers=2, num_heads=4,
                   ffn_intermediate=128, dtype="float32",
                   attention="full")


# (B, S, H) audit payload for the collective-matmul targets: S and H
# divisible by the 8-rank ring, small enough to lower in milliseconds
_MATMUL_SHAPE = (2, 16, 64)


def _tiny_params_bytes() -> int:
    """f32 parameter bytes of the shared tiny audit model — the unit every
    model/train/serve peak-memory ceiling is priced in (the analytic
    "model size" the memory audit's ceilings are seeded from)."""
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.models.transformer import num_parameters

    return num_parameters(ModelConfig(**_TINY_MODEL)) * 4


def _collective_matmul_target(op_name: str, schedule: str,
                              num_ranks: int = 8) -> AuditTarget:
    """One audit target per (micro-op, schedule).  The fused schedule must
    show its defining gather/scatter; the decomposed schedules must show
    the pure collective-permute chain (``overlap_op_expectation``) —
    comm-lint is the correctness gate for the overlap claim."""
    import numpy as np

    def build():
        import jax.numpy as jnp

        from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
        from dlbb_tpu.comm.ops import (
            build_ag_matmul,
            build_matmul_rs,
            get_op,
            make_payload,
        )

        mesh = build_mesh(MeshSpec.ring(num_ranks))
        builder = (build_ag_matmul if op_name == "ag_matmul"
                   else build_matmul_rs)
        fn = builder(mesh, ("ranks",), schedule=schedule)
        x = make_payload(
            get_op(op_name), mesh, ("ranks",),
            int(np.prod(_MATMUL_SHAPE)), dtype=jnp.float32,
            shape=_MATMUL_SHAPE,
        )
        return fn, (x,)

    per_rank = int(np.prod(_MATMUL_SHAPE)) * 4  # float32
    if schedule == "fused":
        # the gather/scatter result may span the whole gathered payload
        exp = op_expectation(op_name, per_rank * num_ranks)
        # resident: gathered activations (P x per-rank) + input + weight
        # + partials — a fused schedule's peak is gather-dominated
        exp.max_peak_bytes = int(2.5 * per_rank * num_ranks)
    else:
        # each hop carries at most one travelling per-rank chunk
        exp = overlap_op_expectation(num_ranks, per_rank)
        # the whole point of the ring: never materialise the P x gather
        # — input + weight + accumulator + in-flight chunks stay within
        # a few per-rank payloads, far under the fused ceiling (XLA
        # undoing the decomposition blows this before the kind gate)
        exp.max_peak_bytes = 8 * per_rank
    return AuditTarget(
        name=f"comm/ops.py::{op_name}[{schedule}]",
        build=build,
        expectation=exp,
        min_devices=num_ranks,
    )


def _compressed_op_target(op_name: str, compression: str,
                          num_ranks: int = 8,
                          num_elements: int = 4096) -> AuditTarget:
    """One audit target per (compressed micro-op, wire dtype).  The
    expectation is the compression proof: a pure quantised ring (plus the
    wire-dtype gather phase for allreduce_q) whose TOTAL analytic wire —
    scale side channel included — stays under 0.55x the uncompressed
    bf16 wire (``expectations.compressed_op_expectation``,
    docs/compression.md)."""
    import jax.numpy as jnp

    def build():
        from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
        from dlbb_tpu.comm.ops import get_op, make_payload

        op = get_op(op_name)
        mesh = build_mesh(MeshSpec.ring(num_ranks))
        fn = op.build(mesh, ("ranks",), compression=compression)
        # bf16 payload: the baseline the 0.55x ceiling is priced against
        x = make_payload(op, mesh, ("ranks",), num_elements,
                         dtype=jnp.bfloat16)
        return fn, (x,)

    exp = compressed_op_expectation(
        op_name, num_ranks, num_elements, compression=compression)
    # bf16 payload + quantised wire buffers + scales; the per-peer
    # reducescatter_q input is a [P, n] slab per rank
    exp.max_peak_bytes = (
        2 * num_ranks * num_elements * 2 if op_name == "reducescatter_q"
        else 4 * num_elements * 2 + 8192
    )
    return AuditTarget(
        name=f"comm/ops.py::{op_name}[{compression}]",
        build=build,
        expectation=exp,
        min_devices=num_ranks,
    )


def _registry_op_target(op_name: str, num_ranks: int = 8,
                        num_elements: int = 256) -> AuditTarget:
    import jax.numpy as jnp

    def build():
        from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
        from dlbb_tpu.comm.ops import get_op, make_payload

        op = get_op(op_name)
        if op_name == "allreduce_hierarchical":
            mesh = build_mesh(MeshSpec.grid(
                (2, num_ranks // 2), ("outer", "inner")))
            axes = ("outer", "inner")
        else:
            mesh = build_mesh(MeshSpec.ring(num_ranks))
            axes = ("ranks",)
        fn = op.build(mesh, axes)
        x = make_payload(op, mesh, axes, num_elements, dtype=jnp.float32)
        return fn, (x,)

    per_rank = num_elements * 4  # float32 payloads
    # gather-family results hold every rank's buffer on each device; the
    # per-peer input kinds already carry a [P, n] slab per rank
    if op_name in ("allgather", "gather", "scatter", "alltoall",
                   "reducescatter"):
        ceiling = per_rank * num_ranks
    else:
        ceiling = per_rank
    exp = op_expectation(op_name, ceiling)
    # resident: input (+ the [P, n] slab for per-peer kinds), result, and
    # a couple of masked-contribution temps — all payload-scale
    exp.max_peak_bytes = 4 * ceiling + 8192
    return AuditTarget(
        name=f"comm/ops.py::{op_name}",
        build=build,
        expectation=exp,
        min_devices=num_ranks,
    )


def _barrier_target(num_ranks: int = 8) -> AuditTarget:
    """``build_barrier`` is the timing synchronisation point, not a
    registry op, so it gets its own target — the barrier must stay a
    scalar-sized all-reduce, never anything that moves real data."""
    import jax.numpy as jnp

    def build():
        from dlbb_tpu.comm.mesh import MeshSpec, build_mesh
        from dlbb_tpu.comm.ops import build_barrier

        mesh = build_mesh(MeshSpec.ring(num_ranks))
        fn = build_barrier(mesh, ("ranks",))
        x = jnp.ones((num_ranks, 1), jnp.float32)
        return fn, (x,)

    exp = op_expectation("barrier", 4)  # one f32 scalar/device
    exp.max_peak_bytes = 8192  # scalars only — anything more is data
    return AuditTarget(
        name="comm/ops.py::barrier",
        build=build,
        expectation=exp,
        min_devices=num_ranks,
    )


def _tp_forward_target(dp: int = 2, tp: int = 4) -> AuditTarget:
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from dlbb_tpu.comm.mesh import build_parallelism_mesh
        from dlbb_tpu.models.configs import ModelConfig
        from dlbb_tpu.models.sharding import batch_spec
        from dlbb_tpu.models.transformer import (
            forward,
            init_params_sharded,
        )

        cfg = ModelConfig(**_TINY_MODEL)
        mesh = build_parallelism_mesh(data_parallel=dp, tensor_parallel=tp)
        params = init_params_sharded(cfg, jax.random.key(0), mesh)
        x = jax.device_put(
            jnp.ones((2 * dp, 8, cfg.hidden_size), jnp.float32),
            NamedSharding(mesh, batch_spec(mesh)),
        )
        fn = jax.jit(
            lambda p, a: forward(p, a, cfg, mesh=mesh),
            out_shardings=NamedSharding(mesh, batch_spec(mesh)),
        )
        return fn, (params, x)

    # per-device activation shard: [B/dp, S, H] f32
    act_bytes = (2 * dp // dp) * 8 * _TINY_MODEL["hidden_size"] * 4
    return AuditTarget(
        name="models/transformer.py::forward[dp,tp]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=dp, tp=tp),
            required_any={"all-reduce"},
            min_required=1,  # Megatron row-parallel psum (XLA may combine)
            max_bytes_per_instr=int(act_bytes * 1.25),
            # tp-sharded weights (~n4/tp) + activations/temps; a Megatron
            # layout collapsing to replication puts the FULL n4 resident
            # and blows this before the all-gather even fires
            max_peak_bytes=int(0.7 * _tiny_params_bytes()),
        ),
        min_devices=dp * tp,
    )


def _cp_forward_target(attention: str, dp: int = 2, sp: int = 4) -> AuditTarget:
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from dlbb_tpu.comm.mesh import build_parallelism_mesh
        from dlbb_tpu.models.configs import ModelConfig
        from dlbb_tpu.models.sharding import batch_spec
        from dlbb_tpu.models.transformer import forward, init_params_sharded

        cfg = ModelConfig(**{**_TINY_MODEL, "attention": attention})
        mesh = build_parallelism_mesh(data_parallel=dp, sequence_parallel=sp)
        params = init_params_sharded(cfg, jax.random.key(0), mesh)
        x = jax.device_put(
            jnp.ones((dp, 16, cfg.hidden_size), jnp.float32),
            NamedSharding(mesh, batch_spec(mesh)),
        )
        fn = jax.jit(
            lambda p, a: forward(p, a, cfg, mesh=mesh),
            out_shardings=NamedSharding(mesh, batch_spec(mesh)),
        )
        return fn, (params, x)

    required = ("collective-permute" if attention == "ring"
                else "all-to-all")
    return AuditTarget(
        name=f"models/transformer.py::forward[sp,{attention}]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=dp, sp=sp, attention=attention),
            required_any={required},
            min_required=1,
            # sp shards the sequence, NOT the weights: the full f32
            # parameter set is resident per device, plus sp-sharded
            # activations/ring buffers
            max_peak_bytes=int(1.3 * _tiny_params_bytes()) + 65536,
        ),
        min_devices=dp * sp,
    )


def _tp_overlap_forward_target(schedule: str, dp: int = 2,
                               tp: int = 4) -> AuditTarget:
    """The overlapped TP forward (model.tp_overlap = ring|bidir).  The
    audit is the correctness gate for the decomposition: every projection
    collective must be a ppermute chain (>= 4 ring matmuls x (tp-1) hops
    in the scanned layer body), NO all-reduce may survive, and the only
    all-gather allowed is the single activation-sized reshard back to the
    caller's batch layout — anything bigger means the Megatron layout
    collapsed or the decomposition was undone."""
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from dlbb_tpu.comm.mesh import build_parallelism_mesh
        from dlbb_tpu.models.configs import ModelConfig
        from dlbb_tpu.models.sharding import batch_spec
        from dlbb_tpu.models.transformer import (
            forward,
            init_params_sharded,
        )

        cfg = ModelConfig(**_TINY_MODEL, tp_overlap=schedule)
        mesh = build_parallelism_mesh(data_parallel=dp, tensor_parallel=tp)
        params = init_params_sharded(cfg, jax.random.key(0), mesh)
        x = jax.device_put(
            jnp.ones((2 * dp, 8, cfg.hidden_size), jnp.float32),
            NamedSharding(mesh, batch_spec(mesh)),
        )
        fn = jax.jit(
            lambda p, a: forward(p, a, cfg, mesh=mesh),
            out_shardings=NamedSharding(mesh, batch_spec(mesh)),
        )
        return fn, (params, x)

    # per-device activation shard: [B/dp, S, H] f32 — the ceiling for the
    # final reshard gather AND every travelling ring chunk (chunks are
    # 1/tp of it)
    act_bytes = (2 * dp // dp) * 8 * _TINY_MODEL["hidden_size"] * 4
    return AuditTarget(
        name=f"models/transformer.py::forward[dp,tp,overlap={schedule}]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(tp=tp, tp_overlap=schedule),
            required_any={"collective-permute"},
            # 4 ring matmuls per scanned layer body, (tp-1) hops each
            min_required=4 * (tp - 1),
            max_bytes_per_instr=int(act_bytes * 1.25),
            # every ring hop must be hidden behind a partial matmul —
            # the schedule auditor's serialized-collective gate
            expect_overlap=True,
            # same resident set as the GSPMD forward: tp-sharded weights
            # + sequence-sharded activations + ring chunks
            max_peak_bytes=int(0.7 * _tiny_params_bytes()),
        ),
        min_devices=dp * tp,
    )


def _tp_overlap_train_target(schedule: str, dp: int = 2,
                             tp: int = 4) -> AuditTarget:
    """The overlapped train step: the custom VJP must keep the backward
    on ppermute chains too (forward + dx + dw rings), with the only
    all-reduces the dp gradient reductions (weight-shard sized, inserted
    by the psum over batch axes inside the weight-grad rings) — and the
    state donation of the train-step convention intact."""
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        import optax

        from dlbb_tpu.comm.mesh import build_parallelism_mesh
        from dlbb_tpu.models.configs import ModelConfig
        from dlbb_tpu.models.sharding import batch_spec
        from dlbb_tpu.models.transformer import init_params_sharded
        from dlbb_tpu.train.loop import make_train_step

        cfg = ModelConfig(**_TINY_MODEL, tp_overlap=schedule)
        mesh = build_parallelism_mesh(data_parallel=dp, tensor_parallel=tp)
        params = init_params_sharded(cfg, jax.random.key(0), mesh)
        jit_step, state = make_train_step(
            cfg, mesh, optax.adam(1e-3), params, zero_stage=0,
        )
        sharding = NamedSharding(mesh, batch_spec(mesh))
        batch = jax.device_put(
            jnp.ones((2 * dp, 8, cfg.hidden_size), jnp.float32), sharding)
        tgt = jax.device_put(
            jnp.ones((2 * dp, 8, cfg.hidden_size), jnp.float32), sharding)
        return jit_step, (state, batch, tgt)

    # combined dp weight-grad all-reduces are bounded by the full f32
    # parameter pytree; every ring chunk and the final activation reshard
    # are far below it
    params_bytes = _tiny_params_bytes()
    return AuditTarget(
        name=f"train/loop.py::train_step[dp,tp,overlap={schedule}]",
        build=build,
        expectation=TargetExpectation(
            # all-to-all: GSPMD reshards the scanned backward's
            # broadcast-zero cotangent init with a (tiny, constant-operand)
            # all-to-all on some jaxlibs — covered by the byte ceiling, and
            # absent from the forward target where the strict set holds
            allowed=plan_expected_kinds(dp=dp, tp=tp, tp_overlap=schedule)
            | {"all-to-all"},
            required_any={"collective-permute"},
            # forward chain alone is 4 rings x (tp-1); the backward adds
            # its own dx/dw rings on top
            min_required=4 * (tp - 1),
            max_bytes_per_instr=int(params_bytes * 1.25),
            expect_donation=True,
            expect_overlap=True,
            # tp-sharded Adam state (3 x n4/tp, donated) + grads + ring
            # transients; a dropped donation re-adds the whole state
            # shard and blows this first
            max_peak_bytes=int(2.0 * params_bytes),
        ),
        min_devices=dp * tp,
    )


def _compressed_train_target(compression: str = "int8",
                             dp: int = 8) -> AuditTarget:
    """The compressed DDP train step (training.grad_compression): the dp
    gradient reduction must be the quantised ring — collective-permutes
    plus the wire-dtype all-gather — with the only all-reduce the scalar
    loss mean, the error-feedback residual donated with the rest of the
    state, and the TOTAL analytic wire (scales included) under 0.55x the
    bf16 baseline's ``2(P-1)/P x 2 bytes x n_params``.  This is the
    acceptance gate proving XLA did not dequantise before the wire."""
    def build():
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding

        from dlbb_tpu.comm.mesh import build_parallelism_mesh
        from dlbb_tpu.models.configs import ModelConfig
        from dlbb_tpu.models.sharding import batch_spec
        from dlbb_tpu.models.transformer import init_params_sharded
        from dlbb_tpu.train.loop import make_train_step

        cfg = ModelConfig(**_TINY_MODEL)
        mesh = build_parallelism_mesh(data_parallel=dp)
        params = init_params_sharded(cfg, jax.random.key(0), mesh)
        jit_step, state = make_train_step(
            cfg, mesh, optax.adam(1e-3), params, zero_stage=0,
            grad_compression=compression,
        )
        sharding = NamedSharding(mesh, batch_spec(mesh))
        batch = jax.device_put(
            jnp.ones((dp, 8, cfg.hidden_size), jnp.float32), sharding)
        tgt = jax.device_put(
            jnp.ones((dp, 8, cfg.hidden_size), jnp.float32), sharding)
        return jit_step, (state, batch, tgt)

    from dlbb_tpu.analysis.expectations import (
        compression_wire_ceiling,
        op_wire_bytes,
        scale_bytes,
    )

    n_params = _tiny_params_bytes() // 4
    baseline = wire_bytes("all-reduce", n_params * 2, dp)  # bf16 ring AR
    # the grads ride as one flat allreduce_q-shaped reduction; the
    # ceiling is the shared contract of compression_wire_ceiling
    analytic = op_wire_bytes("allreduce_q", n_params, dp, 2,
                             compression=compression)
    return AuditTarget(
        name=f"train/loop.py::train_step[ddp,compressed={compression}]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=dp, compression=compression),
            required_any={"collective-permute"},
            min_required=dp - 1,
            # largest legitimate instruction: the quantised flat-grad
            # all-gather (~n_params wire bytes, chunk-padded)
            max_bytes_per_instr=int(
                n_params * 1.25 + scale_bytes(n_params) * dp),
            max_total_wire_bytes=compression_wire_ceiling(
                baseline, analytic),
            expect_donation=True,
            # DDP Adam state (3 x n4) + the P("dp")-sharded EF residual
            # (~n4/device) + grads + quantise/dequantise ring buffers
            max_peak_bytes=int(7.5 * n_params * 4),
        ),
        min_devices=dp,
    )


# Serving audit geometry (dlbb_tpu/serve/): the tiny model on a dp2 x
# tp4 mesh, 4 decode slots of 4 x 8-token cache blocks, one 16-token
# prefill bucket.  Shared by the decode and prefill targets so their
# byte ceilings price the same cache.
_SERVE_SHAPE = dict(max_batch=4, num_blocks=4, block_size=8, bucket=16)


def _serve_cache_bytes_per_device(dp: int, tp: int,
                                  num_layers: Optional[int] = None,
                                  kv_quantization: str = "none") -> int:
    """Analytic per-device KV-cache footprint of the serving audit
    geometry — the SAME ``models.configs.kv_cache_bytes_per_device``
    the build-time HBM budget gate prices, wired into the decode/prefill
    expectations as ``donated_bytes_expected`` so the memory audit's
    ``serving-cache-drift`` rule pins formula and compiled program to
    each other.  ``num_layers`` overrides the tiny model's depth — the
    speculative draft plane (1 layer) prices through the same formula;
    ``kv_quantization="int8"`` prices the quantized layout (int8 data
    planes + the per-(block, kv-head) fp32 scale side-channel)."""
    from dlbb_tpu.models.configs import (
        ModelConfig,
        kv_cache_bytes_per_device,
    )

    model = dict(_TINY_MODEL)
    if num_layers is not None:
        model["num_layers"] = num_layers
    return kv_cache_bytes_per_device(
        ModelConfig(**model),
        _SERVE_SHAPE["max_batch"],
        _SERVE_SHAPE["num_blocks"] * _SERVE_SHAPE["block_size"],
        dp=dp, tp=tp,
        kv_quantization=kv_quantization,
        block_size=_SERVE_SHAPE["block_size"],
    )


def _serve_build(dp: int, tp: int, what: str, k: int = 4):
    """Common builder for the serving targets: the GPT family's jits
    (``serve/gpt.py``) + example args on a (dp, tp) mesh — the exact
    programs ``serve/engine.py`` runs, so the audit gates the real
    decode/prefill/fast-path lowerings.  ``what`` selects decode /
    decode_fused / prefill / prefill_chunk — plus the
    speculative-decoding programs decode_fused_token / verify /
    draft_scan; ``k`` is the fused-scan trip count (and doubles as γ
    for the speculative targets)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlbb_tpu.comm.mesh import build_parallelism_mesh
    from dlbb_tpu.data.synthetic import token_embedding_table
    from dlbb_tpu.models.configs import ModelConfig
    from dlbb_tpu.models.transformer import init_params_sharded
    from dlbb_tpu.serve.gpt import (
        build_decode_fused,
        build_decode_fused_token,
        build_decode_step,
        build_draft_scan,
        build_prefill,
        build_prefill_chunk,
        build_verify_step,
        decode_batch_spec,
    )
    from dlbb_tpu.serve.kvcache import create_kv_cache

    cfg = ModelConfig(**_TINY_MODEL)
    mesh = build_parallelism_mesh(data_parallel=dp, tensor_parallel=tp)
    params = init_params_sharded(cfg, jax.random.key(0), mesh)
    cache = create_kv_cache(
        cfg, _SERVE_SHAPE["max_batch"], _SERVE_SHAPE["num_blocks"],
        _SERVE_SHAPE["block_size"], mesh=mesh,
    )
    x = jax.device_put(
        jnp.zeros((_SERVE_SHAPE["max_batch"], 1, cfg.hidden_size),
                  jnp.float32),
        NamedSharding(mesh, decode_batch_spec(mesh)),
    )
    active = jax.device_put(
        jnp.ones((_SERVE_SHAPE["max_batch"],), bool),
        NamedSharding(mesh, P()),
    )
    if what == "decode":
        fn = build_decode_step(cfg, mesh)
        return fn, ((cache, x), params, active)
    if what == "decode_fused":
        fn = build_decode_fused(cfg, mesh, k)
        remaining = jax.device_put(
            jnp.full((_SERVE_SHAPE["max_batch"],), k, jnp.int32),
            NamedSharding(mesh, P()),
        )
        return fn, ((cache, x), params, active, remaining)
    if what in ("decode_fused_token", "verify", "draft_scan"):
        table = token_embedding_table(cfg.hidden_size, dtype=jnp.float32)
        remaining = jax.device_put(
            jnp.full((_SERVE_SHAPE["max_batch"],), k, jnp.int32),
            NamedSharding(mesh, P()),
        )
        if what == "decode_fused_token":
            fn = build_decode_fused_token(cfg, mesh, k)
            return fn, ((cache, x), params, table, active, remaining)
        if what == "verify":
            fn = build_verify_step(cfg, mesh, k)
            ids = jax.device_put(
                jnp.zeros((_SERVE_SHAPE["max_batch"], k), jnp.int32),
                NamedSharding(mesh, P(decode_batch_spec(mesh)[0], None)),
            )
            return fn, ((cache, x), params, table, ids, active, remaining)
        # draft_scan: the SHALLOW draft model (1 layer, everything else
        # identical) over its OWN cache plane, host-committed lengths
        # passed explicitly — the exact program the draft-model drafter
        # dispatches
        draft_cfg = ModelConfig(**{**_TINY_MODEL, "num_layers": 1})
        draft_params = init_params_sharded(draft_cfg, jax.random.key(1),
                                           mesh)
        draft_cache = create_kv_cache(
            draft_cfg, _SERVE_SHAPE["max_batch"], _SERVE_SHAPE["num_blocks"],
            _SERVE_SHAPE["block_size"], mesh=mesh,
        )
        lengths = jax.device_put(
            jnp.zeros((_SERVE_SHAPE["max_batch"],), jnp.int32),
            NamedSharding(mesh, P()),
        )
        fn = build_draft_scan(draft_cfg, mesh, k)
        return fn, (draft_cache, draft_params, table, x, lengths, active)
    if what == "prefill_chunk":
        # second chunk (nonzero static offset): nonempty prefix carry +
        # offset block write — the interesting lowering
        from dlbb_tpu.serve.gpt import prefix_spec

        chunk = _SERVE_SHAPE["block_size"]
        fn = build_prefill_chunk(cfg, mesh, chunk, chunk)
        pre_sh = NamedSharding(mesh, prefix_spec(mesh))
        pk = jax.device_put(
            jnp.zeros((cfg.num_layers, chunk, cfg.kv_heads,
                       cfg.head_dim), jnp.float32), pre_sh)
        xc = jnp.zeros((1, chunk, cfg.hidden_size), jnp.float32)
        return fn, (cache, (pk, pk), params, xc, np.int32(0),
                    np.int32(2 * chunk))
    if what == "prefix_attach":
        # one matched block copied donor -> destination slot plus the
        # dequantised fp prefix carry — the shared-prefix admission's
        # entire device program (dp=1 by contract)
        from dlbb_tpu.serve.gpt import build_prefix_attach

        fn = build_prefix_attach(cfg, mesh, _SERVE_SHAPE["block_size"],
                                 _SERVE_SHAPE["block_size"])
        return fn, (cache, np.int32(0), np.int32(1))
    if what == "decode_quant":
        from dlbb_tpu.serve.kvcache import create_quant_kv_cache

        qcache = create_quant_kv_cache(
            cfg, _SERVE_SHAPE["max_batch"], _SERVE_SHAPE["num_blocks"],
            _SERVE_SHAPE["block_size"], mesh=mesh,
        )
        fn = build_decode_step(cfg, mesh, quantized=True)
        return fn, ((qcache, x), params, active)
    fn = build_prefill(cfg, mesh)
    xp = jnp.zeros((1, _SERVE_SHAPE["bucket"], cfg.hidden_size),
                   jnp.float32)
    return fn, (cache, params, xp, np.int32(0),
                np.int32(_SERVE_SHAPE["bucket"]))


def _decode_step_target(dp: int = 2, tp: int = 4) -> AuditTarget:
    """The serving decode step (``serve/engine.py::decode_step``).  The
    contract is the serving-path comm story: ONLY tiny per-token tp
    collectives (row-parallel psums of [max_batch, 1, H]) may exist — dp
    contributes nothing (no gradients) — and the activation-sized byte
    ceiling is the proof that no step re-gathers the KV-cache: even one
    slot's single-layer cache shard is several times the ceiling, so a
    cache regather fails on both the kind axis and the byte axis.  The
    cache carry must stay donated (an undonated decode doubles cache
    HBM — fatal at real sizes)."""
    def build():
        return _serve_build(dp, tp, "decode")

    cfg_dict = _TINY_MODEL
    # largest legitimate instruction: an all-reduce of one decode step's
    # activations — [max_batch, 1, qkv_width] f32 bounds every
    # projection collective.  One layer's k (or v) cache plane
    # [max_batch, num_blocks, block_size, kvh, d] is ~8.5x this ceiling
    # (a single slot's plane alone is ~2x), so any cache-sized transfer
    # trips.
    qkv_width = 3 * cfg_dict["hidden_size"]
    act_bytes = _SERVE_SHAPE["max_batch"] * qkv_width * 4
    cache_dev = _serve_cache_bytes_per_device(dp, tp)
    return AuditTarget(
        name="serve/engine.py::decode_step[dp,tp]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=dp, tp=tp, decode=True),
            required_any={"all-reduce"},
            min_required=1,  # row-parallel psum per scanned layer
            max_bytes_per_instr=int(act_bytes * 1.25),
            expect_donation=True,
            # resident: tp-sharded weights + the donated cache shard +
            # per-token activations — a cache REGATHER (the full
            # unsharded cache materialising) adds (dp*tp - 1) x
            # cache_dev and blows this before the byte/kind axes even
            # report
            max_peak_bytes=int(
                1.3 * (_tiny_params_bytes() // tp + cache_dev)
            ) + 16 * act_bytes,
            # the validate_serving cross-check: the donated decode
            # carry IS the cache (plus the [max_batch, 1, H] hidden
            # state and the lengths vector, together <5% here) — the
            # analytic kv_cache_bytes_per_device must match it
            donated_bytes_expected=cache_dev,
        ),
        min_devices=dp * tp,
    )


def _prefill_target(dp: int = 2, tp: int = 4) -> AuditTarget:
    """The serving prefill (cache-append) step: full causal attention
    over one request's bucketed prompt, K/V written into the request's
    slot by one in-place block write.  Same kind set as decode; the ceiling is one
    bucket of activations — the cache write itself must lower to zero
    collectives (a write that round-trips the wire would trip it)."""
    def build():
        return _serve_build(dp, tp, "prefill")

    act_bytes = _SERVE_SHAPE["bucket"] * 3 * _TINY_MODEL["hidden_size"] * 4
    cache_dev = _serve_cache_bytes_per_device(dp, tp)
    return AuditTarget(
        name="serve/engine.py::prefill[dp,tp]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=dp, tp=tp, decode=True),
            required_any={"all-reduce"},
            min_required=1,
            max_bytes_per_instr=int(act_bytes * 1.25),
            expect_donation=True,
            # weights + donated cache + one bucket of activations/scores
            max_peak_bytes=int(
                1.3 * (_tiny_params_bytes() // tp + cache_dev)
            ) + 8 * act_bytes,
            donated_bytes_expected=cache_dev,
        ),
        min_devices=dp * tp,
    )


def _decode_fused_target(dp: int = 2, tp: int = 4,
                         k: int = 4) -> AuditTarget:
    """The fused multi-step decode scan (``serve/engine.py::
    build_decode_fused``): the scan body may contain only the tiny
    per-token tp collectives, execution-weighted through the scan's
    ``known_trip_count`` — the body's row-parallel psum must fire >= k
    times (the while-body pricing from the schedule auditor), each
    within ONE step's activation byte ceiling.  A cache regather inside
    the body is k-times amplified on the wire axis, so the committed
    schedule baseline turns it into an ``analyze diff`` failure as well
    as an audit error."""
    from dlbb_tpu.analysis.expectations import decode_scan_expectation

    def build():
        return _serve_build(dp, tp, "decode_fused", k=k)

    qkv_width = 3 * _TINY_MODEL["hidden_size"]
    act_bytes = _SERVE_SHAPE["max_batch"] * qkv_width * 4
    cache_dev = _serve_cache_bytes_per_device(dp, tp)
    exp = decode_scan_expectation(dp, tp, k, act_bytes)
    # the fused scan carries the same donated (cache, x) as the per-step
    # engine — K trips reuse the carry in place, so the peak must NOT
    # scale with k
    exp.max_peak_bytes = int(
        1.3 * (_tiny_params_bytes() // tp + cache_dev)) + 16 * act_bytes
    exp.donated_bytes_expected = cache_dev
    return AuditTarget(
        name=f"serve/engine.py::decode_fused[k{k},dp,tp]",
        build=build,
        expectation=exp,
        min_devices=dp * tp,
    )


def _decode_fused_token_target(dp: int = 2, tp: int = 4,
                               k: int = 4) -> AuditTarget:
    """The token-feedback fused scan (``serve/engine.py::
    build_decode_fused_token``) — the n-gram-drafted engine's
    between-verify workhorse and the speculative modes' plain-decode
    fallback.  Identical contract to the float fused scan: the greedy
    quantisation (argmax + a replicated [H, H] table take) adds ZERO
    collectives, so the same ``decode_scan_expectation`` applies
    unchanged — any new wire from the token feedback is a regression."""
    from dlbb_tpu.analysis.expectations import decode_scan_expectation

    def build():
        return _serve_build(dp, tp, "decode_fused_token", k=k)

    qkv_width = 3 * _TINY_MODEL["hidden_size"]
    act_bytes = _SERVE_SHAPE["max_batch"] * qkv_width * 4
    cache_dev = _serve_cache_bytes_per_device(dp, tp)
    exp = decode_scan_expectation(dp, tp, k, act_bytes)
    exp.max_peak_bytes = int(
        1.3 * (_tiny_params_bytes() // tp + cache_dev)) + 16 * act_bytes
    exp.donated_bytes_expected = cache_dev
    return AuditTarget(
        name=f"serve/engine.py::decode_fused_token[k{k},dp,tp]",
        build=build,
        expectation=exp,
        min_devices=dp * tp,
    )


def _verify_step_target(dp: int = 2, tp: int = 4,
                        gamma: int = 4) -> AuditTarget:
    """The speculative verify step (``serve/engine.py::
    build_verify_step``): γ drafted tokens + the carry token through ONE
    batched [max_batch, γ+1, H] target forward.  The expectation
    (``verify_step_expectation``) pins the "one fused forward, zero
    per-draft-token collectives" contract: per-token decode kinds only,
    one psum per scanned layer, every instruction within (γ+1) x one
    step's activation bytes — the γ+1 cache appends must lower
    collective-free exactly like the decode step's single
    append, and the acceptance math (argmax + cumprod + gather) is
    elementwise/local."""
    from dlbb_tpu.analysis.expectations import verify_step_expectation

    def build():
        return _serve_build(dp, tp, "verify", k=gamma)

    qkv_width = 3 * _TINY_MODEL["hidden_size"]
    act_bytes = _SERVE_SHAPE["max_batch"] * qkv_width * 4
    cache_dev = _serve_cache_bytes_per_device(dp, tp)
    exp = verify_step_expectation(dp, tp, gamma, act_bytes)
    # weights + donated cache + (γ+1)-wide activations/scores (the
    # verify's [B, γ+1, S] mask and [B, n, γ+1, S] score planes are a
    # few KB at the audit geometry)
    exp.max_peak_bytes = int(
        1.3 * (_tiny_params_bytes() // tp + cache_dev)
    ) + 16 * (gamma + 1) * act_bytes
    exp.donated_bytes_expected = cache_dev
    return AuditTarget(
        name=f"serve/engine.py::verify_step[gamma{gamma},dp,tp]",
        build=build,
        expectation=exp,
        min_devices=dp * tp,
    )


def _draft_scan_target(dp: int = 2, tp: int = 4,
                       gamma: int = 4) -> AuditTarget:
    """The draft-model proposal scan (``serve/engine.py::
    build_draft_scan``): γ greedy steps of the 1-layer draft transformer
    over its OWN donated cache plane, sharded by the SAME plan as the
    target (``draft_model_config``).  The fused-scan expectation applies
    at trip count γ; the donated-bytes cross-check prices the SECOND
    cache plane — the same ``kv_cache_bytes_per_device`` formula
    ``validate_serving``'s draft-aware HBM gate prices at admission, so
    the build-time rejection can never drift from the draft plane XLA
    actually allocates."""
    from dlbb_tpu.analysis.expectations import decode_scan_expectation

    def build():
        return _serve_build(dp, tp, "draft_scan", k=gamma)

    qkv_width = 3 * _TINY_MODEL["hidden_size"]
    act_bytes = _SERVE_SHAPE["max_batch"] * qkv_width * 4
    draft_cache_dev = _serve_cache_bytes_per_device(dp, tp, num_layers=1)
    exp = decode_scan_expectation(dp, tp, gamma, act_bytes)
    # 1-layer draft weights are a fraction of the target's; pricing the
    # full tiny-model params keeps comfortable headroom while the
    # donated check stays exact on the draft plane
    exp.max_peak_bytes = int(
        1.3 * (_tiny_params_bytes() // tp + draft_cache_dev)
    ) + 16 * act_bytes
    exp.donated_bytes_expected = draft_cache_dev
    return AuditTarget(
        name=f"serve/engine.py::draft_scan[gamma{gamma},dp,tp]",
        build=build,
        expectation=exp,
        min_devices=dp * tp,
    )


def _prefill_chunk_target(dp: int = 2, tp: int = 4) -> AuditTarget:
    """One chunk of a chunked prefill at a nonzero static offset: the
    prefix K/V rides an explicit (slot-dim-free) carry, so the lowered
    program must look exactly like monolithic prefill — tp collectives
    only, one chunk of activations as the ceiling, zero collectives for
    the cache write, cache carry donated."""

    def build():
        return _serve_build(dp, tp, "prefill_chunk")

    chunk = _SERVE_SHAPE["block_size"]
    act_bytes = chunk * 3 * _TINY_MODEL["hidden_size"] * 4
    cache_dev = _serve_cache_bytes_per_device(dp, tp)
    return AuditTarget(
        name="serve/engine.py::prefill_chunk[dp,tp]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=dp, tp=tp, decode=True),
            required_any={"all-reduce"},
            min_required=1,
            max_bytes_per_instr=int(act_bytes * 1.25),
            expect_donation=True,
            # weights + donated cache + explicit prefix K/V carry + one
            # chunk of activations
            max_peak_bytes=int(
                1.3 * (_tiny_params_bytes() // tp + cache_dev)
            ) + 12 * act_bytes,
            donated_bytes_expected=cache_dev,
        ),
        min_devices=dp * tp,
    )


def _prefix_attach_target(tp: int = 4) -> AuditTarget:
    """The shared-prefix attach jit (``serve/engine.py::prefix_attach``,
    dp=1 by contract): an in-place block copy of the donor slot's matched
    blocks into the destination slot plus the dequantised fp prefix
    carry.  Pure LOCAL data movement — the slot dim is unsharded and
    the kv-head shard is untouched, so the lowering must contain ZERO
    collectives: a shared-prefix prefill that costs even one extra
    collective has no TTFT story.  The donated carry is the cache (the
    serving-cache-drift pin extends to the attach program)."""
    def build():
        return _serve_build(1, tp, "prefix_attach")

    exp = TargetExpectation(allowed=set(), required_any=None)
    cache_dev = _serve_cache_bytes_per_device(1, tp)
    # the full donated cache + the one-block prefix carry + the masked
    # copy's transient
    exp.max_peak_bytes = int(2.2 * cache_dev)
    exp.donated_bytes_expected = cache_dev
    return AuditTarget(
        name="serve/engine.py::prefix_attach[tp]",
        build=build,
        expectation=exp,
        min_devices=tp,
    )


def _decode_quant_target(tp: int = 4) -> AuditTarget:
    """The int8-KV decode step (``serve/engine.py::decode_step`` with
    ``serving.kv_quantization=int8``, dp=1 — the prefix/quant serving
    envelope): same tiny-collectives contract as the fp decode target,
    but the donated carry and the peak ceiling are priced from the
    QUANTIZED layout — int8 data planes + fp32 per-(block, kv-head)
    scales, ~4x smaller than fp32 planes.  This is the static proof of
    the capacity claim: if the compiled carry were still fp-sized, the
    donation pin (serving-cache-drift) trips on the analytic int8
    number."""
    def build():
        return _serve_build(1, tp, "decode_quant")

    qkv_width = 3 * _TINY_MODEL["hidden_size"]
    act_bytes = _SERVE_SHAPE["max_batch"] * qkv_width * 4
    cache_q = _serve_cache_bytes_per_device(1, tp,
                                            kv_quantization="int8")
    # dequantise-to-fp32 transients: each scanned layer materialises one
    # layer's fp32 view of its k/v planes (cache_q * ~4 / num_layers per
    # plane pair) — bounded inside the peak term below
    fp_layer = 4 * cache_q // _TINY_MODEL["num_layers"]
    # the donated carry also holds the [B, 1, H] f32 hidden state and
    # the int32 lengths vector — negligible against fp planes but >10%
    # of the 4x-smaller int8 cache, so the pin must price them
    carry_extra = _SERVE_SHAPE["max_batch"] * (
        _TINY_MODEL["hidden_size"] * 4 + 4)
    return AuditTarget(
        name="serve/engine.py::decode_step[int8,tp]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=1, tp=tp, decode=True),
            required_any={"all-reduce"},
            min_required=1,
            max_bytes_per_instr=int(act_bytes * 1.25),
            expect_donation=True,
            max_peak_bytes=int(
                1.3 * (_tiny_params_bytes() // tp + cache_q + fp_layer)
            ) + 16 * act_bytes,
            donated_bytes_expected=cache_q + carry_extra,
        ),
        min_devices=tp,
    )


def _train_step_target(zero_stage: int, dp: int = 8) -> AuditTarget:
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from dlbb_tpu.comm.mesh import build_parallelism_mesh
        from dlbb_tpu.models.configs import ModelConfig
        from dlbb_tpu.models.sharding import batch_spec
        from dlbb_tpu.models.transformer import init_params_sharded
        from dlbb_tpu.train.loop import make_train_step

        import optax

        cfg = ModelConfig(**_TINY_MODEL)
        mesh = build_parallelism_mesh(data_parallel=dp)
        params = init_params_sharded(cfg, jax.random.key(0), mesh)
        jit_step, state = make_train_step(
            cfg, mesh, optax.adam(1e-3), params, zero_stage=zero_stage,
        )
        sharding = NamedSharding(mesh, batch_spec(mesh))
        batch = jax.device_put(
            jnp.ones((dp, 8, cfg.hidden_size), jnp.float32), sharding)
        tgt = jax.device_put(
            jnp.ones((dp, 8, cfg.hidden_size), jnp.float32), sharding)
        return jit_step, (state, batch, tgt)

    # resident train state: full f32 params everywhere; Adam moments
    # replicated at ZeRO-0, dp-sharded at ZeRO-1 — plus gradients and
    # backward transients.  A dropped donation is the donation rule's to
    # catch, not this ceiling's: XLA:CPU of jaxlib 0.9.0 computes the
    # donated step's new state into temporaries (peak 5.94 n4), and the
    # undonated step peaks at 6.42 n4.
    n4 = _tiny_params_bytes()
    peak_ceiling = int(6.5 * n4) if zero_stage == 0 else int(2.85 * n4)
    return AuditTarget(
        name=f"train/loop.py::train_step[zero{zero_stage},dp]",
        build=build,
        expectation=TargetExpectation(
            allowed=plan_expected_kinds(dp=8, zero_stage=zero_stage),
            required_any={"all-reduce", "reduce-scatter"},
            min_required=1,  # the gradient reduction must exist
            expect_donation=True,
            max_peak_bytes=peak_ceiling,
        ),
        min_devices=dp,
    )


def registry_op_targets() -> list[AuditTarget]:
    """One audit target per ``comm/ops.py`` registry collective — the
    collective-matmul micro-ops need LLM-shaped payloads and get one
    dedicated target per schedule (fused vs the decomposed rings); the
    compressed micro-ops get one per wire dtype, audited against the
    compression byte ceiling instead of the plain kind table."""
    from dlbb_tpu.comm.ops import COMPRESSED_OPS, MATMUL_OPS, OPERATIONS

    targets = [
        _registry_op_target(name)
        for name in sorted(OPERATIONS)
        if name not in MATMUL_OPS and name not in COMPRESSED_OPS
    ]
    targets += [
        _collective_matmul_target(name, schedule)
        for name in MATMUL_OPS
        for schedule in ("fused", "ring", "bidir")
    ]
    targets += [
        _compressed_op_target(name, compression)
        for name in COMPRESSED_OPS
        for compression in ("int8", "fp8")
    ]
    return targets


def default_targets() -> list[AuditTarget]:
    """The repo's standing audit surface: every registry collective, the
    TP/sequence-parallel model forwards (the e2e benchmark's jit) with
    and without the overlapped collective-matmul schedule, the
    DDP + ZeRO-1 + overlapped-TP train steps, and the serving programs
    — per-step decode + monolithic prefill plus the decode fast path
    (fused K-step scan, chunked prefill), the
    speculative-decoding programs (token-feedback fused scan, γ-token
    verify step, draft-model proposal scan), and the prefix/quant cache
    programs (zero-collective shared-prefix attach, int8-KV decode with
    the quantized-layout donation pin) — all tiny-collectives-only with
    the cache-regather byte gate."""
    targets = registry_op_targets()
    targets.append(_barrier_target())
    targets.append(_tp_forward_target())
    targets.append(_tp_overlap_forward_target("ring"))
    targets.append(_tp_overlap_forward_target("bidir"))
    targets.append(_cp_forward_target("ring"))
    targets.append(_cp_forward_target("ulysses"))
    targets.append(_train_step_target(zero_stage=0))
    targets.append(_train_step_target(zero_stage=1))
    targets.append(_tp_overlap_train_target("ring"))
    targets.append(_compressed_train_target("int8"))
    targets.append(_decode_step_target())
    targets.append(_prefill_target())
    targets.append(_decode_fused_target())
    targets.append(_decode_fused_token_target())
    targets.append(_verify_step_target())
    targets.append(_draft_scan_target())
    targets.append(_prefill_chunk_target())
    targets.append(_prefix_attach_target())
    targets.append(_decode_quant_target())
    return targets


# device_kind (as jax reports it) -> cost-model tier; a device that is not
# here is an error, never a default priced at another chip's peaks
TIER_BY_DEVICE_KIND = {"cpu": "cpu-sim", "TPU v5 lite": "tpu-v5lite"}


def default_tier() -> str:
    """The cost-model tier matching the current device: the CPU-simulated
    mesh prices at ``cpu-sim`` (the committed-baseline tier), a v5e at
    ``tpu-v5lite``."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in TIER_BY_DEVICE_KIND:
        raise KeyError(
            f"no cost-model tier for device_kind {kind!r} (known: "
            f"{sorted(TIER_BY_DEVICE_KIND)}); pass --tier explicitly"
        )
    return TIER_BY_DEVICE_KIND[kind]


def run_hlo_audit(
    targets: Optional[Sequence[AuditTarget]] = None,
    verbose: bool = False,
    passes: Sequence[str] = ("hlo",),
    tier: Optional[str] = None,
    model: str = "cm1",
) -> AnalysisReport:
    """Audit ``targets`` (default: the standing registry) on the current
    backend.  ``passes`` selects the byte auditor (``"hlo"``), the α–β
    schedule auditor (``"schedule"``), or both — one lowering per target
    either way.  ``model`` selects the cost model the schedule pass
    prices with (cm1 analytic / cm2 fitted).  Targets needing more
    devices than available are recorded as skipped, not failed — the
    CLI's ``--simulate N`` controls the mesh."""
    import jax

    if "schedule" in passes or "memory" in passes:
        if tier is None:
            tier = default_tier()
        # resolve once, before any lowering: a mistyped --tier/--model
        # must be EXIT_CRASH (unusable arguments), not 30 repeated
        # audit-crash findings after minutes of wasted compiles — and a
        # cm2 fit-missing fallback must warn ONCE, not per target
        from dlbb_tpu.analysis.costmodel import resolve_tier

        tier = resolve_tier(tier, model=model)
    report = AnalysisReport()
    n_devices = len(jax.devices())
    for target in targets if targets is not None else default_targets():
        if target.min_devices > n_devices:
            report.skipped_targets.append({
                "target": target.name,
                "reason": (f"needs {target.min_devices} devices, "
                           f"{n_devices} available"),
            })
            continue
        try:
            findings, _meta = audit_target(target, passes=passes, tier=tier)
        except Exception as e:  # noqa: BLE001 — one target's lowering
            # failure must not abort the audit of the rest (same per-config
            # containment convention as bench/runner.run_sweep); it is still
            # an error finding, not a silent skip
            report.findings.append(Finding(
                pass_name="hlo", rule="audit-crash",
                severity=SEVERITY_ERROR, target=target.name,
                message=f"audit raised {type(e).__name__}: {e}",
            ))
            if verbose:
                print(f"[hlo] {target.name}: CRASH ({type(e).__name__})")
            continue
        report.findings.extend(findings)
        report.targets_audited.append(target.name)
        if "schedule" in _meta:
            report.schedule[target.name] = _meta["schedule"]
        if "memory" in _meta:
            report.memory[target.name] = _meta["memory"]
        if "numerics" in _meta:
            report.numerics[target.name] = _meta["numerics"]
        if verbose:
            status = "FAIL" if findings else "ok"
            sched = _meta.get("schedule")
            n_coll = _meta.get(
                "num_collectives",
                sched["num_collectives"] if sched else 0,
            )
            extra = ""
            if sched is not None:
                eff = sched["overlap_efficiency"]
                extra = (
                    f", cp {sched['critical_path_us']:.1f}us"
                    + (f", overlap {eff:.2f}" if eff is not None else "")
                )
            mem = _meta.get("memory")
            if mem is not None:
                extra += (f", peak "
                          f"{mem['peak_live_bytes'] / 1024:.1f}KiB")
            num = _meta.get("numerics")
            if num is not None:
                extra += (f", err<="
                          f"{num['numerics_max_rel_error_bound']:.2g}")
            print(f"[hlo] {target.name}: {status} "
                  f"({n_coll} collective(s){extra})")
    return report
