"""Ring-decomposed collective matmul correctness on the simulated mesh.

The overlap claim rests on two invariants this file pins:

1. **Numerics**: the decomposed schedules (ring, bidir) must be
   value-equivalent to the GSPMD fused path — forward AND backward
   (the custom VJP replaces autodiff) — on every supported mesh shape.
2. **Schedule shape**: the compiled program must actually contain the
   collective-permute chain with no fused collective left (the HLO-audit
   contract, ``analysis/expectations.overlap_op_expectation``; the full
   audit gate runs in test_analysis via the default target registry).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import abstract_forward
from jax.sharding import NamedSharding, PartitionSpec as P

from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.models.configs import ModelConfig, validate_tp_overlap
from dlbb_tpu.models.sharding import batch_spec
from dlbb_tpu.models.transformer import forward, init_params, shard_params
from dlbb_tpu.parallel.collective_matmul import (
    activation_spec,
    allgather_matmul,
    matmul_reducescatter,
)

TINY = ModelConfig(hidden_size=64, num_layers=2, num_heads=4,
                   ffn_intermediate=128, attention="full", dtype="float32")


def _operands(mesh, b=4, s=16, h=16, f=16, dtype=jnp.float32):
    x = jax.random.normal(jax.random.key(0), (b, s, h), dtype)
    w_col = jax.random.normal(jax.random.key(1), (h, f), dtype)
    w_row = jax.random.normal(jax.random.key(2), (f, h), dtype)
    xs = jax.device_put(x, NamedSharding(mesh, activation_spec(mesh)))
    w_cols = jax.device_put(w_col, NamedSharding(mesh, P(None, "tp")))
    w_rows = jax.device_put(w_row, NamedSharding(mesh, P("tp", None)))
    return (x, w_col, w_row), (xs, w_cols, w_rows)


MESHES = {
    "dp2xtp4": dict(data_parallel=2, tensor_parallel=4),
    "tp8": dict(data_parallel=1, tensor_parallel=8),
    "dp2xsp2xtp2": dict(data_parallel=2, sequence_parallel=2,
                        tensor_parallel=2),
}


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
@pytest.mark.parametrize("schedule", ["ring", "bidir"])
def test_primitives_match_unsharded(devices, mesh_name, schedule):
    """allgather_matmul / matmul_reducescatter == plain matmul chain on
    (dp,tp), flat tp, and (dp,sp,tp) meshes, forward and grad (the custom
    VJP vs autodiff of the unsharded reference)."""
    mesh = build_parallelism_mesh(**MESHES[mesh_name])
    (x, w1, w2), (xs, w1s, w2s) = _operands(mesh)

    y = jax.jit(
        lambda a, b: allgather_matmul(a, b, mesh, schedule=schedule)
    )(xs, w1s)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w1),
                               rtol=1e-5, atol=1e-5)
    z = jax.jit(
        lambda a, b, c: matmul_reducescatter(
            allgather_matmul(a, b, mesh, schedule=schedule), c, mesh,
            schedule=schedule)
    )(xs, w1s, w2s)
    np.testing.assert_allclose(np.asarray(z), np.asarray((x @ w1) @ w2),
                               rtol=1e-4, atol=1e-4)

    def loss_overlap(a, b, c):
        return jnp.sum(matmul_reducescatter(
            allgather_matmul(a, b, mesh, schedule=schedule), c, mesh,
            schedule=schedule) ** 2)

    def loss_ref(a, b, c):
        return jnp.sum(((a @ b) @ c) ** 2)

    got = jax.jit(jax.grad(loss_overlap, argnums=(0, 1, 2)))(xs, w1s, w2s)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w1, w2)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_uneven_shard_counts_rejected(devices):
    """Sequence or weight dims that do not divide the ring must fail at
    trace time with a clear message, never silently mis-shard."""
    mesh = build_parallelism_mesh(data_parallel=2, tensor_parallel=4)
    with pytest.raises(ValueError, match="not divisible by the"):
        allgather_matmul(jnp.ones((2, 10, 8)), jnp.ones((8, 12)), mesh)
    with pytest.raises(ValueError, match="weight dim .* not divisible"):
        allgather_matmul(jnp.ones((2, 16, 8)), jnp.ones((8, 10)), mesh)
    with pytest.raises(ValueError, match="weight dim .* not divisible"):
        matmul_reducescatter(jnp.ones((2, 16, 8)), jnp.ones((10, 8)), mesh)
    with pytest.raises(ValueError, match="unknown tp_overlap schedule"):
        allgather_matmul(jnp.ones((2, 16, 8)), jnp.ones((8, 16)), mesh,
                         schedule="zigzag")
    from dlbb_tpu.comm.mesh import MeshSpec, build_mesh

    no_tp = build_mesh(MeshSpec.ring(8))  # "ranks" axis only
    with pytest.raises(ValueError, match="no 'tp' axis"):
        allgather_matmul(jnp.ones((2, 16, 8)), jnp.ones((8, 16)), no_tp)


@pytest.mark.overlap_smoke
@pytest.mark.parametrize("schedule", ["ring", "bidir"])
def test_forward_overlap_matches_gspmd(mesh2x4, schedule):
    """Model-level gate (also run standalone by
    scripts/run_static_analysis.sh): tp_overlap=ring|bidir forward ==
    the off (GSPMD fused) path on the dp2 x tp4 mesh."""
    params = init_params(TINY, jax.random.key(1))
    x = jax.random.normal(jax.random.key(0), (4, 16, 64), jnp.float32)
    sharded = shard_params(params, mesh2x4)
    xs = jax.device_put(x, NamedSharding(mesh2x4, batch_spec(mesh2x4)))
    out_sh = NamedSharding(mesh2x4, batch_spec(mesh2x4))
    y_off = jax.jit(lambda p, a: forward(p, a, TINY, mesh=mesh2x4),
                    out_shardings=out_sh)(sharded, xs)
    cfg = TINY.with_(tp_overlap=schedule)
    y = jax.jit(lambda p, a: forward(p, a, cfg, mesh=mesh2x4),
                out_shardings=out_sh)(sharded, xs)
    np.testing.assert_allclose(np.asarray(y_off), np.asarray(y),
                               rtol=2e-4, atol=2e-4)


def test_forward_overlap_bf16_tolerance(mesh2x4):
    """The acceptance dtype: bf16 overlapped forward matches the fused
    path within bf16 tolerances (ring adds sequentially where the fused
    all-reduce adds in XLA's order — both bf16-rounded)."""
    cfg16 = TINY.with_(dtype="bfloat16")
    params = init_params(cfg16, jax.random.key(1))
    x = jax.random.normal(jax.random.key(0), (4, 16, 64), jnp.bfloat16)
    sharded = shard_params(params, mesh2x4)
    xs = jax.device_put(x, NamedSharding(mesh2x4, batch_spec(mesh2x4)))
    out_sh = NamedSharding(mesh2x4, batch_spec(mesh2x4))
    y_off = jax.jit(lambda p, a: forward(p, a, cfg16, mesh=mesh2x4),
                    out_shardings=out_sh)(sharded, xs)
    for schedule in ("ring", "bidir"):
        cfg = cfg16.with_(tp_overlap=schedule)
        y = jax.jit(lambda p, a: forward(p, a, cfg, mesh=mesh2x4),
                    out_shardings=out_sh)(sharded, xs)
        np.testing.assert_allclose(
            np.asarray(y_off, np.float32), np.asarray(y, np.float32),
            rtol=2e-2, atol=2e-2,
        )


def test_forward_overlap_with_sp_mesh(devices):
    """tp_overlap composes with a sequence-parallel axis: on the
    (dp, sp, tp) mesh the residual stream is sequence-sharded over
    (sp, tp) and ring attention sees exactly the layout the off path
    gives it."""
    cfg_off = TINY.with_(attention="ring")
    mesh = build_parallelism_mesh(data_parallel=2, sequence_parallel=2,
                                  tensor_parallel=2)
    params = init_params(cfg_off, jax.random.key(1))
    x = jax.random.normal(jax.random.key(0), (4, 16, 64), jnp.float32)
    sharded = shard_params(params, mesh)
    xs = jax.device_put(x, NamedSharding(mesh, batch_spec(mesh)))
    out_sh = NamedSharding(mesh, batch_spec(mesh))
    y_off = jax.jit(lambda p, a: forward(p, a, cfg_off, mesh=mesh),
                    out_shardings=out_sh)(sharded, xs)
    for schedule in ("ring", "bidir"):
        cfg = cfg_off.with_(tp_overlap=schedule)
        y = jax.jit(lambda p, a: forward(p, a, cfg, mesh=mesh),
                    out_shardings=out_sh)(sharded, xs)
        np.testing.assert_allclose(np.asarray(y_off), np.asarray(y),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("schedule", ["ring", "bidir"])
def test_train_grads_match_fused(mesh2x4, schedule):
    """Custom-VJP gradients through the full model == autodiff of the
    fused GSPMD path (the train-step backward is this composition)."""
    from dlbb_tpu.train.loop import mse_loss

    params = init_params(TINY, jax.random.key(1))
    sharded = shard_params(params, mesh2x4)
    sh = NamedSharding(mesh2x4, batch_spec(mesh2x4))
    x = jax.device_put(
        jax.random.normal(jax.random.key(0), (4, 16, 64), jnp.float32), sh)
    t = jax.device_put(
        jax.random.normal(jax.random.key(2), (4, 16, 64), jnp.float32), sh)
    cfg = TINY.with_(tp_overlap=schedule)
    g_off = jax.jit(
        lambda p, a, b: jax.grad(mse_loss)(p, a, b, TINY, mesh=mesh2x4)
    )(sharded, x, t)
    g = jax.jit(
        lambda p, a, b: jax.grad(mse_loss)(p, a, b, cfg, mesh=mesh2x4)
    )(sharded, x, t)
    for a, b in zip(jax.tree.leaves(g_off), jax.tree.leaves(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_overlap_hlo_has_permute_chain_no_allreduce(mesh2x4):
    """The decomposition in the compiled program: the scanned layer body
    must contain the ppermute chain (4 ring matmuls x (tp-1) hops) and
    ZERO all-reduce; the only all-gather is the single final reshard to
    the caller's batch layout.  (The standing registry-wide gate is the
    comm-lint HLO audit — this pins the model-level shape directly.)"""
    import re

    cfg = TINY.with_(tp_overlap="ring", attention="simplified")
    params = init_params(cfg, jax.random.key(1))
    sharded = shard_params(params, mesh2x4)
    xs = jax.device_put(
        jnp.ones((4, 16, 64), jnp.float32),
        NamedSharding(mesh2x4, batch_spec(mesh2x4)))
    out_sh = NamedSharding(mesh2x4, batch_spec(mesh2x4))
    hlo = jax.jit(
        lambda p, a: forward(p, a, cfg, mesh=mesh2x4),
        out_shardings=out_sh,
    ).lower(sharded, xs).compile().as_text()
    body = hlo.split("ENTRY")[0]
    tp = mesh2x4.shape["tp"]
    assert len(re.findall(r"collective-permute\(", body)) >= 4 * (tp - 1), \
        "overlapped forward lost its ppermute chain"
    assert not re.findall(r"\ball-reduce\(", body), \
        "an all-reduce survived in the overlapped layer body — the " \
        "decomposition collapsed back to the fused lowering"
    assert len(re.findall(r"\ball-gather\(", hlo)) <= 1, \
        "more than the single final activation reshard all-gather"


def test_micro_ops_decomposed_match_fused(mesh8):
    """The registry micro-ops: overlap_ring / overlap_bidir variants
    compute exactly what the fused default computes (same deterministic
    weight, same payload)."""
    from dlbb_tpu.comm.ops import (
        build_ag_matmul,
        build_matmul_rs,
        get_op,
        make_payload,
    )

    for opname, builder in (("ag_matmul", build_ag_matmul),
                            ("matmul_rs", build_matmul_rs)):
        op = get_op(opname)
        x = make_payload(op, mesh8, ("ranks",), 2 * 16 * 64,
                         dtype=jnp.float32, shape=(2, 16, 64))
        ref = np.asarray(builder(mesh8, ("ranks",), schedule="fused")(x))
        for schedule in ("ring", "bidir"):
            got = np.asarray(
                builder(mesh8, ("ranks",), schedule=schedule)(x))
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{opname}/{schedule}")


def test_micro_ops_flat_payload_rejected(mesh8):
    """The matmul micro-ops are 3D-only: a flat 1D payload must fail with
    a pointer at bench3d, not produce nonsense."""
    from dlbb_tpu.comm.ops import build_ag_matmul, build_matmul_rs

    with pytest.raises(ValueError, match="3D sweep"):
        build_ag_matmul(mesh8, ("ranks",), schedule="ring")(
            jnp.ones((8, 256), jnp.float32))
    with pytest.raises(ValueError, match="3D sweep"):
        build_matmul_rs(mesh8, ("ranks",), schedule="fused")(
            jnp.ones((8, 256), jnp.float32))
    # and a typo'd schedule must be rejected at build time, never silently
    # measured as the ring schedule under a wrong variant label
    with pytest.raises(ValueError, match="unknown collective-matmul"):
        build_ag_matmul(mesh8, ("ranks",), schedule="bi-dir")
    with pytest.raises(ValueError, match="unknown collective-matmul"):
        build_matmul_rs(mesh8, ("ranks",), schedule="zigzag")


def test_micro_ops_donation_safe_under_chained_timing(mesh8):
    """Chained timing donates its carry; the chain glue must map each
    op's output back to a valid next input so the donated buffers never
    resurface (the sweep engine's chained path runs these ops inside one
    jitted fori_loop)."""
    from dlbb_tpu.comm.ops import (
        build_ag_matmul,
        build_matmul_rs,
        get_op,
        make_payload,
    )
    from dlbb_tpu.utils.timing import time_fn_chained

    for opname, builder, schedule in (
            ("ag_matmul", build_ag_matmul, "ring"),
            ("matmul_rs", build_matmul_rs, "bidir")):
        op = get_op(opname)
        fn = builder(mesh8, ("ranks",), schedule=schedule)
        x = make_payload(op, mesh8, ("ranks",), 2 * 16 * 64,
                         dtype=jnp.float32, shape=(2, 16, 64))
        samples, meta, carry = time_fn_chained(
            fn, x, chain=op.make_chain(8), warmup=1, iterations=10)
        assert len(samples) >= 1
        assert meta["timing_mode"] == "chained"
        # the returned carry is alive and shaped like the next input
        assert carry.shape == (8, 2, 16, 64)
        assert np.isfinite(np.asarray(samples)).all()


def test_validate_tp_overlap_rejections():
    """Plan-level validation: the knob needs tp > 1, no pipeline, a dense
    FFN, and a divisible sequence."""
    cfg = TINY.with_(tp_overlap="ring")
    with pytest.raises(ValueError, match="world_size"):
        validate_tp_overlap(cfg, tp=1)
    with pytest.raises(ValueError, match="pipeline"):
        validate_tp_overlap(cfg, tp=4, pp=2)
    moe = TINY.with_(num_experts=4, tp_overlap="ring")
    with pytest.raises(ValueError, match="dense FFN"):
        validate_tp_overlap(moe, tp=4)
    with pytest.raises(ValueError, match="sequence_length"):
        validate_tp_overlap(cfg, tp=4, seq_len=10)
    with pytest.raises(ValueError, match="unknown tp_overlap"):
        TINY.with_(tp_overlap="diagonal")
    # the off default validates anywhere, tp=1 included
    validate_tp_overlap(TINY, tp=1)
    validate_tp_overlap(cfg, tp=4, seq_len=16)


def test_plan_carries_tp_overlap(devices):
    """ParallelismPlan records the schedule and enforces the validation
    from the YAML surface (sequence divisibility included)."""
    from dlbb_tpu.parallel.plan import ParallelismPlan

    cfg = TINY.with_(tp_overlap="ring")
    config = {"parallelism": {"world_size": 4, "data_parallel": 2},
              "input": {"batch_size": 4, "sequence_length": 16}}
    plan = ParallelismPlan.from_config(config, cfg)
    assert plan.tp_overlap == "ring"
    bad = {"parallelism": {"world_size": 4, "data_parallel": 2},
           "input": {"batch_size": 4, "sequence_length": 18}}
    with pytest.raises(ValueError, match="sequence_length=18"):
        ParallelismPlan.from_config(bad, cfg)


# -- the rule behind ``tp_overlap: auto`` -------------------------------------

# the benchmark cell ``fwd13b_tp4`` (benchmarks/configs/paper13b-tp4.json x
# fwd_b8_s512): batch 8, sequence 512, the 13B's widths, bf16
CELL = ModelConfig(hidden_size=5120, num_layers=2, num_heads=40,
                   ffn_intermediate=20480, attention="full",
                   dtype="bfloat16")
CELL_SHAPE = (8, 512, 5120)

# name -> (mesh degrees, config, activation shape, the route expected);
# None stands for ``collective_matmul.AUTO_SCHEDULE``
ROUTE_CASES = {
    "cell": (dict(tensor_parallel=4), CELL, CELL_SHAPE, None),
    "cell_under_dp": (dict(data_parallel=2, tensor_parallel=4), CELL,
                      (16, 512, 5120), None),
    "tp1": (dict(tensor_parallel=1), CELL, CELL_SHAPE, "off"),
    "experts": (dict(tensor_parallel=4), CELL.with_(num_experts=4),
                CELL_SHAPE, "off"),
    "pp2": (dict(pipeline_parallel=2, tensor_parallel=4), CELL,
            CELL_SHAPE, "off"),
    "seq510": (dict(tensor_parallel=4), CELL, (8, 510, 5120), "off"),
    "audit_tiny": (dict(data_parallel=2, tensor_parallel=4), TINY,
                   (4, 8, 64), "off"),
    "short_hops": (dict(tensor_parallel=4), CELL, (1, 8, 5120), "off"),
    "narrow": (dict(tensor_parallel=4),
               CELL.with_(hidden_size=512, num_heads=4,
                          ffn_intermediate=2048), (64, 4096, 512), "off"),
    "forced_off": (dict(tensor_parallel=4), CELL.with_(tp_overlap="off"),
                   CELL_SHAPE, "off"),
    "forced_ring": (dict(tensor_parallel=4), CELL.with_(tp_overlap="ring"),
                    CELL_SHAPE, "ring"),
    "forced_bidir": (dict(tensor_parallel=4),
                     CELL.with_(tp_overlap="bidir"), CELL_SHAPE, "bidir"),
    "forced_ring_tiny": (dict(data_parallel=2, tensor_parallel=4),
                         TINY.with_(tp_overlap="ring"), (4, 8, 64), "ring"),
    "forced_ring_tp1": (dict(tensor_parallel=1),
                        CELL.with_(tp_overlap="ring"), CELL_SHAPE, "off"),
}


@pytest.mark.parametrize("case", sorted(ROUTE_CASES))
def test_route_is_decided_from_shapes(devices, case):
    """``tp_overlap_route``: under the default "auto" the cell's shapes
    take the schedule the chip runs kept and every program no ring can
    take, or whose hops are too small or too narrow to hide, is fused
    without an error; a forced value is taken at its word wherever there
    is a tp axis."""
    from dlbb_tpu.models.transformer import tp_overlap_route
    from dlbb_tpu.parallel.collective_matmul import AUTO_SCHEDULE

    degrees, cfg, shape, want = ROUTE_CASES[case]
    mesh = build_parallelism_mesh(**degrees)
    assert tp_overlap_route(cfg, mesh, shape) == (want or AUTO_SCHEDULE)
    assert tp_overlap_route(cfg, None, shape) == "off"


def test_cell_forward_compiles_to_rings_by_itself(devices):
    """The cell's forward at its widths (two layers: the layer loop's
    body does not depend on their number), lowered and compiled, never
    run, on the CPU mesh with tp 4 and NOTHING said about ``tp_overlap``:
    the layer body holds the permute chain of four rings, every permute
    under a ``ring_hop_*`` scope, and no all-reduce."""
    from dlbb_tpu.analysis.hlo_parse import parse_collectives
    from dlbb_tpu.parallel.collective_matmul import AUTO_SCHEDULE

    assert CELL.tp_overlap == "auto"
    mesh = build_parallelism_mesh(tensor_parallel=4)
    fn, args = abstract_forward(CELL, mesh, CELL_SHAPE, jnp.bfloat16)
    hlo = fn.lower(*args).compile().as_text()
    body = [c for c in parse_collectives(hlo) if c.execution_count > 1]
    permutes = [c for c in body if c.kind == "collective-permute"]
    hops = {"ring": 4 * 3, "bidir": 2 * 3 + 2 * 6}[AUTO_SCHEDULE]
    assert len(permutes) == hops, [c.op_name for c in permutes]
    assert all("ring_hop_" in (c.op_name or "") for c in permutes), \
        [c.op_name for c in permutes]
    assert not [c for c in body if c.kind == "all-reduce"], \
        "an all-reduce survived in the overlapped layer body"


@pytest.mark.parametrize("target", ["one_device", "tiny_dp_tp"])
def test_program_the_rule_leaves_alone_is_the_fused_one(devices, target):
    """Where there is no tp axis, or the shapes fail the rule, "auto"
    lowers to the text the forced fused route lowers to."""
    if target == "one_device":
        mesh = build_parallelism_mesh(tensor_parallel=1)
        cfg, shape = TINY, (4, 16, 64)
    else:  # the audits' dp x tp target (analysis/hlo_audit.py)
        mesh = build_parallelism_mesh(data_parallel=2, tensor_parallel=4)
        cfg, shape = TINY, (4, 8, 64)
    texts = []
    for route in ("auto", "off"):
        fn, args = abstract_forward(cfg.with_(tp_overlap=route), mesh,
                                     shape, jnp.float32)
        texts.append(fn.lower(*args).as_text())
    assert texts[0] == texts[1]
    assert "collective_permute" not in texts[0]


def test_forward_the_rule_overlaps_by_itself_equals_one_device_f32(devices):
    """The smallest shape at which "auto" takes the rings BY ITSELF in
    float32 (the source's 7B widths at tp 4, one layer, hops of exactly
    ``MIN_HOP_BYTES``: half the widths or half the rows and the rule
    says no) against the one-device forward, as
    ``test_model.py::test_tp_forward_equals_one_device_f32`` holds the
    fused route."""
    from dlbb_tpu.models.transformer import tp_overlap_route
    from dlbb_tpu.parallel.collective_matmul import AUTO_SCHEDULE

    cfg = ModelConfig(hidden_size=4096, num_layers=1, num_heads=32,
                      ffn_intermediate=16384, attention="full",
                      dtype="float32")
    shape = (2, 512, 4096)
    mesh = build_parallelism_mesh(tensor_parallel=4)
    assert tp_overlap_route(cfg, mesh, shape) == AUTO_SCHEDULE
    assert tp_overlap_route(cfg, mesh, (1, 512, 4096)) == "off"
    assert tp_overlap_route(
        cfg.with_(hidden_size=2048, num_heads=16, ffn_intermediate=8192),
        mesh, (4, 512, 2048)) == "off"

    params = init_params(cfg, jax.random.key(1))
    x = jax.random.normal(jax.random.key(0), shape, jnp.float32)
    y_one = forward(params, x, cfg)
    sh = NamedSharding(mesh, batch_spec(mesh))
    fn = jax.jit(lambda p, a: forward(p, a, cfg, mesh=mesh),
                 out_shardings=sh)
    args = (shard_params(params, mesh), jax.device_put(x, sh))
    assert "collective-permute" in fn.lower(*args).compile().as_text()
    np.testing.assert_allclose(np.asarray(fn(*args)), np.asarray(y_one),
                               rtol=1e-5, atol=1e-5)
