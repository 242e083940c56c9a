"""Compressed collectives (docs/compression.md): quantise/dequantise
round-trip bounds, psum_compressed == psum within wire tolerance across
(dp) and (dp, tp) meshes, the error-feedback residual's checkpoint
round-trip, the comm-lint compression byte ceiling (clean pass + seeded
dequant-before-collective violation), and the analytic wire model pinned
against the audited HLO totals."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlbb_tpu.analysis.expectations import (
    SCALE_CHUNK_ELEMS,
    TargetExpectation,
    compressed_op_expectation,
    op_wire_bytes,
    scale_bytes,
    wire_bytes,
)
from dlbb_tpu.analysis.hlo_audit import (
    AuditTarget,
    _compressed_op_target,
    audit_target,
)
from dlbb_tpu.comm.compression import (
    dequantize_chunked,
    psum_compressed,
    quantization_error,
    quantize_chunked,
    reduce_scatter_compressed,
)
from dlbb_tpu.comm.mesh import build_parallelism_mesh
from dlbb_tpu.comm.ops import get_op, make_payload
from dlbb_tpu.compat import shard_map
from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.models.transformer import init_params
from dlbb_tpu.train.loop import make_train_step, run_train

AXES = ("ranks",)
N = 4096

TINY = ModelConfig(hidden_size=32, num_layers=2, num_heads=4,
                   ffn_intermediate=64, attention="full", dtype="float32")


def _train_config(**training_over):
    training = {"learning_rate": 1e-2}
    training.update(training_over)
    return {
        "experiment": {"name": "train_compression"},
        "model": {
            "hidden_size": 32, "num_layers": 2, "num_heads": 4,
            "ffn_intermediate": 64, "attention": "full", "dtype": "float32",
        },
        "parallelism": {"world_size": 1, "data_parallel": 4},
        "input": {"batch_size": 8, "sequence_length": 16, "seed": 42},
        "execution": {"warmup_iterations": 1, "benchmark_iterations": 5},
        "training": training,
    }


# ---------------------------------------------------------------------------
# quantise / dequantise kernels
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_bound():
    """Chunked symmetric int8: per-element error <= half a quantisation
    step of the chunk's own scale (amax/127), never the global amax."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal(N).astype(np.float32)
    x[:SCALE_CHUNK_ELEMS] *= 100.0  # a hot chunk must not hurt the others
    q, scales = quantize_chunked(jnp.asarray(x), "int8")
    assert q.dtype == jnp.int8
    got = np.asarray(dequantize_chunked(q, scales, N, jnp.float32))
    chunk_amax = np.abs(x.reshape(-1, SCALE_CHUNK_ELEMS)).max(axis=1)
    bound = np.repeat(chunk_amax / 126.0, SCALE_CHUNK_ELEMS) + 1e-7
    assert (np.abs(got - x) <= bound).all()


def test_fp8_roundtrip_error_bound():
    """fp8(e4m3) keeps ~2 decimal digits: relative error per element
    bounded by 2^-3 of the value (plus a scale-floor term)."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal(N).astype(np.float32)
    q, scales = quantize_chunked(jnp.asarray(x), "fp8")
    assert q.dtype == jnp.float8_e4m3fn
    got = np.asarray(dequantize_chunked(q, scales, N, jnp.float32))
    chunk_amax = np.abs(x.reshape(-1, SCALE_CHUNK_ELEMS)).max(axis=1)
    floor = np.repeat(chunk_amax / 448.0, SCALE_CHUNK_ELEMS)
    assert (np.abs(got - x) <= np.abs(x) / 8.0 + floor + 1e-7).all()


def test_quantization_error_is_exact_complement():
    """x == D(Q(x)) + quantization_error(x) — the error-feedback identity
    the residual contract relies on."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)  # pad path too
    for comp in ("int8", "fp8"):
        q, s = quantize_chunked(x, comp)
        recon = dequantize_chunked(q, s, 1000, jnp.float32)
        err = quantization_error(x, comp)
        np.testing.assert_allclose(
            np.asarray(recon + err), np.asarray(x), rtol=1e-6, atol=1e-7
        )


def test_unknown_compression_rejected():
    with pytest.raises(ValueError, match="unknown compression"):
        quantize_chunked(jnp.zeros(8), "int4")


# ---------------------------------------------------------------------------
# compressed reductions == their uncompressed primitives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp,tol", [("int8", 0.04), ("fp8", 0.15)])
def test_psum_compressed_matches_psum_ring(mesh8, comp, tol):
    """psum_compressed == lax.psum within the wire dtype's tolerance on
    the flat 8-rank ring, and every rank holds the identical result."""
    op = get_op("allreduce")
    x = make_payload(op, mesh8, AXES, 1000, dtype=jnp.float32)
    host = np.asarray(x, np.float64)

    fn = jax.jit(shard_map(
        lambda xl: psum_compressed(xl[0], "ranks", compression=comp)[None],
        mesh=mesh8, in_specs=P("ranks"), out_specs=P("ranks"),
    ))
    out = np.asarray(fn(x))
    expected = host.sum(axis=0)
    scale = np.abs(expected).max()
    assert np.abs(out - expected).max() <= tol * scale
    assert np.abs(out - out[0]).max() == 0.0  # replicated result


def test_psum_compressed_dp_axis_of_dp_tp_mesh(mesh2x4):
    """Reduction over ONE axis ('dp') of a (dp, tp) mesh: each tp column
    reduces independently — the exact composition the train path uses."""
    rng = np.random.default_rng(3)
    host = rng.standard_normal((8, 256)).astype(np.float32)
    x = jax.device_put(host, NamedSharding(mesh2x4, P(("dp", "tp"))))

    fn = jax.jit(shard_map(
        lambda xl: psum_compressed(xl[0], "dp", compression="int8")[None],
        mesh=mesh2x4, in_specs=P(("dp", "tp")), out_specs=P(("dp", "tp")),
    ))
    out = np.asarray(fn(x))
    grid = host.reshape(2, 4, 256).astype(np.float64)
    expected = grid.sum(axis=0)  # per tp column
    for dp_i in range(2):
        for tp_j in range(4):
            diff = np.abs(out[dp_i * 4 + tp_j] - expected[tp_j]).max()
            assert diff <= 0.04 * np.abs(expected[tp_j]).max()


def test_allreduce_q_matches_allreduce(mesh8):
    op_q, op = get_op("allreduce_q"), get_op("allreduce")
    x = make_payload(op, mesh8, AXES, N, dtype=jnp.float32)
    baseline = np.asarray(op.build(mesh8, AXES)(x), np.float64)
    for comp, tol in (("int8", 0.04), ("fp8", 0.15)):
        out = np.asarray(op_q.build(mesh8, AXES, compression=comp)(x))
        scale = np.abs(baseline).max()
        assert np.abs(out - baseline).max() <= tol * scale, comp


def test_allreduce_q_bf16_accumulation(mesh8):
    """The bf16-accumulation variant stays within a (looser) tolerance —
    the bandwidth-vs-accuracy leg the sweep engine prices."""
    op_q, op = get_op("allreduce_q"), get_op("allreduce")
    x = make_payload(op, mesh8, AXES, N, dtype=jnp.float32)
    baseline = np.asarray(op.build(mesh8, AXES)(x), np.float64)
    out = np.asarray(op_q.build(
        mesh8, AXES, compression="int8", accum_dtype=jnp.bfloat16)(x))
    assert np.abs(out - baseline).max() <= 0.08 * np.abs(baseline).max()


def test_reducescatter_q_matches_reducescatter(mesh8):
    op_q, op = get_op("reducescatter_q"), get_op("reducescatter")
    x = make_payload(op, mesh8, AXES, 512, dtype=jnp.float32)
    baseline = np.asarray(op.build(mesh8, AXES)(x), np.float64)
    out = np.asarray(op_q.build(mesh8, AXES, compression="int8")(x))
    assert out.shape == baseline.shape
    scale = np.abs(baseline).max()
    assert np.abs(out - baseline).max() <= 0.04 * scale


def test_reduce_scatter_compressed_row_gate(mesh8):
    with pytest.raises(ValueError, match="leading dim"):
        jax.jit(shard_map(
            lambda xl: reduce_scatter_compressed(xl[0], "ranks")[None],
            mesh=mesh8, in_specs=P("ranks"), out_specs=P("ranks"),
        ))(make_payload(get_op("allreduce"), mesh8, AXES, 64))


def test_compressed_ops_single_axis_only(mesh2x2x2):
    for name in ("allreduce_q", "reducescatter_q"):
        with pytest.raises(ValueError, match="single mesh axis"):
            get_op(name).build(mesh2x2x2, ("x", "y", "z"))


# ---------------------------------------------------------------------------
# analytic wire model (stats bytes_on_wire) pinned against the audited HLO
# ---------------------------------------------------------------------------


def test_wire_model_matches_audited_totals(devices):
    """op_wire_bytes IS the audit's per-instruction sum for the
    compressed ops (chunk sizes chosen padding-free), scale side channel
    included — the stats column and the lint ceiling can never drift
    apart."""
    for name in ("allreduce_q", "reducescatter_q"):
        target = _compressed_op_target(name, "int8", num_elements=N)
        findings, meta = audit_target(target)
        assert findings == [], [f.render() for f in findings]
        analytic = op_wire_bytes(name, N, 8, 2, compression="int8")
        assert meta["total_wire_bytes"] == analytic, name


def test_wire_model_counts_chunk_padding(devices):
    """A payload whose ring chunk is NOT a SCALE_CHUNK multiple travels
    zero-padded; the analytic model charges the padding, so a correct
    ring still audits clean (ceiling = max(ratio x baseline, 1.1 x its
    own analytic wire)) and the stats column reports the real bytes."""
    n = 3000  # ring chunks of 375 -> padded to 512 on the wire
    target = _compressed_op_target("allreduce_q", "int8", num_elements=n)
    findings, meta = audit_target(target)
    assert findings == [], [f.render() for f in findings]
    analytic = op_wire_bytes("allreduce_q", n, 8, 2, compression="int8")
    assert meta["total_wire_bytes"] == analytic
    # the padded model is what the audit saw — an unpadded one would
    # undercount by the 512/375 ratio and reject this very module
    unpadded_ring = 7 * (375 * 1 + scale_bytes(375))
    assert analytic > 2 * unpadded_ring


def test_wire_model_uncompressed_consistency():
    """The per-op formulas agree with the per-instruction ring model for
    the single-collective encodings."""
    n, p, b = 1024, 8, 2
    assert op_wire_bytes("allreduce", n, p, b) == \
        wire_bytes("all-reduce", n * b, p)
    assert op_wire_bytes("allgather", n, p, b) == \
        wire_bytes("all-gather", p * n * b, p)
    assert op_wire_bytes("reducescatter", n, p, b) == \
        wire_bytes("reduce-scatter", n * b, p)
    assert op_wire_bytes("sendrecv", n, p, b) == n * b
    # compressed vs baseline: the 0.55x acceptance ratio holds
    # analytically at chunk-aligned, compression-meaningful sizes
    big = 16384  # ring chunks of 2048 elements, SCALE_CHUNK-aligned
    ratio = op_wire_bytes("allreduce_q", big, p, b) / \
        op_wire_bytes("allreduce", big, p, b)
    assert ratio <= 0.55, ratio
    # ...and at tiny payloads the padding + scale overhead honestly
    # EXCEEDS the baseline (compression does not pay below a ring chunk
    # of SCALE_CHUNK_ELEMS) — the model must report that, not hide it
    tiny_ratio = op_wire_bytes("allreduce_q", 256, p, b) / \
        op_wire_bytes("allreduce", 256, p, b)
    assert tiny_ratio > 1.0, tiny_ratio
    assert op_wire_bytes("ag_matmul", n, p, b) is None  # schedule-dependent


def test_stats_rows_carry_bytes_on_wire(tmp_path):
    """stats1d rows (and through them the comparison) carry the analytic
    wire volume; compressed rows show the saving while bandwidth_gbps
    stays normalised by LOGICAL payload bytes."""
    from dlbb_tpu.stats.stats1d import process_file

    rows = {}
    for op_name, extra in (("allreduce", {}),
                           ("allreduce_q", {"compression": "int8"})):
        art = {
            "implementation": "x", "operation": op_name, "num_ranks": 8,
            "num_elements": N, "dtype": "bfloat16",
            "data_size_name": "8KB", "timings": [[0.001] * 4],
            **extra,
        }
        f = tmp_path / f"{op_name}.json"
        f.write_text(json.dumps(art))
        rows[op_name] = process_file(f)
    assert rows["allreduce"]["bytes_on_wire"] == \
        op_wire_bytes("allreduce", N, 8, 2)
    assert rows["allreduce_q"]["bytes_on_wire"] == \
        op_wire_bytes("allreduce_q", N, 8, 2, compression="int8")
    # identical logical-bandwidth normalisation on both rows
    assert rows["allreduce"]["bandwidth_gbps"] == \
        rows["allreduce_q"]["bandwidth_gbps"]
    assert rows["allreduce_q"]["bytes_on_wire"] < \
        0.55 * rows["allreduce"]["bytes_on_wire"]


# ---------------------------------------------------------------------------
# comm-lint: clean passes + seeded violations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp", ["int8", "fp8"])
@pytest.mark.parametrize("op_name", ["allreduce_q", "reducescatter_q"])
def test_compressed_targets_audit_clean(devices, op_name, comp):
    """The compression proof: pure quantised ring, total wire (scales
    included) under 0.55x the bf16 baseline — for BOTH wire dtypes (fp8
    rides the wire bitcast to int8, so backend float-normalisation can
    never silently double it)."""
    findings, meta = audit_target(_compressed_op_target(op_name, comp))
    assert findings == [], [f.render() for f in findings]
    assert meta["num_collectives"] >= 7  # >= P-1 permute hops


def test_dequant_before_collective_flagged(mesh8):
    """Seeded violation: quantise, dequantise locally, then psum in bf16
    — exactly the 'XLA undid the compression' failure mode.  The audit
    must flag the uncompressed all-reduce AND the blown byte ceiling."""
    from dlbb_tpu.comm.compression import (
        dequantize_chunked as deq,
        quantize_chunked as quant,
    )

    def build():
        def body(x):
            q, s = quant(x[0], "int8")
            back = deq(q, s, N, jnp.bfloat16)  # dequantised BEFORE the wire
            return jax.lax.psum(back, "ranks")[None]

        fn = jax.jit(shard_map(body, mesh=mesh8, in_specs=P("ranks"),
                               out_specs=P("ranks")))
        x = make_payload(get_op("allreduce_q"), mesh8, AXES, N,
                         dtype=jnp.bfloat16)
        return fn, (x,)

    target = AuditTarget(
        name="fixture/dequant_before_collective", build=build,
        expectation=compressed_op_expectation("allreduce_q", 8, N),
    )
    findings, _ = audit_target(target)
    rules = {f.rule for f in findings}
    assert "unexpected-collective" in rules, rules
    assert "wire-volume-ceiling" in rules, rules


def test_wire_volume_ceiling_fires_alone_on_fat_ring(mesh8):
    """A ring whose KINDS are right but whose wire is uncompressed bf16:
    only the total-volume rule can catch it — pinned here in isolation
    (no per-instruction ceiling set)."""
    n = 512

    def build():
        def body(x):
            part = x[0]
            perm = [(i, (i + 1) % 8) for i in range(8)]
            for _ in range(7):  # bf16 chunks on the wire: 2x the claim
                part = jax.lax.ppermute(part, "ranks", perm) + x[0]
            return part[None]

        fn = jax.jit(shard_map(body, mesh=mesh8, in_specs=P("ranks"),
                               out_specs=P("ranks")))
        x = make_payload(get_op("allreduce"), mesh8, AXES, n,
                         dtype=jnp.bfloat16)
        return fn, (x,)

    ceiling = int(0.55 * wire_bytes("reduce-scatter", n * 2, 8))
    target = AuditTarget(
        name="fixture/bf16_wire_ring", build=build,
        expectation=TargetExpectation(
            allowed={"collective-permute"},
            required_any={"collective-permute"},
            min_required=7,
            max_total_wire_bytes=ceiling,
        ),
    )
    findings, meta = audit_target(target)
    assert [f.rule for f in findings] == ["wire-volume-ceiling"]
    assert meta["total_wire_bytes"] > ceiling


# ---------------------------------------------------------------------------
# train-loop integration: error feedback, checkpointing, validation
# ---------------------------------------------------------------------------


def _compressed_setup(tmp_dir=None, compression="int8", zero_stage=0):
    mesh = build_parallelism_mesh(data_parallel=4)
    params = init_params(TINY, jax.random.key(0))
    jit_step, state = make_train_step(
        TINY, mesh, optax.adam(1e-2), params, zero_stage=zero_stage,
        grad_compression=compression,
    )
    x = jax.random.normal(jax.random.key(1), (8, 16, 32))
    y = jax.random.normal(jax.random.key(2), (8, 16, 32))
    return jit_step, state, x, y


def test_residual_state_shape_and_sharding(devices):
    """The error-feedback residual is an optimizer-state leaf: [dp, total
    params], dp-sharded (one row per rank, never replicated)."""
    _, state, _, _ = _compressed_setup()
    inner, comp = state.opt_state
    total = sum(p.size for p in jax.tree.leaves(state.params))
    assert comp.residual.shape == (4, total)
    spec = comp.residual.sharding.spec
    assert tuple(spec) and spec[0] == "dp"


def test_residual_checkpoint_roundtrip(devices, tmp_path):
    """Error-feedback residual survives save/restore bit-exactly, with
    its dp sharding — the optimizer-state-leaf contract."""
    from dlbb_tpu.train.checkpoint import CheckpointConfig, Checkpointer

    jit_step, state, x, y = _compressed_setup()
    for _ in range(3):
        state, _ = jit_step(state, x, y)
    res = np.asarray(jax.device_get(state.opt_state[1].residual))
    assert np.abs(res).max() > 0.0  # quantisation error accumulated

    with Checkpointer(CheckpointConfig(str(tmp_path / "ck"))) as ckpt:
        assert ckpt.maybe_save(state, force=True)
        restored = ckpt.restore(state)

    assert int(restored.step) == 3
    r_res = restored.opt_state[1].residual
    np.testing.assert_array_equal(np.asarray(jax.device_get(r_res)), res)
    assert r_res.sharding == state.opt_state[1].residual.sharding
    # the restored state steps on without retracing surprises
    restored, loss = jit_step(restored, x, y)
    assert np.isfinite(float(loss))


def test_compressed_zero2_trains(devices):
    r = run_train(_train_config(grad_compression="int8"), zero_stage=2,
                  verbose=False)
    assert r["zero_stage"] == 2 and r["grad_compression"] == "int8"
    assert all(np.isfinite(r["losses"]))
    assert r["losses"][-1] < r["losses"][0]


def test_residual_moments_dtype_cast(devices):
    """residual follows the moments-storage dtype (memory-reduced Adam)."""
    mesh = build_parallelism_mesh(data_parallel=4)
    params = init_params(TINY, jax.random.key(0))
    _, state = make_train_step(
        TINY, mesh, optax.adam(1e-2), params, zero_stage=0,
        grad_compression="int8", residual_dtype="bfloat16",
    )
    assert state.opt_state[1].residual.dtype == jnp.bfloat16


def test_grad_compression_validation(devices):
    mesh_tp = build_parallelism_mesh(data_parallel=2, tensor_parallel=2)
    mesh_dp = build_parallelism_mesh(data_parallel=4)
    params = init_params(TINY, jax.random.key(0))
    opt = optax.adam(1e-2)
    with pytest.raises(ValueError, match="unknown grad_compression"):
        make_train_step(TINY, mesh_dp, opt, params, grad_compression="int4")
    with pytest.raises(ValueError, match="pure data-parallel"):
        make_train_step(TINY, mesh_tp, opt, params, grad_compression="int8")
    with pytest.raises(ValueError, match="data_parallel=1"):
        # dp=1 has no reduction: the residual would feed back an error
        # that was never incurred on the wire
        make_train_step(TINY, build_parallelism_mesh(data_parallel=1),
                        opt, params, grad_compression="int8")
    with pytest.raises(ValueError, match="ZeRO stages 0"):
        make_train_step(TINY, mesh_dp, opt, params, zero_stage=1,
                        grad_compression="int8")
    with pytest.raises(ValueError, match="gradient_accumulation"):
        make_train_step(TINY, mesh_dp, opt, params, grad_accum=2,
                        grad_compression="int8")
    with pytest.raises(ValueError, match="grad_compression"):
        run_train(_train_config(grad_compression="lossy"), verbose=False)
    with pytest.raises(ValueError, match="compression_accum_dtype"):
        run_train(_train_config(grad_compression="int8",
                                compression_accum_dtype="float16"),
                  verbose=False)


# ---------------------------------------------------------------------------
# compression_smoke marker stage (scripts/run_static_analysis.sh)
# ---------------------------------------------------------------------------


@pytest.mark.compression_smoke
def test_compressed_train_tracks_uncompressed(devices):
    """Loss curve of the int8 error-feedback run tracks the uncompressed
    run step for step — the train-side acceptance gate (BENCH_compress
    measures the same divergence over a longer horizon)."""
    r_base = run_train(_train_config(), verbose=False)
    r_int8 = run_train(_train_config(grad_compression="int8"),
                       verbose=False)
    r_fp8 = run_train(_train_config(grad_compression="fp8"), verbose=False)
    for r in (r_int8, r_fp8):
        assert all(np.isfinite(r["losses"]))
    div8 = max(abs(a - b) / max(abs(a), 1e-9)
               for a, b in zip(r_base["losses"], r_int8["losses"]))
    assert div8 <= 0.02, (div8, r_base["losses"], r_int8["losses"])
    divf = max(abs(a - b) / max(abs(a), 1e-9)
               for a, b in zip(r_base["losses"], r_fp8["losses"]))
    assert divf <= 0.05, divf
    assert r_int8["losses"][-1] < r_int8["losses"][0]


@pytest.mark.compression_smoke
def test_compression_mini_sweep_and_topology(tmp_path, devices):
    """allreduce_q variant mini-sweep through the real engine: artifacts
    carry the compression field, and the sweep manifest + journal carry
    the topology record (platform, device count, simulated flag)."""
    from dlbb_tpu.bench.runner import Sweep1D, run_sweep
    from dlbb_tpu.resilience.journal import read_journal

    for variant, expect_comp in (("compress_int8", "int8"),
                                 ("compress_fp8", "fp8"),
                                 ("compress_int8_bf16acc", "int8")):
        out = tmp_path / variant
        sweep = Sweep1D(
            implementation="comp_smoke", variant=variant,
            operations=("allreduce_q",), data_sizes=(("1KB", 256),),
            rank_counts=(8,), warmup_iterations=1,
            measurement_iterations=3, output_dir=str(out),
            compile_cache="off", pipeline=False,
        )
        files = run_sweep(sweep, verbose=False)
        assert len(files) == 1
        art = json.loads(files[0].read_text())
        assert art["compression"] == expect_comp
        assert art["variant"] == variant

        manifest = json.loads((out / "sweep_manifest.json").read_text())
        topo = manifest["topology"]
        assert topo["platform"] == "cpu"
        assert topo["num_devices"] >= 8
        assert topo["simulated"] is True

        events, torn = read_journal(out)
        assert torn == 0
        topo_events = [e for e in events if e["event"] == "topology"]
        assert topo_events and topo_events[0]["platform"] == "cpu"


def test_topology_record_refuses_unrequested_cpu(monkeypatch):
    """The no-chip rule at the library door: a requested simulation gets
    its record; the same CPU backend with nobody having asked for it is
    an error that names the way out, not a labelled run."""
    from dlbb_tpu.utils import simulate

    rec = simulate.topology_record()  # conftest forced the simulation
    assert rec["platform"] == "cpu" and rec["simulated"] is True
    assert "degraded" not in rec
    monkeypatch.setattr(simulate, "_SIMULATION_FORCED", False)
    with pytest.raises(simulate.NoAcceleratorError, match="--simulate N"):
        simulate.topology_record()
