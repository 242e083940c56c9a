"""Collective correctness tests on the simulated 8-device mesh.

Mirrors the reference's 12-case MPI smoke suite ``test/test_open.py``
(sendrecv :35, bcast :65, scatter :86, gather :105, allgather :125,
reduce :142, allreduce :159, buffer Bcast :175, buffer Allreduce :195,
barrier :214, ring isend/irecv :227, MAX/MIN/PROD :248) as asserted pytest
cases instead of mpirun-launched scripts.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.comm import get_op, make_payload
from dlbb_tpu.comm.ops import (
    build_allreduce,
    build_allreduce_hierarchical,
    build_barrier,
)

AXES = ("ranks",)
N = 64


def _np_input(op_name, mesh, dtype=jnp.float32):
    op = get_op(op_name)
    x = make_payload(op, mesh, AXES, N, dtype=dtype)
    return op, x, np.asarray(x).astype(np.float64)


def test_allreduce_sum(mesh8):
    op, x, host = _np_input("allreduce", mesh8)
    fn = op.build(mesh8, AXES)
    out = np.asarray(fn(x))
    expected = host.sum(axis=0)
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("red,npfn", [("max", np.max), ("min", np.min), ("prod", np.prod)])
def test_allreduce_max_min_prod(mesh8, red, npfn):
    """MAX/MIN/PROD reduction ops (reference ``test/test_open.py:248``)."""
    op, x, host = _np_input("allreduce", mesh8)
    fn = build_allreduce(mesh8, AXES, reduce_op=red)
    out = np.asarray(fn(x))
    expected = npfn(host, axis=0)
    rtol = 1e-3 if red == "prod" else 1e-5
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=rtol, atol=1e-5)


def test_allgather(mesh8):
    op, x, host = _np_input("allgather", mesh8)
    fn = op.build(mesh8, AXES)
    out = np.asarray(fn(x))  # [8, 8, N] — every rank holds all 8 buffers
    for r in range(8):
        np.testing.assert_allclose(out[r], host, rtol=1e-5, atol=1e-5)


def test_allgather_3d_payload(mesh8):
    """Shaped (B,S,H) payloads keep their structure through allgather
    (3D sweep path, reference ``collectives/3d/openmpi.py:21-23``)."""
    op = get_op("allgather")
    x = make_payload(op, mesh8, AXES, 0, dtype=jnp.float32, shape=(2, 4, 8))
    out = np.asarray(op.build(mesh8, AXES)(x))
    assert out.shape == (8, 8, 2, 4, 8)
    np.testing.assert_allclose(out[3], np.asarray(x), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("root", [0, 3])
def test_broadcast(mesh8, root):
    op, x, host = _np_input("broadcast", mesh8)
    fn = op.build(mesh8, AXES, root)
    out = np.asarray(fn(x))
    for r in range(8):
        np.testing.assert_allclose(out[r], host[root], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("root", [0, 5])
def test_gather(mesh8, root):
    op, x, host = _np_input("gather", mesh8)
    fn = op.build(mesh8, AXES, root)
    out = np.asarray(fn(x))  # [8, 8, N]
    np.testing.assert_allclose(out[root], host, rtol=1e-5, atol=1e-5)
    for r in range(8):
        if r != root:
            assert np.all(out[r] == 0.0)


@pytest.mark.parametrize("root", [0, 2])
def test_scatter(mesh8, root):
    op = get_op("scatter")
    x = make_payload(op, mesh8, AXES, N)  # [8, 8, N]
    host = np.asarray(x)
    fn = op.build(mesh8, AXES, root)
    out = np.asarray(fn(x))  # [8, N]
    # rank i must receive row i of the ROOT's sendbuf
    for r in range(8):
        np.testing.assert_allclose(out[r], host[root, r], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("root", [0, 7])
def test_reduce(mesh8, root):
    op, x, host = _np_input("reduce", mesh8)
    fn = op.build(mesh8, AXES, root)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out[root], host.sum(axis=0), rtol=1e-4, atol=1e-4)
    for r in range(8):
        if r != root:
            assert np.all(out[r] == 0.0)


def test_alltoall(mesh8):
    op = get_op("alltoall")
    x = make_payload(op, mesh8, AXES, N)  # [8, 8, N]
    host = np.asarray(x)
    fn = op.build(mesh8, AXES)
    out = np.asarray(fn(x))
    # out[i][j] == in[j][i]  (rank i receives chunk i from every rank j)
    for i in range(8):
        for j in range(8):
            np.testing.assert_allclose(out[i, j], host[j, i], rtol=1e-5, atol=1e-5)


def test_sendrecv_ring(mesh8):
    """Ring shift: rank i's buffer lands on rank (i+1) % P
    (reference ``test/test_open.py:227`` ring isend/irecv)."""
    op, x, host = _np_input("sendrecv", mesh8)
    fn = op.build(mesh8, AXES)
    out = np.asarray(fn(x))
    for r in range(8):
        np.testing.assert_allclose(out[(r + 1) % 8], host[r], rtol=1e-5, atol=1e-5)


def test_reducescatter(mesh8):
    op = get_op("reducescatter")
    x = make_payload(op, mesh8, AXES, N, dtype=jnp.float32)  # [8, 8, N]
    host = np.asarray(x).astype(np.float64)
    fn = op.build(mesh8, AXES)
    out = np.asarray(fn(x))  # [8, 1, N]
    # rank i gets sum over senders j of chunk i
    for r in range(8):
        np.testing.assert_allclose(out[r, 0], host[:, r].sum(axis=0), rtol=1e-4, atol=1e-4)


def test_barrier(mesh8):
    fn = build_barrier(mesh8, AXES)
    x = make_payload(get_op("allreduce"), mesh8, AXES, 1)
    out = fn(x)
    out.block_until_ready()  # completion == all devices reached the psum


def test_allreduce_bf16(mesh8):
    """Buffer-typed allreduce parity (reference numpy-buffer Allreduce
    ``test/test_open.py:195``); bf16 is the native TPU payload type."""
    op = get_op("allreduce")
    x = make_payload(op, mesh8, AXES, N, dtype=jnp.bfloat16)
    fn = op.build(mesh8, AXES)
    out = np.asarray(fn(x).astype(jnp.float32))
    expected = np.asarray(x.astype(jnp.float32)).sum(axis=0)
    np.testing.assert_allclose(out[0], expected, rtol=0.05, atol=0.5)


def test_hierarchical_allreduce_matches_flat(mesh2x2x2):
    """Per-axis hierarchical psum == joint psum on a 2x2x2 mesh
    (BASELINE.json config 3)."""
    axes = ("x", "y", "z")
    op = get_op("allreduce")
    x = make_payload(op, mesh2x2x2, axes, N, dtype=jnp.float32)
    flat = op.build(mesh2x2x2, axes)
    hier = build_allreduce_hierarchical(mesh2x2x2, axes)
    np.testing.assert_allclose(
        np.asarray(flat(x)), np.asarray(hier(x)), rtol=1e-4, atol=1e-4
    )


def test_allreduce_on_4rank_mesh(mesh4):
    """Rank-count sweep axis works (reference RANK_COUNTS gate,
    ``collectives/1d/openmpi.py:210-214``)."""
    op, x, host = _np_input("allreduce", mesh4)
    fn = op.build(mesh4, AXES)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out[0], host.sum(axis=0), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("root", [0, 3])
def test_oracle_agrees_on_every_reference_op(mesh8, root):
    """``comm/oracle.py`` states the same semantics the cases above pin
    inline — it is what ``chip_smoke.py`` checks a real multi-chip ring
    against — and its comparison is not vacuous."""
    from dlbb_tpu.bench.runner import OPERATIONS_1D
    from dlbb_tpu.comm.oracle import check_op, expected_output

    for name in OPERATIONS_1D + ("reducescatter",):
        seen = check_op(name, mesh8, AXES, N, root=root)
        assert seen["devices"] == 8, name
    host = np.arange(8.0 * N).reshape(8, N)
    assert not np.array_equal(expected_output("sendrecv", host), host)
    assert not np.array_equal(expected_output("reduce", host, root),
                              expected_output("allreduce", host))
