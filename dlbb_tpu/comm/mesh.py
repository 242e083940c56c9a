"""Device-mesh construction and multi-host bootstrap.

TPU-native replacement for the reference's bootstrap / process-group layer:

- reference ``run_mpi.py:29-49`` (``initialize_mpi_backend`` /
  ``cleanup_mpi_backend`` via mpi4py ``MPI.COMM_WORLD``),
- reference ``collectives/1d/dsgloo.py:53-67`` and ``dsccl.py:47-57``
  (``deepspeed.init_distributed``),
- reference rank/core binding tables ``collectives/3d/config_{4,8}.txt``.

Instead of mpirun-spawned ranks holding an opaque communicator, we build a
``jax.sharding.Mesh`` over the devices XLA exposes.  "Rank count" becomes the
mesh size; "topology tuning" becomes the mesh *shape* (1D ring vs multi-axis),
which is how ICI reductions are steered on TPU.

Development happens on a CPU-simulated mesh:
``XLA_FLAGS=--xla_force_host_platform_device_count=N JAX_PLATFORMS=cpu``
gives N fake devices in one process — the idiomatic JAX analogue of
``mpirun -np N`` on localhost (reference ``collectives/launch_openmpi.sh:5-12``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Single flat collective axis used by the 1D microbenchmarks — the analogue of
# MPI_COMM_WORLD's rank dimension.
DEFAULT_AXIS = "ranks"


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh description.

    Replaces the reference's ``RANK_COUNTS`` module constants
    (``collectives/1d/openmpi.py:19-20``) and core-binding tables with a
    first-class config object.

    shape:      devices per mesh axis, e.g. ``(8,)`` or ``(2, 2, 2)``.
    axis_names: one name per axis, e.g. ``("ranks",)`` or ``("x","y","z")``.
    """

    shape: tuple[int, ...]
    axis_names: tuple[str, ...] = (DEFAULT_AXIS,)

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.axis_names):
            raise ValueError(
                f"shape {self.shape} and axis_names {self.axis_names} "
                "must have the same length"
            )

    @classmethod
    def ring(cls, num_ranks: int, axis: str = DEFAULT_AXIS) -> "MeshSpec":
        """1D ring of ``num_ranks`` devices — the default microbenchmark mesh."""
        return cls((num_ranks,), (axis,))

    @classmethod
    def grid(cls, shape: Sequence[int], axis_names: Sequence[str]) -> "MeshSpec":
        """Multi-axis mesh, e.g. ``grid((2,2,2), ("x","y","z"))`` for the
        hierarchical-allreduce benchmark (BASELINE.json config 3)."""
        return cls(tuple(shape), tuple(axis_names))

    @property
    def num_ranks(self) -> int:
        return math.prod(self.shape)

    @property
    def name(self) -> str:
        return "x".join(str(s) for s in self.shape)


def available_devices(platform: Optional[str] = None) -> list:
    """All addressable-or-not devices, optionally filtered by platform."""
    if platform is None:
        return list(jax.devices())
    return list(jax.devices(platform))


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    """Build a ``jax.sharding.Mesh`` for ``spec`` from the first
    ``spec.num_ranks`` devices.

    Mirrors the reference's world-size gate (``collectives/1d/openmpi.py:210-214``,
    ``run_mpi.py:73-77``): raises if fewer devices are available than the spec
    needs, so sweeps can skip infeasible rank counts.
    """
    devs = list(devices) if devices is not None else available_devices()
    n = spec.num_ranks
    if len(devs) < n:
        raise ValueError(
            f"mesh spec {spec.shape} needs {n} devices, "
            f"only {len(devs)} available"
        )
    grid = np.asarray(devs[:n], dtype=object).reshape(spec.shape)
    return Mesh(grid, spec.axis_names)


# (spec, device identity) -> Mesh.  jax.sharding.Mesh equality is cheap but
# object identity matters downstream: jitted programs, NamedShardings, and
# the sweep scheduler's work-unit/payload cache keys all want one Mesh per
# topology per process, not a fresh object per run_sweep call.
_MESH_CACHE: dict[tuple, Mesh] = {}


def get_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    """``build_mesh`` with per-process memoisation.

    Repeated sweeps over the same topology (the publisher's stage loops, a
    resume re-run, the 1D/3D grids sharing a rank count) reuse one
    ``Mesh`` object instead of rebuilding it per ``run_sweep`` call.  Keyed
    by the spec and the identity of the devices that would populate it, so
    an explicit ``devices`` subset never aliases the default-device mesh.
    """
    devs = list(devices) if devices is not None else available_devices()
    key = (
        spec.shape,
        spec.axis_names,
        tuple(id(d) for d in devs[: spec.num_ranks]),
    )
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = build_mesh(spec, devices=devs)
        _MESH_CACHE[key] = mesh
    return mesh


def build_parallelism_mesh(
    data_parallel: int = 1,
    sequence_parallel: int = 1,
    pipeline_parallel: int = 1,
    tensor_parallel: int = 1,
    expert_parallel: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """The model-parallelism mesh shared by the E2E and train harnesses:
    ``(dp[, sp][, pp][, ep], tp)``.  dp is always present (outermost),
    sp/pp/ep only when > 1, and tp always innermost — the per-layer TP
    allreduces are the most frequent collective, so tp gets the fastest
    ICI neighbours."""
    shape, names = [data_parallel], ["dp"]
    if sequence_parallel > 1:
        shape.append(sequence_parallel)
        names.append("sp")
    if pipeline_parallel > 1:
        shape.append(pipeline_parallel)
        names.append("pp")
    if expert_parallel > 1:
        shape.append(expert_parallel)
        names.append("ep")
    shape.append(tensor_parallel)
    names.append("tp")
    spec = MeshSpec.grid(tuple(shape), tuple(names))
    return build_mesh(spec, devices=_ici_order(spec, devices))


def _ici_order(spec: MeshSpec, devices: Optional[Sequence]) -> Sequence:
    """The first ``spec.num_ranks`` devices in the order that makes
    neighbours along the mesh's inner axes neighbours on the ICI, where
    they are TPUs and jax has an assignment for the shape
    (``mesh_utils.create_device_mesh``: the four chips of a v5e 2x2 tray
    go round as 0, 1, 3, 2).  In their plain order 1 -> 2 and 3 -> 0 of a
    ``tp`` = 4 ring are diagonals of the tray, two links each, and the
    rings of ``parallel/collective_matmul.py`` hop i -> i + 1.  Any other
    platform, one device, or a shape jax refuses keeps the plain order."""
    devs = list(devices) if devices is not None else available_devices()
    n = spec.num_ranks
    if n < 2 or len(devs) < n or devs[0].platform != "tpu":
        return devs
    from jax.experimental import mesh_utils

    try:
        laid = mesh_utils.create_device_mesh(spec.shape, devices=devs[:n])
    except (ValueError, NotImplementedError, AssertionError):
        return devs
    return list(laid.reshape(-1))


def partition_devices(
    devices: Optional[Sequence] = None,
    groups: int = 1,
) -> list[list]:
    """Partition the device list into ``groups`` contiguous, equal-size,
    disjoint failure domains — the replica sub-meshes of the serving
    fleet (``serve/fleet.py``).

    Contiguity matters: XLA enumerates the simulated (and, on hardware,
    the physically-adjacent) devices in order, so contiguous slices give
    each replica the tightest ICI neighbourhood and guarantee no device
    is shared between domains — one replica's failure can never corrupt
    another's collectives.  Raises when the device count does not divide
    evenly (a lopsided fleet would skew every per-replica capacity
    claim)."""
    devs = list(devices) if devices is not None else available_devices()
    if groups < 1:
        raise ValueError(f"need at least one device group, got {groups}")
    if len(devs) % groups != 0:
        raise ValueError(
            f"{len(devs)} device(s) do not partition into {groups} "
            "equal failure domains"
        )
    per = len(devs) // groups
    return [devs[i * per:(i + 1) * per] for i in range(groups)]


def fault_domain_record(groups: Sequence[Sequence]) -> dict[str, list[int]]:
    """JSON-able ``fault_domains`` map (replica id -> device ids) for
    the topology record / serving manifest — the key fleet artifacts
    carry so fleet runs never silently aggregate with single-replica
    runs (``utils/simulate.topology_record``)."""
    return {
        str(i): [int(getattr(d, "id", j)) for j, d in enumerate(grp)]
        for i, grp in enumerate(groups)
    }


def mesh_num_ranks(mesh: Mesh, axes: Optional[Sequence[str]] = None) -> int:
    """Total ranks along ``axes`` (all axes if None)."""
    names = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    return math.prod(mesh.shape[a] for a in names)


def flat_axes(mesh: Mesh) -> tuple[str, ...]:
    """All axis names of a mesh, for collectives that reduce over the whole
    mesh (hierarchical variants reduce over them one at a time instead)."""
    return tuple(mesh.axis_names)


@dataclass
class DistributedContext:
    """What the reference's ``initialize_mpi_backend`` returns — ``(rank,
    world_size, comm)`` (``run_mpi.py:29-43``) — recast for JAX multi-host:
    process index/count at the host level, device count at the chip level."""

    process_id: int = 0
    num_processes: int = 1
    num_devices: int = field(default_factory=lambda: len(jax.devices()))

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    auto: bool = False,
) -> DistributedContext:
    """Multi-host bootstrap — the TPU-pod analogue of ``mpirun`` +
    ``MPI.COMM_WORLD`` (reference ``run_mpi.py:29-43``) and of the DeepSpeed
    launcher env handshake (``collectives/3d/launch_dsccl.sh:69-74``).

    Three modes:
    - explicit args → ``jax.distributed.initialize`` with them;
    - ``auto=True`` (what pod launchers pass — ``launch/launch_tpu_pod.sh``) →
      argument-free ``jax.distributed.initialize()``, which auto-discovers
      coordinator/processes from the TPU metadata server;
    - no args, ``auto=False`` (the default) → single-host no-op, so library
      users on one host or the CPU-simulated mesh never touch the
      coordinator handshake.
    """
    if num_processes is not None or coordinator_address is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif auto:
        # fail fast: auto=True means "we are on a pod" (launch_tpu_pod.sh);
        # degrading one host to single-process while its peers initialize
        # would hang the collective or silently mislabel single-host numbers
        jax.distributed.initialize()
    return DistributedContext(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        num_devices=len(jax.devices()),
    )
