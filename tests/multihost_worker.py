"""Real 2-process worker for the non-mock multi-host test.

Launched by ``tests/test_multihost.py::test_real_two_process_sweep`` as
``python tests/multihost_worker.py <process_id> <coordinator_port> <out_dir>``.
Each process initialises ``jax.distributed`` against a local TCP
coordinator (CPU backend, gloo cross-process collectives, 2 simulated
devices per process -> one global 4-device mesh) and drives a tiny real
``Sweep1D`` through the code paths the mocked tests can only fake:

- ``_gather_timings``: process_count == 2 -> the host-side allgather
  branch; the written artifact must carry one timing row per host.
- ``_resume_ok``: the collective resume decision (existence + artifact
  validation); exercised with the hosts *disagreeing* (only process 0
  holds a valid artifact at the probe path) -> must return False on BOTH
  hosts, and with both agreeing -> must return True on both.

NOT imported by pytest collection (no ``test_`` prefix in module-level
names); runs standalone only.
"""

import dataclasses
import json
import sys
from pathlib import Path

# two simulated CPU devices per process; must precede any backend use
from dlbb_tpu.utils.simulate import force_cpu_simulation  # noqa: E402

force_cpu_simulation(2)

import jax  # noqa: E402

jax.config.update("jax_cpu_collectives_implementation", "gloo")


def main(process_id: int, port: int, out_dir: str) -> None:
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=2,
        process_id=process_id,
    )
    assert jax.process_count() == 2, jax.process_count()
    assert jax.local_device_count() == 2
    assert len(jax.devices()) == 4

    from dlbb_tpu.bench.runner import (
        Sweep1D,
        _resume_ok,
        run_sweep,
    )

    sweep = Sweep1D(
        operations=("allreduce",),
        data_sizes=(("1KB", 256),),
        rank_counts=(4,),
        warmup_iterations=1,
        measurement_iterations=3,
        timing_mode="per_iter",
        output_dir=out_dir,
    )
    written = run_sweep(sweep, verbose=process_id == 0)
    assert len(written) == 1, written
    artifact = json.loads(Path(written[0]).read_text())
    # the multi-host gather branch: one timing row per host
    assert len(artifact["timings"]) == 2, len(artifact["timings"])
    assert len(artifact["timings"][0]) == 3
    assert artifact["num_ranks"] == 4

    # resume pass: shared disk, both hosts hold the artifact -> both skip
    resumed = run_sweep(
        dataclasses.replace(sweep, resume=True), verbose=False
    )
    assert resumed == written, (resumed, written)

    # disagreeing hosts: only process 0 holds a VALID artifact at the
    # probe path (a copy of the real one, so its local check passes) ->
    # the collective decision must be False on BOTH (a per-host decision
    # here is exactly the pod-hang bug the docstring warns about)
    mine = Path(out_dir) / f"probe_proc{process_id}.json"
    if process_id == 0:
        mine.write_text(Path(written[0]).read_text())
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("probe_written")
    disagree, _ = _resume_ok(mine)
    assert disagree is False, disagree

    # agreeing hosts: the shared VALID artifact exists everywhere -> True
    agree, _ = _resume_ok(Path(written[0]))
    assert agree is True, agree

    # a torn artifact (truncated JSON) must not be trusted even though it
    # EXISTS on both hosts — the validation half of the collective check
    torn = Path(out_dir) / f"torn_shared_proc{process_id}.json"
    torn.write_text(Path(written[0]).read_text()[:40])
    multihost_utils.sync_global_devices("torn_written")
    trusted, why = _resume_ok(torn)
    assert trusted is False, (trusted, why)

    # e2e cross-host CV branch (bench/e2e.py): a tiny forward benchmark
    # over the global 4-device dp mesh.  The fixed-seed data layer is
    # multi-process-correct by construction: every process materialises
    # the identical batch, so the global device_put's same-value check
    # passes — exactly the property this exercises.
    from dlbb_tpu.bench.e2e import run_e2e

    e2e_cfg = {
        "experiment": {"name": "mh2_e2e"},
        "model": {"hidden_size": 64, "num_layers": 1, "num_heads": 2,
                  "ffn_intermediate": 128, "attention": "dense",
                  "dtype": "float32"},
        "parallelism": {"world_size": 1, "data_parallel": 4},
        "input": {"batch_size": 4, "sequence_length": 32, "seed": 42},
        "execution": {"warmup_iterations": 1, "benchmark_iterations": 3},
    }
    r = run_e2e(e2e_cfg, output_dir=out_dir if process_id == 0 else None,
                verbose=False)
    # the host-side allgather of per-host forward means: 2 entries, and
    # the CV is a real cross-host number (>= 0), not the single-process 0
    assert len(r["per_host_means_s"]) == 2, r["per_host_means_s"]
    assert r["cross_host_cv"] >= 0.0

    print(f"WORKER-OK proc={process_id}", flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), sys.argv[3])
