"""Pipelined sweep engine tests (``dlbb_tpu.bench.schedule``).

Tier-1 guarantees for the compile-ahead scheduler: dedup keys never
collide across variants, one poisoned work unit skips its configs while
the pipeline drains, serial (``--no-pipeline``) and pipelined runs produce
identical result-JSON schemas, and the payload cache never hands out a
donated (deleted) array.
"""

import json
import threading

import pytest

from dlbb_tpu.bench import Sweep1D, run_sweep
from dlbb_tpu.bench.schedule import (
    CompileAheadScheduler,
    PayloadCache,
    WorkUnit,
    work_unit_key,
)
from dlbb_tpu.comm.mesh import MeshSpec, get_mesh
from dlbb_tpu.comm.ops import OPERATIONS, CollectiveOp, get_op, payload_aval

# every sweep here caches into a private directory, not the checkout's
pytestmark = pytest.mark.usefixtures("compile_cache_dir")


def _key(variant="default", op="allreduce", n=256, mode="per_iter",
         options=None, mesh=None):
    mesh = mesh if mesh is not None else get_mesh(MeshSpec.ring(4))
    axes = ("ranks",)
    aval = payload_aval(get_op(op), mesh, axes, n)
    return work_unit_key(get_op(op), variant, mesh, axes, 0, aval, mode,
                         100, options)


def test_work_unit_key_identity_and_variant_collision(devices):
    """Equal build parameters intern to one key; the same payload shape
    under a DIFFERENT variant (hierarchical vs joint reduction compiles a
    different program) must never share a cache entry."""
    assert _key() == _key()
    assert _key(variant="default") != _key(variant="hier2x2x2")
    assert _key(op="allreduce") != _key(op="broadcast")
    assert _key(n=256) != _key(n=512)
    assert _key(mode="per_iter") != _key(mode="chained")
    assert _key(options=None) != _key(options={"xla_foo": "1"})


def test_work_unit_key_mesh_identity(devices):
    """Same shape on a different device subset is a different program."""
    m4 = get_mesh(MeshSpec.ring(4))
    m4b = get_mesh(MeshSpec.ring(4), devices=list(reversed(devices))[:4])
    assert _key(mesh=m4) != _key(mesh=m4b)
    # and the mesh cache returns the SAME object for the same request
    assert get_mesh(MeshSpec.ring(4)) is m4


def _tiny(tmp_path, **kw):
    defaults = dict(
        implementation="xla_test",
        operations=("allreduce", "broadcast"),
        data_sizes=(("1KB", 256),),
        rank_counts=(4,),
        dtype="float32",
        warmup_iterations=1,
        measurement_iterations=3,
        output_dir=str(tmp_path / "results"),
        # exercise the compile-ahead thread regardless of the host-auto
        # default (schedule.default_pipeline is core-count dependent)
        pipeline=True,
    )
    defaults.update(kw)
    return Sweep1D(**defaults)


def test_serial_and_pipelined_results_equivalent(tmp_path, devices,
                                                 compile_cache_dir):
    """--no-pipeline and the pipelined engine must emit the same artifact
    set with the same schema and identical non-timing fields."""
    fp = run_sweep(_tiny(tmp_path, output_dir=str(tmp_path / "pipe")),
                   verbose=False)
    fs = run_sweep(_tiny(tmp_path, output_dir=str(tmp_path / "serial"),
                         pipeline=False), verbose=False)
    assert [p.name for p in fp] == [p.name for p in fs]
    for pp, ps in zip(fp, fs):
        dp, ds = json.loads(pp.read_text()), json.loads(ps.read_text())
        assert sorted(dp) == sorted(ds)
        for k in ("implementation", "operation", "num_ranks",
                  "num_elements", "dtype", "timing_mode", "mesh_shape"):
            assert dp[k] == ds[k], k
        for d in (dp, ds):
            assert d["compile_seconds"] >= 0.0
            assert isinstance(d["compile_cache_hit"], bool)
    manifests = [
        json.loads((tmp_path / d / "sweep_manifest.json").read_text())
        for d in ("pipe", "serial")
    ]
    assert manifests[0]["pipeline"] is True
    assert manifests[1]["pipeline"] is False
    assert all(m["configs"]["measured"] == 2 for m in manifests)


def test_compile_failure_contained_pipeline_drains(tmp_path, devices,
                                                   monkeypatch):
    """A work unit whose build raises skips its configs but the pipeline
    drains: later configs still measure and the manifest records the
    failure."""
    def boom_build(mesh, axes, root=0):
        raise RuntimeError("poisoned work unit")

    monkeypatch.setitem(
        OPERATIONS, "boom",
        CollectiveOp("boom", "per_rank", "per_rank", boom_build),
    )
    files = run_sweep(
        _tiny(tmp_path, operations=("boom", "allreduce", "broadcast")),
        verbose=False,
    )
    names = sorted(p.name for p in files)
    assert names == [
        "xla_test_allreduce_ranks4_1KB_fp32.json",
        "xla_test_broadcast_ranks4_1KB_fp32.json",
    ]
    man = json.loads(
        (tmp_path / "results" / "sweep_manifest.json").read_text()
    )
    assert man["configs"]["failed"] == 1
    assert man["configs"]["measured"] == 2
    assert man["work_units"]["compile_failed"] == 1


def test_planning_failure_contained(tmp_path, devices):
    """A config that cannot even be PLANNED (unknown op) is skipped like a
    measurement failure: the rest of the sweep proceeds and the cache
    scoping still unwinds.  The memory cap is set because its estimator
    also resolves the op name — containment must cover that path too (a
    publisher stage always sets max_global_bytes)."""
    files = run_sweep(
        _tiny(tmp_path, operations=("nosuchop", "allreduce"),
              max_global_bytes=1 << 30),
        verbose=False,
    )
    assert [p.name for p in files] == [
        "xla_test_allreduce_ranks4_1KB_fp32.json"
    ]
    man = json.loads(
        (tmp_path / "results" / "sweep_manifest.json").read_text()
    )
    assert man["configs"]["failed"] == 1
    assert man["configs"]["measured"] == 1


def test_chained_mode_through_engine(tmp_path, devices):
    """timing_mode=chained AOT-compiles the donating timing loop; results
    keep chained-mode metadata and the donated payload is never reused."""
    files = run_sweep(
        _tiny(tmp_path, operations=("allreduce", "reduce"),
              timing_mode="chained"),
        verbose=False,
    )
    assert len(files) == 2
    for f in files:
        d = json.loads(f.read_text())
        assert d["timing_mode"] == "chained"
        assert "chunk_size" in d
        assert "compile_seconds" in d and "compile_cache_hit" in d


def test_warm_persistent_cache_hits(tmp_path, devices, compile_cache_dir):
    """A second sweep over the same grid (fresh jit objects, same
    programs) deserialises from the persistent cache: every artifact
    reports a compile-cache hit, and every entry landed in the directory
    JAX_COMPILATION_CACHE_DIR named."""
    import os

    cold = run_sweep(_tiny(tmp_path, output_dir=str(tmp_path / "cold")),
                     verbose=False)
    for f in cold:
        assert json.loads(f.read_text())["compile_cache_hit"] is False
    assert os.listdir(compile_cache_dir)
    warm = run_sweep(_tiny(tmp_path, output_dir=str(tmp_path / "warm")),
                     verbose=False)
    assert warm
    for f in warm:
        assert json.loads(f.read_text())["compile_cache_hit"] is True
    man = json.loads((tmp_path / "warm" / "sweep_manifest.json").read_text())
    assert man["compile_cache"]["dir"] == compile_cache_dir
    assert man["compile_cache"]["persistent_hits"] == 2
    assert man["compile_cache"]["persistent_misses"] == 0


def test_default_pipeline_env_overrides(monkeypatch):
    from dlbb_tpu.bench.schedule import default_pipeline

    monkeypatch.setenv("DLBB_SWEEP_PIPELINE", "1")
    assert default_pipeline() is True
    monkeypatch.setenv("DLBB_SWEEP_PIPELINE", "off")
    assert default_pipeline() is False
    monkeypatch.delenv("DLBB_SWEEP_PIPELINE")
    monkeypatch.setenv("DLBB_COMPILE_OVERLAP", "1")
    assert default_pipeline() is True
    monkeypatch.delenv("DLBB_COMPILE_OVERLAP")
    # unforced: purely a core-count policy
    import os

    assert default_pipeline() is ((os.cpu_count() or 1) >= 4)


def _spy_config_updates(monkeypatch):
    import jax

    seen = []
    real = jax.config.update

    def update(name, value):
        seen.append(name)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return seen


def test_compile_cache_env_dir_is_left_alone(compile_cache_dir,
                                             monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory of its
    own — no ``jax_compilation_cache_dir`` update is made by the one
    configuring function or by a sweep's scope, not even to restore it."""
    import jax

    from dlbb_tpu.utils import compile_cache

    seen = _spy_config_updates(monkeypatch)
    assert compile_cache.configure_compile_cache() == compile_cache_dir
    with compile_cache.sweep_scope("auto") as d:
        assert d == compile_cache_dir
        assert jax.config.jax_enable_compilation_cache is True
    with compile_cache.sweep_scope("off") as d:
        assert d is None
    assert "jax_compilation_cache_dir" not in seen
    assert jax.config.jax_compilation_cache_dir == compile_cache_dir


def test_compile_cache_default_dir_ignores_cwd(tmp_path, monkeypatch):
    """Variable unset: ``<checkout>/.jax_cache``, resolved from the
    package's own location — the same path from any working directory
    (the path is part of the cache key; one that moves never hits)."""
    from pathlib import Path

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from dlbb_tpu.utils import compile_cache

    repo = Path(__file__).resolve().parents[1]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prior = jax.config.jax_compilation_cache_dir
    try:
        for cwd in (tmp_path, repo / "tests"):
            monkeypatch.chdir(cwd)
            jax.config.update("jax_compilation_cache_dir", None)
            assert compile_cache.configure_compile_cache() \
                == str(repo / ".jax_cache")
        assert compile_cache.DEFAULT_CACHE_DIR == str(repo / ".jax_cache")
        assert not (tmp_path / ".jax_cache").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
        cc.reset_cache()


def test_sweep_scope_is_the_only_cache_window_on_the_simulated_mesh(
        compile_cache_dir):
    """force_cpu_simulation leaves the cache off (XLA:CPU aborts on some
    deserialised non-sweep programs, utils/compile_cache.py); a sweep's
    scope turns it on and off again; 'off' and a bare directory — the
    form that is gone — never turn it on."""
    import jax

    from dlbb_tpu.utils.compile_cache import sweep_scope

    assert jax.config.jax_enable_compilation_cache is False
    with sweep_scope("auto"):
        assert jax.config.jax_enable_compilation_cache is True
    assert jax.config.jax_enable_compilation_cache is False
    for off in ("off", None):
        with sweep_scope(off) as d:
            assert d is None
            assert jax.config.jax_enable_compilation_cache is False
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        with sweep_scope("/some/dir"):
            pass
    assert jax.config.jax_enable_compilation_cache is False


def test_scheduler_dedup_and_drain():
    """Each unit compiles exactly once however many configs consume it,
    and a failing build never wedges the worker."""
    compiles = []

    def make_build(name, fail=False):
        def build():
            compiles.append(name)
            if fail:
                raise ValueError(f"{name} failed")
            return (lambda x: x), (lambda x: x)
        return build

    units = [
        WorkUnit(key=("a",), build=make_build("a")),
        WorkUnit(key=("b",), build=make_build("b", fail=True)),
        WorkUnit(key=("c",), build=make_build("c")),
    ]
    sched = CompileAheadScheduler(units, prefetch=1, pipeline=True)
    sched.start()
    # consume unit a twice (two configs sharing it), then b, then c
    for u in (units[0], units[0], units[1], units[2]):
        sched.get(u)
    sched.close()
    assert compiles == ["a", "b", "c"]  # once each, in order
    assert units[0].error is None and units[0].consumers == 2
    assert isinstance(units[1].error, ValueError)
    assert units[2].error is None


def test_scheduler_serial_mode_compiles_inline():
    built = threading.Event()
    unit = WorkUnit(
        key=("x",),
        build=lambda: (built.set() or ((lambda x: x), (lambda x: x))),
    )
    sched = CompileAheadScheduler([unit], pipeline=False)
    sched.start()  # no thread in serial mode
    assert not built.is_set()
    got = sched.get(unit)
    assert built.is_set() and got.error is None
    sched.close()


def test_payload_cache_lru_and_invalidate():
    class FakeArr:
        def __init__(self, nbytes):
            self.nbytes = nbytes

    cache = PayloadCache(max_bytes=100)
    a = cache.get(("a",), lambda: FakeArr(40))
    assert cache.get(("a",), lambda: FakeArr(999)) is a  # hit, no rebuild
    cache.get(("b",), lambda: FakeArr(40))
    cache.get(("c",), lambda: FakeArr(40))  # evicts LRU ("a")
    assert cache.evictions == 1
    assert cache.get(("a",), lambda: FakeArr(40)) is not a  # rebuilt
    # oversized payloads pass through uncached
    big = cache.get(("big",), lambda: FakeArr(1000))
    assert cache.get(("big",), lambda: FakeArr(1000)) is not big
    # donated entries are dropped so a deleted array is never handed out
    cache.invalidate(("a",))
    fresh = cache.get(("a",), lambda: FakeArr(40))
    assert isinstance(fresh, FakeArr)
    stats = cache.stats()
    assert stats["budget_bytes"] == 100
    assert stats["hits"] >= 1 and stats["misses"] >= 4
