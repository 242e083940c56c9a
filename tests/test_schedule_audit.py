"""Schedule-auditor tests (docs/schedule_audit.md).

Three layers, mirroring the comm-lint convention of test_analysis.py:

- dependency-graph parser units — synthetic HLO text pinning operand /
  control-dep edges, async start/done pairing, while-loop trip-count
  propagation (the scanned-ring undercount bugfix), and conditional
  branch extraction;
- seeded-violation fixtures — a deliberately serialized ring (no
  straddling compute), a divergent-branch collective mismatch, and a
  baseline-diff regression must each fail with exactly the expected
  finding, and their fixed twins must pass clean;
- real lowered targets — the PR-4 ring/bidir collective-matmul targets
  must report ``overlap_efficiency > 0`` with every hop straddled, and
  the `analyze` exit-code contract (0 clean / 1 findings / 2 crash) is
  pinned so the CI diff gate composes with the other smoke stages.
"""

import json
import textwrap

import pytest

from dlbb_tpu.analysis.costmodel import (
    COST_MODEL_VERSION,
    collective_cost_us,
    compute_cost_us,
    get_tier,
)
from dlbb_tpu.analysis.expectations import TargetExpectation, wire_bytes
from dlbb_tpu.analysis.findings import EXIT_CLEAN, EXIT_CRASH, EXIT_FINDINGS
from dlbb_tpu.analysis.hlo_parse import parse_collectives, parse_module
from dlbb_tpu.analysis.schedule_audit import (
    analyze_schedule,
    diff_baselines,
    baseline_path,
    snapshot_baselines,
)

GROUPS8 = "replica_groups={{0,1,2,3,4,5,6,7}}"
RING4 = "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}"


# ---------------------------------------------------------------------------
# dependency-graph parser units
# ---------------------------------------------------------------------------


WHILE_MODULE = textwrap.dedent("""
    HloModule scanned, is_scheduled=true

    %body (p.1: (s32[], f32[64])) -> (s32[], f32[64]) {
      %p.1 = (s32[], f32[64]{0}) parameter(0)
      %gte.0 = s32[] get-tuple-element((s32[], f32[64]{0}) %p.1), index=0
      %gte.1 = f32[64]{0} get-tuple-element((s32[], f32[64]{0}) %p.1), index=1
      %ar = f32[64]{0} all-reduce(f32[64]{0} %gte.1), channel_id=1, """
    + GROUPS8 + """, to_apply=%add
      ROOT %tuple = (s32[], f32[64]{0}) tuple(s32[] %gte.0, f32[64]{0} %ar)
    }

    %cond (p.2: (s32[], f32[64])) -> pred[] {
      %p.2 = (s32[], f32[64]{0}) parameter(0)
      ROOT %lt = pred[] constant(true)
    }

    ENTRY %main (arg: f32[64]) -> f32[64] {
      %arg = f32[64]{0} parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[64]{0}) tuple(s32[] %zero, f32[64]{0} %arg)
      %while = (s32[], f32[64]{0}) while((s32[], f32[64]{0}) %init), \
condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
      ROOT %out = f32[64]{0} get-tuple-element((s32[], f32[64]{0}) %while), index=1
    }
""")


def test_while_body_collectives_carry_trip_count():
    """The scanned-ring undercount bugfix: a collective inside a while
    body executes ``known_trip_count`` times per module invocation, and
    the inventory must charge it that many times — the old line-oriented
    parser counted one iteration of wire volume regardless."""
    module = parse_module(WHILE_MODULE)
    assert module.entry == "main"
    assert module.computations["body"].execution_count == 3
    assert module.computations["main"].execution_count == 1

    (ar,) = parse_collectives(module)
    assert ar.kind == "all-reduce"
    assert ar.computation == "body"
    assert ar.execution_count == 3
    assert ar.result_bytes == 64 * 4

    _, meta = analyze_schedule(
        module, TargetExpectation(), "fixture/while", tier="cpu-sim")
    per_iter = wire_bytes("all-reduce", 64 * 4, 8)
    assert meta["total_wire_bytes"] == 3 * per_iter
    assert meta["collective_kinds"] == {"all-reduce": 3}
    # the while's critical path prices trip_count executions of the body
    tier = get_tier("cpu-sim")
    assert meta["critical_path_us"] >= 3 * collective_cost_us(per_iter, tier)


def test_while_body_wire_counted_in_hlo_audit_total(mesh8):
    """End-to-end pin of the undercount fix on a REAL lowered scan: a
    psum inside a 3-step lax.scan lowers to a while body, and the audit's
    total wire must charge all 3 iterations."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlbb_tpu.analysis.hlo_audit import AuditTarget, audit_target
    from dlbb_tpu.compat import pcast, shard_map

    def build():
        def body(x):
            def step(c, _):
                # psum's result is replicated over "ranks"; the carry came
                # in varying, and check_vma wants the two types to agree
                return pcast(lax.psum(c, "ranks") * 0.125, "ranks",
                             to="varying"), None

            y, _ = lax.scan(step, x, None, length=3)
            return y

        fn = jax.jit(shard_map(
            body, mesh=mesh8, in_specs=(P("ranks"),), out_specs=P("ranks"),
        ))
        x = jax.device_put(
            jnp.ones((8, 32), jnp.float32),
            NamedSharding(mesh8, P("ranks")),
        )
        return fn, (x,)

    findings, meta = audit_target(AuditTarget(
        name="fixture/scanned_psum",
        build=build,
        expectation=TargetExpectation(
            allowed={"all-reduce"}, required_any={"all-reduce"},
            min_required=3,  # 3 loop iterations, execution-weighted
        ),
        min_devices=8,
    ), passes=("hlo", "schedule"))
    assert findings == [], [f.render() for f in findings]
    scanned = [c for c in meta["collectives"] if c["execution_count"] == 3]
    assert scanned, meta["collectives"]
    assert meta["num_collectives"] >= 3
    per_iter = wire_bytes("all-reduce", scanned[0]["result_bytes"], 8)
    assert meta["total_wire_bytes"] >= 3 * per_iter


ASYNC_MODULE = textwrap.dedent("""
    ENTRY %main (p: f32[32,32]) -> f32[256,32] {
      %p = f32[32,32]{1,0} parameter(0)
      %w = f32[32,32]{1,0} parameter(1)
      %ags = (f32[32,32]{1,0}, f32[256,32]{1,0}) all-gather-start(\
f32[32,32]{1,0} %p), channel_id=1, """ + GROUPS8 + """, dimensions={0}
      %dot.in = f32[32,32]{1,0} dot(f32[32,32]{1,0} %p, f32[32,32]{1,0} \
%w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %agd = f32[256,32]{1,0} all-gather-done((f32[32,32]{1,0}, \
f32[256,32]{1,0}) %ags)
      %dot.out = f32[32,32]{1,0} dot(f32[32,32]{1,0} %dot.in, \
f32[32,32]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %out = f32[256,32]{1,0} add(f32[256,32]{1,0} %agd, \
f32[256,32]{1,0} %agd)
    }
""")


def test_async_pair_window_and_payload():
    """Async start/done pairing: the inventory counts the pair once with
    the gathered payload on the start; the overlap window is the
    scheduled span strictly between start and done, so only %dot.in (in
    the window, independent) hides wire time — %dot.out comes after the
    done and hides nothing."""
    module = parse_module(ASYNC_MODULE)
    (ag,) = parse_collectives(module)
    assert ag.kind == "all-gather"
    assert ag.result_bytes == 256 * 32 * 4  # the gathered result array

    _, meta = analyze_schedule(
        module, TargetExpectation(), "fixture/async", tier="cpu-sim")
    (c,) = meta["collectives"]
    assert c["async"] is True
    dot_flops = 2 * 32 * 32 * 32
    assert c["straddling_flops"] == dot_flops  # dot.in only
    tier = get_tier("cpu-sim")
    assert c["hidden_us"] == pytest.approx(
        min(c["cost_us"], compute_cost_us(dot_flops, tier)))


def test_control_dependency_serialises_compute():
    """control-predecessors are dependency edges: a dot forced after the
    permute by a control dep is NOT straddling compute."""
    base = textwrap.dedent("""
        ENTRY %main (p: f32[64,64]) -> f32[64,64] {
          %p = f32[64,64]{1,0} parameter(0)
          %w = f32[64,64]{1,0} parameter(1)
          %cp = f32[64,64]{1,0} collective-permute(f32[64,64]{1,0} %p), \
channel_id=1, """ + RING4 + """
          %dot = f32[64,64]{1,0} dot(f32[64,64]{1,0} %p, f32[64,64]{1,0} \
%w), lhs_contracting_dims={1}, rhs_contracting_dims={0}CTRL
          ROOT %out = f32[64,64]{1,0} add(f32[64,64]{1,0} %cp, \
f32[64,64]{1,0} %dot)
        }
    """)
    free = parse_module(base.replace("CTRL", ""))
    _, meta = analyze_schedule(
        free, TargetExpectation(), "fixture/ctrl", tier="cpu-sim")
    assert meta["collectives"][0]["straddling_flops"] > 0

    pinned = parse_module(
        base.replace("CTRL", ", control-predecessors={%cp}"))
    instr = pinned.computations["main"].by_name()["dot"]
    assert instr.control_deps == ("cp",)
    _, meta = analyze_schedule(
        pinned, TargetExpectation(), "fixture/ctrl", tier="cpu-sim")
    assert meta["collectives"][0]["straddling_flops"] == 0


# ---------------------------------------------------------------------------
# seeded violation: deliberately serialized ring
# ---------------------------------------------------------------------------


SERIALIZED_RING = textwrap.dedent("""
    ENTRY %main (p: f32[128,128]) -> f32[128,128] {
      %p = f32[128,128]{1,0} parameter(0)
      %w = f32[128,128]{1,0} parameter(1)
      %cp.1 = f32[128,128]{1,0} collective-permute(f32[128,128]{1,0} %p), \
channel_id=1, """ + RING4 + """
      %dot.1 = f32[128,128]{1,0} dot(f32[128,128]{1,0} %cp.1, \
f32[128,128]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %cp.2 = f32[128,128]{1,0} collective-permute(f32[128,128]{1,0} \
%dot.1), channel_id=2, """ + RING4 + """
      ROOT %dot.2 = f32[128,128]{1,0} dot(f32[128,128]{1,0} %cp.2, \
f32[128,128]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
    }
""")

OVERLAPPED_RING = textwrap.dedent("""
    ENTRY %main (p: f32[128,128]) -> f32[128,128] {
      %p = f32[128,128]{1,0} parameter(0)
      %w = f32[128,128]{1,0} parameter(1)
      %cp.1 = f32[128,128]{1,0} collective-permute(f32[128,128]{1,0} %p), \
channel_id=1, """ + RING4 + """
      %dot.1 = f32[128,128]{1,0} dot(f32[128,128]{1,0} %p, \
f32[128,128]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %cp.2 = f32[128,128]{1,0} collective-permute(f32[128,128]{1,0} \
%cp.1), channel_id=2, """ + RING4 + """
      %dot.2 = f32[128,128]{1,0} dot(f32[128,128]{1,0} %cp.1, \
f32[128,128]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %add = f32[128,128]{1,0} add(f32[128,128]{1,0} %dot.1, \
f32[128,128]{1,0} %dot.2)
    }
""")


@pytest.mark.schedule_smoke
def test_serialized_ring_yields_finding():
    """Every hop of the serialized fixture is an ancestor/descendant of
    every dot — zero straddling compute, one finding per hop."""
    exp = TargetExpectation(expect_overlap=True)
    findings, meta = analyze_schedule(
        SERIALIZED_RING, exp, "fixture/serialized_ring", tier="cpu-sim")
    assert [f.rule for f in findings] == ["serialized-collective"] * 2
    assert all(f.severity == "error" for f in findings)
    assert meta["overlap_efficiency"] == 0.0
    assert meta["ring_hops"] == {"total": 2, "straddled": 0}
    # the whole comm time sits on the critical path
    assert meta["comm_on_critical_path_us"] == pytest.approx(
        meta["comm_total_us"])
    json.dumps([f.to_dict() for f in findings])


@pytest.mark.schedule_smoke
def test_overlapped_ring_twin_is_clean():
    """The fixed twin — same hops, dots independent of the chunk in
    flight — passes with every hop straddled and efficiency > 0."""
    exp = TargetExpectation(expect_overlap=True)
    findings, meta = analyze_schedule(
        OVERLAPPED_RING, exp, "fixture/overlapped_ring", tier="cpu-sim")
    assert findings == [], [f.render() for f in findings]
    assert meta["ring_hops"] == {"total": 2, "straddled": 2}
    assert meta["overlap_efficiency"] > 0
    # without the overlap claim the same module yields no findings either
    assert analyze_schedule(
        SERIALIZED_RING, TargetExpectation(), "fixture/no_claim",
        tier="cpu-sim",
    )[0] == []


def test_real_serialized_ring_target(mesh8):
    """A REAL lowered serialized ring: matmul feeding each hop (the
    anti-pattern the decomposition exists to avoid) — the auditor must
    refuse it even though the permute-count contract would pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlbb_tpu.analysis.hlo_audit import AuditTarget, audit_target
    from dlbb_tpu.compat import shard_map

    fwd = [(i, (i + 1) % 8) for i in range(8)]

    def build():
        def body(x, w):
            cur = x
            for _ in range(4):
                cur = lax.ppermute(cur, "ranks", fwd)
                cur = cur @ w  # every dot consumes the chunk in flight
            return cur

        fn = jax.jit(shard_map(
            body, mesh=mesh8,
            in_specs=(P("ranks"), P(None, None)),
            out_specs=P("ranks"),
        ))
        sharding = NamedSharding(mesh8, P("ranks"))
        x = jax.device_put(jnp.ones((8, 64), jnp.float32), sharding)
        w = jax.device_put(
            jnp.ones((64, 64), jnp.float32),
            NamedSharding(mesh8, P(None, None)),
        )
        return fn, (x, w)

    findings, meta = audit_target(AuditTarget(
        name="fixture/serialized_real_ring",
        build=build,
        expectation=TargetExpectation(
            allowed={"collective-permute"},
            required_any={"collective-permute"},
            min_required=4,
            expect_overlap=True,
        ),
        min_devices=8,
    ), passes=("hlo", "schedule"))
    rules = {f.rule for f in findings}
    assert rules == {"serialized-collective"}, [f.render() for f in findings]
    assert meta["schedule"]["overlap_efficiency"] == 0.0


def test_ring_collective_matmul_targets_overlap_clean(devices):
    """The PR-4 acceptance gate: the ring/bidir micro-op targets must
    report overlap_efficiency > 0 with EVERY hop straddled by a matmul,
    and the hops must be the ring_hop-named permutes (the naming hook in
    parallel/collective_matmul.py)."""
    from dlbb_tpu.analysis.hlo_audit import (
        _collective_matmul_target,
        audit_target,
    )

    for op in ("ag_matmul", "matmul_rs"):
        for schedule in ("ring", "bidir"):
            target = _collective_matmul_target(op, schedule)
            findings, meta = audit_target(
                target, passes=("hlo", "schedule"))
            assert findings == [], (op, schedule,
                                    [f.render() for f in findings])
            s = meta["schedule"]
            assert s["overlap_efficiency"] > 0, (op, schedule)
            assert s["ring_hops"]["total"] >= 7, (op, schedule)
            assert (s["ring_hops"]["straddled"]
                    == s["ring_hops"]["total"]), (op, schedule)
            named = [c for c in s["collectives"] if c["is_ring_hop"]]
            assert len(named) == s["ring_hops"]["total"]


def test_fused_target_reports_zero_overlap(devices):
    """The fused schedule is the serialized baseline: efficiency 0 — and
    no finding, because its expectation makes no overlap claim."""
    from dlbb_tpu.analysis.hlo_audit import (
        _collective_matmul_target,
        audit_target,
    )

    findings, meta = audit_target(
        _collective_matmul_target("ag_matmul", "fused"),
        passes=("schedule",))
    assert findings == []
    assert meta["schedule"]["overlap_efficiency"] == 0.0


# ---------------------------------------------------------------------------
# seeded violation: divergent-branch collective mismatch
# ---------------------------------------------------------------------------


def _conditional_module(true_body: str, false_body: str) -> str:
    return textwrap.dedent("""
        %branch_true (bt: f32[64]) -> f32[64] {
          %bt = f32[64]{0} parameter(0)
          TRUE_BODY
        }

        %branch_false (bf: f32[64]) -> f32[64] {
          %bf = f32[64]{0} parameter(0)
          FALSE_BODY
        }

        ENTRY %main (pr: pred[], x: f32[64]) -> f32[64] {
          %pr = pred[] parameter(0)
          %x = f32[64]{0} parameter(1)
          ROOT %cond = f32[64]{0} conditional(pred[] %pr, f32[64]{0} %x, \
f32[64]{0} %x), true_computation=%branch_true, \
false_computation=%branch_false
        }
    """).replace("TRUE_BODY", true_body).replace("FALSE_BODY", false_body)


_AR_TRUE = ("ROOT %ar.t = f32[64]{0} all-reduce(f32[64]{0} %bt), "
            "channel_id=1, " + GROUPS8 + ", to_apply=%add")
_AR_FALSE = ("ROOT %ar.f = f32[64]{0} all-reduce(f32[64]{0} %bf), "
             "channel_id=2, " + GROUPS8 + ", to_apply=%add")


@pytest.mark.schedule_smoke
def test_divergent_branch_collectives_yield_finding():
    """Branches posting different collective sequences (all-reduce vs
    all-gather) are the classic cross-shard deadlock on pods."""
    diverged = _conditional_module(
        _AR_TRUE,
        "ROOT %ag.f = f32[64]{0} all-gather(f32[8]{0} %bf), channel_id=2, "
        + GROUPS8 + ", dimensions={0}",
    )
    findings, _ = analyze_schedule(
        diverged, TargetExpectation(), "fixture/divergent", tier="cpu-sim")
    assert [f.rule for f in findings] == ["divergent-branch-collectives"]
    assert findings[0].severity == "error"
    assert "deadlock" in findings[0].message
    branches = findings[0].details["branches"]
    assert set(branches) == {"branch_true", "branch_false"}


@pytest.mark.schedule_smoke
def test_matching_branch_collectives_are_clean():
    """Same kind + replica groups on both branches: no finding (the
    channel id may differ — it is not part of the posted signature), and
    the inventory charges exactly ONE branch per invocation (only one
    executes — charging both would double the wire totals)."""
    matching = _conditional_module(_AR_TRUE, _AR_FALSE)
    findings, meta = analyze_schedule(
        matching, TargetExpectation(), "fixture/matching", tier="cpu-sim")
    assert findings == [], [f.render() for f in findings]
    assert meta["collective_kinds"] == {"all-reduce": 1}
    assert meta["num_collectives"] == 1
    assert meta["total_wire_bytes"] == wire_bytes("all-reduce", 64 * 4, 8)


def test_divergent_replica_groups_yield_finding():
    """Same kind but different replica groups diverges too — the shards
    would post mismatched groups and hang just the same."""
    diverged = _conditional_module(
        _AR_TRUE,
        "ROOT %ar.f = f32[64]{0} all-reduce(f32[64]{0} %bf), channel_id=2, "
        "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add",
    )
    findings, _ = analyze_schedule(
        diverged, TargetExpectation(), "fixture/groups", tier="cpu-sim")
    assert [f.rule for f in findings] == ["divergent-branch-collectives"]


# ---------------------------------------------------------------------------
# seeded violation: baseline-diff regression
# ---------------------------------------------------------------------------


def _schedule_meta(**overrides):
    meta = {
        "cost_model_version": COST_MODEL_VERSION,
        "tier": "cpu-sim",
        "critical_path_us": 10.0,
        "comm_on_critical_path_us": 4.0,
        "comm_total_us": 5.0,
        "compute_total_us": 6.0,
        "overlap_efficiency": 0.8,
        "total_wire_bytes": 4096,
        "num_collectives": 7,
        "collective_kinds": {"collective-permute": 7},
    }
    meta.update(overrides)
    return meta


@pytest.mark.schedule_smoke
def test_baseline_snapshot_and_clean_diff(tmp_path):
    metas = {"t/one": _schedule_meta(), "t/two": _schedule_meta()}
    written = snapshot_baselines(metas, tmp_path)
    assert len(written) == 2
    assert baseline_path(tmp_path, "t/one").exists()
    data = json.loads(baseline_path(tmp_path, "t/one").read_text())
    assert data["target"] == "t/one"
    assert data["cost_model_version"] == COST_MODEL_VERSION
    assert diff_baselines(metas, tmp_path) == []
    # a snapshot on a smaller host must NOT prune baselines of targets it
    # merely skipped for lack of devices...
    snapshot_baselines({"t/one": _schedule_meta()}, tmp_path,
                       skipped_targets=("t/two",))
    assert baseline_path(tmp_path, "t/two").exists()
    # ...but a re-snapshot does prune baselines for removed targets
    snapshot_baselines({"t/one": _schedule_meta()}, tmp_path)
    assert not baseline_path(tmp_path, "t/two").exists()


@pytest.mark.schedule_smoke
def test_baseline_diff_regressions(tmp_path):
    """The three gated regressions: >10% critical-path growth, any new
    collective kind, >10% wire growth — each exactly one error finding;
    growth under the gate passes."""
    snapshot_baselines({"t": _schedule_meta()}, tmp_path)

    ok = diff_baselines(
        {"t": _schedule_meta(critical_path_us=10.9)}, tmp_path)
    assert ok == [], [f.render() for f in ok]

    cp = diff_baselines(
        {"t": _schedule_meta(critical_path_us=11.2)}, tmp_path)
    assert [f.rule for f in cp] == ["critical-path-regression"]
    assert cp[0].details["ratio"] == pytest.approx(1.12)

    kinds = diff_baselines({"t": _schedule_meta(
        collective_kinds={"collective-permute": 7, "all-gather": 1},
    )}, tmp_path)
    assert [f.rule for f in kinds] == ["new-collective-kind"]
    assert kinds[0].details["new_kinds"] == ["all-gather"]

    wire = diff_baselines(
        {"t": _schedule_meta(total_wire_bytes=8192)}, tmp_path)
    assert [f.rule for f in wire] == ["wire-volume-regression"]


def test_baseline_diff_bookkeeping(tmp_path):
    """missing-baseline (new target / empty dir) and cost-model skew are
    errors; a stale baseline and a big improvement are warnings only."""
    empty = tmp_path / "empty"
    (finding,) = diff_baselines({"t": _schedule_meta()}, empty)
    assert finding.rule == "missing-baseline"
    assert finding.severity == "error"

    snapshot_baselines({"t": _schedule_meta()}, tmp_path)
    new = diff_baselines(
        {"t": _schedule_meta(), "t/new": _schedule_meta()}, tmp_path)
    assert [f.rule for f in new] == ["missing-baseline"]

    skew = diff_baselines(
        {"t": _schedule_meta(cost_model_version="cm999")}, tmp_path)
    assert [f.rule for f in skew] == ["cost-model-mismatch"]

    stale = diff_baselines({}, tmp_path)
    assert [(f.rule, f.severity) for f in stale] == [
        ("stale-baseline", "warning")]
    # ...but not when the target was merely skipped for lack of devices
    assert diff_baselines({}, tmp_path, skipped_targets=("t",)) == []

    improved = diff_baselines(
        {"t": _schedule_meta(critical_path_us=2.0)}, tmp_path)
    assert [(f.rule, f.severity) for f in improved] == [
        ("baseline-improved", "warning")]


# ---------------------------------------------------------------------------
# exit-code contract (0 clean / 1 findings / 2 crash)
# ---------------------------------------------------------------------------


@pytest.mark.schedule_smoke
def test_analyze_exit_code_contract(tmp_path, monkeypatch):
    """Pinned so the CI diff gate composes with the chaos and compression
    smoke stages: 0 = clean, 1 = findings, 2 = analyzer crash."""
    from pathlib import Path

    from dlbb_tpu import analysis

    repo_root = Path(__file__).resolve().parents[1]
    assert (EXIT_CLEAN, EXIT_FINDINGS, EXIT_CRASH) == (0, 1, 2)
    assert analysis.run_analysis(
        which="lint", root=str(repo_root), verbose=False) == EXIT_CLEAN
    # findings -> 1 (vacuous lint root is itself a finding, fail-closed)
    assert analysis.run_analysis(
        which="lint", root=str(tmp_path), verbose=False) == EXIT_FINDINGS
    # analyzer crash -> 2, never an unhandled traceback with code 1
    monkeypatch.setattr(
        "dlbb_tpu.analysis.run_source_lint",
        lambda **kw: (_ for _ in ()).throw(RuntimeError("boom")))
    assert analysis.run_analysis(
        which="lint", root=str(repo_root), verbose=False) == EXIT_CRASH


def test_cost_model_table_pins():
    """The versioned table: the committed-baseline tier exists in the
    current version, and pricing is monotone in bytes/FLOPs (the property
    the regression gate leans on)."""
    tier = get_tier("cpu-sim")
    assert get_tier(None).name == tier.name  # default tier
    assert collective_cost_us(0, tier) == pytest.approx(tier.alpha_us)
    assert (collective_cost_us(1 << 20, tier)
            > collective_cost_us(1 << 10, tier))
    assert compute_cost_us(2_000_000, tier) > compute_cost_us(1_000, tier)
    with pytest.raises(KeyError):
        get_tier("no-such-tier")
    with pytest.raises(KeyError):
        get_tier("cpu-sim", version="no-such-version")
