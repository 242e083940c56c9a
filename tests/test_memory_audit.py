"""Static memory auditor (buffer liveness / peak HBM) tests.

Three layers, mirroring docs/memory_audit.md:

- ``hlo_parse`` buffer-size edge cases the liveness pass depends on:
  tuple-shaped outputs, bitcast (zero-cost alias), zero-sized buffers,
  while-carried tuples, and the ``input_output_alias`` donation table —
  pinned on synthetic HLO plus one real ``lax.scan`` lowering.
- the liveness analysis itself: peak/live-set computation, donation
  accounting, nested-computation composition (while / conditional /
  fusion), and every memory rule on seeded-violation fixtures.
- the gate integration: real serving/train targets prove their donated
  buffers aliased and the analytic cache formula pinned to the compiled
  carry; the baseline diff fails on the memory axis alone; the
  ``analyze memory --output`` observability surface (manifest +
  ``analysis_peak_live_bytes`` gauges) round-trips.

The ``memory_smoke`` marker subset is also invoked standalone by
``scripts/run_static_analysis.sh``.
"""

import json

import pytest

from dlbb_tpu.analysis.costmodel import get_tier
from dlbb_tpu.analysis.expectations import TargetExpectation
from dlbb_tpu.analysis.findings import EXIT_FINDINGS
from dlbb_tpu.analysis.hlo_parse import (
    BufferAlias,
    parse_alias_table,
    parse_module,
)
from dlbb_tpu.analysis.memory_audit import (
    REPLICATED_FLOOR_BYTES,
    analyze_memory,
    memory_metrics,
    write_memory_artifacts,
)

# ---------------------------------------------------------------------------
# hlo_parse edge cases (the buffer-size substrate)
# ---------------------------------------------------------------------------


def test_parse_tuple_shaped_output_bytes():
    """A tuple result's bytes sum its elements; get-tuple-element keeps
    per-element types."""
    hlo = """
ENTRY %main (p: f32[8]) -> (f32[8], s32[]) {
  %p = f32[8]{0} parameter(0)
  %i = s32[] constant(3)
  ROOT %t = (f32[8]{0}, s32[]) tuple(f32[8]{0} %p, s32[] %i)
}
"""
    mod = parse_module(hlo)
    t = mod.entry_computation().by_name()["t"]
    assert t.arrays == [("f32", (8,)), ("s32", ())]
    assert t.result_bytes == 8 * 4 + 4


def test_parse_zero_sized_buffer():
    hlo = "%z = f32[0,128]{1,0} parameter(0)"
    mod = parse_module(hlo)
    (comp, instr), = mod.all_instructions()
    assert instr.shape == (0, 128)
    assert instr.result_bytes == 0


def test_parse_parameter_number():
    hlo = """
ENTRY %main (a: f32[4], b: f32[8]) -> f32[8] {
  %a = f32[4]{0} parameter(0)
  ROOT %b = f32[8]{0} parameter(1)
}
"""
    by_name = parse_module(hlo).entry_computation().by_name()
    assert by_name["a"].parameter_number == 0
    assert by_name["b"].parameter_number == 1


def test_parse_alias_table_entries():
    header = ("HloModule jit_step, is_scheduled=true, "
              "input_output_alias={ {0}: (0, {}, may-alias), "
              "{1,0}: (2, {1}, must-alias) }, "
              "entry_computation_layout={(f32[4]{0})->f32[4]{0}}")
    table = parse_alias_table(header)
    assert table == [
        BufferAlias(output_index=(0,), parameter_number=0),
        BufferAlias(output_index=(1, 0), parameter_number=2,
                    parameter_index=(1,)),
    ]
    assert parse_alias_table("HloModule plain, is_scheduled=true") == []


def test_parse_module_carries_alias_table():
    hlo = ("HloModule m, input_output_alias={ {}: (0, {}, may-alias) }\n"
           "ENTRY %main (p: f32[4]) -> f32[4] {\n"
           "  %p = f32[4]{0} parameter(0)\n"
           "  ROOT %n = f32[4]{0} negate(f32[4]{0} %p)\n"
           "}\n")
    mod = parse_module(hlo)
    assert mod.input_output_alias == [
        BufferAlias(output_index=(), parameter_number=0)
    ]


# ---------------------------------------------------------------------------
# liveness analysis units
# ---------------------------------------------------------------------------

CHAIN_HLO = """
HloModule chain, is_scheduled=true
ENTRY %main (p: f32[100]) -> f32[100] {
  %p = f32[100]{0} parameter(0)
  %a = f32[100]{0} negate(f32[100]{0} %p)
  %b = f32[100]{0} exponential(f32[100]{0} %a)
  ROOT %c = f32[100]{0} add(f32[100]{0} %a, f32[100]{0} %b)
}
"""


def test_liveness_chain_peak():
    """At the root instant: param (live whole run) + a (still consumed
    by c) + b + the output buffer = 4 x 400 B."""
    findings, meta = analyze_memory(CHAIN_HLO, TargetExpectation(), "t")
    assert findings == []
    assert meta["peak_live_bytes"] == 1600
    assert meta["peak_instruction"] == "c"
    assert {x["name"] for x in meta["live_at_peak"]} == {"p", "a", "b", "c"}
    assert meta["parameter_bytes"] == 400
    assert meta["output_bytes"] == 400


def test_liveness_dead_buffer_freed():
    """A buffer whose last consumer has executed stops counting: b dies
    before d runs, so the peak instant holds a+b (+p), not a+b+c+d."""
    hlo = """
HloModule t, is_scheduled=true
ENTRY %main (p: f32[100]) -> f32[100] {
  %p = f32[100]{0} parameter(0)
  %a = f32[100]{0} negate(f32[100]{0} %p)
  %b = f32[100]{0} exponential(f32[100]{0} %a)
  %c = f32[100]{0} add(f32[100]{0} %b, f32[100]{0} %b)
  ROOT %d = f32[100]{0} negate(f32[100]{0} %c)
}
"""
    _, meta = analyze_memory(hlo, TargetExpectation(), "t")
    # 400 (p) + the widest instant: a+b at b / b+c at c / c+d at d = 800
    assert meta["peak_live_bytes"] == 1200


def test_liveness_bitcast_is_zero_cost_alias():
    """bitcast charges nothing and keeps its SOURCE alive through the
    bitcast's consumers."""
    hlo = """
HloModule t, is_scheduled=true
ENTRY %main (p: f32[100]) -> f32[100] {
  %p = f32[100]{0} parameter(0)
  %a = f32[100]{0} negate(f32[100]{0} %p)
  %v = f32[4,25]{1,0} bitcast(f32[100]{0} %a)
  %w = f32[4,25]{1,0} negate(f32[4,25]{1,0} %v)
  ROOT %c = f32[100]{0} bitcast(f32[4,25]{1,0} %w)
}
"""
    _, meta = analyze_memory(hlo, TargetExpectation(), "t")
    # p + a (kept alive through v) + w; the two bitcasts add nothing
    assert meta["peak_live_bytes"] == 1200
    names = {x["name"] for x in meta["live_at_peak"]}
    assert "v" not in names and "c" not in names


def test_liveness_while_carried_tuple():
    """While bodies charge their internal peak (params excluded — they
    alias the carry) at the call instant; the body root is the new carry
    double-buffering against the old one."""
    hlo = """
HloModule t, is_scheduled=true

%body (bp: (f32[256], s32[])) -> (f32[256], s32[]) {
  %bp = (f32[256]{0}, s32[]) parameter(0)
  %x = f32[256]{0} get-tuple-element((f32[256]{0}, s32[]) %bp), index=0
  %i = s32[] get-tuple-element((f32[256]{0}, s32[]) %bp), index=1
  %t = f32[2,256]{1,0} broadcast(f32[256]{0} %x), dimensions={1}
  %y = f32[256]{0} slice(f32[2,256]{1,0} %t), slice={[0:1], [0:256]}
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  ROOT %out = (f32[256]{0}, s32[]) tuple(f32[256]{0} %y, s32[] %i2)
}

%cond (cp: (f32[256], s32[])) -> pred[] {
  %cp = (f32[256]{0}, s32[]) parameter(0)
  %ci = s32[] get-tuple-element((f32[256]{0}, s32[]) %cp), index=1
  %lim = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %ci, s32[] %lim), direction=LT
}

ENTRY %main (p: f32[256]) -> f32[256] {
  %p = f32[256]{0} parameter(0)
  %zero = s32[] constant(0)
  %tup = (f32[256]{0}, s32[]) tuple(f32[256]{0} %p, s32[] %zero)
  %w = (f32[256]{0}, s32[]) while((f32[256]{0}, s32[]) %tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %res = f32[256]{0} get-tuple-element((f32[256]{0}, s32[]) %w), index=0
}
"""
    _, meta = analyze_memory(hlo, TargetExpectation(), "t")
    # at the while instant: p (1024, the carry, live as operand AND as
    # the loop result consumed by res) + body extra: t (2048) + y (the
    # new carry, 1024) + scalars — and NO phantom copy of the carry for
    # the while's own result (it reuses the carry buffers in place)
    assert 4096 <= meta["peak_live_bytes"] <= 4200
    assert meta["peak_instruction"] == "w"
    # the body's big transient is visible in the cross-computation table
    top = meta["top_transients"][0]
    assert top["name"] == "t" and top["computation"] == "body"
    assert top["execution_count"] == 4
    assert meta["max_transient_bytes"] == 2048


def test_liveness_conditional_takes_max_branch():
    hlo = """
HloModule t, is_scheduled=true

%small (sp: f32[16]) -> f32[16] {
  %sp = f32[16]{0} parameter(0)
  %sm = f32[16]{0} negate(f32[16]{0} %sp)
  ROOT %sr = f32[16]{0} add(f32[16]{0} %sm, f32[16]{0} %sm)
}

%big (bp: f32[16]) -> f32[16] {
  %bp = f32[16]{0} parameter(0)
  %fat = f32[64,16]{1,0} broadcast(f32[16]{0} %bp), dimensions={1}
  %red = f32[16]{0} slice(f32[64,16]{1,0} %fat), slice={[0:1], [0:16]}
  ROOT %br = f32[16]{0} negate(f32[16]{0} %red)
}

ENTRY %main (p: f32[16], q: pred[]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  %q = pred[] parameter(1)
  ROOT %c = f32[16]{0} conditional(pred[] %q, f32[16]{0} %p, f32[16]{0} %p), true_computation=%big, false_computation=%small
}
"""
    _, meta = analyze_memory(hlo, TargetExpectation(), "t")
    # p (64) + q (1) + worst-branch internal peak: fat (4096) + red (64)
    # both live at red's instant — never the small branch's 192 B
    assert meta["peak_live_bytes"] == 65 + 4096 + 64
    assert meta["max_transient_bytes"] == 4096


def test_liveness_fusion_charges_root_only():
    """Fused intermediates never materialise: the fusion instruction's
    own result is the only charge."""
    hlo = """
HloModule t, is_scheduled=true

%fused (fp: f32[32]) -> f32[32] {
  %fp = f32[32]{0} parameter(0)
  %fa = f32[32]{0} negate(f32[32]{0} %fp)
  %fb = f32[32]{0} exponential(f32[32]{0} %fa)
  ROOT %fc = f32[32]{0} add(f32[32]{0} %fb, f32[32]{0} %fa)
}

ENTRY %main (p: f32[32]) -> f32[32] {
  %p = f32[32]{0} parameter(0)
  ROOT %f = f32[32]{0} fusion(f32[32]{0} %p), kind=kLoop, calls=%fused
}
"""
    _, meta = analyze_memory(hlo, TargetExpectation(), "t")
    assert meta["peak_live_bytes"] == 128 + 128  # p + the fusion result
    assert meta["max_transient_bytes"] == 0
    assert all(t["computation"] != "fused" for t in meta["top_transients"])


DONATED_HLO = """
HloModule t, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }
ENTRY %main (state: f32[512], x: f32[512]) -> (f32[512], f32[]) {
  %state = f32[512]{0} parameter(0)
  %x = f32[512]{0} parameter(1)
  %new = f32[512]{0} add(f32[512]{0} %state, f32[512]{0} %x)
  %loss = f32[] constant(0)
  ROOT %out = (f32[512]{0}, f32[]) tuple(f32[512]{0} %new, f32[] %loss)
}
"""


def test_donation_single_counts_the_carry():
    """The donated param stays resident to program end; the output
    element reusing its region is charged zero — 2048 (state) + 2048 (x)
    + the scalar, never 3 x 2048."""
    findings, meta = analyze_memory(
        DONATED_HLO, TargetExpectation(expect_donation=True), "t",
        lowered_text="{jax.buffer_donor = true}")
    assert findings == []
    assert meta["peak_live_bytes"] == 2048 + 2048 + 4
    assert meta["donated_param_bytes"] == 2048
    donated = {p["name"]: p for p in meta["donated_params"]}
    assert donated["state"]["aliased"] is True
    assert donated["x"]["aliased"] is False


def test_unaliased_donation_fires():
    """Donor markers in the lowered module but no compiled alias table =
    XLA silently dropped the donation."""
    undonated = DONATED_HLO.replace(
        ", input_output_alias={ {0}: (0, {}, may-alias) }", "")
    findings, meta = analyze_memory(
        undonated, TargetExpectation(expect_donation=True), "t",
        lowered_text="{jax.buffer_donor = true}")
    assert [f.rule for f in findings] == ["unaliased-donation"]
    assert findings[0].severity == "error"
    # and the carry is now double-resident
    assert meta["peak_live_bytes"] == 2048 + 2048 + 2048 + 4


def test_peak_memory_ceiling_fires():
    findings, _ = analyze_memory(
        CHAIN_HLO, TargetExpectation(max_peak_bytes=1000), "t")
    assert [f.rule for f in findings] == ["peak-memory-ceiling"]
    d = findings[0].details
    assert d["peak_live_bytes"] == 1600 and d["max_peak_bytes"] == 1000


def _replicated_hlo(elems: int = 131072) -> str:
    return f"""
HloModule t, is_scheduled=true
ENTRY %main (p: f32[{elems}]) -> f32[{elems}] {{
  %p = f32[{elems}]{{0}} parameter(0)
  %fat = f32[8,{elems}]{{1,0}} broadcast(f32[{elems}]{{0}} %p), dimensions={{1}}
  %s = f32[1,{elems}]{{1,0}} slice(f32[8,{elems}]{{1,0}} %fat), slice={{[0:1], [0:{elems}]}}
  ROOT %r = f32[{elems}]{{0}} reshape(f32[1,{elems}]{{1,0}} %s)
}}
"""


def test_transient_replicated_buffer_fires():
    findings, meta = analyze_memory(
        _replicated_hlo(), TargetExpectation(), "t", num_devices=8)
    assert [f.rule for f in findings] == ["transient-replicated-buffer"]
    assert findings[0].details["name"] == "fat"
    assert findings[0].details["num_devices"] == 8


def test_transient_replicated_buffer_exemptions():
    # single device: replication is meaningless
    f1, _ = analyze_memory(_replicated_hlo(), TargetExpectation(), "t",
                           num_devices=1)
    # under the floor: KB-scale broadcasts are everywhere and harmless
    small = _replicated_hlo(elems=1024)
    f2, _ = analyze_memory(small, TargetExpectation(), "t", num_devices=8)
    assert f1 == [] and f2 == []
    assert 1024 * 4 * 8 < REPLICATED_FLOOR_BYTES
    # a collective producing P x its operand is doing its job (the wire
    # auditor prices it) — an all-gather result is exempt
    gathered = _replicated_hlo().replace(
        "broadcast(f32[131072]{0} %p), dimensions={1}",
        "all-gather(f32[131072]{0} %p), replica_groups={{0,1,2,3,4,5,6,7}}"
        ", dimensions={0}")
    f3, _ = analyze_memory(gathered, TargetExpectation(), "t",
                           num_devices=8)
    assert [f.rule for f in f3] == []


def test_serving_cache_drift_fires():
    findings, meta = analyze_memory(
        DONATED_HLO,
        TargetExpectation(donated_bytes_expected=4096,
                          donated_bytes_tolerance=0.10),
        "t", lowered_text="{jax.buffer_donor = true}")
    assert [f.rule for f in findings] == ["serving-cache-drift"]
    assert findings[0].details["donated_param_bytes"] == 2048
    # within tolerance: clean
    ok, _ = analyze_memory(
        DONATED_HLO,
        TargetExpectation(donated_bytes_expected=2000,
                          donated_bytes_tolerance=0.10),
        "t", lowered_text="{jax.buffer_donor = true}")
    assert ok == []


def test_hbm_headroom_and_infeasible_warning():
    tier = get_tier("cpu-sim")
    _, meta = analyze_memory(CHAIN_HLO, TargetExpectation(), "t",
                             tier=tier)
    assert meta["hbm_bytes"] == int(tier.hbm_bytes)
    assert meta["hbm_headroom_bytes"] == int(tier.hbm_bytes) - 1600
    assert meta["feasible"] is True
    from dataclasses import replace

    tiny_tier = replace(tier, hbm_bytes=1024.0)
    findings, meta2 = analyze_memory(CHAIN_HLO, TargetExpectation(), "t",
                                     tier=tiny_tier)
    assert meta2["feasible"] is False
    assert [f.rule for f in findings] == ["hbm-infeasible"]
    assert findings[0].severity == "warning"


# ---------------------------------------------------------------------------
# real lowerings (the lax.scan pin + the serving/train donation proof)
# ---------------------------------------------------------------------------


def test_real_lax_scan_lowering(devices):
    """The liveness pass on a real donated lax.scan program: alias table
    parsed, donated carry aliased, scan while-body analysed without
    double-charging the carry."""
    import jax
    import jax.numpy as jnp

    def step(state, xs):
        def body(c, x):
            return c + jnp.dot(x, x.T).sum(), c
        return jax.lax.scan(body, state, xs)

    jitted = jax.jit(step, donate_argnums=(0,))
    state = jnp.zeros((), jnp.float32)
    xs = jnp.ones((8, 16, 16), jnp.float32)
    lowered = jitted.lower(state, xs)
    module = parse_module(lowered.compile().as_text())
    assert any(a.parameter_number == 0
               for a in module.input_output_alias)
    findings, meta = analyze_memory(
        module, TargetExpectation(expect_donation=True), "scan",
        lowered_text=lowered.as_text())
    assert findings == []
    # xs (8*16*16*4 = 8192) dominates; the while machinery must stay a
    # small constant over it, far under a per-trip duplication (8x)
    assert 8192 < meta["peak_live_bytes"] < 3 * 8192
    assert any(p["aliased"] for p in meta["donated_params"])


@pytest.mark.memory_smoke
def test_decode_step_cache_crosscheck(devices):
    """The acceptance pin: the decode-step target audits clean, its
    donated cache carry is aliased in the liveness report, and the
    analytic kv_cache_bytes_per_device agrees with the compiled donated
    bytes within the documented tolerance."""
    from dlbb_tpu.analysis.hlo_audit import (
        _decode_step_target,
        _serve_cache_bytes_per_device,
        audit_target,
    )

    target = _decode_step_target()
    findings, meta = audit_target(target, passes=("memory",),
                                  tier=get_tier("cpu-sim"))
    assert findings == [], [f.render() for f in findings]
    mem = meta["memory"]
    analytic = _serve_cache_bytes_per_device(2, 4)
    assert mem["analytic_donated_bytes"] == analytic
    donated = mem["donated_param_bytes"]
    tol = target.expectation.donated_bytes_tolerance
    assert abs(donated - analytic) <= tol * analytic
    assert donated >= mem["peak_live_bytes"] * 0.1  # cache is material
    aliased = [p for p in mem["donated_params"] if p["aliased"]]
    assert aliased, "decode carry must be aliased (donated)"
    assert mem["feasible"] is True


@pytest.mark.memory_smoke
def test_train_step_donation_proof(devices):
    """A donating train step shows its state aliased; the SAME program
    jitted without donation trips unaliased-donation, the seeded
    violation the CI stage pins (exit 1), and holds more at its peak.
    How much more is the lowering's: XLA:CPU of jaxlib 0.9.0 computes
    the donated step's new state into temporaries, so the undonated
    one peaks 8 % higher, not by the whole state, and stays under the
    ceiling (ROADMAP.md, Queue 3 item 6)."""
    import jax
    import optax

    from dlbb_tpu import analysis
    from dlbb_tpu.analysis.hlo_audit import (
        AuditTarget,
        _train_step_target,
        audit_target,
    )

    target = _train_step_target(zero_stage=0)
    findings, meta = audit_target(target, passes=("memory",))
    assert findings == [], [f.render() for f in findings]
    mem = meta["memory"]
    assert mem["donated_param_bytes"] > 0
    assert any(p["aliased"] for p in mem["donated_params"])

    # seeded violation: strip the donation (wrap the donating jit in an
    # outer donation-free jit): nothing is aliased, the donation rule fires
    def undonated_build():
        jit_step, args = target.build()
        return jax.jit(lambda *a: jit_step(*a)), args

    bad = AuditTarget(
        name=target.name, build=undonated_build,
        expectation=target.expectation, min_devices=target.min_devices,
    )
    bad_findings, bad_meta = audit_target(bad, passes=("memory",))
    rules = {f.rule for f in bad_findings}
    assert "unaliased-donation" in rules
    assert bad_meta["memory"]["donated_param_bytes"] == 0
    # the undonated lowering keeps input and output state resident
    assert (bad_meta["memory"]["peak_live_bytes"]
            > mem["peak_live_bytes"])
    del optax, analysis


class _FixtureProgram:
    """A pre-lowered stand-in driving ``audit_target`` from fixed HLO
    text: seeded-violation modules stay deterministic (a real lowering
    of a replicated spike is at XLA's mercy — the simplifier can
    algebraically remove a broadcast+reduce pair)."""

    def __init__(self, compiled_text: str, lowered_text: str = ""):
        self._compiled = compiled_text
        self._lowered = lowered_text

    def lower(self, *args):
        return _FixtureProgram(self._compiled, self._lowered)

    def compile(self):
        return self

    def as_text(self):
        # audit_target reads lowered.as_text() for the donor markers and
        # compiled.as_text() for the module; returning the compiled text
        # from both is fine for marker-free fixtures
        return self._compiled


@pytest.mark.memory_smoke
def test_seeded_replicated_fixture_exits_one(monkeypatch, devices):
    """`analyze memory` over a seeded fat-replicated-intermediate
    fixture must exit 1 (findings) through the real CLI driver."""
    from dlbb_tpu import analysis
    from dlbb_tpu.analysis.hlo_audit import AuditTarget

    seeded = AuditTarget(
        name="fixture/replicated_spike",
        build=lambda: (_FixtureProgram(_replicated_hlo()), ()),
        expectation=TargetExpectation(),
        min_devices=8,
    )
    monkeypatch.setattr(
        "dlbb_tpu.analysis.hlo_audit.default_targets", lambda: [seeded])
    assert analysis.run_analysis(which="memory",
                                 verbose=False) == EXIT_FINDINGS


# ---------------------------------------------------------------------------
# gate integration: baseline diff + observability surface
# ---------------------------------------------------------------------------


def test_diff_fails_on_memory_axis_alone(tmp_path):
    """A donation regression moves ONLY peak_live_bytes — the committed
    baseline must fail CI on the memory axis with the schedule axes
    untouched."""
    from dlbb_tpu.analysis.schedule_audit import (
        diff_baselines,
        snapshot_baselines,
    )

    base = {
        "cost_model_version": "cm1", "tier": "cpu-sim",
        "critical_path_us": 10.0, "comm_on_critical_path_us": 5.0,
        "comm_total_us": 6.0, "compute_total_us": 2.0,
        "overlap_efficiency": 0.5, "total_wire_bytes": 4096,
        "num_collectives": 4, "collective_kinds": {"all-reduce": 4},
        "peak_live_bytes": 100_000, "max_transient_bytes": 10_000,
    }
    snapshot_baselines({"t": base}, tmp_path)
    ok = diff_baselines({"t": dict(base)}, tmp_path)
    assert [f for f in ok if f.severity == "error"] == []

    regressed = dict(base, peak_live_bytes=150_000)
    findings = diff_baselines({"t": regressed}, tmp_path)
    errors = [f.rule for f in findings if f.severity == "error"]
    assert errors == ["peak-memory-regression"]

    fat_transient = dict(base, max_transient_bytes=20_000)
    findings = diff_baselines({"t": fat_transient}, tmp_path)
    errors = [f.rule for f in findings if f.severity == "error"]
    assert errors == ["transient-buffer-regression"]

    improved = dict(base, peak_live_bytes=50_000)
    findings = diff_baselines({"t": improved}, tmp_path)
    assert [f.rule for f in findings] == ["baseline-improved"]


def test_committed_baselines_carry_memory_axis():
    """Every committed per-target snapshot records the memory keys the
    diff gate needs."""
    from dlbb_tpu.analysis.schedule_audit import (
        DEFAULT_BASELINE_DIR,
        load_baselines,
    )

    baselines = load_baselines(DEFAULT_BASELINE_DIR)
    assert len(baselines) >= 30
    for name, base in baselines.items():
        assert base.get("peak_live_bytes", 0) > 0, name
        assert "max_transient_bytes" in base, name


def test_attribution_peak_bytes_column():
    """`obs attribute`'s per-phase static memory prediction: populated
    from a serving report's geometry, honest-blank otherwise."""
    from dlbb_tpu.obs.attribution import _serving_peak_bytes

    report = {
        "model": {"hidden_size": 256, "num_layers": 4, "num_heads": 8,
                  "kv_heads": 8, "dtype": "bfloat16"},
        "mesh": {"dp": 2, "tp": 4},
        "serving": {"max_batch": 8, "max_seq": 128,
                    "prefill_buckets": [16, 32, 64]},
    }
    peaks = _serving_peak_bytes(report)
    cache_dev = (2 * 4 * 8 * 128 * 8 * 32 * 2) // 8
    assert peaks["decode"] > cache_dev  # cache + sharded weights + act
    assert peaks["prefill"] > cache_dev
    # a sweep report (no serving geometry) stays honest-blank
    assert _serving_peak_bytes({}) == {}
    assert _serving_peak_bytes({"model": {"hidden_size": 256}}) == {}


def test_memory_metrics_and_artifacts(tmp_path):
    """`analyze memory --output DIR`: gauges + manifest merge without
    clobbering a co-located sweep export."""
    memory = {
        "comm/ops.py::allreduce": {"peak_live_bytes": 2048,
                                   "hbm_headroom_bytes": 4096,
                                   "max_transient_bytes": 0},
        "serve/engine.py::decode_step[dp,tp]": {
            "peak_live_bytes": 121_793, "max_transient_bytes": 12_288},
    }
    tier = get_tier("cpu-sim")
    registry = memory_metrics(memory, tier)
    text = registry.to_prometheus()
    assert ('dlbb_analysis_peak_live_bytes{target="comm/ops.py::'
            'allreduce",tier="cpu-sim"} 2048') in text
    assert "dlbb_analysis_memory_targets" in text

    # pre-existing sweep export must survive the fold
    (tmp_path / "metrics.prom").write_text(
        "# TYPE dlbb_sweep_wall_seconds gauge\n"
        "dlbb_sweep_wall_seconds 1.5\n")
    (tmp_path / "sweep_manifest.json").write_text(
        json.dumps({"schema": "dlbb_sweep_manifest_v1", "kind": "1d"}))
    write_memory_artifacts(memory, tmp_path, tier)
    prom = (tmp_path / "metrics.prom").read_text()
    assert "dlbb_sweep_wall_seconds 1.5" in prom
    assert "dlbb_analysis_peak_live_bytes" in prom
    manifest = json.loads((tmp_path / "sweep_manifest.json").read_text())
    assert manifest["kind"] == "1d"  # merged, not clobbered
    audit = manifest["memory_audit"]
    assert audit["tier"] == "cpu-sim"
    assert audit["peak_live_bytes"][
        "serve/engine.py::decode_step[dp,tp]"] == 121_793
    report = json.loads((tmp_path / "memory_audit.json").read_text())
    assert report["schema"] == "dlbb_memory_audit_v1"
