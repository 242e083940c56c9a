"""The ``kanana_serve_longctx_backlog`` cell's own pieces on the CPU at
toy widths: it resolves from data; ``kind_backlog_latent`` runs end to
end with the chip check stubbed and decides ``correct`` by logits,
routing and gates; the issue's controls and the precision below
(``scripts/kanana_controls.py``) are not correct; a routing flip that is a near-tie passes and one that is
not fails; the operations against a hand count; the roofline reader on a
hand-made profile, at and under 100%.  No number here is a measurement."""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path
from types import ModuleType, SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for path in (ROOT, ROOT / "scripts"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import kanana_controls as controls                              # noqa: E402
import test_named_readers as enc                                # noqa: E402
from benchmarks.harness import cells, flops_kanana2 as counts   # noqa: E402
from benchmarks.harness import kind_backlog_latent              # noqa: E402
from benchmarks.readers import moe_latent_roofline, mfu_served  # noqa: E402
from benchmarks.readers import named_ops                        # noqa: E402

CELL = "kanana_serve_longctx_backlog"
TOY_MODEL = dict(hidden_size=64, num_layers=3, num_heads=4, num_kv_heads=4,
                 ffn_intermediate=96, vocab_size=256, dtype="float32",
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=24)


def _toy(**mix) -> cells.Cell:
    cell = cells.resolve_cell(CELL)
    config = copy.deepcopy(cell.config)
    config["program"]["model"].update(TOY_MODEL)
    config["program"]["serving"].update(
        max_batch=4, max_seq=128, block_size=8, prefill_chunk=16,
        decode_horizon=4)
    traffic = copy.deepcopy(cell.traffic)
    # 16 requests over 4 slots: request 0 takes an unused slot, request
    # 10 a recycled one
    traffic.update(prompt_range=[8, 60], output_range=[4, 24],
                   backlog_rps=8, warmup_prompt_stride=16,
                   trace_start_s=0.2, trace_seconds=0.5, check_rids=[0, 10])
    traffic.update(mix)
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.fixture()
def harness(monkeypatch, tmp_path):
    from benchmarks.harness import device, peaks

    monkeypatch.setattr(device, "require_chips", lambda chips: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return device.CompileCounter(), str(tmp_path)


def test_cell_resolves_from_data_and_holds_every_published_width():
    cell = cells.resolve_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "backlog_latent"
    assert cells.runner_for("backlog_latent") is kind_backlog_latent.run
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    serving = cell.config["program"]["serving"]
    assert cell.traffic["warmup_prompt_stride"] == serving["prefill_chunk"]
    assert (cell.traffic["prompt_range"][1] + cell.traffic["output_range"][1]
            <= serving["max_seq"])
    assert cell.traffic["prompt_range"] == [1024, 4096]
    assert cell.traffic["output_range"] == [128, 512]
    # the envelope and the traced slice the issue fixed
    assert (serving["max_batch"], serving["max_seq"]) == (64, 4608)
    assert serving["max_seq"] % serving["prefill_chunk"] == 0
    assert (cell.traffic["trace_start_s"],
            cell.traffic["trace_seconds"]) == (2.0, 3.0)
    # one checked request among the first 64 admitted (an unused slot)
    # with a prompt of three chunks or more, one into a recycled slot
    first, second = cell.traffic["check_rids"]
    assert first < serving["max_batch"] <= second
    from benchmarks.harness import traffic as traffic_gen
    records = traffic_gen.generate(
        cell.traffic, 1, round(cell.traffic["backlog_rps"] * 30))
    assert records[first]["prompt_len"] > 2 * serving["prefill_chunk"]
    names = [m["name"] for m in cell.per_layer]
    assert all(name.endswith(".kanana") for name in names)
    assert set(names) == {
        "step.moe_share.kanana", "step.moe_route_share.kanana",
        "step.latent_attend_share.kanana", "step.latent_update_share.kanana",
        "step.lm_head_share.kanana", "step.decode_device_share.kanana",
        "step.prefill_device_share.kanana", "step.prefill_share.kanana",
        "step.decode_ms_p50.kanana", "sched.batch_occupancy.kanana",
        "sched.admission_idle_share.kanana", "sched.embed_idle_share.kanana",
        "device.idle_share.kanana", "kernel.top_share.kanana",
        "step.mfu.kanana", "kernel.expert_roofline.kanana",
        "kernel.latent_decode_roofline.kanana"}
    assert all(m["moves"] == "out_tokens_per_s"
               and m["workloads"] == [CELL] for m in cell.per_layer)
    # every width as the catalog's config.json has it, at the top level
    # of the file too; only the depth differs
    top, model = cell.config, cell.config["program"]["model"]
    assert cell.config["reduced"].keys() == {"num_layers"}
    for ours, theirs in [("hidden_size", "hidden_size"),
                         ("ffn_intermediate", "intermediate_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("vocab_size", "vocab_size"),
                         ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("rope_theta", "rope_theta"),
                         ("n_routed_experts", "n_routed_experts"),
                         ("num_experts_per_tok", "num_experts_per_tok"),
                         ("n_shared_experts", "n_shared_experts"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("first_k_dense_replace", "first_k_dense_replace"),
                         ("routed_scaling_factor", "routed_scaling_factor"),
                         ("rms_norm_eps", "rms_norm_eps")]:
        assert model[ours] == top[theirs], ours
    assert (model["num_layers"], top["num_hidden_layers"]) == (8, 48)
    assert top["qk_head_dim"] == (model["qk_nope_head_dim"]
                                  + model["qk_rope_head_dim"])
    assert top["q_lora_rank"] is None and top["n_group"] == 1


def test_latent_runner_decides_correct_by_logits_routing_and_gates(
        harness, capsys):
    from benchmarks.harness.result import result_line

    compiles, scratch = harness
    cell = _toy()
    run = cells.runner_for("backlog_latent")(cell, 2**31 + 11, 2.0, False,
                                             compiles, scratch)
    assert run.correct, run.faults
    assert run.failed == 0 and run.attempted == 16
    # float32 toy against the float32 reference: far inside the limits
    for name in ("prefill", "decode", "decode_step"):
        assert 0.0 < run.scalars[f"{name}_rel_l2"] < 1e-3, name
    assert run.scalars["routing_agreement"] == 1.0
    assert run.scalars["routing_tie"] == 0.0
    assert 0.0 <= run.scalars["gate"] < 1e-4
    assert 0.0 < run.scalars["experts_touched_share"] <= 1.0
    assert 0.0 < run.scalars["latent_live_share"] <= 1.0
    assert len(run.samples["moe_unit_touched"]) == \
        len(run.samples["unit_live_tokens"])
    err = capsys.readouterr().err
    assert "recycled=False" in err and "recycled=True" in err
    assert f"routing_tie {run.scalars['routing_tie']:.5f} (limit " \
        f"{kind_backlog_latent.ROUTING_TIE_MAX})" in err
    line = json.loads(result_line(run, setup_s=1.0, trace=False))
    assert line["correct"] and set(line["metrics"]) == {"out_tokens_per_s",
                                                        "setup_s"}
    layer = json.loads(result_line(run, setup_s=1.0, trace=True))
    assert 0.0 < layer["metrics"]["step.mfu.kanana"]["value"] < 100.0
    # no device plane on the CPU: the trace readers leave theirs out
    assert "kernel.expert_roofline.kanana" not in layer["metrics"]


@pytest.mark.parametrize("control, limit", [
    ("rope_off_cache", "decode"),
    ("latent_not_normed", "decode"),
    ("scaling_left_out", "gate"),
    ("topk_norm_left_out", "gate"),
    ("bias_as_weight", "gate"),
    ("one_shared_expert", "prefill"),
])
def test_every_control_is_not_correct(control, limit, harness, monkeypatch):
    cell = _toy()
    controls.apply(control, monkeypatch.setattr,
                   cell.config["program"]["model"])
    compiles, scratch = harness
    run = cells.runner_for("backlog_latent")(cell, 7, 2.0, False, compiles,
                                             scratch)
    assert run.failed == 0        # every request was served, and wrongly
    assert not run.correct
    assert any(f.startswith(limit) and "from the reference" in f
               for f in run.faults), run.faults


@pytest.mark.parametrize("control", ["float32_parts_bfloat16",
                                     "router_bfloat16"])
def test_the_precision_below_is_not_correct_by_the_gates(control, harness,
                                                        monkeypatch):
    """The nearest precision below the configuration's (what it states
    as float32 rounded to bfloat16, and the router alone so): the first
    expert layer's gates read it."""
    cell = _toy()
    controls.apply(control, monkeypatch.setattr,
                   cell.config["program"]["model"])
    compiles, scratch = harness
    run = cells.runner_for("backlog_latent")(cell, 7, 2.0, False, compiles,
                                             scratch)
    assert run.failed == 0
    assert not run.correct
    assert run.scalars["gate"] > kind_backlog_latent.GATE_MEAN_REL_MAX
    assert any(f.startswith("gate") and "from the reference" in f
               for f in run.faults), run.faults
    # what it flips, if anything, are near-ties
    assert run.scalars["routing_tie"] < kind_backlog_latent.ROUTING_TIE_MAX


def _fake(monkeypatch, select, chosen, experts):
    """An engine that probed one request of two positions and a
    reference that returns what it is told."""
    positions, layers, e = select.shape
    # the reference's gates weight the experts it was made to take
    gates = np.zeros(select.shape, np.float32)
    np.put_along_axis(gates, experts, 1.0, axis=-1)
    logits = np.ones((positions, 8), np.float32)
    ref = ModuleType("benchmarks.reference.fake_latent")
    ref.weight_faults = lambda params, model: []
    ref.forward_logits = lambda params, ids, model, positions, \
        with_routing, forced_experts: (logits, select, chosen, gates)
    monkeypatch.setitem(sys.modules, ref.__name__, ref)
    sys_gates = np.ones(experts.shape, np.float32)
    records = {rid: {"slot": rid, "recycled": bool(rid), "prompt_ids": [1],
                     "tokens": [2] * positions,
                     "logits": list(logits), "experts": list(experts),
                     "gates": list(sys_gates)} for rid in (0, 1)}
    engine = SimpleNamespace(params=None, probe_results=lambda: records)
    cell = cells.resolve_cell(CELL)
    return engine, dataclasses.replace(
        cell, traffic={**cell.traffic, "reference": "fake_latent",
                       "check_rids": [0, 1]})


@pytest.mark.parametrize("gap, passes", [(1e-4, True), (0.2, False)])
def test_a_routing_flip_passes_only_as_a_near_tie(gap, passes, monkeypatch):
    # two positions, two layers, four experts, two a token: at position 1
    # the system took expert 2 in the first layer where the reference
    # (fed the system's choices upstream) took expert 1, which it scores
    # ``gap`` higher; the second layer agrees
    first = [[0.9, 0.8, 0.1, 0.0], [0.9, 0.5 + gap, 0.5, 0.0]]
    second = [[0.9, 0.8, 0.1, 0.0], [0.9, 0.8, 0.1, 0.0]]
    select = np.array([[first[0], second[0]], [first[1], second[1]]],
                      np.float32)
    chosen = np.array([[[0, 1], [0, 1]], [[0, 1], [0, 1]]])
    experts = np.array([[[0, 1], [0, 1]], [[0, 2], [0, 1]]])
    engine, cell = _fake(monkeypatch, select, chosen, experts)
    faults, worst = kind_backlog_latent.check_outputs(engine, cell)
    assert worst["routing_tie"] == pytest.approx(gap, rel=1e-3)
    assert worst["routing_agreement"] == 0.75
    assert (faults == []) == passes, faults
    if not passes:
        assert faults[0].startswith("routing_tie")
    # every layer is judged on its own: the best the system passed over
    # (0.9) less the worst it took instead (0.0), in the second layer
    experts[1, 1] = [2, 3]
    engine, cell = _fake(monkeypatch, select, chosen, experts)
    faults, worst = kind_backlog_latent.check_outputs(engine, cell)
    assert worst["routing_tie"] == pytest.approx(0.9, rel=1e-3)
    assert faults and faults[0].startswith("routing_tie")


def test_flops_match_a_hand_count():
    model = cells.resolve_cell(CELL).config["program"]["model"]
    assert counts.layer_counts(model) == (1, 7)
    h = 2048
    attention = h * 32 * 192 + h * 576 + 512 * 32 * 256 + 32 * 128 * h
    assert counts.attention_params(model) == attention == 26_345_472
    assert counts.expert_params(model) == 3 * h * 768 == 4_718_592
    # a token activates the router, 6 routed and 2 shared experts
    sparse = h * 128 + 8 * 4_718_592
    assert counts.token_matmul_flops(model) == 2 * (
        8 * attention + 3 * h * 6144 + 7 * sparse)
    assert counts.pair_flops(model) == 32 * (2 * 192 + 2 * 128)
    fed = 3000 + 200 - 1
    assert counts.request_flops(model, 3000, 200) == pytest.approx(
        fed * counts.token_matmul_flops(model)
        + 8 * fed * (fed + 1) / 2 * 32 * 640 + 200 * 2 * h * 128256)
    # about 1.0 GFLOP a token before attention and head
    assert counts.token_matmul_flops(model) == pytest.approx(1.03e9, rel=0.02)
    assert counts.expert_products_flops(model, 384) == 384 * 6 * h * 768
    assert counts.expert_products_bytes(model, 122, 384) == \
        2 * (122 * 4_718_592 + 384 * 2 * h)
    assert counts.latent_row_bytes(model) == 1152
    assert counts.latent_decode_bytes(model, 1000) == 8 * 1000 * 1152
    assert counts.latent_decode_flops(model, 1000) == \
        8 * 1000 * 32 * 2 * (576 + 512)


# -- the roofline reader on a hand-made profile --------------------------------

PID = 4242
MODEL = cells.resolve_cell(CELL).config["program"]["model"]
# what the program counted of the two decode units and the one chunk
# that fall into the traced window (units 1 and 2, chunk 1)
SAMPLES = {
    "unit_live_tokens": [9e9, 150_000, 40_000, 9e9],
    "moe_unit_touched": [9e9, 7 * 120, 7 * 110, 9e9],
    "moe_unit_assignments": [9e9, 7 * 384, 7 * 300, 9e9],
    "moe_chunk_touched": [9e9, 7 * 128, 9e9],
    "moe_chunk_assignments": [9e9, 7 * 6 * 2048, 9e9],
}


def _least(kernel: str) -> float:
    bw, fl = 819.0e9, 197.0e12
    if kernel == "latent_decode":
        return sum(max(8 * t * 1152 / bw, 8 * t * 32 * 2 * 1088 / fl)
                   for t in SAMPLES["unit_live_tokens"][1:3])
    total = 0.0
    for t, a in [(7 * 120, 7 * 384), (7 * 110, 7 * 300),
                 (7 * 128, 7 * 6 * 2048)]:
        total += max(2 * (t * 4_718_592 + a * 4096) / bw,
                     a * 2 * 4_718_592 / fl)
    return total


def _profile(experts_ms: float, latent_ms: float) -> bytes:
    """One device, window 0..100 ms: two decode units
    (``latent_ms`` under ``latent_attend`` between them, and
    ``experts_ms`` under ``moe_experts`` between them and the chunk), one
    prompt chunk whose ``latent_attend`` (the expanded form) is no decode
    attention, and a step outside the window; ``bench-sync`` at 10 ms."""
    def op(scope, program):
        return [enc._stat(2, program),
                enc._stat(1, f"jit(x)/while/body/{scope}")]

    decode, chunk = PID, PID + 1
    metadata = {
        1: ("%fusion.1 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("latent_attend/latent_attend_decode/pallas_call", decode)),
        2: ("%fusion.2 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("moe_experts/jit(gmm)/pallas_call", decode)),
        3: ("%fusion.3 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("latent_attend/kv_attend/dot_general", chunk)),
        4: ("%fusion.4 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("moe_experts/jit(gmm)/pallas_call", chunk)),
        5: (f"jit_serve_decode_k4({decode})", []),
        7: (f"jit_serve_prefill_chunk_o1024({chunk})", []),
    }
    ms = enc.MS
    third = int(experts_ms * ms / 3)
    half = int(latent_ms * ms / 2)
    device = enc._plane("/device:TPU:0", [
        ("XLA Modules", 10**9, [(5, 0, 30 * ms, []), (5, 30 * ms, 30 * ms, []),
                                (7, 60 * ms, 35 * ms, []),
                                (5, 200 * ms, 10 * ms, [])]),
        # each of the two kinds of op has at most 5 and 22 ms of room
        ("XLA Ops", 10**9, [(1, 0, half, []), (2, 8 * ms, third, []),
                            (1, 31 * ms, half, []), (2, 37 * ms, third, []),
                            (3, 60 * ms, 5 * ms, []), (4, 66 * ms, third, []),
                            (1, 200 * ms, 5 * ms, [])]),
    ], metadata)
    host = enc._plane("/host:CPU", [
        ("python3", 10**9, [(1, 0, 100 * ms, []), (2, 10 * ms, 1, [])]),
    ], {1: ("bench-window", []), 2: ("bench-sync", [])})
    return b"".join(enc._bytes(1, p) for p in (device, host))


def _traced(tmp_path, monkeypatch, experts_ms, latent_ms, samples=SAMPLES):
    monkeypatch.setattr(named_ops, "ROOT", tmp_path)
    monkeypatch.setattr(named_ops, "_CACHE", {})
    scratch = tmp_path / ".bench_scratch" / CELL
    where = scratch / "plugins" / "profile" / "2026_10_02"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_profile(experts_ms, latent_ms))

    def begin(name, at_s, **args):
        return {"name": name, "ph": "B", "ts": at_s * 1e6, "tid": 1,
                "args": args}

    # the span file's clock: bench-sync at 5.0 s there is 10 ms on the
    # profile's, so 4.99 s is the window's start and 5.09 s its end
    events = [
        {"name": "bench-sync", "ph": "i", "ts": 5.0e6, "tid": 2},
        begin("serve-decode", 4.5, active=4, steps=1, unit=0),    # before
        begin("serve-decode", 4.995, active=3, steps=4, unit=1),
        begin("serve-decode", 5.03, active=2, steps=1, unit=2),
        begin("serve-prefill-chunk", 5.05, rid=1, chunk=1, seq=1),
        begin("serve-prefill-chunk", 5.5, rid=1, chunk=2, seq=2),  # after
        begin("serve-decode", 5.6, active=2, steps=1, unit=3),
    ]
    (scratch / "spans.json").write_text(json.dumps({"traceEvents": events}))
    return SimpleNamespace(cell=cells.resolve_cell(CELL), seconds=2.0,
                           device={"kind": "TPU v5 lite"}, samples=samples,
                           scalars={},
                           profile={"busy_s": 0.09, "window_s": 0.1})


def test_roofline_reader_prices_the_traced_slice_not_the_run(
        tmp_path, monkeypatch):
    run = _traced(tmp_path, monkeypatch, 60.0, 10.0)
    loaded = named_ops.load(run)
    assert moe_latent_roofline.traced_indices(run, loaded) == ([1, 2], [1])
    # the expert products' three ops in the window; of latent_attend only
    # what ran in the decode programs
    assert moe_latent_roofline.read(run, "experts") == pytest.approx(
        100 * _least("experts") / 0.060, rel=1e-6)
    assert moe_latent_roofline.read(run, "latent_decode") == pytest.approx(
        100 * _least("latent_decode") / 0.010, rel=1e-6)
    assert 0.0 < moe_latent_roofline.read(run, "experts") < 100.0
    with pytest.raises(ValueError):
        moe_latent_roofline.read(run, "prefill")


def test_a_kernel_that_reads_exactly_the_least_bytes_reads_100(
        tmp_path, monkeypatch):
    """Neither share can pass 100%: the traced time of a kernel that
    moves exactly the bytes the function needs, at exactly the published
    rate, is the least time."""
    run = _traced(tmp_path, monkeypatch, 1e3 * _least("experts"),
                  1e3 * _least("latent_decode"))
    assert moe_latent_roofline.read(run, "experts") == pytest.approx(
        100.0, rel=1e-4)
    assert moe_latent_roofline.read(run, "latent_decode") == pytest.approx(
        100.0, rel=1e-4)


def test_readers_find_nothing_where_the_program_has_no_such_names(
        tmp_path, monkeypatch):
    # the parent's run: no samples, no span arguments, no scopes
    run = _traced(tmp_path, monkeypatch, 60.0, 10.0, samples={})
    assert moe_latent_roofline.read(run, "experts") is None
    assert moe_latent_roofline.read(run, "latent_decode") is None
    untraced = SimpleNamespace(cell=run.cell, profile={}, samples={},
                               scalars={}, device=run.device)
    assert moe_latent_roofline.read(untraced, "experts") is None
    assert mfu_served.read(untraced, "flops_kanana2") is None
    monkeypatch.setattr(named_ops, "ROOT", tmp_path / "elsewhere")
    monkeypatch.setattr(named_ops, "_CACHE", {})
    assert moe_latent_roofline.read(run, "experts") is None
