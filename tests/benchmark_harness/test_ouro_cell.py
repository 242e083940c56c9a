"""The ``ouro_serve_reason_backlog`` cell's own pieces on the CPU at toy
widths: it resolves from data; ``kind_backlog_looped`` runs end to end
with the chip check stubbed and decides ``correct`` by logits and exit
gates; faults of the loop and of the block (``scripts/ouro_controls.py``)
are not correct; the operations and bytes against a hand count; the
roofline reader on a hand-made profile, at and under 100%.  No number
here is a measurement."""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
for path in (ROOT, ROOT / "scripts"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import ouro_controls as controls                                # noqa: E402
import test_named_readers as enc                                # noqa: E402
from benchmarks.harness import cells, flops_ouro as counts      # noqa: E402
from benchmarks.harness import kind_backlog_looped              # noqa: E402
from benchmarks.readers import loop_roofline, mfu_served        # noqa: E402
from benchmarks.readers import named_ops                        # noqa: E402

CELL = "ouro_serve_reason_backlog"
TOY_MODEL = dict(hidden_size=64, num_layers=3, num_heads=4, num_kv_heads=4,
                 ffn_intermediate=96, vocab_size=256, dtype="float32")


def _toy(**mix) -> cells.Cell:
    cell = cells.resolve_cell(CELL)
    config = copy.deepcopy(cell.config)
    config["program"]["model"].update(TOY_MODEL)
    config["program"]["serving"].update(
        max_batch=4, max_seq=128, block_size=8, prefill_chunk=16,
        decode_horizon=4)
    traffic = copy.deepcopy(cell.traffic)
    # 16 requests over 4 slots: request 2 takes an unused slot with a
    # prompt of two chunks and more, request 10 a recycled one
    traffic.update(prompt_range=[8, 60], output_range=[4, 24],
                   backlog_rps=8, warmup_prompt_stride=16,
                   trace_start_s=0.2, trace_seconds=0.5, check_rids=[2, 10])
    traffic.update(mix)
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.fixture()
def harness(monkeypatch, tmp_path):
    from benchmarks.harness import device, peaks

    monkeypatch.setattr(device, "require_chips", lambda chips: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return device.CompileCounter(), str(tmp_path)


def test_cell_resolves_from_data_and_holds_every_published_size():
    cell = cells.resolve_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "backlog_looped"
    assert cells.runner_for("backlog_looped") is kind_backlog_looped.run
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    serving = cell.config["program"]["serving"]
    traffic = cell.traffic
    assert traffic["warmup_prompt_stride"] == serving["prefill_chunk"] == 128
    assert traffic["prompt_range"] == [64, 256]
    assert traffic["output_range"] == [128, 384]
    assert (traffic["prompt_range"][1] + traffic["output_range"][1]
            <= serving["max_seq"])
    # the envelope and the traced slice the issue fixed
    assert (serving["max_batch"], serving["max_seq"],
            serving["block_size"]) == (8, 640, 16)
    assert (serving["decode_horizon"], serving["inflight_window"],
            serving["queue_capacity"]) == (16, 2, 4096)
    assert (traffic["trace_start_s"], traffic["trace_seconds"]) == (8.0, 3.0)
    # one checked request among the first 8 admitted (an unused slot)
    # with a prompt of two chunks, one into a recycled slot
    first, second = traffic["check_rids"]
    assert first < serving["max_batch"] <= second
    from benchmarks.harness import traffic as traffic_gen
    records = traffic_gen.generate(
        traffic, 1, traffic_gen.request_count(traffic, 30))
    assert records[first]["prompt_len"] > serving["prefill_chunk"]
    assert all(r["arrival_s"] == 0.0 for r in records)
    # AT LEAST these per-layer metrics, each of this cell alone
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "step.mfu.ouro", "step.weight_pass_roofline.ouro",
        "kernel.kv_attend_roofline.ouro", "step.kv_attend_share.ouro",
        "step.kv_update_share.ouro", "step.fullattn_share.ouro",
        "step.mlp_share.ouro", "step.loop_norm_share.ouro",
        "step.lm_head_share.ouro", "step.decode_device_share.ouro",
        "step.prefill_device_share.ouro", "step.prefill_share.ouro",
        "step.decode_ms_p50.ouro", "sched.batch_occupancy.ouro",
        "sched.admission_idle_share.ouro", "sched.embed_idle_share.ouro",
        "device.idle_share.ouro", "kernel.top_share.ouro"}
    assert all(m["moves"] == "out_tokens_per_s"
               and m["workloads"] == [CELL] for m in cell.per_layer)
    # every size as the catalog's config.json has it, at the top level of
    # the file too, and nothing reduced
    top, model = cell.config, cell.config["program"]["model"]
    assert cell.config["reduced"] == {}
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == []
    for ours, theirs in [("hidden_size", "hidden_size"),
                         ("num_layers", "num_hidden_layers"),
                         ("ffn_intermediate", "intermediate_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("vocab_size", "vocab_size"),
                         ("rope_theta", "rope_theta"),
                         ("rms_norm_eps", "rms_norm_eps"),
                         ("total_ut_steps", "total_ut_steps"),
                         ("early_exit_threshold", "early_exit_threshold")]:
        assert model[ours] == top[theirs] == cell.config["published"][ours]
    assert (model["num_layers"], model["num_heads"], model["ffn_intermediate"],
            model["vocab_size"], model["total_ut_steps"],
            model["early_exit_threshold"]) == (48, 16, 5632, 49152, 4, 1)
    assert top["head_dim"] == model["hidden_size"] // model["num_heads"]
    assert set(top["layer_types"]) == set(model["layer_types"]) \
        and len(top["layer_types"]) == 48
    assert top["model_type"] == "ouro" and top["rope_scaling"] is None
    # the cache as the file says it: 1,572,864 B a token, 8.05 GB
    from dlbb_tpu.models.configs import ModelConfig, kv_cache_bytes
    config = ModelConfig.from_dict(model)
    assert kv_cache_bytes(config, 1, 1) == counts.kv_token_bytes(model) \
        == 1_572_864
    assert kv_cache_bytes(config, 8, 640) == 8_053_063_680
    # a plane stays under 2^31 elements and 2^32 bytes
    assert kv_cache_bytes(config, 8, 640) // 4 < 2**31
    assert kv_cache_bytes(config, 8, 640) // 2 < 2**32


def test_looped_runner_decides_correct_by_logits_and_gates(harness, capsys):
    from benchmarks.harness.result import result_line

    compiles, scratch = harness
    cell = _toy()
    run = cells.runner_for("backlog_looped")(cell, 2**31 + 11, 2.0, False,
                                             compiles, scratch)
    assert run.correct, run.faults
    assert run.failed == 0 and run.attempted == 16
    # float32 toy against the float32 reference: far inside the limits
    for name in ("prefill", "decode", "decode_step"):
        assert 0.0 < run.scalars[f"{name}_rel_l2"] < 1e-3, name
    for name in ("gate_first", "gate_any", "gate_pass1", "gate_pass4", "key",
                 "key_token"):
        assert 0.0 <= run.scalars[name] < 1e-5, name
    assert run.scalars["exit_pass_mean"] == 4.0
    assert 0.0 < run.scalars["kv_live_share"] <= 1.0
    assert len(run.samples["unit_slot_steps"]) == \
        len(run.samples["unit_live_tokens"])
    err = capsys.readouterr().err
    assert "recycled=False" in err and "recycled=True" in err
    assert f"gate_first {run.scalars['gate_first']:.5f} (limit " \
        f"{kind_backlog_looped.GATE_FIRST_PASS_MAX})" in err
    line = json.loads(result_line(run, setup_s=1.0, trace=False))
    assert line["correct"] and set(line["metrics"]) == {"out_tokens_per_s",
                                                        "setup_s"}
    layer = json.loads(result_line(run, setup_s=1.0, trace=True))
    assert 0.0 < layer["metrics"]["step.mfu.ouro"]["value"] < 100.0
    # no device plane on the CPU: the trace readers leave theirs out
    assert "step.weight_pass_roofline.ouro" not in layer["metrics"]
    assert "kernel.kv_attend_roofline.ouro" not in layer["metrics"]


@pytest.mark.parametrize("control, limit", [
    ("three_passes", "decode"),
    ("loop_norm_left_out", "decode"),
    ("previous_pass_planes", "decode"),
    ("stale_last_pass", "decode"),
    ("rope_off", "gate_first"),
    ("rope_adjacent", "gate_first"),
    ("sandwich_outputs_left_out", "gate_first"),
    # the nearest precision below the configuration's: no limit but the
    # first layer's keys reads it
    ("float32_parts_bfloat16", "key"),
])
def test_every_control_is_not_correct(control, limit, harness, monkeypatch):
    cell = _toy()
    controls.apply(control, monkeypatch.setattr,
                   cell.config["program"]["model"])
    compiles, scratch = harness
    run = cells.runner_for("backlog_looped")(cell, 7, 2.0, False, compiles,
                                             scratch)
    assert run.failed == 0        # every request was served, and wrongly
    assert not run.correct
    assert any(f.startswith(limit) and "from the reference" in f
               for f in run.faults), run.faults


def test_a_request_the_programs_returned_no_gates_for_is_a_fault():
    records = {rid: {"slot": rid, "recycled": bool(rid), "prompt_ids": [1],
                     "tokens": [2, 3], "logits": [None, None],
                     "exit_gates": None} for rid in (0, 1)}
    params = SimpleNamespace()
    engine = SimpleNamespace(params=params, probe_results=lambda: records)
    cell = cells.resolve_cell(CELL)
    from benchmarks.reference import ouro
    real = ouro.weight_faults
    ouro.weight_faults = lambda params, model: []
    try:
        faults, _ = kind_backlog_looped.check_outputs(
            engine, dataclasses.replace(
                cell, traffic={**cell.traffic, "check_rids": [0, 1]}))
    finally:
        ouro.weight_faults = real
    assert sorted(faults) == [
        "request 0: the programs returned no gates",
        "request 1: the programs returned no gates"]


def test_flops_and_bytes_match_a_hand_count():
    model = cells.resolve_cell(CELL).config["program"]["model"]
    h = 2048
    layer = 4 * h * h + 3 * h * 5632
    assert counts.layer_params(model) == layer == 51_380_224
    assert counts.passes(model) == 4
    assert counts.token_matmul_flops(model) == 2 * 4 * 48 * layer
    # 19.7 GFLOP a token before attention and head: four times a 2.5B model
    assert counts.token_matmul_flops(model) == pytest.approx(19.73e9,
                                                             rel=1e-3)
    assert counts.pair_flops(model) == 4 * h
    fed = 200 + 300 - 1
    assert counts.request_flops(model, 200, 300) == pytest.approx(
        fed * counts.token_matmul_flops(model)
        + 4 * 48 * fed * (fed + 1) / 2 * 4 * h + 300 * 2 * h * 49152)
    # what a decode unit reads: 4 x 4.93 GB of shared weights a step, the
    # head, the live K/V of 192 planes
    assert counts.stack_weight_bytes(model) == 2 * 48 * layer == 4_932_501_504
    assert counts.weight_pass_bytes(model, 3) == 3 * 4 * 4_932_501_504
    assert counts.head_bytes(model) == 2 * h * 49152
    assert counts.kv_token_bytes(model) == 4 * 48 * 2 * 16 * 128 * 2
    assert counts.kv_live_bytes(model, 1000) == 1000 * 1_572_864
    assert counts.kv_attend_flops(model, 1000) == 1000 * 192 * 4 * h
    assert counts.decode_unit_bytes(model, 2, 1000) == (
        2 * 4 * 4_932_501_504 + 2 * 2 * h * 49152 + 1000 * 1_572_864)
    assert counts.weight_pass_flops(model, 16) == 16 * 2 * 4 * 48 * layer


# -- the roofline reader on a hand-made profile --------------------------------

PID = 4242
MODEL = cells.resolve_cell(CELL).config["program"]["model"]
# what the program counted of the two decode units that fall into the
# traced window (units 1 and 2: a fused scan of 4 steps over 8 slots and
# a single step over 6)
SAMPLES = {"unit_slot_steps": [9e9, 32, 6, 9e9],
           "unit_live_tokens": [9e9, 9_600, 1_500, 9e9]}
STEPS = (4, 1)


def _least(kernel: str) -> float:
    bw = 819.0e9
    if kernel == "kv_attend":
        return sum(t * 1_572_864 / bw
                   for t in SAMPLES["unit_live_tokens"][1:3])
    return sum(k * 4 * 4_932_501_504 / bw for k in STEPS)


def _profile(weights_ms: float, attend_ms: float) -> bytes:
    """One device, window 0..1 s: two decode units (``weights_ms`` under
    the projections' and the MLP's scopes, ``attend_ms`` under
    ``kv_attend`` between them), one prompt chunk whose matmuls and
    ``kv_attend`` are no decode step's, and a step outside the window;
    ``bench-sync`` at 10 ms."""
    def op(scope, program):
        return [enc._stat(2, program),
                enc._stat(1, f"jit(x)/while/body/while/body/{scope}")]

    decode, chunk = PID, PID + 1
    metadata = {
        1: ("%fusion.1 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("attn_core/kv_attend/kv_attend_decode/pallas_call", decode)),
        2: ("%fusion.2 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("mlp_up/dot_general", decode)),
        3: ("%fusion.3 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("attn_qkv/bsh,hnd->bsnd/dot_general", decode)),
        4: ("%fusion.4 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("attn_core/kv_attend/dot_general", chunk)),
        6: ("%fusion.6 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("mlp_down/dot_general", chunk)),
        5: (f"jit_serve_decode_k4({decode})", []),
        7: (f"jit_serve_prefill_chunk_o128({chunk})", []),
    }
    ms = enc.MS
    half_w = int(weights_ms * ms / 2)
    half_a = int(attend_ms * ms / 2)
    device = enc._plane("/device:TPU:0", [
        ("XLA Modules", 10**9, [(5, 0, 400 * ms, []),
                                (5, 400 * ms, 200 * ms, []),
                                (7, 600 * ms, 350 * ms, []),
                                (5, 2000 * ms, 10 * ms, [])]),
        ("XLA Ops", 10**9, [(2, 0, half_w, []), (1, 300 * ms, half_a, []),
                            (3, 400 * ms, half_w, []),
                            (1, 560 * ms, half_a, []),
                            (6, 600 * ms, 100 * ms, []),
                            (4, 700 * ms, 50 * ms, []),
                            (1, 2000 * ms, 5 * ms, [])]),
    ], metadata)
    host = enc._plane("/host:CPU", [
        ("python3", 10**9, [(1, 0, 1000 * ms, []), (2, 10 * ms, 1, [])]),
    ], {1: ("bench-window", []), 2: ("bench-sync", [])})
    return b"".join(enc._bytes(1, p) for p in (device, host))


def _traced(tmp_path, monkeypatch, weights_ms, attend_ms, samples=SAMPLES):
    monkeypatch.setattr(named_ops, "ROOT", tmp_path)
    monkeypatch.setattr(named_ops, "_CACHE", {})
    scratch = tmp_path / ".bench_scratch" / CELL
    where = scratch / "plugins" / "profile" / "2026_10_03"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_profile(weights_ms, attend_ms))

    def begin(name, at_s, **args):
        return {"name": name, "ph": "B", "ts": at_s * 1e6, "tid": 1,
                "args": args}

    # the span file's clock: bench-sync at 5.0 s there is 10 ms on the
    # profile's, so 4.99 s is the window's start and 5.99 s its end
    events = [
        {"name": "bench-sync", "ph": "i", "ts": 5.0e6, "tid": 2},
        begin("serve-decode", 4.5, active=8, steps=1, unit=0),    # before
        begin("serve-decode", 4.995, active=8, steps=4, unit=1),
        begin("serve-decode", 5.4, active=6, steps=1, unit=2),
        begin("serve-prefill-chunk", 5.6, rid=1, chunk=1, seq=1),
        begin("serve-decode", 6.6, active=6, steps=1, unit=3),     # after
    ]
    (scratch / "spans.json").write_text(json.dumps({"traceEvents": events}))
    return SimpleNamespace(cell=cells.resolve_cell(CELL), seconds=2.0,
                           device={"kind": "TPU v5 lite"}, samples=samples,
                           scalars={},
                           profile={"busy_s": 0.9, "window_s": 1.0})


def test_roofline_reader_prices_the_traced_slice_not_the_run(
        tmp_path, monkeypatch):
    run = _traced(tmp_path, monkeypatch, 200.0, 40.0)
    loaded = named_ops.load(run)
    assert loop_roofline.traced_units(run, loaded) == [(1, 4), (2, 1)]
    # the decode programs' matmuls and kernel alone: the chunk's are not
    # a decode step's
    assert loop_roofline.read(run, "weight_pass") == pytest.approx(
        100 * _least("weight_pass") / 0.200, rel=1e-6)
    assert loop_roofline.read(run, "kv_attend") == pytest.approx(
        100 * _least("kv_attend") / 0.040, rel=1e-6)
    assert 0.0 < loop_roofline.read(run, "weight_pass") < 100.0
    assert 0.0 < loop_roofline.read(run, "kv_attend") < 100.0
    with pytest.raises(ValueError):
        loop_roofline.read(run, "prefill")


def test_a_step_that_reads_exactly_the_least_bytes_reads_100(
        tmp_path, monkeypatch):
    """Neither share can pass 100%: the traced time of a step that moves
    exactly the bytes the function needs, at exactly the published rate,
    is the least time."""
    run = _traced(tmp_path, monkeypatch, 1e3 * _least("weight_pass"),
                  1e3 * _least("kv_attend"))
    assert loop_roofline.read(run, "weight_pass") == pytest.approx(
        100.0, rel=1e-4)
    assert loop_roofline.read(run, "kv_attend") == pytest.approx(
        100.0, rel=1e-4)


def test_readers_find_nothing_where_the_program_has_no_such_names(
        tmp_path, monkeypatch):
    # the parent's run: no samples, no span arguments
    run = _traced(tmp_path, monkeypatch, 200.0, 40.0, samples={})
    assert loop_roofline.read(run, "weight_pass") is None
    assert loop_roofline.read(run, "kv_attend") is None
    untraced = SimpleNamespace(cell=run.cell, profile={}, samples={},
                               scalars={}, device=run.device)
    assert loop_roofline.read(untraced, "weight_pass") is None
    assert mfu_served.read(untraced, "flops_ouro") is None
    monkeypatch.setattr(named_ops, "ROOT", tmp_path / "elsewhere")
    monkeypatch.setattr(named_ops, "_CACHE", {})
    assert loop_roofline.read(run, "weight_pass") is None
