"""The ``granite4h_serve_chat_backlog`` cell's own pieces on the CPU at
toy widths: it resolves from data; ``kind_backlog_ssm`` runs end to end
with the chip check stubbed and decides ``correct`` by logits and by the
first state-space layer's state; the named controls
(``scripts/granite4h_controls.py``) are not correct, each by the limit
meant for it; the operations and bytes against a hand count at the
published sizes; the three roofline readers on a hand-made profile, at
and under 100%.  No number here is a measurement."""

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

import granite4h_controls as controls                           # noqa: E402
import test_named_readers as enc                                # noqa: E402
from benchmarks.harness import cells                            # noqa: E402
from benchmarks.harness import flops_granite4h as counts        # noqa: E402
from benchmarks.harness import kind_backlog_ssm                 # noqa: E402
from benchmarks.readers import mfu_served, named_ops            # noqa: E402
from benchmarks.readers import ssm_roofline                     # noqa: E402

CELL = "granite4h_serve_chat_backlog"
# the structure kept: a period with both kinds, a group of 4 query heads
# a K/V head, heads of 64 (K/V planes of whole rows), one group of B and C
TOY_MODEL = dict(
    hidden_size=512, num_layers=4, num_heads=8, num_kv_heads=2,
    ffn_intermediate=128, vocab_size=256, dtype="float32",
    layer_types=["mamba", "mamba", "full_attention", "mamba"],
    mamba_n_heads=16, mamba_d_head=64, mamba_d_state=16, mamba_chunk_size=16)


def _toy(**mix) -> cells.Cell:
    cell = cells.resolve_cell(CELL)
    config = copy.deepcopy(cell.config)
    config["program"]["model"].update(TOY_MODEL)
    config["program"]["serving"].update(
        max_batch=4, max_seq=128, block_size=8, prefill_chunk=16,
        decode_horizon=4)
    traffic = copy.deepcopy(cell.traffic)
    # 16 requests over 4 slots: request 2 takes an unused slot, request
    # 10 a recycled one with a prompt of two chunks and more
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
    assert cell.chips == 1 and cell.traffic["kind"] == "backlog_ssm"
    assert cells.runner_for("backlog_ssm") is kind_backlog_ssm.run
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    serving = cell.config["program"]["serving"]
    traffic = cell.traffic
    assert traffic["warmup_prompt_stride"] == serving["prefill_chunk"] == 256
    assert traffic["prompt_range"] == [64, 768]
    assert traffic["output_range"] == [64, 384]
    assert (traffic["prompt_range"][1] + traffic["output_range"][1]
            <= serving["max_seq"] == 1280)
    assert serving["max_batch"] in (64, 80) and serving["block_size"] == 16
    assert (serving["decode_horizon"], serving["inflight_window"],
            serving["queue_capacity"]) == (16, 2, 4096)
    assert (traffic["trace_start_s"], traffic["trace_seconds"]) == (4.0, 3.0)
    assert traffic["reference"] == "granite4_hybrid"
    # one checked request among the first admitted (an unused slot), one
    # into a recycled slot with a prompt of at least two chunks
    first, second = traffic["check_rids"]
    assert first < serving["max_batch"] <= second
    from benchmarks.harness import traffic as traffic_gen
    records = traffic_gen.generate(
        traffic, 1, traffic_gen.request_count(traffic, 30))
    assert records[second]["prompt_len"] > serving["prefill_chunk"]
    assert all(r["arrival_s"] == 0.0 for r in records)
    # AT LEAST these per-layer metrics, each of this cell alone
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "kernel.ssm_decode_roofline.granite4h",
        "kernel.ssm_prefill_roofline.granite4h",
        "kernel.kv_attend_roofline.granite4h", "step.mfu.granite4h",
        "step.ssm_share.granite4h", "step.state_update_share.granite4h",
        "step.fullattn_share.granite4h", "step.kv_attend_share.granite4h",
        "step.mlp_share.granite4h", "step.lm_head_share.granite4h",
        "step.decode_device_share.granite4h",
        "step.prefill_device_share.granite4h",
        "step.prefill_share.granite4h",
        "step.decode_device_ms_p50.granite4h",
        "sched.batch_occupancy.granite4h",
        "sched.launch_idle_share.granite4h",
        "sched.notice_idle_share.granite4h",
        "sched.host_idle_share.granite4h", "device.idle_share.granite4h",
        "kernel.top_share.granite4h"}
    assert all(m["moves"] == "out_tokens_per_s"
               and m["workloads"] == [CELL] for m in cell.per_layer)
    # every number of the catalog's config at the top level of the file
    # under its own key, the program's sizes beside them, nothing reduced
    top, model = cell.config, cell.config["program"]["model"]
    assert cell.config["reduced"] == {}
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == [] and entry["source"] == top["source"]
    for ours, theirs in [("hidden_size", "hidden_size"),
                         ("num_layers", "num_hidden_layers"),
                         ("ffn_intermediate", "shared_intermediate_size"),
                         ("ffn_intermediate", "intermediate_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("vocab_size", "vocab_size"),
                         ("rms_norm_eps", "rms_norm_eps"),
                         ("tie_word_embeddings", "tie_word_embeddings")] \
            + [(key, key) for key in (
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_expand", "mamba_d_conv",
                "mamba_chunk_size", "mamba_conv_bias",
                "attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling")]:
        assert model[ours] == top[theirs] == cell.config["published"][ours]
    assert (model["num_layers"], model["num_heads"], model["num_kv_heads"],
            model["ffn_intermediate"], model["vocab_size"]) == (
        40, 32, 8, 8192, 100352)
    assert (top["attention_multiplier"], top["embedding_multiplier"],
            top["residual_multiplier"], top["logits_scaling"]) == (
        0.015625, 12, 0.22, 8)
    assert top["model_type"] == "granitemoehybrid"
    assert top["position_embedding_type"] == "nope" \
        and model["rope_theta"] == 0 and not model["qk_norm"]
    assert (top["num_local_experts"], top["num_experts_per_tok"]) == (0, 0)
    # the file's 40 layers are four of the program's period of ten
    kinds = {"mamba": "mamba", "attention": "full_attention"}
    assert [kinds[k] for k in top["layer_types"]] == model["layer_types"] * 4
    assert len(model["layer_types"]) == 10
    # the cache as the file says it: 8,192 B of K/V a token, 75.6 MB of
    # state and convolution inputs a slot
    from dlbb_tpu.models.configs import (
        ModelConfig, kv_cache_bytes, state_cache_bytes)
    config = ModelConfig.from_dict(model)
    assert kv_cache_bytes(config, 1, 16) // 16 == 8192 \
        == counts.kv_live_bytes(model, 1)
    assert state_cache_bytes(config, 1) == 36 * (
        counts.state_bytes(model) + counts.conv_bytes(model)) == 76_437_504


def test_ssm_runner_decides_correct_by_logits_and_state(harness, capsys):
    from benchmarks.harness.result import result_line

    compiles, scratch = harness
    cell = _toy()
    run = cells.runner_for("backlog_ssm")(cell, 2**31 + 11, 2.0, False,
                                          compiles, scratch)
    assert run.correct, run.faults
    assert run.failed == 0 and run.attempted == 16
    # float32 toy against the float32 reference: far inside the limits
    for name in ("prefill", "decode", "decode_step", "state"):
        assert 0.0 < run.scalars[f"{name}_rel_l2"] < 1e-4, name
    assert 0.0 < run.scalars["chunk_real_token_share"] <= 1.0
    assert 0.0 < run.scalars["kv_live_share"] <= 1.0
    assert len(run.samples["unit_slot_steps"]) == \
        len(run.samples["unit_live_tokens"])
    assert len(run.samples["chunk_real_tokens"]) == \
        len(run.samples["chunk_rows"])
    assert sum(run.samples["chunk_real_tokens"]) == \
        sum(run.samples["served_prompt_len"])
    err = capsys.readouterr().err
    assert "recycled=False" in err and "recycled=True" in err
    assert f"state {run.scalars['state_rel_l2']:.5f} (limit " \
        f"{kind_backlog_ssm.STATE_REL_L2_MAX})" in err
    line = json.loads(result_line(run, setup_s=1.0, trace=False))
    assert line["correct"] and set(line["metrics"]) == {"out_tokens_per_s",
                                                        "setup_s"}
    layer = json.loads(result_line(run, setup_s=1.0, trace=True))
    assert 0.0 < layer["metrics"]["step.mfu.granite4h"]["value"] < 100.0
    assert layer["metrics"]["sched.batch_occupancy.granite4h"]["value"] > 0
    # no device plane on the CPU: the trace readers leave theirs out
    for name in ("kernel.ssm_decode_roofline.granite4h",
                 "kernel.ssm_prefill_roofline.granite4h",
                 "kernel.kv_attend_roofline.granite4h"):
        assert name not in layer["metrics"]


@pytest.mark.parametrize("control, limit", [
    # the nearest precision below the configuration's: no limit but the
    # first layer's state reads it
    ("state_bfloat16", "state"),
    ("decay_skipped", "state"),
    ("residual_multiplier_1", "decode"),
    ("stale_state", "state"),
])
def test_every_control_is_not_correct(control, limit, harness, monkeypatch):
    # rounding the state shows with the steps that round it: on the chip
    # a hundred and more, here at least forty
    cell = _toy(**(dict(output_range=[40, 64])
                   if control == "state_bfloat16" else {}))
    controls.apply(control, monkeypatch.setattr,
                   cell.config["program"]["model"])
    compiles, scratch = harness
    run = cells.runner_for("backlog_ssm")(cell, 7, 2.0, False, compiles,
                                          scratch)
    assert run.failed == 0        # every request was served, and wrongly
    print(control, run.scalars, run.faults)
    assert not run.correct
    assert any(f.startswith(limit) and "from the reference" in f
               for f in run.faults), run.faults
    if control == "state_bfloat16":
        # ... and by that limit ALONE
        assert all(f.startswith("state") for f in run.faults), run.faults


def test_flops_and_bytes_match_a_hand_count():
    model = cells.resolve_cell(CELL).config["program"]["model"]
    h, f = 2048, 8192
    assert counts.layer_counts(model) == (36, 4)
    mlp = 3 * h * f
    attention = 2 * h * h + 2 * h * 512
    ssm = h * (4096 + 4352 + 64) + 4 * 4352 + 4096 * h
    # the issue's arithmetic without norms, biases and the scalars a head
    assert (mlp, attention, ssm) == (50_331_648, 10_485_760, 25_838_592)
    assert counts.token_matmul_flops(model) == 2 * (
        40 * mlp + 4 * attention + 36 * ssm)
    # 5.97 GFLOP a token before the recurrence, attention and the head
    assert counts.token_matmul_flops(model) == pytest.approx(5.97e9,
                                                             rel=1e-3)
    assert counts.recurrence_flops(model, 1) == 36 * 5 * 64 * 64 * 128
    fed = 222 + 157 - 1
    assert counts.request_flops(model, 222, 157) == pytest.approx(
        fed * counts.token_matmul_flops(model)
        + fed * 36 * 5 * 64 * 64 * 128
        + 4 * fed * (fed + 1) / 2 * 4 * h + 157 * 2 * h * 100352)
    # what a decode step moves of a slot's state, and a chunk's scan
    assert counts.state_bytes(model) == 64 * 64 * 128 * 4 == 2_097_152
    assert counts.conv_bytes(model) == 3 * 4352 * 2 == 26_112
    assert counts.decode_state_bytes(model, 64) == \
        64 * 36 * 2 * (2_097_152 + 26_112) == 9_784_000_512
    assert counts.prefill_scan_bytes(model, 256, 1) == 36 * (
        256 * (2 * 4096 + 2 * 128) * 2 + 2 * 2_097_152)
    assert counts.kv_live_bytes(model, 1000) == 1000 * 8192


# -- the roofline readers on a hand-made profile -------------------------------

PID = 4242
MODEL = cells.resolve_cell(CELL).config["program"]["model"]
# what the program counted of the two decode units and the one prompt
# chunk that fall into the traced window (units 1 and 2: a fused scan of
# 4 steps over 8 slots and a single step over 6; chunk 1: 200 real
# tokens in 256 rows)
SAMPLES = {"unit_slot_steps": [9e9, 32, 6, 9e9],
           "unit_live_tokens": [9e9, 9_600, 1_500, 9e9],
           "chunk_real_tokens": [9e9, 200, 9e9],
           "chunk_rows": [256, 256, 256]}


def _least(kernel: str) -> float:
    bw, fl = 819.0e9, 197.0e12
    if kernel == "kv_attend":
        return sum(t * 8192 / bw for t in SAMPLES["unit_live_tokens"][1:3])
    if kernel == "ssm_decode":
        return sum(s * 36 * 2 * (2_097_152 + 26_112) / bw
                   for s in SAMPLES["unit_slot_steps"][1:3])
    return max(200 * 36 * 5 * 64 * 64 * 128 / fl,
               36 * (200 * (2 * 4096 + 2 * 128) * 2 + 2 * 2_097_152) / bw)


def _profile(update_ms: float, attend_ms: float, scan_ms: float) -> bytes:
    """One device, window 0..1 s: two decode units (``update_ms`` under
    ``state_update``, ``attend_ms`` in the kernel ``kv_attend_decode``
    between them), one prompt chunk (``scan_ms`` under ``state_scan``,
    and a ``kv_attend`` that is no decode step's), and a step outside
    the window; ``bench-sync`` at 10 ms."""
    def op(scope, program):
        return [enc._stat(2, program),
                enc._stat(1, f"jit(x)/while/body/{scope}")]

    decode, chunk = PID, PID + 1
    metadata = {
        1: ("%fusion.1 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("attn_core/kv_attend/kv_attend_decode/pallas_call", decode)),
        2: ("%fusion.2 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("ssm_core/state_update/mul", decode)),
        3: ("%fusion.3 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("mlp_up/dot_general", decode)),
        4: ("%fusion.4 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("attn_core/kv_attend/dot_general", chunk)),
        6: ("%fusion.6 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("ssm_core/state_scan/while/body/dot_general", chunk)),
        5: (f"jit_serve_decode_k4({decode})", []),
        7: (f"jit_serve_prefill_chunk_o256({chunk})", []),
    }
    ms = enc.MS
    half_u = int(update_ms * ms / 2)
    half_a = int(attend_ms * ms / 2)
    device = enc._plane("/device:TPU:0", [
        ("XLA Modules", 10**9, [(5, 0, 400 * ms, []),
                                (5, 400 * ms, 200 * ms, []),
                                (7, 600 * ms, 350 * ms, []),
                                (5, 2000 * ms, 10 * ms, [])]),
        ("XLA Ops", 10**9, [(2, 0, half_u, []), (1, 300 * ms, half_a, []),
                            (2, 400 * ms, half_u, []),
                            (1, 560 * ms, half_a, []),
                            (3, 580 * ms, 10 * ms, []),
                            (6, 600 * ms, int(scan_ms * ms), []),
                            (4, 900 * ms, 20 * ms, []),
                            (2, 2000 * ms, 5 * ms, [])]),
    ], metadata)
    host = enc._plane("/host:CPU", [
        ("python3", 10**9, [(1, 0, 1000 * ms, []), (2, 10 * ms, 1, [])]),
    ], {1: ("bench-window", []), 2: ("bench-sync", [])})
    return b"".join(enc._bytes(1, p) for p in (device, host))


def _traced(tmp_path, monkeypatch, update_ms, attend_ms, scan_ms,
            samples=SAMPLES):
    monkeypatch.setattr(named_ops, "ROOT", tmp_path)
    monkeypatch.setattr(named_ops, "_CACHE", {})
    scratch = tmp_path / ".bench_scratch" / CELL
    where = scratch / "plugins" / "profile" / "2026_10_04"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        _profile(update_ms, attend_ms, scan_ms))

    def begin(name, at_s, **args):
        return {"name": name, "ph": "B", "ts": at_s * 1e6, "tid": 1,
                "args": args}

    # the span file's clock: bench-sync at 5.0 s there is 10 ms on the
    # profile's, so 4.99 s is the window's start and 5.99 s its end
    events = [
        {"name": "bench-sync", "ph": "i", "ts": 5.0e6, "tid": 2},
        begin("serve-decode", 4.5, active=8, steps=1, unit=0),    # before
        begin("serve-prefill-chunk", 4.6, rid=0, chunk=0, seq=0),  # before
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


def test_roofline_readers_price_the_traced_slice_not_the_run(
        tmp_path, monkeypatch):
    run = _traced(tmp_path, monkeypatch, 200.0, 40.0, 100.0)
    loaded = named_ops.load(run)
    events = ssm_roofline.spans_in_window(run, loaded)
    assert ssm_roofline.traced_indices(events, "ssm_decode") == [1, 2]
    assert ssm_roofline.traced_indices(events, "kv_attend") == [1, 2]
    assert ssm_roofline.traced_indices(events, "ssm_prefill") == [1]
    for kernel, traced_s in (("ssm_decode", 0.200), ("kv_attend", 0.040),
                             ("ssm_prefill", 0.100)):
        share = ssm_roofline.read(run, kernel)
        assert share == pytest.approx(100 * _least(kernel) / traced_s,
                                      rel=1e-6)
        assert 0.0 < share < 100.0
    with pytest.raises(ValueError):
        ssm_roofline.read(run, "weight_pass")


def test_a_step_that_moves_exactly_the_least_bytes_reads_100(
        tmp_path, monkeypatch):
    """No share can pass 100%: the traced time of a step that moves
    exactly the bytes the function needs, at exactly the published rate,
    is the least time."""
    run = _traced(tmp_path, monkeypatch, 1e3 * _least("ssm_decode"),
                  1e3 * _least("kv_attend"), 1e3 * _least("ssm_prefill"))
    for kernel in ("ssm_decode", "kv_attend", "ssm_prefill"):
        assert ssm_roofline.read(run, kernel) == pytest.approx(100.0,
                                                               rel=1e-4)


def test_readers_find_nothing_where_the_program_has_no_such_names(
        tmp_path, monkeypatch):
    # a run without the samples and span arguments this PR added
    run = _traced(tmp_path, monkeypatch, 200.0, 40.0, 100.0, samples={})
    for kernel in ("ssm_decode", "kv_attend", "ssm_prefill"):
        assert ssm_roofline.read(run, kernel) is None
    untraced = SimpleNamespace(cell=run.cell, profile={}, samples={},
                               scalars={}, device=run.device)
    assert ssm_roofline.read(untraced, "ssm_decode") is None
    assert mfu_served.read(untraced, "flops_granite4h") is None
    monkeypatch.setattr(named_ops, "ROOT", tmp_path / "elsewhere")
    monkeypatch.setattr(named_ops, "_CACHE", {})
    assert ssm_roofline.read(run, "ssm_decode") is None
