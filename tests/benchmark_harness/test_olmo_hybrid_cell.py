"""The ``olmohyb_longgen_backlog`` cell's own pieces on the CPU at toy
widths: it resolves from data; ``kind_backlog_checked`` runs end to end
with the chip check stubbed and decides ``correct`` by logits; an engine
that is wrong in the ways the check exists for is not correct; the
FLOPs and bytes against a hand count; the two roofline readers on a
hand-made profile.  No number here is a measurement."""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import test_named_readers as enc                               # noqa: E402
from benchmarks.harness import cells, flops_olmo_hybrid as counts  # noqa: E402
from benchmarks.harness import kind_backlog_checked             # noqa: E402
from benchmarks.readers import lin_roofline, mfu_served, named_ops  # noqa: E402

CELL = "olmohyb_longgen_backlog"
TOY_MODEL = dict(hidden_size=64, num_layers=8, num_heads=4, num_kv_heads=4,
                 ffn_intermediate=96, vocab_size=256, dtype="float32",
                 linear_num_key_heads=4, linear_num_value_heads=4,
                 linear_key_head_dim=8, linear_value_head_dim=16)


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


def test_cell_resolves_from_data_and_claims_what_the_issue_names():
    cell = cells.resolve_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "backlog_checked"
    assert cells.runner_for("backlog_checked") is kind_backlog_checked.run
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    serving = cell.config["program"]["serving"]
    assert cell.traffic["warmup_prompt_stride"] == serving["prefill_chunk"]
    assert (cell.traffic["prompt_range"][1] + cell.traffic["output_range"][1]
            <= serving["max_seq"])
    assert len(cell.traffic["check_rids"]) == 2
    assert [m["name"] for m in cell.per_layer] == [
        "step.linattn_share.olmohyb", "step.fullattn_share.olmohyb",
        "step.state_update_share.olmohyb", "step.lm_head_share.olmohyb",
        "step.decode_device_share.olmohyb",
        "step.prefill_device_share.olmohyb", "device.idle_share.olmohyb",
        "kernel.top_share.olmohyb", "sched.batch_occupancy.olmohyb",
        "step.decode_ms_p50.olmohyb", "step.mfu.olmohyb",
        "kernel.lin_decode_roofline.olmohyb",
        "kernel.lin_prefill_roofline.olmohyb",
        "step.kv_update_share.olmohyb", "sched.admission_idle_share.olmohyb",
        "sched.embed_idle_share.olmohyb", "step.prefill_share.olmohyb"]
    # every width as the catalog's config.json has it, at the top level
    # of the file too; only the depth differs
    top, model = cell.config, cell.config["program"]["model"]
    assert cell.config["reduced"].keys() == {"num_layers"}
    for ours, theirs in [("hidden_size", "hidden_size"),
                         ("ffn_intermediate", "intermediate_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("vocab_size", "vocab_size"),
                         ("linear_key_head_dim", "linear_key_head_dim"),
                         ("linear_value_head_dim", "linear_value_head_dim"),
                         ("linear_num_key_heads", "linear_num_key_heads"),
                         ("linear_conv_kernel_dim", "linear_conv_kernel_dim")]:
        assert model[ours] == top[theirs], ours
    assert model["layer_types"] * 8 == top["layer_types"]
    assert model["num_layers"] * 2 == top["num_hidden_layers"]


def test_checked_runner_decides_correct_by_logits(harness, capsys):
    from benchmarks.harness.result import result_line

    compiles, scratch = harness
    cell = _toy()
    run = cells.runner_for("backlog_checked")(cell, 2**31 + 11, 2.0, False,
                                              compiles, scratch)
    assert run.correct, run.faults
    assert run.failed == 0 and run.attempted == 16
    # float32 toy against the float32 reference: far inside the limits
    for name in ("prefill", "decode", "decode_step", "state"):
        assert 0.0 < run.scalars[f"{name}_rel_l2"] < 1e-3, name
    err = capsys.readouterr().err
    assert "recycled=False" in err and "recycled=True" in err
    assert f"decode {run.scalars['decode_rel_l2']:.5f} (limit " \
        f"{kind_backlog_checked.DECODE_MEAN_REL_L2_MAX})" in err
    assert f"state {run.scalars['state_rel_l2']:.5f} (limit " \
        f"{kind_backlog_checked.STATE_REL_L2_MAX})" in err
    line = json.loads(result_line(run, setup_s=1.0, trace=False))
    assert line["correct"] and set(line["metrics"]) == {"out_tokens_per_s",
                                                        "setup_s"}
    layer = json.loads(result_line(run, setup_s=1.0, trace=True))
    assert 0.0 < layer["metrics"]["step.mfu.olmohyb"]["value"] < 100.0
    # no device plane on the CPU: the trace readers leave theirs out
    assert "kernel.lin_decode_roofline.olmohyb" not in layer["metrics"]


@pytest.mark.parametrize("fault", ["state_not_reset", "decay_skipped",
                                   "state_bfloat16"])
def test_a_wrong_engine_is_not_correct(fault, harness, monkeypatch):
    import jax.numpy as jnp

    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid

    cell, limit = _toy(), "prefill"
    if fault == "state_not_reset":
        # what a recycled slot would hand a new request: a state that is
        # not zero (stood in for by ones: any left-over state will do)
        fresh = serve_hybrid.create_prefix

        def stale(config, mesh):
            k, v, state, conv = fresh(config, mesh)
            return k, v, jnp.ones_like(state), conv

        monkeypatch.setattr(serve_hybrid, "create_prefix", stale)
    elif fault == "decay_skipped":
        gates = hybrid.linear_gates

        def no_decay(x, layer, config):
            log_alpha, beta = gates(x, layer, config)
            return jnp.zeros_like(log_alpha), beta

        monkeypatch.setattr(hybrid, "linear_gates", no_decay)
    else:
        # the nearest precision below the configuration's: the state
        # rounded at every decode step.  It moves the logits little (on
        # the chip they cannot tell it from float32, PERF.md section 6)
        # and the state itself by several times its limit, once the
        # answers are as long as the cell's
        monkeypatch.setattr(hybrid, "STATE_DTYPE", jnp.bfloat16)
        cell, limit = _toy(prompt_range=[8, 24], output_range=[64, 100]), \
            "state"
    compiles, scratch = harness
    run = cells.runner_for("backlog_checked")(cell, 7, 2.0, False,
                                              compiles, scratch)
    assert run.failed == 0        # every request was served, and wrongly
    assert not run.correct
    assert any(f.startswith(limit) and "from the reference" in f
               for f in run.faults), run.faults


@pytest.mark.parametrize("fault, said", [
    ("none", None),
    ("conv_transposed", "periods[1].lin_conv has shape"),
    ("decay_constant", "periods[0].A_log: exp(A_log) spans"),
    ("kernel_doubled", "periods[3].wq has mean"),
    ("scale_missing", "periods[2].o_norm is missing"),
])
def test_the_reference_judges_the_weights_it_is_handed(fault, said):
    """``weight_faults`` knows the tree's shapes and statistics from the
    configuration's sizes alone, so a fault in the program's initialiser
    or layout is not shared by both sides of the comparison."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import olmo_hybrid as reference
    from dlbb_tpu.models import init_params
    from dlbb_tpu.models.configs import ModelConfig

    model = _toy().config["program"]["model"]
    params = init_params(ModelConfig.from_dict(model), jax.random.key(3))
    periods = [dict(p) for p in params["periods"]]
    if fault == "conv_transposed":
        periods[1]["lin_conv"] = jnp.swapaxes(periods[1]["lin_conv"], 2, 3)
    elif fault == "decay_constant":
        periods[0]["A_log"] = jnp.full_like(periods[0]["A_log"], -1.0)
    elif fault == "kernel_doubled":
        periods[3]["wq"] = 2 * periods[3]["wq"]
    elif fault == "scale_missing":
        del periods[2]["o_norm"]
    faults = reference.weight_faults({**params, "periods": tuple(periods)},
                                     model)
    if said is None:
        assert faults == []
    else:
        assert len(faults) == 1 and said in faults[0], faults


def test_flops_and_bytes_match_a_hand_count():
    model = cells.resolve_cell(CELL).config["program"]["model"]
    assert counts.layer_counts(model) == (12, 4)
    h, f, v = 3840, 11008, 100352
    mlp = 6 * h * f
    full = 8 * h * h
    # q, k (30 x 96 each), v and the output gate (30 x 192 each), out,
    # and the two gate vectors
    linear = 2 * h * (2880 + 2880 + 5760 + 5760 + 5760 + 60)
    assert counts.token_matmul_flops(model) == 16 * mlp + 4 * full \
        + 12 * linear
    assert counts.delta_rule_flops(model, 10) == 12 * 10 * 30 * 7 * 96 * 192
    fed = 300 + 100 - 1
    assert counts.request_flops(model, 300, 100) == pytest.approx(
        fed * counts.token_matmul_flops(model)
        + counts.delta_rule_flops(model, fed)
        + 4 * 4 * h * fed * (fed + 1) / 2 + 100 * 2 * h * v)
    # 6.7 GFLOP a token before attention and head, as ISSUE 27 reckons
    assert counts.token_matmul_flops(model) == pytest.approx(6.7e9, rel=0.02)
    assert counts.state_bytes(model) == 30 * 192 * 96 * 4
    assert counts.conv_bytes(model) == 3 * 11520 * 2
    assert counts.decode_step_state_bytes(model, 32) == \
        12 * 32 * 2 * (2211840 + 69120)
    assert counts.prefill_scan_bytes(model, 512, 1) == \
        12 * (512 * 30 * (2 * 96 + 2 * 192) * 2 + 2 * 2211840)


# -- the roofline readers on a hand-made profile -------------------------------

PID = 4242


def _profile() -> bytes:
    """One device, window 0..100 ms.  A fused scan of 4 steps (20 ms of
    ``state_update`` in it), two single steps (5 ms each), one prompt
    chunk (10 ms of ``state_scan`` in it) and a step outside the window;
    ``bench-sync`` at 10 ms."""
    def op(scope):
        return [enc._stat(2, PID), enc._stat(1, f"jit(x)/while/body/{scope}")]

    metadata = {
        1: ("%fusion.1 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("lin_core/state_update/mul")),
        2: ("%fusion.2 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("lin_core/state_scan/dot_general")),
        3: ("%fusion.3 = f32[4,4]{1,0} fusion(%p0), kind=kLoop",
            op("mlp_up/dot_general")),
        5: (f"jit_serve_decode_k4({PID})", []),
        6: (f"jit_serve_decode_step({PID})", []),
        7: (f"jit_serve_prefill_chunk_o16({PID})", []),
    }
    ms = enc.MS
    device = enc._plane("/device:TPU:0", [
        ("XLA Modules", 10**9, [(5, 0, 40 * ms, []), (6, 40 * ms, 10 * ms, []),
                                (6, 50 * ms, 10 * ms, []),
                                (7, 60 * ms, 30 * ms, []),
                                (6, 200 * ms, 10 * ms, [])]),
        ("XLA Ops", 10**9, [(1, 0, 20 * ms, []), (3, 20 * ms, 20 * ms, []),
                            (1, 40 * ms, 5 * ms, []), (1, 50 * ms, 5 * ms, []),
                            (2, 60 * ms, 10 * ms, []),
                            (1, 200 * ms, 5 * ms, [])]),
    ], metadata)
    host = enc._plane("/host:CPU", [
        ("python3", 10**9, [(1, 0, 100 * ms, []), (2, 10 * ms, 1, [])]),
    ], {1: ("bench-window", []), 2: ("bench-sync", [])})
    return b"".join(enc._bytes(1, p) for p in (device, host))


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    monkeypatch.setattr(named_ops, "ROOT", tmp_path)
    monkeypatch.setattr(named_ops, "_CACHE", {})
    scratch = tmp_path / ".bench_scratch" / CELL
    where = scratch / "plugins" / "profile" / "2026_09_30"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_profile())
    # the span file's clock: bench-sync at 5.0 s there is 1.010 s on the
    # profile's, so 4.99 s is the window's start and 5.09 s its end
    def begin(name, at_s, **args):
        return {"name": name, "ph": "B", "ts": at_s * 1e6, "tid": 1,
                "args": args}

    events = [
        {"name": "bench-sync", "ph": "i", "ts": 5.0e6, "tid": 2},
        begin("serve-decode", 4.5, active=4, steps=1),        # before it
        begin("serve-decode", 4.995, active=3, steps=4),
        begin("serve-decode", 5.03, active=2, steps=1),
        begin("serve-decode", 5.04, active=2, steps=1),
        begin("serve-prefill-chunk", 5.05, rid=1, chunk=1),
        begin("serve-prefill-chunk", 5.5, rid=1, chunk=2),    # after it
    ]
    (scratch / "spans.json").write_text(json.dumps({"traceEvents": events}))
    cell = _toy()
    cell = dataclasses.replace(cell, traffic={**cell.traffic,
                                              "order_seed": 0})
    return SimpleNamespace(cell=cell, seconds=2.0,
                           device={"kind": "TPU v5 lite"},
                           profile={"busy_s": 0.09, "window_s": 0.1})


def test_traced_work_counts_the_slice_not_the_run(traced):
    from benchmarks.harness import traffic as traffic_gen

    loaded = named_ops.load(traced)
    work = lin_roofline.traced_work(traced, loaded)
    assert work["decode_steps"] == 4 + 1 + 1 and work["chunks"] == 1
    assert work["active_slots"] == pytest.approx((3 * 4 + 2 + 2) / 6)
    prompt = {r["rid"]: r["prompt_len"] for r in traffic_gen.generate(
        traced.cell.traffic, 0, 16)}[1]
    assert work["prompt_tokens"] == max(0, min(16, prompt - 16))


def test_roofline_readers_on_a_hand_made_profile(traced):
    model = traced.cell.config["program"]["model"]
    loaded = named_ops.load(traced)
    work = lin_roofline.traced_work(traced, loaded)
    # decode: 6 steps x 6 linear layers x mean active slots x state and
    # convolution inputs read and written, over 819 GB/s and 30 ms traced
    state = 4 * 16 * 8 * 4 + 3 * 4 * (2 * 8 + 16) * 2
    least = 6 * 6 * work["active_slots"] * 2 * state / 819.0e9
    assert lin_roofline.read(traced, "decode") == pytest.approx(
        100 * least / 0.030)
    # prefill: bytes bound at these widths; 10 ms traced
    tokens = work["prompt_tokens"]
    bytes_ = 6 * (tokens * 4 * (2 * 8 + 2 * 16) * 2 + 2 * 4 * 16 * 8 * 4)
    flops = 6 * tokens * 4 * 7 * 8 * 16
    least = max(bytes_ / 819.0e9, flops / 197.0e12)
    assert lin_roofline.read(traced, "prefill") == pytest.approx(
        100 * least / 0.010)
    with pytest.raises(ValueError):
        lin_roofline.read(traced, "verify")


def test_readers_find_nothing_where_the_program_has_no_such_names(
        traced, tmp_path, monkeypatch):
    # a profile without the scopes (the parent's): nothing, no raise
    untraced = SimpleNamespace(cell=traced.cell, profile={}, samples={},
                               scalars={}, device=traced.device)
    assert lin_roofline.read(untraced, "decode") is None
    assert mfu_served.read(untraced, "flops_olmo_hybrid") is None
    monkeypatch.setattr(named_ops, "ROOT", tmp_path / "elsewhere")
    monkeypatch.setattr(named_ops, "_CACHE", {})
    assert lin_roofline.read(traced, "prefill") is None
