"""The two ``step.kv_attend_share.*`` metrics (PR 28): data files for the
reader the benchmark has, each read from a hand-made profile of a decode
program with the ``kv_attend_decode`` kernel in it, and from one without
(a parent's), which reads nothing and does not raise.  And what
``test_olmo_hybrid_cell.py::test_cell_resolves_from_data_and_claims_what_
the_issue_names`` holds the hybrid cell to, kept here: that test pins the
cell's per-layer metrics to PR 27's exact list, fails at that line since
this PR adds one, and so no longer reaches the lines after it."""

from __future__ import annotations

from types import SimpleNamespace

import pytest
# the protobuf encoder of the readers' own test, which also puts the
# repo's root on the path
from test_named_readers import MS, _bytes, _plane, _stat

from benchmarks.harness import cells
from benchmarks.readers import named_ops, trace_scope_share

METRICS = {"step.kv_attend_share.backlog": "serve7b_backlog",
           "step.kv_attend_share.olmohyb": "olmohyb_longgen_backlog"}
PID = 4242


def _xspace(with_kernel: bool) -> bytes:
    """One device, window 0..100 ms: ``jit_serve_decode_step`` runs 0-80
    ms, a 50 ms weight fusion under ``mlp_up`` and a 20 ms op under
    ``attn_core/kv_attend``: the kernel, found by its scope, or (a
    parent's program) nothing under that scope at all."""
    scope = "jit(serve_decode_step)/while/body/attn_core"
    kernel = ('%kv_attend_decode.12 = bf16[32,32,128]{2,1,0} custom-call('
              '%p0), custom_call_target="tpu_custom_call"',
              [_stat(2, PID), _stat(1, f"{scope}/kv_attend/shard_map/"
                                       "kv_attend_decode/pallas_call:")])
    other = ("%fusion.9 = bf16[32,1,3840]{2,1,0} fusion(%p0), kind=kOutput",
             [_stat(2, PID), _stat(1, f"{scope}/dot_general:")])
    metadata = {
        1: ("%fusion.7 = bf16[32,11008]{1,0} fusion(%p0), kind=kOutput",
            [_stat(2, PID),
             _stat(1, "jit(serve_decode_step)/while/body/mlp_up/"
                      "dot_general:")]),
        2: kernel if with_kernel else other,
        3: (f"jit_serve_decode_step({PID})", []),
    }
    device = _plane("/device:TPU:0", [
        ("XLA Modules", 10**9, [(3, 0, 80 * MS, [])]),
        ("XLA Ops", 10**9, [(1, 0, 50 * MS, []), (2, 55 * MS, 20 * MS, [])]),
    ], metadata)
    host = _plane("/host:CPU", [
        ("python3", 10**9, [(1, 0, 100 * MS, [])])], {1: ("bench-window", [])})
    return b"".join(_bytes(1, p) for p in (device, host))


def _traced(tmp_path, monkeypatch, with_kernel):
    monkeypatch.setattr(named_ops, "ROOT", tmp_path)
    monkeypatch.setattr(named_ops, "_CACHE", {})
    where = (tmp_path / ".bench_scratch" / "toy_cell" / "plugins"
             / "profile" / "2026_10_01")
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_xspace(with_kernel))
    cell = SimpleNamespace(name="toy_cell", traffic={})
    return SimpleNamespace(cell=cell,
                           profile={"busy_s": 0.07, "window_s": 0.1})


@pytest.mark.parametrize("metric, cell_name", sorted(METRICS.items()))
def test_metric_is_declared_for_its_cell_alone(metric, cell_name):
    entry = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}[metric]
    assert entry["workloads"] == [cell_name]
    assert (entry["layer"], entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == ("model step", "%", "lower",
                                 "out_tokens_per_s", "device_trace")
    mine = [m for m in cells.resolve_cell(cell_name).per_layer
            if m["name"] == metric]
    assert len(mine) == 1 and mine[0]["reader"] == "trace_scope_share"
    assert cells.reader_for(mine[0]["reader"]) is trace_scope_share.read
    for other in cells.load_benchmark()["workloads"]:
        if other["name"] != cell_name:
            assert metric not in [
                m["name"] for m in cells.resolve_cell(other["name"]).per_layer]


@pytest.mark.parametrize("metric, cell_name", sorted(METRICS.items()))
def test_metric_reads_the_kernel_by_its_scope(metric, cell_name, tmp_path,
                                              monkeypatch):
    args = next(m for m in cells.resolve_cell(cell_name).per_layer
                if m["name"] == metric)["args"]
    run = _traced(tmp_path, monkeypatch, with_kernel=True)
    # 20 ms under kv_attend of 70 ms busy
    assert trace_scope_share.read(run, **args) == pytest.approx(100 * 20 / 70)


@pytest.mark.parametrize("metric, cell_name", sorted(METRICS.items()))
def test_metric_reads_nothing_where_no_op_is_under_the_scope(
        metric, cell_name, tmp_path, monkeypatch):
    args = next(m for m in cells.resolve_cell(cell_name).per_layer
                if m["name"] == metric)["args"]
    run = _traced(tmp_path, monkeypatch, with_kernel=False)
    assert trace_scope_share.read(run, **args) is None


PR27_METRICS = [
    "step.linattn_share.olmohyb", "step.fullattn_share.olmohyb",
    "step.state_update_share.olmohyb", "step.lm_head_share.olmohyb",
    "step.decode_device_share.olmohyb", "step.prefill_device_share.olmohyb",
    "device.idle_share.olmohyb", "kernel.top_share.olmohyb",
    "sched.batch_occupancy.olmohyb", "step.decode_ms_p50.olmohyb",
    "step.mfu.olmohyb", "kernel.lin_decode_roofline.olmohyb",
    "kernel.lin_prefill_roofline.olmohyb", "step.kv_update_share.olmohyb",
    "sched.admission_idle_share.olmohyb", "sched.embed_idle_share.olmohyb",
    "step.prefill_share.olmohyb"]


def test_olmo_hybrid_cell_still_resolves_as_the_pinned_test_holds_it():
    """Every assertion of the accepted test but the exact list: the
    cell's metrics are PR 27's, in their order, and after them only this
    PR's; the runner, the traffic's limits, the two probed requests, the
    one reduced key, and every Olmo-Hybrid width of the program equal to
    the published key at the file's top level."""
    from benchmarks.harness import kind_backlog_checked

    cell = cells.resolve_cell("olmohyb_longgen_backlog")
    assert cell.chips == 1 and cell.traffic["kind"] == "backlog_checked"
    assert cells.runner_for("backlog_checked") is kind_backlog_checked.run
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    serving = cell.config["program"]["serving"]
    assert cell.traffic["warmup_prompt_stride"] == serving["prefill_chunk"]
    assert (cell.traffic["prompt_range"][1] + cell.traffic["output_range"][1]
            <= serving["max_seq"])
    assert len(cell.traffic["check_rids"]) == 2
    names = [m["name"] for m in cell.per_layer]
    assert names[:len(PR27_METRICS)] == PR27_METRICS
    assert names[len(PR27_METRICS):] == ["step.kv_attend_share.olmohyb"]
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
