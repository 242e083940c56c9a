"""The benchmark harness (``benchmarks/``) on the CPU at toy widths,
through its own code paths; the chip check is stubbed here, not by an
option of the harness.  What a run measures is only ever true on the
chip: these tests hold the yardstick's arithmetic and the data-driven
layout, not any number."""

from __future__ import annotations

import copy
import dataclasses
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import cells, flops, trace_reduce  # noqa: E402
from benchmarks.harness import traffic as traffic_gen      # noqa: E402
from benchmarks.harness.feed import NOT_YET, DueFeed       # noqa: E402

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOY_MODEL = dict(hidden_size=64, num_layers=2, num_heads=4,
                 ffn_intermediate=128, dtype="float32")


def _all_names() -> list[str]:
    names = [m["name"] for m in METRICS]
    names += [c["name"] for c in BENCH["configs"]]
    for w in BENCH["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for c in BENCH["configs"]:
        names += list(c["reduced"])
    return sorted(set(names))


@pytest.mark.parametrize("name", _all_names())
def test_names_use_the_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries_meet_the_contract(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    if metric["name"] in e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        # the metric it moves is reported wherever it is
        moved = e2e[metric["moves"]]
        for cell in metric.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS), (metric, cell)


def test_cells_and_chips_meet_the_contract():
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_from_data_alone(name):
    """Config, traffic, layer-metric files, runner and readers are all
    found by name: adding a cell edits no harness code."""
    cell = cells.resolve_cell(name)
    assert cell.config["program"]["model"]["hidden_size"] > 0
    assert callable(cells.runner_for(cell.traffic["kind"]))
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    for metric in cell.per_layer:
        assert callable(cells.reader_for(metric["reader"]))
    # no width is cut: what the program runs is what the source published
    published = cell.config["published"]
    model = cell.config["program"]["model"]
    for key, value in published.items():
        if key not in cell.config["reduced"]:
            assert model[key] == value, key
    declared = next(c for c in BENCH["configs"]
                    if c["name"] == cell.config_name)
    assert sorted(declared["reduced"]) == sorted(cell.config["reduced"])


def _serving_traffic(kind: str) -> dict:
    for path in sorted((cells.BENCH_DIR / "traffic").glob("*.json")):
        t = cells.load_json(path)
        if t["kind"] == kind:
            return t
    raise AssertionError(f"no traffic file of kind {kind}")


@pytest.mark.parametrize("kind", ["backlog", "paced"])
def test_traffic_same_seed_same_bytes_other_seed_same_work(kind):
    t = _serving_traffic(kind)
    a = traffic_gen.generate(t, 2**31 + 5, 80)
    b = traffic_gen.generate(t, 2**31 + 5, 80)
    c = traffic_gen.generate(t, 7, 80)
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(a) != json.dumps(c)
    # every seed gets the same sizes and gaps in the same order, so the
    # work compares across seeds; only the embeddings differ
    for key in ("prompt_len", "output_len", "arrival_s"):
        assert [r[key] for r in a] == [r[key] for r in c]
    assert [r["seed"] for r in a] != [r["seed"] for r in c]
    # ... and the order is a shuffle, not the sorted quantiles
    assert [r["prompt_len"] for r in a] != sorted(r["prompt_len"]
                                                  for r in a)
    lo, hi = t["prompt_range"]
    assert all(lo <= r["prompt_len"] <= hi for r in a)
    assert len({r["prompt_len"] for r in a}) > 10


def test_backlog_is_all_due_at_zero_and_fits_the_cache():
    cell = cells.resolve_cell("serve7b_backlog")
    records = traffic_gen.generate(
        cell.traffic, 3, traffic_gen.request_count(cell.traffic, 30.0))
    max_seq = cell.config["program"]["serving"]["max_seq"]
    assert all(r["arrival_s"] == 0.0 for r in records)
    assert all(r["prompt_len"] + r["output_len"] <= max_seq
               for r in records)
    queue = cell.config["program"]["serving"]["queue_capacity"]
    assert len(records) <= queue        # nothing is shed


def test_paced_gaps_are_the_exponential_quantiles():
    gaps = traffic_gen.exponential_gaps(1000, 4.0)
    assert gaps.mean() == pytest.approx(0.25, rel=0.01)
    assert (gaps[1:] >= gaps[:-1]).all()


class _Req:
    def __init__(self, rid, arrival_s):
        self.rid, self.arrival_s = rid, arrival_s


def test_feed_never_shows_a_request_before_it_is_due():
    clock = [float("-inf")]
    feed = DueFeed([_Req(1, 2.0), _Req(0, 0.5), _Req(2, 2.0)],
                   lambda: clock[0])
    assert feed and len(feed) == 3
    assert feed[0] is NOT_YET            # the engine's clock has not started
    with pytest.raises(IndexError):
        feed.popleft()
    clock[0] = 0.49
    assert feed[0] is NOT_YET and feed[0].arrival_s > 1e9
    clock[0] = 0.5
    assert feed[0].rid == 0 and feed.popleft().rid == 0
    assert feed[0] is NOT_YET and feed       # more will come; not now
    clock[0] = 2.0
    assert [feed.popleft().rid, feed.popleft().rid] == [1, 2]
    assert not feed


def test_trace_reduction_on_a_hand_made_timeline():
    # one device, window [0, 10]: a while op wraps two fusions, then a
    # lone copy; idle 0-1 (under "admission"), 4-6 (half under "decode",
    # which nests in "loop"), 8-10 (no span)
    events = [("while.1", 1.0, 3.0), ("fusion.a", 1.0, 1.0),
              ("fusion.b", 2.5, 1.5), ("copy.c", 6.0, 2.0)]
    spans = [("admission", 0.0, 1.0), ("loop", 3.0, 7.0),
             ("decode", 5.0, 6.5)]
    r = trace_reduce.reduce_timeline({"/device:TPU:0": events},
                                     (0.0, 10.0), spans)
    assert r["busy_s"] == pytest.approx(5.0)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["op_seconds"] == pytest.approx(
        {"while.1": 0.5, "fusion.a": 1.0, "fusion.b": 1.5, "copy.c": 2.0})
    assert trace_reduce.top(r["op_seconds"], 1) == [["copy.c", 2.0]]
    assert r["idle_gaps"] == pytest.approx(
        {"admission": 1.0, "loop": 1.0, "decode": 1.0,
         trace_reduce.NO_SPAN: 2.0})
    # two devices: busy and op times are means over them; events are
    # clipped to the window
    r2 = trace_reduce.reduce_timeline(
        {"/device:TPU:0": events, "/device:TPU:1": [("copy.c", 9.0, 5.0)]},
        (0.0, 10.0))
    assert r2["busy_s"] == pytest.approx(3.0)
    assert r2["op_seconds"]["copy.c"] == pytest.approx(1.5)
    assert trace_reduce.reduce_timeline({}, (0.0, 1.0)) == {}


@pytest.mark.parametrize("text, label", [
    ("%fusion.317 = bf16[24,8192,2048]{2,1,0:T(8,128)(2,1)} fusion(bf16[8]"
     " %p), kind=kOutput", "fusion.317_bf16_24_8192_2048"),
    ("%closed_call.14 = (bf16[128,512,128]{2,1,0:T(8,128)(2,1)S(1)}, "
     "f32[128,512,128]{2,1,0}) custom-call(bf16[128,512,128] %b), "
     'custom_call_target="tpu_custom_call"',
     "closed_call.14_bf16_128_512_128.tpu_custom_call"),
    ("%while.6 = (s32[]{:T(128)}, bf16[8,512,2048]{2,1,0}) while(%t)",
     "while.6_s32"),
    ("step-wait", "step-wait"),
])
def test_op_names_as_printed(text, label):
    assert trace_reduce.clean_name(text) == label


def test_flops_match_a_hand_count_for_the_1b_block():
    model = cells.resolve_cell("train1b_step").config["program"]["model"]
    h, f, seq = 2048, 8192, 512
    # per token, per layer: qkv 2*h*3h, out 2*h*h, ffn 2*2*h*f; causal
    # attention 4*h*(seq+1)/2
    per_layer = 6 * h * h + 2 * h * h + 4 * h * f + 2 * h * (seq + 1)
    assert per_layer == 102_764_544
    assert flops.forward_flops_per_token(model, seq) == 24 * per_layer
    assert flops.train_flops_per_token(model, seq) == 3 * 24 * per_layer
    # flash kernels, forward + backward, all heads and layers: 12 FLOPs
    # a pair and head-dim element
    pairs = seq * (seq + 1) // 2
    assert flops.flash_flops(model, 8, seq, 16, backward=True) == \
        24 * 8 * 16 * 12 * pairs * 128
    assert flops.flash_bytes(model, 8, seq, 16, backward=False) == \
        24 * 8 * 16 * seq * 128 * 2 * 4


def _toy(name: str, traffic_name: str = "") -> cells.Cell:
    cell = cells.resolve_cell(name)
    config = copy.deepcopy(cell.config)
    config["program"]["model"].update(TOY_MODEL)
    traffic = (cells.load_json(cells.BENCH_DIR / "traffic"
                               / f"{traffic_name}.json")
               if traffic_name else copy.deepcopy(cell.traffic))
    if "serving" in config["program"]:
        config["program"]["serving"].update(
            max_batch=4, max_seq=128, block_size=8, prefill_chunk=16)
        traffic.update(prompt_range=[8, 96], output_range=[4, 32],
                       backlog_rps=8, rate_rps=10,
                       warmup_prompt_stride=16, trace_start_s=0.2,
                       trace_seconds=0.5)
    else:
        traffic.update(batch_size=4, sequence_length=32)
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.fixture()
def harness(monkeypatch, tmp_path):
    """The harness with the chip check stubbed and the peaks table
    knowing the CPU, so that the per-layer readers run."""
    from benchmarks.harness import device, peaks

    monkeypatch.setattr(device, "require_chips", lambda chips: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    return device.CompileCounter(), str(tmp_path)


CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("name", [c for c in CELLS
                                  if "serve" not in c])
def test_job_runner_prints_the_contract_line(name, harness):
    from benchmarks.harness.result import result_line

    compiles, scratch = harness
    cell = _toy(name)
    run = cells.runner_for("job")(cell, 2**31 + 3, 0.5, False, compiles,
                                  scratch)
    assert run.correct, run.faults
    line = json.loads(result_line(run, setup_s=1.0, trace=False))
    assert set(line) == CONTRACT_KEYS
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["tokens_per_s"]["value"] > 0
    assert line["attempted"] == run.scalars["steps"] >= 2
    traced = cells.runner_for("job")(cell, 2**31 + 3, 0.5, True, compiles,
                                     scratch)
    layer = json.loads(result_line(traced, setup_s=1.0, trace=True))
    # no device plane on the CPU: the trace readers find nothing and
    # their metrics are left out, the others are there
    assert "step.ms_p50.job" in layer["metrics"]
    assert "device.idle_share.job" not in layer["metrics"]
    assert set(layer["metrics"]) <= {m["name"] for m in cell.per_layer}


@pytest.mark.parametrize("traffic_name", ["docs_backlog",
                                          "chat_short_paced"])
def test_serving_runner_serves_every_request_on_time(traffic_name,
                                                     harness):
    from benchmarks.harness.result import result_line

    compiles, scratch = harness
    cell = _toy("serve7b_backlog", traffic_name)
    run = cells.runner_for(cell.traffic["kind"])(
        cell, 11, 2.0, False, compiles, scratch)
    assert run.correct, run.faults
    assert run.failed == 0 and run.attempted == run.scalars["requests"]
    assert len(run.samples["ttft_s"]) == run.attempted
    # no request was taken off the feed before it was due
    assert min(run.samples["arrival_late_s"]) >= 0.0
    assert min(run.samples["ttft_s"]) > 0.0
    assert run.values["out_tokens_per_s"] > 0
    line = json.loads(result_line(run, setup_s=1.0, trace=False))
    assert set(line) == CONTRACT_KEYS
    assert line["metrics"]["setup_s"] == {"value": 1.0, "unit": "s"}


def test_no_chip_is_an_error_not_a_cpu_run(monkeypatch):
    from benchmarks.harness import device
    from dlbb_tpu.utils import simulate

    monkeypatch.setattr(simulate, "_SIMULATION_FORCED", False)
    with pytest.raises(simulate.NoAcceleratorError):
        device.require_chips(1)
    monkeypatch.setattr(simulate, "_SIMULATION_FORCED", True)
    with pytest.raises(device.WrongDeviceError):
        device.require_chips(64)
