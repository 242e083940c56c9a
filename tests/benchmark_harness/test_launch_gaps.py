"""``readers/launch_gaps.py`` on hand-made module events and spans: the
idle gaps of one device split into launch, notice and host time by the
launch numbers the program's spans carry, a decode unit's own device
time, the eight metrics that read it, and the cells that get none.

One timeline serves most cases (seconds on the profile's clock, window
1.0 to 2.0):

    launch span (number, program)       execution            op gaps
    -                                   inject   1.000-1.005 (no launch)
    3 decode_step  k=1   0.95 (before)  A        1.02-1.12   1.005-1.02
    4 prefill_chunk_o0   1.18           B        1.20-1.40   1.12-1.20
    5 decode_k16   k=16  1.25           C        1.40-1.72   -
    -                                   a slice  1.78-1.79   1.72-1.78
    6 decode_step  k=1   1.85           D        1.90-1.95   1.79-1.90
                                                             1.95-2.00
    serve-decode-sync {launch 3} 0.96-1.15, {launch 5} 1.26-1.80

and launches 0 to 2 long before, whose executions the profile did not
catch.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import test_named_readers as enc                                # noqa: E402
from benchmarks.harness import cells, trace_reduce               # noqa: E402
from benchmarks.readers import launch_gaps, named_ops            # noqa: E402

WINDOW = (1.0, 2.0)
STEP, CHUNK, K16 = ("jit_serve_decode_step", "jit_serve_prefill_chunk_o0",
                    "jit_serve_decode_k16")
MODULES = [("jit_serve_inject", 1.000, 0.005), (STEP, 1.02, 0.10),
           (CHUNK, 1.20, 0.20), (K16, 1.40, 0.32),
           ("jit_dynamic_slice", 1.78, 0.01), (STEP, 1.90, 0.05)]
# one op a program: the device is busy exactly while a program runs
OPS = [(f"fusion.{i}", start, dur)
       for i, (_p, start, dur) in enumerate(MODULES)]


def _launch(number, program, at, name="serve-launch", **args):
    return (name, at, at + 0.001, {**args, "launch": number,
                                   "program": program})


SPANS = [
    _launch(0, STEP, 0.10, "serve-decode-dispatch", k=1),
    _launch(1, CHUNK, 0.20, "serve-prefill-chunk", rid=0, chunk=0, seq=0),
    _launch(2, STEP, 0.30, "serve-decode-dispatch", k=1),
    _launch(3, STEP, 0.95, "serve-decode-dispatch", k=1),
    ("serve-decode-sync", 0.96, 1.15, {"k": 1, "launch": 3}),
    _launch(4, CHUNK, 1.18, "serve-prefill-chunk", rid=1, chunk=0, seq=1),
    _launch(5, K16, 1.25, "serve-decode-dispatch", k=16),
    ("serve-decode-sync", 1.26, 1.80, {"k": 16, "launch": 5}),
    _launch(6, STEP, 1.85, "serve-decode-dispatch", k=1),
    # spans that name no launch and are no wait change nothing
    ("serve-decode", 1.84, 1.99, {"active": 4, "steps": 1, "unit": 3}),
]
INSIDE = {4, 5, 6}          # launch 3 was made before the window opened


def _split(modules=MODULES, ops=OPS, spans=SPANS, inside=INSIDE):
    return launch_gaps.split(modules, ops, WINDOW, spans, inside)


def _only(*gap, spans=SPANS):
    """The split of ONE gap of the timeline: everything else busy."""
    g0, g1 = gap
    ops = [("before", WINDOW[0], g0 - WINDOW[0]),
           ("after", g1, WINDOW[1] - g1)]
    found = launch_gaps.split(MODULES, ops, WINDOW, spans)
    return tuple(round(found[f"{part}_s"], 9)
                 for part in ("launch", "notice", "host"))


def test_a_gap_wholly_after_the_launch_is_launch_time():
    # launch 3 was called at 0.95, its program starts at 1.02
    assert _only(1.005, 1.02) == (0.015, 0.0, 0.0)


def test_a_gap_split_by_the_launch_is_notice_host_and_launch():
    # free at 1.12, the wait returns at 1.15, launch 4 begins at 1.18
    assert _only(1.12, 1.20) == (0.02, 0.03, 0.03)


def test_a_gap_inside_a_wait_with_the_next_launch_not_begun_is_notice():
    assert _only(1.72, 1.78) == (0.0, 0.06, 0.0)
    # the same gap once the wait is no ``serve-*-sync`` span: host time
    renamed = [(("serve-decode", *sp[1:]) if sp[3] == {"k": 16, "launch": 5}
                else sp) for sp in SPANS]
    assert _only(1.72, 1.78, spans=renamed) == (0.0, 0.0, 0.06)


def test_a_gap_with_no_span_and_no_next_program_is_host_time():
    assert _only(1.95, 2.00) == (0.0, 0.0, 0.05)


def test_a_launch_made_before_the_window_pairs_with_its_execution_inside():
    found = _split()
    # the three launches long before stay unpaired, launch 3 pairs
    assert found["launches_in_file"] == 7
    assert found["paired"] == 4
    # so its unit's device time is known, though the window did not see
    # its launch: inside the window only by the caller's word
    assert sorted(found["decode_step_ms"]) == pytest.approx([20.0, 50.0])
    everything = _split(inside=None)
    assert sorted(everything["decode_step_ms"]) == pytest.approx(
        [20.0, 50.0, 100.0])


def test_an_execution_with_no_launch_is_left_out_and_counted():
    found = _split()
    assert found["executions"] == 5          # the slice is no serve program
    assert found["unpaired_executions"] == 1
    assert found["unpaired_in_window"] == 1
    # it stays device busy time all the same
    assert found["idle_s"] == pytest.approx(0.315)
    served = [m for m in MODULES if m[0] != "jit_serve_inject"]
    clean = _split(modules=served)
    assert clean["unpaired_executions"] == 0 and clean["paired"] == 4


def test_the_three_parts_sum_to_the_idle_of_reduce_timeline():
    found = _split()
    host = [(name, start, end) for name, start, end, _a in SPANS]
    reduced = trace_reduce.reduce_timeline({"/device:TPU:0": OPS}, WINDOW,
                                           host)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert idle == pytest.approx(sum(reduced["idle_gaps"].values()))
    parts = [found[f"{part}_s"] for part in launch_gaps.SHARES]
    assert sum(parts) == pytest.approx(idle)
    assert found["idle_s"] == pytest.approx(idle)
    assert parts == pytest.approx([0.085, 0.10, 0.13])
    assert found["window_s"] == pytest.approx(1.0)
    # the longest gap, and what it was made of
    big = found["largest_gap"]
    assert (big["seconds"], big["next_program"], big["next_launch"]) == \
        (pytest.approx(0.11), STEP, 6)
    assert (big["launch"], big["notice"], big["host"]) == \
        pytest.approx((0.05, 0.01, 0.05))


def test_a_k16_units_step_is_its_duration_over_16():
    found = _split(inside={5})
    assert found["decode_step_ms"] == pytest.approx([320.0 / 16])


def test_gaps_between_the_ops_of_a_running_program_count_as_launch():
    # C runs 1.40-1.72 with a hole of 10 ms in the middle of it
    ops = [op for op in OPS if op[1] != 1.40] + [
        ("c.first", 1.40, 0.10), ("c.second", 1.51, 0.21)]
    found = _split(ops=ops)
    assert found["inside_program_s"] == pytest.approx(0.01)
    assert found["launch_s"] == pytest.approx(0.085 + 0.01)
    assert found["notice_s"] == pytest.approx(0.10)


def test_the_offset_is_the_one_the_waits_agree_with():
    """Five launches of one program a second apart, each waited for
    till it ends; the profile caught two executions.  Names fit at every
    offset: the waits say which."""
    launches = [(n, "jit_serve_p") for n in range(5)]
    waited = {n: n + 0.52 for n in range(5)}         # each ends at n + 0.5
    caught = [("jit_serve_p", 1.1, 0.4), ("jit_serve_p", 2.1, 0.4),
              ("jit_serve_p", 3.1, 0.4)]
    assert launch_gaps.pair(launches, caught, waited) == (0, 1)
    # one wait that stalled for seconds does not move the median
    assert launch_gaps.pair(launches, caught, {**waited, 3: 9.0}) == (0, 1)
    # an execution ahead of them that nothing launched is dropped
    stray = [("jit_serve_q", 2.0, 0.05), *caught]
    assert launch_gaps.pair(launches, stray, waited) == (1, 1)
    # no wait names a launch of the slice: nothing to go by
    assert launch_gaps.pair(launches, caught, {0: 0.52}) is None
    assert launch_gaps.pair(launches, [("jit_serve_q", 5.0, 0.1)],
                            waited) is None
    # waits that end whole programs away from every execution: no fit
    assert launch_gaps.pair(launches, [("jit_serve_p", 7.1, 0.4)],
                            waited) is None


def _moved(seconds):
    return [(name, start + seconds, end + seconds, args)
            for name, start, end, args in SPANS]


def test_a_device_line_that_leads_the_host_line_is_set_back_by_causality():
    """The profile's device line leads its host line (0.3 to 1.5 ms on
    the chip): programs then start before their calls begin.  The reader
    takes out the least lead that leaves no program ahead of its call,
    so the fastest launch of the slice (launch 4: 20 ms) reads zero."""
    true, led = _split(), _split(spans=_moved(0.03))
    assert true["clock_skew_s"] == 0.0
    assert led["clock_skew_s"] == pytest.approx(0.03 - 0.02)
    # what is left is the split of spans 20 ms late, exactly
    late = _split(spans=_moved(0.02))
    assert late["clock_skew_s"] == pytest.approx(0.0)
    for part in launch_gaps.SHARES:
        assert led[f"{part}_s"] == pytest.approx(late[f"{part}_s"])
    assert sum(led[f"{part}_s"] for part in launch_gaps.SHARES) == \
        pytest.approx(true["idle_s"])
    # the most the lead could be: no program ends after its wait has
    # (A ends 1.12, its wait 1.15 + 0.03); the room between the two
    assert led["clock_slack_s"] == pytest.approx(0.06 - 0.01)
    assert true["clock_slack_s"] == pytest.approx(0.03)
    # launch gives way to notice and host as the spans move later
    assert led["launch_s"] < true["launch_s"]
    assert led["decode_step_ms"] == true["decode_step_ms"]


def test_without_launch_arguments_there_is_nothing_to_read():
    # the parent's span file: the same spans, no ``launch``, no ``program``
    bare = [(name, start, end, {k: v for k, v in args.items()
                                if k not in ("launch", "program")})
            for name, start, end, args in SPANS]
    assert _split(spans=bare) is None
    assert _split(modules=[m for m in MODULES
                           if not m[0].startswith("jit_serve")]) is None
    assert _split(spans=[]) is None


# -- through the profile and the span file ------------------------------------

CELL = "serve7b_backlog"
SHIFT = 10.0        # the span file's clock is the profile's plus this


def _ps(seconds: float) -> int:
    return round(seconds * 1e12)


def _profile() -> bytes:
    names = sorted({m[0] for m in MODULES})
    metadata = {i + 1: (f"{name}({100 + i})", [])
                for i, name in enumerate(names)}
    op = len(names) + 1
    metadata[op] = ("%fusion.1 = f32[4,4]{1,0} fusion(%p0), kind=kLoop", [])
    device = enc._plane("/device:TPU:0", [
        ("XLA Modules", 0, [(names.index(p) + 1, _ps(s), _ps(d), [])
                            for p, s, d in MODULES]),
        ("XLA Ops", 0, [(op, _ps(s), _ps(d), []) for _n, s, d in OPS]),
    ], metadata)
    host = enc._plane("/host:CPU", [
        ("python3", 0, [(1, _ps(WINDOW[0]), _ps(WINDOW[1] - WINDOW[0]), []),
                        (2, _ps(0.5), 1, [])]),
    ], {1: ("bench-window", []), 2: ("bench-sync", [])})
    return b"".join(enc._bytes(1, p) for p in (device, host))


def _traced(tmp_path, monkeypatch, spans=SPANS):
    monkeypatch.setattr(named_ops, "ROOT", tmp_path)
    monkeypatch.setattr(named_ops, "_CACHE", {})
    monkeypatch.setattr(launch_gaps, "_CACHE", {})
    scratch = tmp_path / ".bench_scratch" / CELL
    where = scratch / "plugins" / "profile" / "2026_10_04"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_profile())
    events = [{"name": "bench-sync", "ph": "i", "ts": (0.5 + SHIFT) * 1e6,
               "tid": 2}]
    for name, start, end, args in spans:
        events.append({"name": name, "ph": "B", "tid": 1, "args": args,
                       "ts": (start + SHIFT) * 1e6})
        events.append({"name": name, "ph": "E", "tid": 1,
                       "ts": (end + SHIFT) * 1e6})
    events.sort(key=lambda ev: ev["ts"])
    (scratch / "spans.json").write_text(json.dumps({"traceEvents": events}))
    return SimpleNamespace(cell=cells.resolve_cell(CELL), seconds=30.0,
                           device={"kind": "TPU v5 lite"}, samples={},
                           scalars={},
                           profile={"busy_s": 0.685, "window_s": 1.0})


def test_reader_places_the_span_file_on_the_profiles_clock(
        tmp_path, monkeypatch, capsys):
    run = _traced(tmp_path, monkeypatch)
    shares = {part: launch_gaps.read(run, part)
              for part in launch_gaps.SHARES}
    assert shares == pytest.approx(
        {"launch": 8.5, "notice": 10.0, "host": 13.0}, abs=1e-6)
    # what ``device.idle_share.*`` reads on the same line
    idle = 100.0 * (1.0 - run.profile["busy_s"] / run.profile["window_s"])
    assert sum(shares.values()) == pytest.approx(idle, abs=1e-6)
    # the units launched inside the window: launch 3 was not
    assert launch_gaps.read(run, "decode_step_ms") == pytest.approx(35.0)
    said = capsys.readouterr().err
    assert said.count("launch_gaps:") == 1          # once a run
    assert "4 of 5 jit_serve executions paired" in said
    assert "(1 unpaired inside the window)" in said
    found = launch_gaps.analyse(run)
    assert found["programs"][K16] == [1, pytest.approx(320.0)]
    assert found["programs"][STEP] == [2, pytest.approx(75.0)]
    with pytest.raises(ValueError):
        launch_gaps.read(run, "dispatch")


def test_the_parents_run_reads_nothing_and_does_not_fail(
        tmp_path, monkeypatch):
    bare = [(name, start, end, {k: v for k, v in args.items()
                                if k not in ("launch", "program")})
            for name, start, end, args in SPANS]
    run = _traced(tmp_path, monkeypatch, spans=bare)
    for part in (*launch_gaps.SHARES, "decode_step_ms"):
        assert launch_gaps.read(run, part) is None
    untraced = SimpleNamespace(cell=run.cell, profile={}, samples={},
                               scalars={}, device=run.device)
    assert launch_gaps.read(untraced, "launch") is None
    # no profile where the runner writes it: no names, no line
    monkeypatch.setattr(named_ops, "ROOT", tmp_path / "elsewhere")
    monkeypatch.setattr(named_ops, "_CACHE", {})
    assert launch_gaps.read(run, "host") is None


# -- the metrics ----------------------------------------------------------------

METRICS = {
    "serve7b_backlog": "backlog", "ouro_serve_reason_backlog": "ouro"}
PARTS = {"sched.launch_idle_share": "launch",
         "sched.notice_idle_share": "notice",
         "sched.host_idle_share": "host",
         "step.decode_device_ms_p50": "decode_step_ms"}


@pytest.mark.parametrize("stem", sorted(PARTS))
@pytest.mark.parametrize("cell_name", sorted(METRICS))
def test_each_metric_is_declared_for_its_cell_alone(cell_name, stem):
    name = f"{stem}.{METRICS[cell_name]}"
    declared = [m for m in cells.load_benchmark()["per_layer"]
                if m["name"] == name]
    assert len(declared) == 1 and declared[0]["workloads"] == [cell_name]
    cell = cells.resolve_cell(cell_name)
    metric = next(m for m in cell.per_layer if m["name"] == name)
    assert metric["reader"] == "launch_gaps"
    assert metric["args"] == {"part": PARTS[stem]}
    assert cells.reader_for(metric["reader"]) is launch_gaps.read
    assert metric["moves"] == "out_tokens_per_s"
    assert metric["moves"] in {e["name"] for e in cell.end_to_end}
    assert metric["better"] == "lower"
    if stem.startswith("sched."):
        assert (metric["layer"], metric["source"], metric["unit"]) == \
            ("scheduler", "program_span", "%")
    else:
        assert (metric["layer"], metric["source"], metric["unit"]) == \
            ("model step", "device_trace", "ms")


@pytest.mark.parametrize("cell_name, metrics", [
    ("train1b_step", 9), ("fwd13b_tp4", 7),
    ("olmohyb_longgen_backlog", 18), ("kanana_serve_longctx_backlog", 17)])
def test_the_other_cells_names_are_what_they_were(cell_name, metrics):
    cell = cells.resolve_cell(cell_name)
    assert len(cell.per_layer) == metrics
    assert not [m["name"] for m in cell.per_layer
                if m["reader"] == "launch_gaps"
                or "_idle_share" in m["name"]
                and m["name"].split(".")[1] in (
                    "launch_idle_share", "notice_idle_share",
                    "host_idle_share")]
