"""The readers that group a traced window by the program's own names
(``trace_gap_share``, ``trace_scope_share`` over ``named_ops``), each
against a hand-made profile: a reduced-profile dict for the gaps, and
for the named ops an ``.xplane.pb`` encoded here field by field, so the
wire decoder is held to the format and not to itself."""

from __future__ import annotations

import struct
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import cells                         # noqa: E402
from benchmarks.readers import named_ops                     # noqa: E402
from benchmarks.readers import trace_gap_share               # noqa: E402
from benchmarks.readers import trace_scope_share             # noqa: E402

# -- a protobuf encoder for the few XSpace fields the decoder reads ----------


def _varint(value: int) -> bytes:
    value &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((value & 0x7F) | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def _int(number: int, value: int) -> bytes:
    return _varint(number << 3) + _varint(value)


def _bytes(number: int, value: bytes) -> bytes:
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _stat(stat_id: int, value) -> bytes:
    if isinstance(value, str):
        body = _bytes(5, value.encode())
    elif isinstance(value, bytes):
        body = _bytes(6, value)
    elif isinstance(value, tuple):                     # ("ref", stat id)
        body = _int(7, value[1])
    else:
        body = _int(3, value)                          # uint64_value
    return _int(1, stat_id) + body


STATS = {1: "tf_op", 2: "program_id", 3: "rid", 4: "flops",
         5: "jit(train_step)/jvp(mlp_up)/dot_general:", 6: "Hlo Proto"}


def _hlo_proto(fusion: str, fused: list[tuple[str, str, str]]) -> bytes:
    """An HloProto whose entry computation holds one ``fusion``
    instruction calling a fused computation of ``(name, opcode,
    op_name)`` instructions."""
    def instruction(name, opcode, op_name, called=()):
        out = _bytes(1, name.encode()) + _bytes(2, opcode.encode())
        out += _bytes(7, _bytes(1, b"op_type") + _bytes(2, op_name.encode()))
        if called:                       # packed repeated int64
            out += _bytes(38, b"".join(_varint(c) for c in called))
        return out

    inner = _bytes(1, b"fused_computation") + _int(5, 2) + b"".join(
        _bytes(2, instruction(*ins)) for ins in fused)
    main = _bytes(1, b"main") + _int(5, 1) + _bytes(2, instruction(
        fusion, "fusion", "jit(serve_decode_k4)/while/body/"
        "dynamic_update_slice", called=(2,)))
    module = _bytes(1, b"jit_serve_decode_k4") + _bytes(3, inner) \
        + _bytes(3, main)
    return _bytes(1, module)


def _plane(name: str, lines: list[tuple[str, int, list]],
           metadata: dict[int, tuple[str, list[bytes]]]) -> bytes:
    """``lines``: (name, timestamp_ns, [(metadata id, offset_ps, dur_ps,
    [stat bytes])]); ``metadata``: id -> (name, [stat bytes])."""
    out = _int(1, 7) + _bytes(2, name.encode())
    for line_name, t0_ns, events in lines:
        body = _int(1, 1) + _bytes(2, line_name.encode()) + _int(3, t0_ns)
        for md_id, offset_ps, dur_ps, stats in events:
            ev = _int(1, md_id) + _int(2, offset_ps) + _int(3, dur_ps)
            ev += b"".join(_bytes(4, s) for s in stats)
            body += _bytes(4, ev)
        out += _bytes(3, body)
    for md_id, (md_name, stats) in metadata.items():
        md = _int(1, md_id) + _bytes(2, md_name.encode())
        # a field the decoder must skip: XEventMetadata.metadata (bytes)
        md += _bytes(3, b"\x00\x01") + b"".join(_bytes(5, s) for s in stats)
        out += _bytes(4, _int(1, md_id) + _bytes(2, md))
    for stat_id, stat_name in STATS.items():
        sm = _int(1, stat_id) + _bytes(2, stat_name.encode())
        out += _bytes(5, _int(1, stat_id) + _bytes(2, sm))
    # a fixed64 and a fixed32 field of no schema, to be skipped
    out += _varint(15 << 3 | 1) + struct.pack("<d", 1.5)
    out += _varint(14 << 3 | 5) + struct.pack("<f", 2.5)
    return out


MS = 10**9      # picoseconds in a millisecond


def _xspace() -> bytes:
    """One device, window 0..100 ms at line timestamp 1 s.  Two programs:
    ``jit_train_step`` runs 0-60 ms (a 60 ms ``while`` holding a 30 ms
    ``mlp_up`` dot whose scope is a ref value, a 10 ms flash dq kernel
    under ``attn_core`` and 20 ms of its own), then ``jit_serve_decode_k4``
    runs 60-90 ms: a 15 ms copy that has no scope, and a 15 ms fusion
    named by its root, the scan's stacking, whose ``kv_update`` select
    only the program's HLO shows; 90-100 ms idle.  One op lies outside
    the window."""
    pid_a, pid_b = 1234567890123, 77
    metadata = {
        1: ("%while.1 = (s32[]) while(%tuple)",
            [_stat(2, pid_a),
             _stat(1, "jit(train_step)/jit(main)/while")]),
        2: ("%fusion.9 = bf16[8,512]{1,0} fusion(%p0), kind=kOutput",
            [_stat(2, pid_a), _stat(1, ("ref", 5)), _stat(4, 99)]),
        3: ('%flash_bwd_dq.1 = bf16[8,512]{1,0} custom-call(%p0), '
            'custom_call_target="tpu_custom_call"',
            [_stat(2, pid_a),
             _stat(1, "jit(train_step)/transpose(jvp(attn_core))/"
                      "flash_bwd_dq/flash_bwd_dq/pallas_call:")]),
        4: ("%copy.43 = bf16[16,16]{1,0} copy(%p1)", [_stat(2, pid_b)]),
        5: (f"jit_train_step({pid_a})", []),
        6: (f"jit_serve_decode_k4({pid_b})", []),
        7: ("%select_dynamic-update-slice_fusion.3 = bf16[16,16]{1,0} "
            "fusion(%p0, %p1), kind=kLoop",
            [_stat(2, pid_b),
             _stat(1, "jit(serve_decode_k4)/while/body/"
                      "dynamic_update_slice:")]),
    }
    device = _plane("/device:TPU:0", [
        ("XLA Modules", 10**9, [(5, 0, 60 * MS, []),
                                (6, 60 * MS, 30 * MS, [])]),
        ("XLA Ops", 10**9, [(1, 0, 60 * MS, []),
                            (2, 5 * MS, 30 * MS, []),
                            (3, 40 * MS, 10 * MS, []),
                            (4, 60 * MS, 15 * MS, []),
                            (7, 75 * MS, 15 * MS, []),
                            (4, 500 * MS, 30 * MS, [])]),
    ], metadata)
    host = _plane("/host:CPU", [
        ("python3", 10**9, [(1, 0, 100 * MS, []),
                            (2, 10 * MS, 20 * MS, [_stat(3, 5)])]),
    ], {1: ("bench-window", []), 2: ("serve-prefill", [])})
    where = "jit(serve_decode_k4)/while/body/attn_core/kv_update/jit(_where)"
    programs = _plane("/host:metadata", [], {pid_b: (
        f"jit_serve_decode_k4({pid_b})",
        [_stat(6, _hlo_proto("select_dynamic-update-slice_fusion.3", [
            ("p0", "parameter", ""),
            ("select.1", "select", f"{where}/select_n"),
            ("broadcast.1", "broadcast", f"{where}/broadcast_in_dim"),
            ("dus.1", "dynamic-update-slice",
             "jit(serve_decode_k4)/while/body/dynamic_update_slice")]))])})
    # the programs' plane comes after the device's, as in a real file
    return b"".join(_bytes(1, p) for p in (device, programs, host))


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    """A run whose traced profile is the hand-made file, found where
    ``benchmarks/run.py`` has a runner write it."""
    monkeypatch.setattr(named_ops, "ROOT", tmp_path)
    monkeypatch.setattr(named_ops, "_CACHE", {})
    where = (tmp_path / ".bench_scratch" / "toy_cell" / "plugins"
             / "profile" / "2026_09_29")
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_xspace())
    cell = SimpleNamespace(name="toy_cell", traffic={"trace_steps": 2})
    return SimpleNamespace(cell=cell,
                           profile={"busy_s": 0.09, "window_s": 0.1})


def test_decoder_reads_names_times_scopes_and_programs():
    decoded = named_ops.decode(_xspace())
    ops = decoded["ops"]["/device:TPU:0"]
    # op names as ``trace_reduce.clean_name`` prints them in a breakdown
    assert [op[0] for op in ops] == [
        "while.1_s32", "fusion.9_bf16_8_512",
        "flash_bwd_dq.1_bf16_8_512.tpu_custom_call",
        "copy.43_bf16_16_16",
        "select_dynamic-update-slice_fusion.3_bf16_16_16",
        "copy.43_bf16_16_16"]
    assert ops[1][1:3] == pytest.approx((1.005, 0.030))
    # a scope given as a reference into the stat names, the colon cut
    assert ops[1][3] == "jit(train_step)/jvp(mlp_up)/dot_general"
    assert ops[2][3].endswith("flash_bwd_dq/flash_bwd_dq/pallas_call")
    assert ops[3][3] == ""
    # a fusion: its own (root's) op_name, then those fused into it, once
    assert ops[4][3].split() == [
        "jit(serve_decode_k4)/while/body/dynamic_update_slice",
        "jit(serve_decode_k4)/while/body/attn_core/kv_update/jit(_where)"
        "/select_n",
        "jit(serve_decode_k4)/while/body/attn_core/kv_update/jit(_where)"
        "/broadcast_in_dim",
        "jit(serve_decode_k4)/while/body/dynamic_update_slice"]
    assert [op[4] for op in ops[:5]] == [
        "jit_train_step", "jit_train_step", "jit_train_step",
        "jit_serve_decode_k4", "jit_serve_decode_k4"]
    assert decoded["modules"]["/device:TPU:0"] == [
        ("jit_train_step", pytest.approx(1.0), pytest.approx(0.06)),
        ("jit_serve_decode_k4", pytest.approx(1.06), pytest.approx(0.03))]
    # host annotations on the same clock: the window, the spans' own
    assert ("serve-prefill", pytest.approx(1.01),
            pytest.approx(1.03)) in decoded["host"]


@pytest.mark.parametrize("args, expected", [
    # 30 ms under mlp_up of 90 ms busy
    (dict(match=r"(^|[/(])mlp_up[/)]"), 100 * 30 / 90),
    # the flash kernel is found under attn_core, backward alike, and so
    # is the cache fusion: kv_update is traced inside attn_core
    (dict(match=r"(^|[/(])attn_core[/)]"), 100 * (10 + 15) / 90),
    # ... and by its own name, per traced step: 10 ms over 2 steps
    (dict(match="flash_bwd_(dq|dkv)", over="step"), 5.0),
    # a program owns its ops' self time: the while counts 60 ms once
    (dict(match="^jit_train_step$", by="program"), 100 * 60 / 90),
    # the unscoped copy has an owner all the same; the op outside the
    # window is clipped away
    (dict(match="^jit_serve_decode_", by="program"), 100 * 30 / 90),
    # a fusion is under the scopes of what was fused into it, whatever
    # its root is called
    (dict(match=r"(^|[/(])kv_update[/)]"), 100 * 15 / 90),
    # a name the profile does not carry reads nothing, not zero
    (dict(match=r"(^|[/(])kv_attend[/)]"), None),
    (dict(match="^jit_serve_prefill_", by="program"), None),
])
def test_scope_share_on_a_hand_made_profile(traced, args, expected):
    value = trace_scope_share.read(traced, **args)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)


def test_scope_share_finds_nothing_without_a_profile(traced, tmp_path,
                                                     monkeypatch):
    untraced = SimpleNamespace(cell=traced.cell, profile={})
    assert trace_scope_share.read(untraced, match="mlp_up") is None
    # a reduced profile but no file: nothing again, and no raise
    monkeypatch.setattr(named_ops, "ROOT", tmp_path / "elsewhere")
    assert trace_scope_share.read(traced, match="mlp_up") is None
    with pytest.raises(ValueError):
        trace_scope_share.read(traced, match="x", by="op")


def test_scope_share_reads_nothing_from_a_file_cut_short(traced, tmp_path,
                                                         capsys):
    """The profile is the profiler's file: one that does not decode
    leaves the metric out and says why, it does not fail the run."""
    [path] = (tmp_path / ".bench_scratch").rglob("*.xplane.pb")
    path.write_bytes(_xspace()[:-7])
    assert trace_scope_share.read(traced, match="mlp_up") is None
    assert "cannot decode" in capsys.readouterr().err


def test_groups_cover_the_busy_time_once(traced):
    """Grouping by program, self times add up to the busy union: what
    the coverage table of PERF.md is summed from."""
    loaded = named_ops.load(traced)
    seconds = named_ops.group_seconds(loaded, lambda op: op[4])
    assert seconds == {"jit_train_step": pytest.approx(0.06),
                       "jit_serve_decode_k4": pytest.approx(0.03)}
    by_scope = named_ops.group_seconds(
        loaded, lambda op: "named" if op[3] else "unnamed")
    assert by_scope["unnamed"] == pytest.approx(0.015)


@pytest.mark.parametrize("match, expected", [
    ("^serve-admi(ssion|t-[a-z]+)$", 100 * (0.02 + 0.05 + 0.01) / 3.0),
    ("^serve-admit-embed$", 100 * 0.05 / 3.0),
    # spans the program does not have: nothing, not zero
    ("^serve-admit-sample$", None),
])
def test_gap_share_on_a_hand_made_profile(match, expected):
    run = SimpleNamespace(profile={"window_s": 3.0, "idle_gaps": {
        "serve-admission": 0.02, "serve-admit-embed": 0.05,
        "serve-admit-book": 0.01, "serve-decode-sync": 0.3,
        "serve-administer": 1.0, "_no_host_span_": 0.001}})
    value = trace_gap_share.read(run, match)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)
    assert trace_gap_share.read(SimpleNamespace(profile={}), match) is None


@pytest.mark.parametrize("cell_name", [w["name"] for w in
                                       cells.load_benchmark()["workloads"]])
def test_new_metrics_reach_their_cells_through_the_data_files(cell_name):
    """Each metric of this PR resolves, in the cells it lists, to one of
    the two readers with arguments the reader takes."""
    import inspect

    cell = cells.resolve_cell(cell_name)
    mine = [m for m in cell.per_layer
            if m["reader"] in ("trace_gap_share", "trace_scope_share")]
    assert mine, cell_name
    for m in mine:
        reader = cells.reader_for(m["reader"])
        params = set(inspect.signature(reader).parameters) - {"run"}
        assert set(m["args"]) <= params, m["name"]
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
