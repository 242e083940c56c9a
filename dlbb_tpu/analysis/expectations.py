"""Analytic expected-collective model.

Maps what a benchmark *claims* to do — a registry collective from
``comm/ops.py`` or a ``ParallelismPlan`` axis assignment — to the HLO
collective kinds the lowered program is allowed to contain and the byte
volume each instruction may carry.  The HLO auditor compares the compiled
module against this; anything outside the envelope is a finding.

Two layers:

- ``OP_EXPECTED_KINDS`` — per registry op, the HLO kinds its SPMD encoding
  lowers to (documented next to each entry; see also docs/analysis.md).
- ``plan_expected_kinds`` — per parallelism axis, the kinds the axis is
  allowed to introduce into a model/train computation (Megatron TP =>
  all-reduce, ring sp => collective-permute, Ulysses sp => all-to-all,
  pp => collective-permute, ZeRO dp => reduce-scatter/all-gather, ...).

``wire_bytes`` converts an instruction's per-device result bytes into the
analytic wire volume of the standard ring algorithm for its kind — the
"plan-derived expected volume" attached to every finding.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

# --- compressed-collective wire model (docs/compression.md) ---------------
#
# These constants are the single source of truth for the quantised wire
# format: dlbb_tpu/comm/compression.py imports them (this module must stay
# importable WITHOUT jax — the source lint runs backend-free — so the
# dependency points this way, not comm -> analysis -> comm).
COMPRESSIONS = ("int8", "fp8")
# payload bytes per element on the wire (int8 and fp8 e4m3 are both 1 B)
COMPRESSED_WIRE_ITEM_BYTES = {"int8": 1, "fp8": 1}
# one fp32 scale per chunk of this many elements — the scale-tensor side
# channel, charged to every byte ceiling below
SCALE_CHUNK_ELEMS = 256
SCALE_ITEM_BYTES = 4


def scale_bytes(num_elements: int) -> int:
    """Bytes of the fp32 scale side channel for a quantised payload of
    ``num_elements`` (one scale per SCALE_CHUNK_ELEMS-element chunk)."""
    return -(-num_elements // SCALE_CHUNK_ELEMS) * SCALE_ITEM_BYTES


def padded_elems(num_elements: int) -> int:
    """Elements actually on the wire for a quantised payload of
    ``num_elements``: quantize_chunked zero-pads each payload to a
    SCALE_CHUNK_ELEMS multiple, and the padding travels — an analytic
    model that ignored it would undercount small/misaligned payloads
    and reject correct implementations against their own ceiling."""
    return -(-num_elements // SCALE_CHUNK_ELEMS) * SCALE_CHUNK_ELEMS

# Registry op -> allowed HLO collective kinds, and the kind that MUST
# appear at least once (the op's defining primitive).
#
# The SPMD encodings (comm/ops.py) compose every root-rooted MPI op from
# symmetric collectives, so e.g. broadcast/scatter/reduce legitimately
# lower to all-reduce (psum of a masked contribution), and gather (like
# allgather) to all-gather.  "prod" allreduce is the one all-gather-based
# reduction (no pprod primitive) — the registry default is "sum" so the
# audit pins all-reduce.
OP_EXPECTED_KINDS: dict[str, dict] = {
    "allreduce": {"required": "all-reduce", "allowed": {"all-reduce"}},
    "allreduce_hierarchical": {
        # one psum per mesh axis: >= 2 all-reduce instructions on a
        # multi-axis mesh
        "required": "all-reduce", "allowed": {"all-reduce"},
        "min_required": 2,
    },
    "allgather": {"required": "all-gather", "allowed": {"all-gather"}},
    "broadcast": {"required": "all-reduce", "allowed": {"all-reduce"}},
    "gather": {"required": "all-gather", "allowed": {"all-gather"}},
    "scatter": {"required": "all-reduce", "allowed": {"all-reduce"}},
    "reduce": {"required": "all-reduce", "allowed": {"all-reduce"}},
    "alltoall": {"required": "all-to-all", "allowed": {"all-to-all"}},
    "sendrecv": {
        "required": "collective-permute", "allowed": {"collective-permute"},
    },
    "reducescatter": {
        "required": "reduce-scatter",
        # XLA CPU sometimes legalises psum_scatter to all-reduce + slice
        # (semantically identical, 2x wire volume); accept either lowering
        # but require one of the two.
        "allowed": {"reduce-scatter", "all-reduce"},
        "required_any": {"reduce-scatter", "all-reduce"},
    },
    "barrier": {"required": "all-reduce", "allowed": {"all-reduce"}},
    # Collective-matmul micro-ops, FUSED schedule (the registry default).
    # The decomposed ring/bidir schedules are audited via
    # ``overlap_op_expectation`` below — they must contain the
    # collective-permute chain and NOTHING else.
    "ag_matmul": {"required": "all-gather", "allowed": {"all-gather"}},
    "matmul_rs": {
        "required": "reduce-scatter",
        # same CPU legalisation latitude as `reducescatter`: psum_scatter
        # may lower to all-reduce + slice
        "allowed": {"reduce-scatter", "all-reduce"},
        "required_any": {"reduce-scatter", "all-reduce"},
    },
}

# Parallelism axis -> collective kinds that axis may introduce.
#
# tp is the row-parallel psum and nothing else: the fused-QKV kernel's
# columns are ordered by kv-head group (``models/transformer.py::
# init_params``), so a tp shard of its packed [H + 2*kv*d] output holds
# the q, k and v of its own heads and the split moves nothing between
# chips (verified against the compiled HLO of the tiny TP forward and
# the serving programs, ``tests/test_model.py``) — a collective-permute
# under plain tp means the column order and ``split_qkv`` fell apart.
# The other tripwire for TP mis-sharding remains all-gather: a
# weight-sized gather means the Megatron layout collapsed to replication.
AXIS_EXPECTED_KINDS: dict[str, set[str]] = {
    "dp": {"all-reduce", "reduce-scatter", "all-gather"},  # DDP / ZeRO
    "tp": {"all-reduce"},                                   # row psum
    # tp with the overlapped collective-matmul schedule
    # (model.tp_overlap = ring|bidir): every projection's collective is a
    # ppermute chain; the ONLY legitimate all-gather is the single
    # activation-sized reshard back to the caller's batch layout after the
    # final layernorm.  all-reduce is deliberately absent — a surviving
    # all-reduce means the decomposition collapsed back to the fused
    # lowering.
    "tp_overlap": {"collective-permute", "all-gather"},
    "sp_ring": {"collective-permute"},                      # ring attention
    "sp_ulysses": {"all-to-all"},                           # Ulysses resharding
    "pp": {"collective-permute", "all-reduce"},             # hops + masked psum
    "ep": {"all-reduce"},                                   # expert combine psum
    # dp with quantised gradient reduction (training.grad_compression):
    # ppermute ring + wire-dtype all-gather; all-reduce only for the
    # scalar loss mean (byte-bounded by the total-wire ceiling)
    "dp_compressed": {"collective-permute", "all-gather", "all-reduce"},
}


def plan_expected_kinds(dp: int = 1, tp: int = 1, sp: int = 1, pp: int = 1,
                        ep: int = 1, attention: str = "full",
                        zero_stage: int = 0,
                        tp_overlap: str = "off",
                        compression: str = "none",
                        decode: bool = False) -> set[str]:
    """The union of collective kinds a (plan, attention, ZeRO stage,
    tp_overlap schedule, grad-compression mode) combination is allowed to
    lower to.  Anything else in the compiled module — most importantly an
    all-gather in a plain TP forward, or a surviving all-reduce in an
    overlapped one — is a sharding mismatch.

    ``decode=True`` is the serving inference step (decode AND the prefill
    cache-append step, ``dlbb_tpu/serve/engine.py``): there is no
    gradient reduction, so dp — pure batch parallelism over the cache
    slots — contributes NOTHING, and the only legal collectives are tp's
    tiny per-token row-parallel psums.  The KV-cache itself must never
    reach the wire; the serving audit targets pair this set with an
    activation-sized byte ceiling, so a cache regather (slot-cache-sized
    all-gather) fails on BOTH axes."""
    if decode:
        if sp > 1 or pp > 1 or ep > 1:
            raise ValueError(
                "decode=True models the serving step, which runs on "
                f"(dp, tp) meshes only (got sp={sp}, pp={pp}, ep={ep})"
            )
        return set(AXIS_EXPECTED_KINDS["tp"]) if tp > 1 else set()
    kinds: set[str] = set()
    if dp > 1:
        if compression not in (None, "none"):
            # quantised gradient reduction (docs/compression.md): the dp
            # reduction is a collective-permute ring + a wire-dtype
            # all-gather.  all-reduce stays allowed for the scalar loss
            # mean ONLY — a gradient-sized all-reduce surviving here blows
            # the total-wire ceiling (max_total_wire_bytes), which is the
            # gate proving XLA did not dequantise before the collective.
            kinds |= AXIS_EXPECTED_KINDS["dp_compressed"]
        else:
            kinds |= ({"all-reduce"} if zero_stage == 0
                      else AXIS_EXPECTED_KINDS["dp"])
    if tp > 1:
        kinds |= AXIS_EXPECTED_KINDS[
            "tp_overlap" if tp_overlap != "off" else "tp"
        ]
    if sp > 1:
        kinds |= AXIS_EXPECTED_KINDS[
            "sp_ring" if attention == "ring" else "sp_ulysses"
        ]
    if pp > 1:
        kinds |= AXIS_EXPECTED_KINDS["pp"]
    if ep > 1:
        kinds |= AXIS_EXPECTED_KINDS["ep"]
    return kinds


def wire_bytes(kind: str, result_bytes: int, group_size: Optional[int]) -> int:
    """Analytic per-device wire volume of the standard ring algorithm for
    ``kind``, given the instruction's per-device result bytes.

    all-reduce: 2(P-1)/P x buffer (reduce-scatter + all-gather phases);
    all-gather: result is the gathered buffer, each device receives the
    (P-1)/P of it produced elsewhere; reduce-scatter: mirrors all-gather
    with the roles of operand/result swapped — the wire carries (P-1) x
    the scattered shard; all-to-all: (P-1)/P of the slab changes device;
    collective-permute: the whole buffer moves once.
    """
    p = group_size or 1
    if p <= 1:
        return 0
    if kind == "all-reduce":
        return int(2 * (p - 1) / p * result_bytes)
    if kind == "all-gather":
        return int((p - 1) / p * result_bytes)
    if kind == "reduce-scatter":
        return int((p - 1) * result_bytes)
    if kind == "all-to-all":
        return int((p - 1) / p * result_bytes)
    if kind == "collective-permute":
        return int(result_bytes)
    return int(result_bytes)


@dataclass
class TargetExpectation:
    """The audit contract for one lowered computation.

    allowed:            collective kinds that may appear.
    required_any:       at least one instruction of one of these kinds must
                        appear (None = nothing required, e.g. a pure-local
                        computation that must stay communication-free).
    min_required:       minimum number of instructions among required_any.
    max_bytes_per_instr: per-device result-byte ceiling per instruction
                        (None = unchecked); catches "oversized" collectives
                        such as a full-parameter all-gather where only an
                        activation-sized transfer is planned.
    max_total_wire_bytes: ceiling on the SUM of analytic per-device wire
                        bytes (``wire_bytes``) over every collective in the
                        module (None = unchecked).  The compressed-
                        collective gate: a quantised reduction that XLA
                        secretly dequantised back to bf16 moves ~2x the
                        wire and blows this ceiling even when every
                        individual instruction looks plausible.
    expect_donation:    the computation must donate at least one input
                        buffer (train-step convention — without it XLA
                        keeps input and output state resident).
    expect_overlap:     the target claims its collectives are hidden
                        behind compute (the ring-decomposed collective-
                        matmul schedules): the schedule auditor emits a
                        ``serialized-collective`` error for every ring
                        hop with no straddling matmul
                        (``schedule_audit.analyze_schedule``).
    max_peak_bytes:     per-device ceiling on the program's audited
                        ``peak_live_bytes`` (the buffer-liveness pass,
                        ``memory_audit.py``; None = unchecked).  Seeded
                        from analytic model/cache sizes with slack —
                        the byte-ceiling's whole-program twin: a
                        replicated state pytree or an undonated carry
                        blows it even when every wire instruction looks
                        right.
    policy_dtype:       the target's declared compute/storage dtype in
                        HLO terms ("f32" / "bf16" / "f16"; None = no
                        declared policy).  The numerics auditor's
                        anchor (``numerics_audit.py``): under a low
                        policy, sizeable f32 collectives / while
                        carries are ``silent-upcast``; params or
                        accumulators BELOW policy precision (or any
                        f64) are ``policy-conformance``.  Derive it
                        from ``ModelConfig.dtype`` with
                        :func:`policy_dtype_for` so the declared policy
                        can never drift from the model config the
                        target actually built.
    expect_bitwise_reproducible: the target claims bitwise-identical
                        results across runs/topologies.  Any fp
                        add-reduction on the wire (all-reduce /
                        reduce-scatter) makes that claim unsound —
                        the reduction order is backend-scheduled —
                        so the numerics auditor errors
                        (``nondeterministic-reduction``).  Off by
                        default: no benchmark target claims it; the
                        count is still recorded per target.
    donated_bytes_expected: analytic per-device bytes the program's
                        donated input buffers must sum to, within
                        ``donated_bytes_tolerance`` (relative).  The
                        serving cross-check: the decode step's donated
                        cache carry must agree with
                        ``models.configs.kv_cache_bytes_per_device`` —
                        the same number ``validate_serving``'s HBM
                        budget gate prices — so the build-time
                        rejection can never drift from what XLA
                        actually allocates (``serving-cache-drift``).
    """

    allowed: set[str] = field(default_factory=set)
    required_any: Optional[set[str]] = None
    min_required: int = 1
    max_bytes_per_instr: Optional[int] = None
    max_total_wire_bytes: Optional[int] = None
    expect_donation: bool = False
    expect_overlap: bool = False
    max_peak_bytes: Optional[int] = None
    donated_bytes_expected: Optional[int] = None
    donated_bytes_tolerance: float = 0.10
    policy_dtype: Optional[str] = None
    expect_bitwise_reproducible: bool = False


# ``ModelConfig.dtype`` / numpy-style dtype name -> HLO element type, the
# translation every audit target uses to declare its precision policy
_HLO_POLICY_DTYPE = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "float64": "f64",
    "f32": "f32", "bf16": "bf16", "f16": "f16", "f64": "f64",
}


def policy_dtype_for(dtype: str) -> str:
    """The HLO element type a ``ModelConfig.dtype`` string declares —
    the single translation point between model configs and the numerics
    auditor's ``policy_dtype``."""
    try:
        return _HLO_POLICY_DTYPE[dtype]
    except KeyError:
        raise ValueError(
            f"no HLO policy dtype for {dtype!r}; known: "
            f"{sorted(_HLO_POLICY_DTYPE)}"
        ) from None


def op_expectation(op_name: str, payload_bytes_per_rank: int,
                   slack: float = 1.25) -> TargetExpectation:
    """Expectation for one ``comm/ops.py`` registry op.

    ``payload_bytes_per_rank`` is the per-rank buffer size; the byte
    ceiling allows ``slack`` headroom over the worst-case legitimate
    instruction (the gathered [P, n] result for gather-family ops is
    handled by callers passing the global payload size).
    """
    spec = OP_EXPECTED_KINDS[op_name]
    required_any = spec.get("required_any")
    if required_any is None:
        required_any = {spec["required"]}
    return TargetExpectation(
        allowed=set(spec["allowed"]),
        required_any=set(required_any),
        min_required=spec.get("min_required", 1),
        max_bytes_per_instr=int(payload_bytes_per_rank * slack),
        # registry micro-op payloads are f32 (comm/ops.py make_payload)
        policy_dtype="f32",
    )


# Analytic per-device wire bytes of each registry op's IMPLEMENTATION
# (comm/ops.py SPMD encodings — e.g. broadcast is a psum of a masked
# contribution, so its wire is an all-reduce's, not a tree broadcast's).
# ``n`` is the op's per-rank element count (the [P, n] row / the [P, n]
# slab row for per_peer ops), ``p`` the rank count, ``b`` the payload
# element bytes.  Pinned against the registry by tests/test_compression.py.
def op_wire_bytes(op_name: str, num_elements: int, num_ranks: int,
                  elem_bytes: int,
                  compression: Optional[str] = None) -> Optional[int]:
    """Per-device analytic wire bytes for one registry op, or None for
    ops without a wire model (the collective-matmul micro-ops, whose
    wire depends on the schedule).  For the compressed ops the model
    includes the fp32 scale side channel; ``compression`` defaults to
    the op's default (int8)."""
    n, p, b = num_elements, num_ranks, elem_bytes
    if p <= 1:
        return 0
    if op_name in ("allreduce", "allreduce_hierarchical", "broadcast",
                   "reduce", "barrier"):
        return int(2 * (p - 1) / p * n * b)
    if op_name in ("allgather", "gather", "alltoall"):
        return int((p - 1) * n * b)
    if op_name == "scatter":
        # psum-broadcast of the root's whole [P, n] slab, then local slice
        return int(2 * (p - 1) / p * p * n * b)
    if op_name == "sendrecv":
        return int(n * b)
    if op_name == "reducescatter":
        return int((p - 1) * n * b)
    if op_name in ("allreduce_q", "reducescatter_q"):
        # quantised payloads travel chunk-padded (padded_elems), scale
        # side channel included
        w = COMPRESSED_WIRE_ITEM_BYTES[compression or "int8"]
        if op_name == "reducescatter_q":
            # ring phase only: (P-1) hops of one quantised row + scales
            return (p - 1) * (padded_elems(n) * w + scale_bytes(n))
        # ring reduce-scatter of ceil(n/P)-element chunks, then the
        # all-gather of the quantised reduced chunks (+ scale gathers)
        c = -(-n // p)
        ring = (p - 1) * (padded_elems(c) * w + scale_bytes(c))
        gather = int(
            (p - 1) / p * p * (padded_elems(c) * w + scale_bytes(c)))
        return ring + gather
    return None


def compression_wire_ceiling(baseline_bytes: int, analytic_bytes: int,
                             ratio: float = 0.55,
                             slack: float = 1.1) -> int:
    """The one compression total-wire ceiling, shared by every compressed
    audit target (micro-ops AND the compressed train step — a contract
    change here moves all of them together): the ``ratio`` x uncompressed
    baseline contract, OR ``slack`` x the op's own padding-included
    analytic wire where compression cannot pay (small/misaligned
    payloads), whichever is larger."""
    return max(int(ratio * baseline_bytes), int(slack * analytic_bytes))


def compressed_op_expectation(op_name: str, p: int, num_elements: int,
                              compression: str = "int8",
                              baseline_elem_bytes: int = 2,
                              ratio: float = 0.55) -> TargetExpectation:
    """Expectation for a compressed registry op (``allreduce_q`` /
    ``reducescatter_q``): the lowered module must be the quantised ring —
    collective-permutes (plus, for allreduce_q, the wire-dtype all-gather
    phase) — and its TOTAL analytic wire volume, scale side channel
    included, must stay under ``ratio`` x the uncompressed bf16 wire of
    the op it replaces.  The total ceiling is what proves XLA did not
    dequantise before the collective: a bf16-wire ring moves ~2x and
    fails it even though its instruction kinds look right.

    At small/misaligned payloads the chunk padding + scale overhead can
    legitimately exceed ``ratio`` x baseline (compression only pays above
    ~SCALE_CHUNK_ELEMS elements per ring chunk), so the ceiling is the
    MAX of the ratio contract and 1.1x the op's own analytic wire
    (``op_wire_bytes``, padding included) — strict where compression is
    meaningful, never rejecting a correct ring where it is not."""
    w = COMPRESSED_WIRE_ITEM_BYTES[compression]
    if op_name == "allreduce_q":
        baseline = wire_bytes(
            "all-reduce", num_elements * baseline_elem_bytes, p)
        allowed = {"collective-permute", "all-gather"}
        # largest legitimate instruction: the quantised all-gather result
        # — P chunk-padded ring chunks
        max_instr = p * padded_elems(-(-num_elements // p)) * w
    elif op_name == "reducescatter_q":
        baseline = wire_bytes(
            "reduce-scatter", num_elements * baseline_elem_bytes, p)
        allowed = {"collective-permute"}
        max_instr = padded_elems(num_elements) * w
    else:
        raise ValueError(f"not a compressed registry op: {op_name!r}")
    analytic = op_wire_bytes(op_name, num_elements, p, baseline_elem_bytes,
                             compression=compression)
    return TargetExpectation(
        allowed=allowed,
        required_any={"collective-permute"},
        min_required=p - 1,
        # a dequantised bf16 instruction would be 2x the wire width and
        # trip this even before the total ceiling
        max_bytes_per_instr=int(
            max_instr * 1.25 + scale_bytes(num_elements) * p
        ),
        max_total_wire_bytes=compression_wire_ceiling(
            baseline, analytic, ratio=ratio),
        # the compressed micro-ops carry bf16 payloads (the baseline the
        # ratio contract is priced against) — the numerics pass verifies
        # nothing f32-sized crosses the quantised ring (the scale side
        # channel stays under its byte floor)
        policy_dtype="bf16",
    )


def decode_scan_expectation(dp: int, tp: int, k: int,
                            act_bytes: int,
                            slack: float = 1.25,
                            policy_dtype: Optional[str] = "f32",
                            ) -> TargetExpectation:
    """Expectation for the FUSED multi-step decode scan
    (``serve/engine.py::build_decode_fused``): the scan body may contain
    only the per-token tp collectives (``plan_expected_kinds(decode=
    True)``), and — execution-weighted through the scan's
    ``known_trip_count`` (the while-body pricing the schedule auditor
    already does) — the row-parallel psum must fire at least once per
    trip: ``min_required = k``.

    all-gather is additionally allowed for ONE structural artifact:
    XLA hoists the loop-invariant slot-lengths vector into the while
    carry, GSPMD shards the hoisted copy over dp, and the final
    lengths computation re-gathers it at the loop BOUNDARY — a single
    ``4 B x max_batch`` instruction, executed once per scan (verified
    against the compiled HLO; the engine already keeps lengths out of
    the live carry, which removed the per-trip gathers).  The ceiling
    still prices every instruction at ONE step's activation bytes, so
    a cache regather — ~8x the ceiling for even one layer's plane —
    fails the byte axis outright, and its trip-count-weighted wire
    lands far past the committed baseline's 1.10x ``analyze diff``
    gate."""
    return TargetExpectation(
        allowed=plan_expected_kinds(dp=dp, tp=tp, decode=True)
        | {"all-gather"},
        required_any={"all-reduce"},
        min_required=k,
        max_bytes_per_instr=int(act_bytes * slack),
        expect_donation=True,
        policy_dtype=policy_dtype,
    )


def verify_step_expectation(dp: int, tp: int, gamma: int,
                            act_bytes: int,
                            slack: float = 1.25,
                            policy_dtype: Optional[str] = "f32",
                            ) -> TargetExpectation:
    """Expectation for the speculative-decoding verify step
    (``serve/engine.py::build_verify_step``): the γ drafted tokens plus
    the carry token run through ONE batched ``[max_batch, γ+1, H]``
    target forward — so the lowered program is shaped exactly like a
    decode step whose activations are (γ+1) wide, NOT like γ+1
    sequential decode steps.

    Concretely: the kind set stays the per-token decode set (tp psums;
    the same single boundary all-gather artifact the fused scan
    carries), ``min_required = 1`` — the row-parallel psum fires once
    per scanned layer, with NO per-draft-token trip
    weighting (a per-token re-verify loop would show up as a γ+1-trip
    while body, and its trip-weighted wire lands past the committed
    baseline's ``analyze diff`` gate) — and every instruction is capped
    at (γ+1) x one step's activation bytes.  The γ+1 cache appends
    (one scatter under ``shard_map``) must lower collective-free, exactly
    like the decode step's single append: ``act_bytes`` is the ONE-step
    ceiling, so a cache regather trips the byte axis identically."""
    return TargetExpectation(
        allowed=plan_expected_kinds(dp=dp, tp=tp, decode=True)
        | {"all-gather"},
        required_any={"all-reduce"},
        min_required=1,
        max_bytes_per_instr=int(act_bytes * (gamma + 1) * slack),
        expect_donation=True,
        policy_dtype=policy_dtype,
    )


def overlap_op_expectation(p: int, chunk_bytes: int,
                           slack: float = 1.25) -> TargetExpectation:
    """Expectation for a RING-DECOMPOSED collective matmul (either op,
    either direction): the lowered program must be a pure
    collective-permute chain — at least ``p - 1`` hops (the unidirectional
    ring's count; the bidirectional all-gather ring splits the same count
    across two directions, the bidirectional reduce-scatter doubles it
    with half-sized messages), each carrying at most one travelling chunk
    (``chunk_bytes``) — and no fused collective may survive: an
    all-gather or reduce-scatter here means XLA undid the decomposition
    and the overlap claim is void."""
    return TargetExpectation(
        allowed={"collective-permute"},
        required_any={"collective-permute"},
        min_required=p - 1,
        max_bytes_per_instr=int(chunk_bytes * slack),
        expect_overlap=True,
        policy_dtype="f32",
    )
