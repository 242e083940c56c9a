"""The named controls of ``benchmarks/harness/kind_backlog_looped.py``:
the program made wrong in one stated way, run through the SAME runner,
to see which limit of the comparison with the float32 reference reads it
(``PERF.md`` §6, PR 33; ``tests/test_looped.py`` and
``tests/benchmark_harness/test_ouro_cell.py`` drive the same patches at
toy widths).  Not part of the benchmark and not a way to serve the model.

    python scripts/ouro_controls.py [--seconds S] [--seed N] [--rps R]
        <control> ...

runs the cell ``ouro_serve_reason_backlog`` once per named control
(``sound`` is the program as it is), each in a process of its own (a
chip belongs to one process), and prints one JSON line each: the
control, ``correct``, the comparison's numbers, ``out_tokens_per_s``.
``--rps`` overrides the traffic's ``backlog_rps`` (the sweep that set
it).  The runs themselves are ``scripts/kanana_controls.py``'s.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
for path in (ROOT, ROOT / "scripts"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import kanana_controls as runs                                  # noqa: E402
from kanana_controls import _bf16                               # noqa: E402

CELL = "ouro_serve_reason_backlog"


def _loop(passes_run: Optional[int] = None, norm_between: bool = True,
          stream_float32: bool = True):
    """``models/hybrid.py::run_stack``'s loop over the passes with a
    fault: only ``passes_run`` of them (the last one's gate is given
    once more for each pass left out, so that the shapes stay), the
    final norm applied after the LAST pass alone, or the residual stream
    left in the weights' dtype."""
    def patch(setattr_: Callable[[Any, str, Any], None],
              model: dict) -> None:
        import jax
        import jax.numpy as jnp

        from dlbb_tpu.models import hybrid

        def run_stack(h, params, config, make_mixer, state, xs=None):
            passes = config.total_ut_steps
            ran = passes_run or passes

            def one_pass(carry, xs_t):
                h, t, state = carry
                h, state, outs, _ = hybrid.scan_stack(
                    h, params, config, make_mixer, state, xs_t, passed=t)
                normed = hybrid.rmsnorm(h, params["ln_f"],
                                        config.rms_norm_eps).astype(h.dtype)
                if norm_between:
                    h = normed
                else:
                    h = jnp.where(t == ran - 1, normed, h)
                return (h, t + 1, state), (outs,
                                           hybrid.exit_gate(params, normed))

            if xs is not None:
                xs = tuple(t.reshape((passes, t.shape[0] // passes)
                                     + t.shape[1:])[:ran] for t in xs)
            if stream_float32:
                h = h.astype(jnp.float32)
            (h, _, state), (outs, gates) = jax.lax.scan(
                one_pass, (h, jnp.int32(0), state), xs, length=ran)
            # the passes left out hand on what the last one that ran did
            outs = tuple(
                t if isinstance(t, tuple) else jnp.concatenate(
                    [t, jnp.repeat(t[-1:], passes - ran, axis=0)]
                ).reshape((-1,) + t.shape[2:]) for t in outs)
            gates = jnp.concatenate(
                [gates, jnp.repeat(gates[-1:], passes - ran, axis=0)])
            return h, state, outs, None, gates

        setattr_(hybrid, "run_stack", run_stack)
    return patch


def _previous_pass_planes(setattr_, model):
    """A decode step's pass ``t`` ATTENDS to the planes pass ``t - 1``
    wrote (pass 0 to its own); the appends go where they belong."""
    import jax.numpy as jnp

    from dlbb_tpu.serve import hybrid as serve_hybrid

    attend = serve_hybrid.decode_attention
    layers = model["num_layers"]

    def wrong(q, k_plane, v_plane, layer, *rest):
        return attend(q, k_plane, v_plane,
                      jnp.where(layer >= layers, layer - layers, layer),
                      *rest)

    setattr_(serve_hybrid, "decode_attention", wrong)


def _rope_off(setattr_, model):
    from dlbb_tpu.models import hybrid

    setattr_(hybrid, "rope",
             lambda x, positions, theta, half_split=False: x)


def _rope_adjacent(setattr_, model):
    """The other pairing: adjacent values in place of halves."""
    from dlbb_tpu.models import hybrid

    rope = hybrid.rope
    setattr_(hybrid, "rope", lambda x, positions, theta, half_split=False:
             rope(x, positions, theta))


def _stale_last_pass(setattr_, model):
    """A prompt chunk leaves the LAST pass's planes of its slot as they
    were: a recycled slot keeps there what its previous request wrote."""
    from dlbb_tpu.serve import hybrid as serve_hybrid

    write = serve_hybrid.write_slot_planes
    last = (model["total_ut_steps"] - 1) * model["num_layers"]
    setattr_(serve_hybrid, "write_slot_planes",
             lambda plane, blocks, slot, start_blk=0:
             write(plane, blocks[:last], slot, start_blk))


def _sandwich_outputs_left_out(setattr_, model):
    """The two OUTPUT norms of every layer left out (a pre-norm block)."""
    from dlbb_tpu.models import hybrid

    block = hybrid.hybrid_block

    def pre(h, layer, kind, config, *rest, **kw):
        return block(h, layer, kind, config.with_(norm_placement="pre"),
                     *rest, **kw)

    setattr_(hybrid, "hybrid_block", pre)


def _float32_parts_bfloat16(setattr_, model):
    """The nearest precision below the configuration's: what it states
    as float32 (the norms, the rotary, a prompt chunk's scores and
    softmax, the exit gate) computed in bfloat16, each product rounded
    by ``reduce_precision``.  (The decode kernel's softmax and the
    SwiGLU's activation sit inside a kernel and a fusion, with no seam
    for a control.)"""
    import jax
    import jax.numpy as jnp

    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid

    def rope(x, positions, theta, half_split=False):
        d = x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = positions.astype(jnp.float32)[..., None] * inv
        cos, sin = _bf16(jnp.cos(angle)), _bf16(jnp.sin(angle))
        x32 = x.astype(jnp.float32)
        a, b = x32[..., :d // 2], x32[..., d // 2:]
        return jnp.concatenate(
            [_bf16(_bf16(a * cos) - _bf16(b * sin)),
             _bf16(_bf16(a * sin) + _bf16(b * cos))], axis=-1).astype(x.dtype)

    def exit_gate(params, h):
        score = _bf16(jnp.einsum(
            "...h,h->...", h.astype(jnp.float32),
            params["exit_gate_w"].astype(jnp.float32)))
        return _bf16(jax.nn.sigmoid(_bf16(score + params["exit_gate_b"])))

    setattr_(hybrid, "rmsnorm", runs.rmsnorm_bf16)
    setattr_(hybrid, "rope", rope)
    setattr_(hybrid, "exit_gate", exit_gate)
    setattr_(serve_hybrid, "_chunk_attention", runs.chunk_attention_bf16)


def _norms_bfloat16(setattr_, model):
    """Of the above the norms alone (five a layer and pass)."""
    from dlbb_tpu.models import hybrid

    setattr_(hybrid, "rmsnorm", runs.rmsnorm_bf16)


CONTROLS: dict[str, Callable[[Callable, dict], None]] = {
    "sound": lambda setattr_, model: None,
    # faults of the loop
    "three_passes": _loop(passes_run=3),
    "loop_norm_left_out": _loop(norm_between=False),
    "previous_pass_planes": _previous_pass_planes,
    "stale_last_pass": _stale_last_pass,
    # faults of the block
    "rope_off": _rope_off,
    "rope_adjacent": _rope_adjacent,
    "sandwich_outputs_left_out": _sandwich_outputs_left_out,
    # the nearest precision below the configuration's, its norms alone,
    # and the residual stream alone
    "float32_parts_bfloat16": _float32_parts_bfloat16,
    "norms_bfloat16": _norms_bfloat16,
    "stream_bfloat16": _loop(stream_float32=False),
}


def apply(name: str, setattr_: Callable[[Any, str, Any], None],
          model: dict) -> None:
    """Make the program wrong as ``name`` says, through ``setattr_``
    (``monkeypatch.setattr`` in a test, ``setattr`` in a process that
    ends with the run)."""
    CONTROLS[name](setattr_, model)


if __name__ == "__main__":
    sys.exit(runs.main(script=__file__, cell=CELL, controls=CONTROLS,
                       log="ouro_controls.jsonl", doc=__doc__))
