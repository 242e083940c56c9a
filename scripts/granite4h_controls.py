"""The named controls of ``benchmarks/harness/kind_backlog_ssm.py``: the
program made wrong in one stated way, run through the SAME runner, to
see which limit of the comparison with the float32 reference reads it
(``PERF.md`` §6, PR 37; ``tests/test_ssm.py`` and
``tests/benchmark_harness/test_granite4h_cell.py`` drive the same
patches at toy widths).  Not part of the benchmark and not a way to
serve the model.

    python scripts/granite4h_controls.py [--seconds S] [--seed N]
        [--rps R] [--chunk C] [--slots B] <control> ...

runs the cell ``granite4h_serve_chat_backlog`` once per named control
(``sound`` is the program as it is), each in a process of its own (a
chip belongs to one process), and prints one JSON line each: the
control, ``correct``, the comparison's numbers, ``out_tokens_per_s``.
``--rps`` / ``--chunk`` / ``--slots`` override the traffic's
``backlog_rps`` and the configuration's ``prefill_chunk`` and
``max_batch``: ``sound`` under them is the sweep that set the cell's
envelope (``PERF.md`` §4).  The runs themselves are
``scripts/kanana_controls.py``'s.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[1]
for path in (ROOT, ROOT / "scripts"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import kanana_controls as runs                                  # noqa: E402

CELL = "granite4h_serve_chat_backlog"


def _state_bfloat16(setattr_, model):
    """The nearest precision below the configuration's: the recurrent
    state planes, and the state a prompt chunk hands on, held in
    bfloat16, so that every decode step and chunk rounds the state."""
    import jax.numpy as jnp

    from dlbb_tpu.models import hybrid

    setattr_(hybrid, "STATE_DTYPE", jnp.bfloat16)


def _recurrence(change: Callable[[Any, Any], tuple]):
    """The decode and the chunked form of ``ops/ssd.py`` wherever the
    program reads them, with ``(a, d)`` replaced by ``change(a, d)``."""
    def patch(setattr_, model):
        from dlbb_tpu.models import hybrid
        from dlbb_tpu.ops import ssd
        from dlbb_tpu.serve import hybrid as serve_hybrid

        step, chunked = ssd.ssd_plane_step, ssd.ssd_chunked

        def wrong_step(x, dt, a, b, c, d, *plane):
            a, d = change(a, d)
            return step(x, dt, a, b, c, d, *plane)

        def wrong_chunked(x, dt, a, b, c, d, state, chunk):
            a, d = change(a, d)
            return chunked(x, dt, a, b, c, d, state, chunk)

        setattr_(serve_hybrid, "ssd_plane_step", wrong_step)
        setattr_(serve_hybrid, "ssd_chunked", wrong_chunked)
        setattr_(hybrid, "ssd_chunked", wrong_chunked)
    return patch


def _with_config(**change):
    """Every layer run under a configuration changed so (the weights and
    the reference keep the true one)."""
    def patch(setattr_, model):
        from dlbb_tpu.models import hybrid

        block = hybrid.hybrid_block

        def wrong(h, layer, kind, config, *rest, **kw):
            return block(h, layer, kind, config.with_(**change), *rest, **kw)

        setattr_(hybrid, "hybrid_block", wrong)
    return patch


def _gate_after_norm(setattr_, model):
    from dlbb_tpu.models import hybrid

    import jax
    import jax.numpy as jnp

    def wrong(y, z, scale, eps):
        return (hybrid.rmsnorm(y.astype(jnp.float32), scale, eps)
                * jax.nn.silu(z.astype(jnp.float32)))

    setattr_(hybrid, "gated_norm", wrong)


def _split(change: Callable[[tuple, dict], tuple]):
    """``models/hybrid.py::split_xbc`` called with the layer, and giving
    the ``(x, B, C)``, that ``change`` makes of them."""
    def patch(setattr_, model):
        from dlbb_tpu.models import hybrid

        split = hybrid.split_xbc

        def wrong(conv, layer, config):
            layer, swap = change(layer)
            x, b, c = split(conv, layer, config)
            return (x, c, b) if swap else (x, b, c)

        setattr_(hybrid, "split_xbc", wrong)
    return patch


def _wrong_kv_head(setattr_, model):
    """Query head ``i`` reads K/V head ``i // group - 1`` (the first
    group the last head's), in all three mixers."""
    import jax.numpy as jnp

    from dlbb_tpu.models import hybrid
    from dlbb_tpu.serve import hybrid as serve_hybrid

    group = model["num_heads"] // model["num_kv_heads"]
    for cls in (hybrid.SequenceMixer, serve_hybrid.ChunkMixer,
                serve_hybrid.DecodeMixer):
        def wrong(self, q, k, v, l, state, attention=cls.attention):
            attn, state = attention(self, jnp.roll(q, group, axis=2), k, v,
                                    l, state)
            return jnp.roll(attn, -group, axis=2), state

        setattr_(cls, "attention", wrong)


def _stale(part: int):
    """A prompt's FIRST chunk starts from what the slot holds (its
    previous request's) in place of the zero prefix: the recurrent state
    (``part`` 2) or the convolution's inputs (3)."""
    def patch(setattr_, model):
        import jax

        from dlbb_tpu.serve import hybrid as serve_hybrid

        ssm = serve_hybrid.ChunkMixer.ssm

        def wrong(self, xbc, dt, layer, l, planes):
            if self.start == 0:
                j = len(self.out[2])
                held = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(planes[part], l, 0, False),
                    self.slot, 0, False)
                xs = list(self.xs)
                xs[part] = xs[part].at[j].set(
                    held.reshape(xs[part].shape[1:]).astype(xs[part].dtype))
                self.xs = tuple(xs)
            return ssm(self, xbc, dt, layer, l, planes)

        setattr_(serve_hybrid.ChunkMixer, "ssm", wrong)
    return patch


CONTROLS: dict[str, Callable[[Callable, dict], None]] = {
    "sound": lambda setattr_, model: None,
    # the three the cell's limits are set against on the chip
    "state_bfloat16": _state_bfloat16,
    "decay_skipped": _recurrence(lambda a, d: (a * 0.0, d)),
    "residual_multiplier_1": _with_config(residual_multiplier=1.0),
    # faults of the state-space layer
    "skip_left_out": _recurrence(lambda a, d: (a, d * 0.0)),
    "gate_after_norm": _gate_after_norm,
    "conv_bias_dropped": _split(lambda layer: (
        {k: v for k, v in layer.items() if k != "ssm_conv_b"}, False)),
    "b_c_swapped": _split(lambda layer: (layer, True)),
    # faults of the attention layer and of the cache
    "wrong_kv_head": _wrong_kv_head,
    "stale_state": _stale(2),
    "stale_conv": _stale(3),
}


def apply(name: str, setattr_: Callable[[Any, str, Any], None],
          model: dict) -> None:
    """Make the program wrong as ``name`` says, through ``setattr_``
    (``monkeypatch.setattr`` in a test, ``setattr`` in a process that
    ends with the run)."""
    CONTROLS[name](setattr_, model)


if __name__ == "__main__":
    sys.exit(runs.main(script=__file__, cell=CELL, controls=CONTROLS,
                       log="granite4h_controls.jsonl", doc=__doc__))
