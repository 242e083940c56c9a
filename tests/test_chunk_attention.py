"""The prompt chunk's attention kernel (``ops/chunk_attention.py``,
``chunk_attend``) against the dense float32 attention of
``serve/attend.py::_chunk_attention`` on the same boolean mask, in
interpret mode: offset-causal over a prefix and windowed over a rotated
ring and the chunk's own keys, grouped-query heads, a given scale, a
final partial chunk, and K blocks that see nothing skipped.  Then the
rule that picks the kernel (``serve/attend.py::chunk_on_kernel``) over
the benchmark's serving cells, and one ``tpu``-marked case at the
SmallThinker cell's widths compiled by Mosaic.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.models.configs import ModelConfig
from dlbb_tpu.ops.chunk_attention import (
    MASKED,
    SKIP,
    block_plan,
    chunk_attend,
)
from dlbb_tpu.serve import hybrid as serve_hybrid
from dlbb_tpu.serve.attend import _chunk_attention, chunk_attention
from dlbb_tpu.serve.kvcache import ring_positions

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
CHUNK, WINDOW = 16, 32


def _keys(start: int, window: int, chunk: int = CHUNK) -> np.ndarray:
    """The positions of the keys a chunk at ``start`` reads, as the chunk
    program lays them out: the prefix, or the ring and the chunk."""
    own = start + np.arange(chunk)
    if not window:
        return np.arange(start + chunk)
    return np.concatenate([ring_positions(start, window), own]) if start \
        else own


def _mask(keys: np.ndarray, start: int, window: int, chunk: int = CHUNK):
    at = (start + np.arange(chunk))[:, None]
    mask = (keys[None] >= 0) & (keys[None] <= at)
    if window:
        mask &= keys[None] > at - window
    return mask


def _inputs(seed: int, heads: int, kv_heads: int, keys: int, d: int = 16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((1, heads, CHUNK, d),
                                            np.float32)),
            jnp.asarray(rng.standard_normal((keys, kv_heads, d), np.float32)),
            jnp.asarray(rng.standard_normal((keys, kv_heads, d), np.float32)))


@pytest.mark.parametrize("window, start", [
    (0, 0), (0, CHUNK), (0, 5 * CHUNK),
    # the first chunk, inside the first window (places still at -1), the
    # window's last chunk, and past it with the ring rotated and wrapped
    (WINDOW, 0), (WINDOW, CHUNK), (WINDOW, 2 * CHUNK), (WINDOW, 3 * CHUNK),
    (WINDOW, 7 * CHUNK)],
    ids=lambda v: str(v))
@pytest.mark.parametrize("heads, kv_heads, scale", [
    (14, 2, None), (4, 4, None), (14, 2, 0.015625)],
    ids=["group7", "group1", "scale"])
def test_the_kernel_is_the_dense_attention_on_the_same_mask(
        window, start, heads, kv_heads, scale):
    keys = _keys(start, window)
    q, k, v = _inputs(start + heads, heads, kv_heads, len(keys))
    want = _chunk_attention(q, k, v, start, scale,
                            jnp.asarray(_mask(keys, start, window)))
    got = chunk_attend(q, k, v, keys, start, window, scale, block_q=8,
                       block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)
    # and what chunk_attention hands the kernel or the dense path alike
    np.testing.assert_allclose(
        np.asarray(chunk_attention(q, k, v, start, scale,
                                   keys if window else None, window)),
        np.asarray(want), atol=2e-6, rtol=2e-5)


@pytest.mark.parametrize("window", [0, WINDOW])
def test_a_final_partial_chunk_reaches_its_real_rows_from_real_keys(window):
    """Pad positions of a last chunk hold garbage K/V: the real rows are
    the dense attention's whatever the pad keys hold."""
    start, real = 3 * CHUNK, 5
    keys = _keys(start, window)
    q, k, v = _inputs(1, 14, 2, len(keys))
    pad = slice(len(keys) - CHUNK + real, None)
    want = _chunk_attention(q, k, v, start, None,
                            jnp.asarray(_mask(keys, start, window)))
    got = chunk_attend(q, k.at[pad].set(1e4), v.at[pad].set(-1e4), keys,
                       start, window, None, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got)[:, :, :real],
                               np.asarray(want)[:, :, :real],
                               atol=2e-6, rtol=2e-5)


@pytest.mark.parametrize("start, unseen", [(CHUNK, 2), (7 * CHUNK, 0)])
def test_a_k_block_that_sees_nothing_is_skipped(start, unseen):
    """The plan skips the pairs of a query block and a K block that see
    nothing (ring places no position has reached, positions older than
    the window, the chunk's later keys) and fetches nothing new for them;
    NaN in a K block that no query sees does not reach the output."""
    keys = _keys(start, WINDOW)
    kind, fetch, _base = block_plan(keys, start, CHUNK, WINDOW, 8, 8)
    assert (kind == SKIP).any() and (kind == MASKED).any()
    assert (np.take_along_axis(kind, fetch, axis=1) != SKIP).all()
    blocks = np.flatnonzero((kind == SKIP).all(axis=0))
    assert len(blocks) == unseen
    q, k, v = _inputs(2, 14, 2, len(keys))
    want = _chunk_attention(q, k, v, start, None,
                            jnp.asarray(_mask(keys, start, WINDOW)))
    rows = (blocks[:, None] * 8 + np.arange(8)).reshape(-1)
    got = chunk_attend(q, k.at[rows].set(jnp.nan), v.at[rows].set(jnp.nan),
                       keys, start, WINDOW, None, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


def test_a_block_of_keys_that_are_not_consecutive_is_refused():
    keys = np.concatenate([np.arange(4), np.arange(10, 14)])
    with pytest.raises(ValueError, match="consecutive"):
        block_plan(keys, 8, 8, 0, 8, 8)


def test_only_the_smallthinker_cell_takes_the_kernel():
    """Each serving cell of ``BENCHMARK.json`` (its configuration and
    traffic files, read as they are): its largest prompt chunk, at the
    last offset its longest prompt reaches, reckoned with the rule
    ``chunk_attention`` follows, a window layer over its ring and the
    chunk, a global layer over its prefix and the chunk.  Only the
    SmallThinker cell crosses the line; a cell that comes to cross it is
    named here."""
    from benchmarks.harness import cells

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    crossing, seen = [], []
    for name in (w["name"] for w in bench["workloads"]):
        cell = cells.resolve_cell(name)
        program = cell.config["program"]
        chunk = program.get("serving", {}).get("prefill_chunk")
        if chunk is None:
            continue
        model = ModelConfig.from_dict(program["model"])
        longest = cell.traffic["prompt_range"][1]
        last = (math.ceil(longest / chunk) - 1) * chunk
        kernel, dense = (serve_hybrid.chunk_attn_counts(model, last, chunk)
                         if model.layer_types else (0, model.num_layers))
        assert kernel + dense, name
        seen.append(name)
        if kernel:
            crossing.append(name)
    assert len(seen) == 6, seen
    assert crossing == ["smallthinker_longdoc_backlog"], crossing


def test_the_counts_follow_the_rule_at_the_cells_offsets():
    """SmallThinker's eight layers (two global, six window) at chunks of
    1,024: the first chunk dense, the window layers on the kernel from
    the second, the global ones from keys of 3,072."""
    cfg = ModelConfig.from_dict(dict(
        hidden_size=2560, num_layers=8, num_heads=28, num_kv_heads=4,
        attn_head_dim=128, ffn_intermediate=768, dtype="bfloat16",
        norm="rmsnorm", mlp="reglu", bias=False, layer_types=["full_attention"]
        + ["sliding_attention"] * 3, sliding_window=4096, vocab_size=256))
    assert serve_hybrid.chunk_attn_counts(cfg, 0, 1024) == (0, 8)
    assert serve_hybrid.chunk_attn_counts(cfg, 1024, 1024) == (6, 2)
    assert serve_hybrid.chunk_attn_counts(cfg, 2048, 1024) == (8, 0)


@pytest.mark.tpu
@pytest.mark.parametrize("window, start", [(4096, 4096), (0, 14336)],
                         ids=["window", "global"])
def test_the_compiled_kernel_is_the_dense_attention_on_the_chip(window,
                                                                start):
    """At the SmallThinker cell's widths (28 query heads over 4 K/V heads
    of 128, chunks of 1,024), bfloat16, Mosaic against the dense
    program: both take ``P`` into ``P.V`` as one bfloat16 pass, so what
    is left is where each rounds ``P`` (normalised or not) and the order
    of the sums."""
    assert jax.default_backend() == "tpu"
    c = 1024
    keys = _keys(start, window, c)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal(s, np.float32), jnp.bfloat16)
               for s in ((1, 28, c, 128), (len(keys), 4, 128),
                         (len(keys), 4, 128)))
    want = jax.jit(_chunk_attention, static_argnums=3)(
        q, k, v, start, None, jnp.asarray(_mask(keys, start, window, c)))
    got = jax.jit(lambda q, k, v: chunk_attend(q, k, v, keys, start,
                                               window))(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
