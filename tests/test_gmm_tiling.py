"""The grouped expert products' tiling (``ops/routed_experts.py::
_gmm_tiling``): chosen from the product's shape, the whole contraction
in one tile and columns that divide the width within the VMEM budget.

(a) the rule at the two expert cells' shapes, read from their
configuration files, and at shapes that force its fallbacks; (b) the
products under the rule, interpreted, against a dense product per
expert in float32; (c) on the chip (``tpu``), the compiled kernel at
SmallThinker's chunk widths.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlbb_tpu.ops import routed_experts as moe

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import cells                            # noqa: E402

EXPERT_CELLS = ("smallthinker_longdoc_backlog", "kanana_serve_longctx_backlog")


def _cell_products(cell):
    """``(m, k, n)`` of a cell's chunk and decode products: gate/up
    ``[hidden, width]``, down ``[width, hidden]``."""
    program = cells.resolve_cell(cell).config["program"]
    model, serving = program["model"], program["serving"]
    top_k = model["num_experts_per_tok"]
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    return {(phase, product): (rows * top_k, k, n)
            for phase, rows in (("chunk", serving["prefill_chunk"]),
                                ("decode", serving["max_batch"]))
            for product, (k, n) in (("gate_up", (h, f)), ("down", (f, h)))}


CELL_PRODUCTS = [(cell, phase, product)
                 for cell in EXPERT_CELLS
                 for phase in ("chunk", "decode")
                 for product in ("gate_up", "down")]


# -- (a) the rule ---------------------------------------------------------------


@pytest.mark.parametrize("cell, phase, product", CELL_PRODUCTS)
def test_the_cells_products_take_the_whole_contraction_within_the_budget(
        cell, phase, product):
    m, k, n = _cell_products(cell)[phase, product]
    tm, tk, tn = moe._gmm_tiling(m, k, n, 2)
    assert tm == moe.GMM_ROWS == 128
    assert tk == k
    assert n % tn == 0 and tn % 128 == 0
    assert moe.gmm_vmem_bytes(tm, tk, tn, 2) <= moe.GMM_VMEM_BUDGET
    # the widest column tile that fits: the next wider divisor does not
    wider = [t for t in moe._lane_tiles(n) if t > tn]
    assert all(moe.gmm_vmem_bytes(tm, k, t, 2) > moe.GMM_VMEM_BUDGET
               for t in wider)


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_the_cells_count_every_product_whole(cell):
    """What ``serve_gmm_products`` books there: a chunk's and a decode
    step's three products an expert layer, each one contraction tile."""
    program = cells.resolve_cell(cell).config["program"]
    model, serving = program["model"], program["serving"]
    for tokens in (serving["prefill_chunk"], serving["max_batch"]):
        assert moe.gmm_counts(tokens, model["num_experts_per_tok"],
                              model["hidden_size"],
                              model["moe_intermediate_size"], 2) == (3, 0)


@pytest.mark.parametrize("phase", ["chunk", "decode"])
def test_kananas_gate_and_up_keep_the_fixed_tiling(phase):
    """Its hidden of 2,048 fits the old contraction tile whole: those
    products are the parent's."""
    m, k, n = _cell_products("kanana_serve_longctx_backlog")[phase,
                                                            "gate_up"]
    assert moe._gmm_tiling(m, k, n, 2) == (128, 2048, 768)


@pytest.mark.parametrize("k, n", [(16384, 1024), (32768, 256), (24576, 640)])
def test_a_contraction_that_cannot_fit_whole_falls_to_a_divisor(k, n):
    tm, tk, tn = moe._gmm_tiling(6144, k, n, 2)
    assert moe.gmm_vmem_bytes(tm, k, 128, 2) > moe.GMM_VMEM_BUDGET
    assert tk < k and k % tk == 0 and tk % 128 == 0
    assert n % tn == 0 and tn % 128 == 0
    assert moe.gmm_vmem_bytes(tm, tk, tn, 2) <= moe.GMM_VMEM_BUDGET
    # gate and up split their contraction, down does not
    assert moe.gmm_counts(1024, 6, k, n, 2) == (1, 2)


@pytest.mark.parametrize("k, n", [(64, 24), (24, 64), (32, 32), (100, 300),
                                  (128, 96)])
def test_toy_widths_take_whole_dimensions(k, n):
    assert moe._gmm_tiling(10, k, n, 4) == (128, k, n)


# -- (b) the products under the rule, interpreted --------------------------------


def _dense(rows, w, sizes):
    """Each row through its group's matrix in float32 (``sizes`` over
    ``w``'s groups in order); rows past the last group are zeros."""
    out = np.zeros((rows.shape[0], w.shape[-1]), np.float32)
    at = 0
    for g, size in enumerate(sizes):
        out[at:at + size] = rows[at:at + size] @ w[g]
        at += size
    return out


@pytest.mark.parametrize("k, n, budget, tiling", [
    (384, 256, None, (128, 384, 256)),
    (384, 256, 1_200_000, (128, 384, 128)),
    (384, 256, 900_000, (128, 128, 256)),
], ids=["whole", "columns_split", "contraction_split"])
@pytest.mark.parametrize("layer", [None, 2], ids=["one_layer", "stack_layer2"])
def test_products_under_the_rule_are_a_dense_product_per_expert(
        monkeypatch, k, n, budget, tiling, layer):
    """100 rows over 6 experts: uneven groups, two experts empty, 12
    rows past the last group; a contraction over three lane groups; the
    rule's tiling and, under a smaller budget, its split columns and its
    split contraction; one layer's experts, or the third layer's of a
    stack of three (how ``grouped_products`` hands a scanned layer)."""
    if budget is not None:
        monkeypatch.setattr(moe, "GMM_VMEM_BUDGET", budget)
    assert moe._gmm_tiling(100, k, n, 4) == tiling
    rng = np.random.default_rng(k + n + (layer or 0))
    e, sizes = 6, np.array([30, 0, 7, 41, 0, 10])
    stack = 3 if layer is not None else 1
    w = rng.standard_normal((stack * e, k, n)).astype(np.float32) * k ** -.5
    rows = rng.standard_normal((100, k)).astype(np.float32)
    groups = np.zeros(stack * e, np.int32)
    at = (layer or 0) * e
    groups[at:at + e] = sizes
    got = np.asarray(moe._gmm(jnp.asarray(rows), jnp.asarray(w),
                              jnp.asarray(groups)))
    want = _dense(rows, w[at:at + e], sizes)
    live = sizes.sum()
    np.testing.assert_allclose(got[:live], want[:live], rtol=1e-4,
                               atol=1e-4)


# -- (c) on the chip ---------------------------------------------------------------


@pytest.mark.tpu
@pytest.mark.parametrize("product", ["gate_up", "down"])
def test_the_compiled_products_at_smallthinkers_chunk_widths(product):
    """6,144 rows over 64 experts, the fullest about 16 x the mean and
    at least four experts empty, bfloat16 operands accumulated in float32: Mosaic
    under the rule's tiling against a dense float32 product per expert,
    and against the fixed (128, 2048, 768)."""
    assert jax.default_backend() == "tpu"
    m, k, n = _cell_products("smallthinker_longdoc_backlog")["chunk",
                                                             product]
    rng = np.random.default_rng(42)
    share = rng.permutation(np.arange(1, 61) ** -1.1)
    sizes = np.zeros(64, np.int64)
    sizes[rng.permutation(64)[:60]] = np.floor(share / share.sum() * m)
    sizes[np.argmax(sizes)] += m - sizes.sum()
    w = (rng.standard_normal((64, k, n)).astype(np.float32)
         * k ** -.5).astype(jnp.bfloat16)
    rows = rng.standard_normal((m, k)).astype(jnp.bfloat16)
    groups = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(jax.jit(moe._gmm)(rows, w, groups), np.float32)
    want = _dense(rows.astype(np.float32), w.astype(np.float32), sizes)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < 1e-2

    from jax.experimental.pallas.ops.tpu.megablox import gmm
    fixed = np.asarray(jax.jit(lambda r, w, s: gmm(
        r, w, s, preferred_element_type=r.dtype,
        tiling=(128, min(2048, k), min(768, n))))(rows, w, groups),
        np.float32)
    assert np.max(np.abs(got - fixed)) / scale < 1e-2
