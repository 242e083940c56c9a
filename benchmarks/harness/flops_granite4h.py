"""Operations and bytes Granite 4.0-H (``granitemoehybrid`` with no
experts: state-space layers, a grouped-query attention layer among every
ten, a dense SwiGLU in each, a tied head) requires, computed from shapes
(``model`` is the configuration's ``program.model``).  Beside
``harness/flops.py``, which knows one kind of layer and may not be
edited.

Multiply-adds count 2.  Norms, SiLU, softplus, softmax and the short
convolution's activation are left out (sub-percent); the embedding is a
lookup.  Attention is causal, so only the lower triangle is required
work, at the QUERY heads' width (grouped K/V heads save bytes, not
operations), and only the ``full_attention`` layers have one.  Padded
rows of a prompt chunk and recomputation are never counted.
"""

from __future__ import annotations

from typing import Any

# the recurrence of one token, head and (value, state) pair: the decay
# (1), the outer product's multiply and add (2), S C's multiply and add
# (2).  ``dt x`` and ``D x`` are per value, the exponential per head.
RECURRENCE_TERMS = 5


def layer_counts(model: dict[str, Any]) -> tuple[int, int]:
    """``(mamba layers, full_attention layers)`` of the ``num_layers``
    the program runs."""
    kinds = model["layer_types"]
    periods = model["num_layers"] // len(kinds)
    return (periods * kinds.count("mamba"),
            periods * kinds.count("full_attention"))


def _ssm_sizes(model: dict[str, Any]) -> tuple[int, int, int, int]:
    """``(heads, d_head, d_state, convolution channels)``."""
    heads, p, n = (model["mamba_n_heads"], model["mamba_d_head"],
                   model["mamba_d_state"])
    return heads, p, n, heads * p + 2 * model["mamba_n_groups"] * n


def token_matmul_flops(model: dict[str, Any]) -> float:
    """Per token, all layers, 2 x the parameters a token is multiplied
    by, without attention scores, the recurrence and the head: the three
    MLP matrices everywhere; q and o (``h x h``), k and v (``h x
    kv_heads x d``) in an attention layer; the in-projection (gate, x,
    B, C, step), the convolution's taps and the out-projection in a
    state-space one."""
    h, f = model["hidden_size"], model["ffn_intermediate"]
    n_ssm, n_full = layer_counts(model)
    heads, p, _n, channels = _ssm_sizes(model)
    inner = heads * p
    kv_width = model["num_kv_heads"] * (h // model["num_heads"])
    mlp = 2 * 3 * h * f
    full = 2 * (2 * h * h + 2 * h * kv_width)
    ssm = 2 * (h * (inner + channels + heads)
               + model["mamba_d_conv"] * channels + inner * h)
    return (n_ssm + n_full) * mlp + n_full * full + n_ssm * ssm


def recurrence_flops(model: dict[str, Any], tokens: float) -> float:
    """The recurrence itself for ``tokens`` tokens in every state-space
    layer (the same count whichever form computes it)."""
    heads, p, n, _ = _ssm_sizes(model)
    return (layer_counts(model)[0] * tokens * RECURRENCE_TERMS
            * heads * p * n)


def request_flops(model: dict[str, Any], prompt_len: int,
                  output_len: int) -> float:
    """What serving one request requires: the model over the ``prompt +
    output - 1`` tokens that are fed to it, causal pairs in the
    attention layers over that length, the head once per produced
    token."""
    h = model["hidden_size"]
    fed = prompt_len + output_len - 1
    pairs = fed * (fed + 1) / 2
    attention = layer_counts(model)[1] * 4 * h * pairs      # QK^T and PV
    head = output_len * 2 * h * model["vocab_size"]
    return (fed * token_matmul_flops(model) + recurrence_flops(model, fed)
            + attention + head)


def state_bytes(model: dict[str, Any]) -> float:
    """One slot's float32 recurrent state in one state-space layer."""
    heads, p, n, _ = _ssm_sizes(model)
    return heads * p * n * 4


def conv_bytes(model: dict[str, Any], itemsize: int = 2) -> float:
    """One slot's carried convolution inputs in one state-space layer."""
    return (model["mamba_d_conv"] - 1) * _ssm_sizes(model)[3] * itemsize


def decode_state_bytes(model: dict[str, Any], slot_steps: float) -> float:
    """HBM traffic the recurrent update of ``slot_steps`` (slot, step)
    pairs cannot avoid: every state-space layer reads and writes the
    state and the convolution inputs of each stepping slot."""
    return (layer_counts(model)[0] * slot_steps
            * 2 * (state_bytes(model) + conv_bytes(model)))


def prefill_scan_bytes(model: dict[str, Any], tokens: float,
                       chunks: float, itemsize: int = 2) -> float:
    """HBM traffic the chunked scan cannot avoid: x, B and C read and y
    written for every real token, the state read and written once a
    chunk program, in every state-space layer."""
    heads, p, n, _ = _ssm_sizes(model)
    per_token = (2 * heads * p + 2 * n) * itemsize
    return layer_counts(model)[0] * (tokens * per_token
                                     + chunks * 2 * state_bytes(model))


def kv_live_bytes(model: dict[str, Any], live_tokens: float,
                  itemsize: int = 2) -> float:
    """K and V of ``live_tokens`` cached tokens in every attention
    layer, at the K/V heads' width."""
    d = model["hidden_size"] // model["num_heads"]
    return (layer_counts(model)[1] * live_tokens
            * 2 * model["num_kv_heads"] * d * itemsize)
