"""Operations and bytes the Olmo-Hybrid family requires, computed from
shapes (``model`` is the configuration's ``program.model``).  Beside
``harness/flops.py``, which knows one kind of layer with a ``4 h f`` MLP
and may not be edited.

Multiply-adds count 2.  Norms, SiLU, softmax, the gates' two ``h x
heads`` projections' activations and the short convolution (8 operations
a channel) are left out (sub-percent); the embedding is a lookup.
Attention is causal, so only the lower triangle is required work, and
only the ``full_attention`` layers have one.  Recomputation is never
counted.
"""

from __future__ import annotations

from typing import Any

# the recurrence of one token and head, in units of d_k x d_v: the decay
# (1), S k (2), the rank-one update (2), S q (2)
DELTA_RULE_TERMS = 7


def layer_counts(model: dict[str, Any]) -> tuple[int, int]:
    """``(linear_attention layers, full_attention layers)`` of the
    ``num_layers`` the program runs."""
    kinds = model["layer_types"]
    periods = model["num_layers"] // len(kinds)
    return (periods * kinds.count("linear_attention"),
            periods * kinds.count("full_attention"))


def _linear_sizes(model: dict[str, Any]) -> tuple[int, int, int]:
    return (model["linear_num_value_heads"], model["linear_key_head_dim"],
            model["linear_value_head_dim"])


def token_matmul_flops(model: dict[str, Any]) -> float:
    """Per token, all layers, without attention scores, the delta rule
    and the head: the three MLP matrices everywhere; q, k, v, o in a full
    layer; the five projections (q, k, v, output gate, out) and the two
    gate vectors in a linear one."""
    h, f = model["hidden_size"], model["ffn_intermediate"]
    n_lin, n_full = layer_counts(model)
    heads, dk, dv = _linear_sizes(model)
    mlp = 2 * 3 * h * f
    full = 2 * 4 * h * h
    linear = 2 * h * (heads * (2 * dk + dv) + 2 * heads * dv + 2 * heads)
    return (n_lin + n_full) * mlp + n_full * full + n_lin * linear


def delta_rule_flops(model: dict[str, Any], tokens: float) -> float:
    """The recurrence itself for ``tokens`` tokens in every linear layer."""
    heads, dk, dv = _linear_sizes(model)
    return (layer_counts(model)[0] * tokens * heads * DELTA_RULE_TERMS
            * dk * dv)


def request_flops(model: dict[str, Any], prompt_len: int,
                  output_len: int) -> float:
    """What serving one request requires: the model over the ``prompt +
    output - 1`` tokens that are fed to it, causal pairs in the full
    layers over that length, the head once per produced token."""
    h = model["hidden_size"]
    fed = prompt_len + output_len - 1
    pairs = fed * (fed + 1) / 2
    attention = layer_counts(model)[1] * 4 * h * pairs      # QK^T and PV
    head = output_len * 2 * h * model["vocab_size"]
    return (fed * token_matmul_flops(model) + delta_rule_flops(model, fed)
            + attention + head)


def state_bytes(model: dict[str, Any]) -> float:
    """One slot's float32 recurrent state in one linear layer."""
    heads, dk, dv = _linear_sizes(model)
    return heads * dk * dv * 4


def conv_bytes(model: dict[str, Any], itemsize: int = 2) -> float:
    """One slot's carried convolution inputs in one linear layer."""
    heads, dk, dv = _linear_sizes(model)
    return ((model["linear_conv_kernel_dim"] - 1) * heads * (2 * dk + dv)
            * itemsize)


def decode_step_state_bytes(model: dict[str, Any], slots: float) -> float:
    """HBM traffic one decode step's recurrent update cannot avoid: every
    linear layer reads and writes the state and the convolution inputs
    of each active slot."""
    return (layer_counts(model)[0] * slots
            * 2 * (state_bytes(model) + conv_bytes(model)))


def prefill_scan_bytes(model: dict[str, Any], tokens: float,
                       chunks: float, itemsize: int = 2) -> float:
    """HBM traffic the chunked scan cannot avoid: q, k, v read and o
    written for every token and head, the state read and written once a
    chunk program, in every linear layer."""
    heads, dk, dv = _linear_sizes(model)
    per_token = heads * (2 * dk + 2 * dv) * itemsize
    return layer_counts(model)[0] * (tokens * per_token
                                     + chunks * 2 * state_bytes(model))
