"""Operations and bytes the Ouro block (``model_type: ouro``: a dense
Llama-lineage stack run ``total_ut_steps`` times a token with one set of
weights, K and V kept for every (pass, layer)) requires, computed from
shapes (``model`` is the configuration's ``program.model``).  Beside
``harness/flops.py``, ``flops_olmo_hybrid.py`` and ``flops_kanana2.py``,
which know other blocks.

Multiply-adds count 2.  Norms, SiLU, softmax, the rotation, the exit
gate (one product of ``hidden_size`` a pass) are left out (sub-percent);
the embedding is a lookup.  Attention is causal, so only the lower
triangle is required work.  Every pass is required work: at the
published ``early_exit_threshold`` of 1 the function IS four passes,
whatever shares their weights.  Recomputation and padding are never
counted.
"""

from __future__ import annotations

from typing import Any


def passes(model: dict[str, Any]) -> int:
    return int(model.get("total_ut_steps", 1))


def layer_params(model: dict[str, Any]) -> int:
    """One layer's q, k, v, o projections and SwiGLU (the four norm
    scales are left out)."""
    h, f = model["hidden_size"], model["ffn_intermediate"]
    kv = model.get("num_kv_heads", model["num_heads"]) \
        * (h // model["num_heads"])
    return 2 * h * h + 2 * h * kv + 3 * h * f


def token_matmul_flops(model: dict[str, Any]) -> float:
    """Per token, every pass of every layer, without attention scores
    and the head."""
    return 2.0 * passes(model) * model["num_layers"] * layer_params(model)


def pair_flops(model: dict[str, Any]) -> float:
    """One query-key pair of one layer in one pass, all heads: the score
    and the weighted value."""
    return 4.0 * model["hidden_size"]


def request_flops(model: dict[str, Any], prompt_len: int,
                  output_len: int) -> float:
    """What serving one request requires: the looped stack over the
    ``prompt + output - 1`` tokens that are fed to it, causal pairs over
    that length in every layer of every pass, the head once per produced
    token."""
    fed = prompt_len + output_len - 1
    pairs = fed * (fed + 1) / 2
    head = output_len * 2 * model["hidden_size"] * model["vocab_size"]
    return (fed * token_matmul_flops(model)
            + passes(model) * model["num_layers"] * pairs * pair_flops(model)
            + head)


# -- what a decode unit cannot avoid reading -----------------------------------


def stack_weight_bytes(model: dict[str, Any], itemsize: int = 2) -> int:
    """The stack's matrices once: what ONE pass reads."""
    return itemsize * model["num_layers"] * layer_params(model)


def head_bytes(model: dict[str, Any], itemsize: int = 2) -> int:
    return itemsize * model["hidden_size"] * model["vocab_size"]


def weight_pass_bytes(model: dict[str, Any], steps: float) -> float:
    """``steps`` decode steps read the stack's weights once a pass each:
    shared weights are read again, nothing keeps 4.9 GB on the chip
    between passes."""
    return steps * passes(model) * stack_weight_bytes(model)


def weight_pass_flops(model: dict[str, Any], slot_steps: float) -> float:
    """The same steps' products, ``slot_steps`` tokens in all."""
    return slot_steps * token_matmul_flops(model)


def kv_token_bytes(model: dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one token in every (pass, layer)."""
    kv = model.get("num_kv_heads", model["num_heads"]) \
        * (model["hidden_size"] // model["num_heads"])
    return passes(model) * model["num_layers"] * 2 * kv * itemsize


def kv_live_bytes(model: dict[str, Any], live_tokens: float) -> float:
    """Decode attention reads the K and V of every token under a slot's
    length once a (pass, layer): ``live_tokens`` sums them over slots
    and steps (the report's ``unit_live_tokens``)."""
    return live_tokens * kv_token_bytes(model)


def kv_attend_flops(model: dict[str, Any], live_tokens: float) -> float:
    return (live_tokens * passes(model) * model["num_layers"]
            * pair_flops(model))


def decode_unit_bytes(model: dict[str, Any], steps: float,
                      live_tokens: float) -> float:
    """A decode unit of ``steps`` steps: four weight passes and the head
    a step, and the live K/V of all planes."""
    return (weight_pass_bytes(model, steps) + steps * head_bytes(model)
            + kv_live_bytes(model, live_tokens))
