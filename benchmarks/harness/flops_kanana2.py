"""Operations and bytes the kanana-2-30b-a3b block (``model_type:
deepseek_v3``: latent attention, routed and shared experts) requires,
computed from shapes (``model`` is the configuration's
``program.model``).  Beside ``harness/flops.py`` and
``harness/flops_olmo_hybrid.py``, which know other blocks.

Multiply-adds count 2.  Norms, SiLU, softmax, sigmoid, the rotation and
the sort of the assignments are left out (sub-percent); the embedding is
a lookup.  Attention is causal, so only the lower triangle is required
work.  What is counted is the LEAST arithmetic that computes the
function: a token activates its ``num_experts_per_tok`` routed experts
and the shared ones, never all of them, and attention is reckoned in the
EXPANDED form (per query-key pair and head ``2 (d_nope + d_rope) + 2
d_v``, each token's keys and values expanded once, which the ``W_kv_b``
term of the per-token count is), so that the absorbed form's larger
products (``2 (r + d_rope) + 2 r`` a pair) cannot read as utilisation.
Recomputation (a chunk re-expanding its prefix) is never counted.
"""

from __future__ import annotations

from typing import Any


def layer_counts(model: dict[str, Any]) -> tuple[int, int]:
    """``(leading dense layers, expert layers)`` of the ``num_layers``
    the program runs."""
    lead = model.get("first_k_dense_replace", 0)
    return lead, model["num_layers"] - lead


def attention_params(model: dict[str, Any]) -> int:
    """``W_q``, ``W_kv_a``, ``W_kv_b`` and ``W_o`` of one layer."""
    h, n = model["hidden_size"], model["num_heads"]
    r, dn = model["kv_lora_rank"], model["qk_nope_head_dim"]
    dr, dv = model["qk_rope_head_dim"], model["v_head_dim"]
    return h * n * (dn + dr) + h * (r + dr) + r * n * (dn + dv) + n * dv * h


def expert_params(model: dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def token_matmul_flops(model: dict[str, Any]) -> float:
    """Per token, all layers, without attention scores and the head: the
    parameters a token ACTIVATES, twice."""
    h = model["hidden_size"]
    lead, sparse = layer_counts(model)
    dense_mlp = 3 * h * model["ffn_intermediate"]
    active = (h * model["n_routed_experts"]
              + (model["num_experts_per_tok"] + model["n_shared_experts"])
              * expert_params(model))
    return 2.0 * ((lead + sparse) * attention_params(model)
                  + lead * dense_mlp + sparse * active)


def pair_flops(model: dict[str, Any]) -> float:
    """One query-key pair of one layer, all heads, expanded form."""
    return model["num_heads"] * (
        2 * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"])
        + 2 * model["v_head_dim"])


def request_flops(model: dict[str, Any], prompt_len: int,
                  output_len: int) -> float:
    """What serving one request requires: the model over the ``prompt +
    output - 1`` tokens that are fed to it, causal pairs over that length
    in every layer, the head once per produced token."""
    fed = prompt_len + output_len - 1
    pairs = fed * (fed + 1) / 2
    head = output_len * 2 * model["hidden_size"] * model["vocab_size"]
    return (fed * token_matmul_flops(model)
            + model["num_layers"] * pairs * pair_flops(model) + head)


# -- what the two new kernels' rooflines count ---------------------------------


def expert_products_flops(model: dict[str, Any], assignments: float) -> float:
    """The grouped products of ``assignments`` (token, expert) pairs."""
    return assignments * 2.0 * expert_params(model)


def expert_products_bytes(model: dict[str, Any], touched: float,
                          assignments: float, itemsize: int = 2) -> float:
    """HBM traffic the grouped products cannot avoid: the weights of the
    experts that got a token, once each (``touched`` sums them over
    layers and steps), and every assignment's input row read and output
    row written."""
    return itemsize * (touched * expert_params(model)
                       + assignments * 2 * model["hidden_size"])


def latent_row_bytes(model: dict[str, Any], itemsize: int = 2) -> int:
    """One cached row as counted: the latent and the rotary key."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * itemsize


def latent_decode_bytes(model: dict[str, Any], live_tokens: float) -> float:
    """Decode attention reads each cached row under a slot's length once
    a layer (for all heads): ``live_tokens`` sums them over slots and
    steps."""
    return model["num_layers"] * live_tokens * latent_row_bytes(model)


def latent_decode_flops(model: dict[str, Any], live_tokens: float) -> float:
    """Absorbed scores and values of one query a head against each row:
    ``2 (r + d_rope) + 2 r`` a row and head."""
    r, dr = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return (model["num_layers"] * live_tokens * model["num_heads"]
            * 2.0 * ((r + dr) + r))
