"""Operations and bytes the algorithm requires, computed from shapes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  Multiply-adds count 2; layernorm, GELU, softmax and the
optimizer update are left out (sub-percent).  Attention is causal in
every configuration here, so only the lower triangle (diagonal included)
is required work.  Recomputation (remat, the flash backward's second
QK^T) is never counted: it is work the device does, not work the model
requires.
"""

from __future__ import annotations

from typing import Any


def _attn_pairs(seq: int, causal: bool) -> float:
    """Query-key pairs one sequence requires."""
    return seq * (seq + 1) / 2 if causal else float(seq * seq)


def forward_flops_per_token(model: dict[str, Any], seq: int) -> float:
    h, f, layers = (model["hidden_size"], model["ffn_intermediate"],
                    model["num_layers"])
    causal = model.get("causal", True)
    matmuls = 2 * h * 3 * h + 2 * h * h + 2 * 2 * h * f   # qkv, out, ffn
    attention = 4 * h * _attn_pairs(seq, causal) / seq    # QK^T and PV
    return layers * (matmuls + attention)


def train_flops_per_token(model: dict[str, Any], seq: int) -> float:
    """Forward plus backward (twice the forward: gradients with respect
    to activations and to weights)."""
    return 3 * forward_flops_per_token(model, seq)


def flash_flops(model: dict[str, Any], batch: int, seq: int, heads: int,
                backward: bool) -> float:
    """Flash attention over ``heads`` heads of ``batch`` sequences in all
    layers: forward QK^T and PV (4 x pairs x head_dim); backward dV, dP,
    dQ and dK (8 x pairs x head_dim)."""
    d = model["hidden_size"] // model["num_heads"]
    pairs = _attn_pairs(seq, model.get("causal", True))
    per_head = (4 + (8 if backward else 0)) * pairs * d
    return model["num_layers"] * batch * heads * per_head


def flash_bytes(model: dict[str, Any], batch: int, seq: int, heads: int,
                backward: bool, itemsize: int = 2) -> float:
    """HBM traffic flash attention cannot avoid: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    d = model["hidden_size"] // model["num_heads"]
    tensors = 4 + (8 if backward else 0)
    return (model["num_layers"] * batch * heads * seq * d * itemsize
            * tensors)
