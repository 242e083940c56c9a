"""GSPMD partition specs for the TP transformer.

The reference implements tensor parallelism imperatively: per-rank weight
shards plus a hand-written ``comm.Allreduce`` after each row-parallel matmul
(``models.py:19-47`` column, ``:50-100`` row, allreduce ``:95``).  On TPU the
same Megatron layout is *declared*: shard the QKV / FFN-up kernels on their
output dim and the out-proj / FFN-down kernels on their input dim over the
``tp`` mesh axis, and XLA GSPMD inserts exactly the two per-layer
all-reduces over ICI.

What a ``tp`` shard of ``qkv`` holds: the kernel's columns lie by kv-head
group (a group's query heads, its key head, its value head —
``transformer.py::init_params``), and the shards are contiguous blocks of
columns, so with ``tp`` dividing ``kv_heads`` each shard computes the q, k
and v of ``kv_heads / tp`` whole groups: the heads the attention kernel's
``shard_map`` (head axis over ``tp``) and the serving cache (kv-head axis
over ``tp``) give that same shard.  Nothing moves between chips from the
projection to the row-parallel sum.  Where ``tp`` does not divide
``kv_heads`` a group straddles two shards and GSPMD realigns it.

Layer params are stacked on a leading ``num_layers`` axis (scanned in the
forward pass); that axis is ``None`` for pure TP and carries the ``pp``
mesh axis under pipeline parallelism (each pipeline stage holds a
contiguous block of layers — ``dlbb_tpu/parallel/pipeline.py``).
"""

from __future__ import annotations

from typing import Optional

from jax.sharding import PartitionSpec as P

TP_AXIS = "tp"
DP_AXIS = "dp"
PP_AXIS = "pp"
EP_AXIS = "ep"


def param_specs(tp_axis: Optional[str] = TP_AXIS,
                pp_axis: Optional[str] = None,
                moe: bool = False,
                ep_axis: Optional[str] = None) -> dict:
    """PartitionSpec pytree matching ``init_params``' structure.

    ``pp_axis`` shards the leading stacked-layer axis across pipeline
    stages; ``moe`` switches the FFN specs to the expert-stacked MoE
    layout, whose expert dim shards over ``ep_axis`` (``None`` = no such
    parallelism)."""
    t, l, e = tp_axis, pp_axis, ep_axis
    if moe:
        ffn = {
            # router stays replicated over tp/ep: [L, H, E] is tiny and
            # every device needs the full gate distribution
            "router": {"kernel": P(l, None, None)},
            # experts shard over ep on their leading expert dim, and each
            # expert keeps the Megatron col/row TP split on its features
            "ffn_up": {"kernel": P(l, e, None, t), "bias": P(l, e, t)},
            "ffn_down": {"kernel": P(l, e, t, None), "bias": P(l, e, None)},
        }
    else:
        ffn = {
            "ffn_up": {"kernel": P(l, None, t), "bias": P(l, t)},
            "ffn_down": {"kernel": P(l, t, None), "bias": P(l, None)},
        }
    return {
        "layers": {
            "ln1": {"scale": P(l, None), "bias": P(l, None)},
            # column parallel: shard out_features (reference models.py:19-47)
            "qkv": {"kernel": P(l, None, t), "bias": P(l, t)},
            # row parallel: shard in_features; partial sums -> psum
            # (reference models.py:50-100)
            "out": {"kernel": P(l, t, None), "bias": P(l, None)},
            "ln2": {"scale": P(l, None), "bias": P(l, None)},
            **ffn,
        },
        "ln_f": {"scale": P(None), "bias": P(None)},
    }


def specs_for_mesh(mesh, tp_axis: str = TP_AXIS,
                   pp_axis: str = PP_AXIS, moe: bool = False,
                   ep_axis: str = EP_AXIS) -> dict:
    """Param specs matched to a concrete mesh: each model-parallel axis
    (tp on features, pp on the stacked-layer dim, ep on the expert dim)
    participates iff the mesh actually has it with size > 1."""
    axes = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    use_pp = pp_axis in axes and mesh.shape[pp_axis] > 1
    use_tp = tp_axis in axes
    use_ep = moe and ep_axis in axes and mesh.shape[ep_axis] > 1
    return param_specs(tp_axis if use_tp else None,
                       pp_axis if use_pp else None,
                       moe=moe,
                       ep_axis=ep_axis if use_ep else None)


def batch_spec(mesh=None, dp_axis: str = DP_AXIS, sp_axis: str = "sp") -> P:
    """Activations sharded over data parallelism on the batch dim, and —
    when the mesh has a sequence-parallel axis — over ``sp`` on the
    sequence dim."""
    if mesh is not None and sp_axis in getattr(mesh, "axis_names", ()):
        return P(dp_axis, sp_axis, None)
    return P(dp_axis, None, None)
