"""Pallas TPU kernels for the hot ops.

The compute path of the framework is XLA (which fuses elementwise chains
into the matmuls on its own); these kernels cover the ops where explicit
VMEM blocking beats XLA's default lowering — above all attention, whose
materialised ``[S, S]`` score matrix is the canonical HBM-bandwidth trap.
"""

from dlbb_tpu.ops.flash_attention import flash_attention, mosaic_call_count

__all__ = ["flash_attention", "mosaic_call_count"]
