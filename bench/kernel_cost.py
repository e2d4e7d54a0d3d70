"""Operations and bytes of one call of the affinity kernel
(``kernels.affinity``, ``name="affinity_argmin"``), from the operand
shapes the call is handed: task vectors ``[B, T]``, pair arrays
``[B, T, V]``, VM vectors ``[B, V]``.

Bytes: three 4-byte pair arrays read (missing MB, container ms, tier) —
12 B per pair — three 4-byte task vectors and three 4-byte VM vectors
read, four 4-byte outputs per task written.  Lane padding in VMEM is the
kernel's own overhead, not work the algorithm needs, so it is not
counted.

Operations: the vector arithmetic of Eqs. (1)-(5), the feasibility test
and the three-stage (tier, finish, vmid) reduction, counted per pair from
``ref.pair_estimates`` and ``affinity_ref``: 40.  It runs on the vector
unit in f32, for which no peak is published; against the bf16 matrix peak
it is negligible, so the kernel is bound by bytes.
"""
from __future__ import annotations

OPS_PER_PAIR = 40


def affinity_bytes(B: int, T: int, V: int) -> int:
    return 12 * B * T * V + 12 * B * T + 12 * B * V + 16 * B * T


def affinity_ops(B: int, T: int, V: int) -> int:
    return OPS_PER_PAIR * B * T * V


def least_seconds(shapes, peak: dict) -> float:
    """The least time the chip could take for these calls: per call the
    larger of operations over peak FLOP/s and bytes over HBM bytes/s."""
    return sum(max(affinity_ops(*s) / peak["flops_bf16_per_s"],
                   affinity_bytes(*s) / peak["hbm_bytes_per_s"])
               for s in shapes)
