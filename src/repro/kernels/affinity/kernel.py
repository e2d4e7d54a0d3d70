"""Pallas kernel for the EBPSM task×VM affinity argmin (Alg. 2 inner loop).

At WaaS scale (1000 workflows ≈ 170k tasks, pools of hundreds of VMs) the
O(T·V) scoring loop dominates scheduler runtime.  The kernel scores a whole
grid of independent auctions in one call: the grid is (member, task block),
each step computes Eqs. (1)-(5) for ``bt × V`` pairs of one member and the
three-stage lexicographic reduction ((tier, finish, vmid) argmin) entirely
on-chip, so HBM traffic is one read of the pair features and a
``[bt, 1]``-sized write per output.

TPU layout: every block's last two dims are either (multiple of 8, any) with
the second equal to the array's full dim, or the array's full dims — task
vectors travel as ``[B, T, 1]`` columns, VM vectors as ``[B, 1, V]`` rows,
pairs as ``[B, T, V]``; the member dim is squeezed out of every block.  The
whole VM axis stays resident (V ≤ 2048).  VMEM pads every block's last dim
to 128 lanes, so a ``[bt, 1]`` column costs as much as a 128-wide pair row;
``bt`` is the largest power of two whose double-buffered blocks (three pair
tiles, seven columns) fit ``BLOCK_BYTES``.  The platform scalars (storage bandwidths,
billing period) are static and compile into the kernel as constants, and
the pair estimates are the jnp oracle's own :func:`.ref.pair_estimates`
evaluated on each tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import pair_estimates

BIG = 3.4e38
LANES = 128
BLOCK_BYTES = 2 << 20  # VMEM for one grid step's double-buffered blocks


def _affinity_kernel(size_ref, out_ref, bud_ref, miss_ref, cont_ref, tier_ref,
                     mips_ref, bw_ref, price_ref,
                     vm_ref, tierout_ref, fin_ref, cost_ref,
                     *, gs_read: float, gs_write: float, bp_ms: float):
    tier = tier_ref[...]            # [bt, V]
    budget = bud_ref[...]           # [bt, 1]
    pipe, cost = pair_estimates(size_ref[...], out_ref[...], miss_ref[...],
                                cont_ref[...], mips_ref[...], bw_ref[...],
                                gs_read, gs_write, bp_ms, price_ref[...])

    feas = (tier > 0) & (cost <= budget + 1e-6)
    t_eff = jnp.where(feas, tier, 9)
    best_t = jnp.min(t_eff, axis=1, keepdims=True)          # [bt, 1]
    f_eff = jnp.where(t_eff == best_t, pipe, BIG)
    best_f = jnp.min(f_eff, axis=1, keepdims=True)
    vmids = jax.lax.broadcasted_iota(jnp.int32, tier.shape, 1)
    v_eff = jnp.where(f_eff == best_f, vmids, 1 << 30)
    best_v = jnp.min(v_eff, axis=1, keepdims=True)          # always < V
    none = best_t >= 9
    pick = vmids == best_v
    vm_ref[...] = jnp.where(none, -1, best_v)
    tierout_ref[...] = best_t
    fin_ref[...] = jnp.where(
        none, BIG, jnp.min(jnp.where(pick, pipe, BIG), axis=1, keepdims=True))
    cost_ref[...] = jnp.where(
        none, BIG, jnp.min(jnp.where(pick, cost, BIG), axis=1, keepdims=True))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def affinity_pallas(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
                    vm_mips, vm_bw, vm_price, gs_read: float, gs_write: float,
                    bp_ms: float, interpret: bool | None = None):
    """Batched affinity: task arrays ``[B, T]``, pair arrays ``[B, T, V]``,
    VM arrays ``[B, V]``; returns ``(best_vm, best_tier, est_finish,
    est_cost)``, each ``[B, T]``.

    ``interpret=None`` compiles for a TPU backend and interprets elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, V = missing_mb.shape
    # Bytes per task row: three lane-padded pair rows and seven lane-padded
    # columns, 4-byte elements, two buffers each.
    row_bytes = 2 * 4 * (3 * _round_up(V, LANES) + 7 * LANES)
    bt = min(max(8, 1 << ((BLOCK_BYTES // row_bytes).bit_length() - 1)),
             _round_up(T, 8))
    tp = _round_up(T, bt)
    padT = lambda a: jnp.pad(a, ((0, 0), (0, tp - T))
                             + ((0, 0),) * (a.ndim - 2))
    col = lambda a: padT(a)[:, :, None]          # [B, tp, 1]
    row = lambda a: a[:, None, :]                # [B, 1, V]
    col_spec = pl.BlockSpec((None, bt, 1), lambda b, i: (b, i, 0))
    pair_spec = pl.BlockSpec((None, bt, V), lambda b, i: (b, i, 0))
    row_spec = pl.BlockSpec((None, 1, V), lambda b, i: (b, 0, 0))
    out_dtypes = (jnp.int32, jnp.int32, jnp.float32, jnp.float32)
    outs = pl.pallas_call(
        functools.partial(_affinity_kernel, gs_read=float(gs_read),
                          gs_write=float(gs_write), bp_ms=float(bp_ms)),
        grid=(B, tp // bt),
        in_specs=[col_spec] * 3 + [pair_spec] * 3 + [row_spec] * 3,
        out_specs=[col_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((B, tp, 1), d) for d in out_dtypes],
        interpret=interpret,
        name="affinity_argmin",
    )(col(size_mi), col(out_mb), col(budget),
      padT(missing_mb), padT(cont_ms), padT(tier),
      row(vm_mips), row(vm_bw), row(vm_price))
    return tuple(o[:, :T, 0] for o in outs)
