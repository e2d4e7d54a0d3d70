"""Pure-jnp oracle for the EBPSM affinity kernel (Alg. 2 inner loop).

Given T queued tasks × V pooled VMs, score every pair with the paper's
locality-aware finish-time estimate and pick, per task, the feasible VM
minimizing the lexicographic key (tier, est_finish, vmid).

Tiers follow Alg. 2: 1 = idle VM holding all the task's input data,
2 = idle VM with the task's container deployed, 3 = any idle VM.
``tier = 0`` marks pairs out of scope (busy VM, wrong owner tag).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

BIG = jnp.float32(3.4e38)
MS = 1000.0


class AffinityOut(NamedTuple):
    best_vm: jnp.ndarray    # [T] int32, -1 when no feasible VM
    best_tier: jnp.ndarray  # [T] int32, 9 when none
    est_finish: jnp.ndarray  # [T] f32 ms
    est_cost: jnp.ndarray   # [T] f32 cents


CEIL_TOL = 1.0 - 1e-6  # matches core.costs.ceil_ms (see comment there)


def billing_periods(pipe, bp_ms):
    """Exact ``ceil(pipe / bp_ms)`` for integer-valued f32 ``pipe`` < 2**24,
    as ``core.costs.billed_cost`` counts periods.

    f32 division is not exact everywhere: XLA rewrites division by a
    constant into multiplication by its rounded reciprocal (3000 / 1000
    gives 3.0000002), and TPU division is not correctly rounded.  The
    products ``q * bp_ms`` are exact, so one step each way restores the
    integer ceiling."""
    q = jnp.ceil(pipe / bp_ms)
    q = jnp.where(q * bp_ms < pipe, q + 1.0, q)
    return jnp.where((q - 1.0) * bp_ms >= pipe, q - 1.0, q)


def pair_estimates(size_mi, out_mb, missing_mb, cont_ms, vm_mips, vm_bw,
                   gs_read, gs_write, bp_ms, vm_price):
    """Vectorized Eqs. (1)-(5) without provisioning: [T,V] pipe_ms, cost.

    Task operands are ``[T, 1]`` columns, VM operands ``[1, V]`` rows, pair
    operands ``[T, V]``; the Pallas kernel evaluates this same function on
    its tiles, so both paths share one formula."""
    # One folded constant: XLA folds ``x * MS * CEIL_TOL`` into
    # ``x * (MS * CEIL_TOL)`` and Mosaic does not, so writing the product
    # out keeps the kernel and this oracle bit-equal on a TPU.
    scale = MS * CEIL_TOL
    pipe = (jnp.ceil(missing_mb * (1.0 / vm_bw + 1.0 / gs_read) * scale)
            + jnp.ceil(size_mi / vm_mips * scale)
            + jnp.ceil(out_mb * (1.0 / vm_bw + 1.0 / gs_write) * scale)
            + cont_ms)
    cost = billing_periods(pipe, bp_ms) * vm_price
    return pipe, cost


def affinity_ref(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
                 vm_mips, vm_bw, vm_price, gs_read, gs_write,
                 bp_ms) -> AffinityOut:
    """All task arrays [T]; pair arrays [T,V]; vm arrays [V]."""
    pipe, cost = pair_estimates(size_mi[:, None], out_mb[:, None],
                                missing_mb, cont_ms, vm_mips[None, :],
                                vm_bw[None, :], gs_read, gs_write, bp_ms,
                                vm_price[None, :])
    feasible = (tier > 0) & (cost <= budget[:, None] + 1e-6)
    t_eff = jnp.where(feasible, tier, 9).astype(jnp.int32)
    best_tier = jnp.min(t_eff, axis=1)
    f_eff = jnp.where(t_eff == best_tier[:, None], pipe, BIG)
    best_fin = jnp.min(f_eff, axis=1)
    vmids = jnp.arange(tier.shape[1], dtype=jnp.int32)
    v_eff = jnp.where(f_eff == best_fin[:, None], vmids[None, :], 1 << 30)
    best_vm = jnp.min(v_eff, axis=1).astype(jnp.int32)
    none = best_tier >= 9
    best_vm = jnp.where(none, -1, best_vm)
    idx = jnp.clip(best_vm, 0, tier.shape[1] - 1)
    est_f = jnp.take_along_axis(pipe, idx[:, None], axis=1)[:, 0]
    est_c = jnp.take_along_axis(cost, idx[:, None], axis=1)[:, 0]
    return AffinityOut(best_vm, best_tier,
                       jnp.where(none, BIG, est_f), jnp.where(none, BIG, est_c))
