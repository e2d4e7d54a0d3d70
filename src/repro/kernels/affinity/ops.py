"""jit'd dispatchers for the affinity scoring: Pallas kernel or jnp oracle.

Two entry points share one core:

* :func:`affinity` — one scheduling cycle, ``[T, V]`` pair arrays.
* :func:`affinity_batch` — a whole grid of independent simulations'
  cycles, ``[B, T, V]``.  This is what ``core.jax_engine`` drives: one
  device pass scores every member's auction round (the Pallas kernel
  carries the member dim as a grid axis; the oracle is vmapped over it).
"""
from __future__ import annotations

from functools import partial

import jax

from .kernel import affinity_pallas
from .ref import AffinityOut, affinity_ref


def resolve_use_pallas(flag) -> bool:
    """``"auto"`` → Pallas on TPU, jnp oracle elsewhere (the interpreter
    that backs Pallas off-TPU is orders of magnitude slower than the
    compiled jnp path, so "auto" only engages the kernel where it pays).
    Booleans pass through."""
    if flag == "auto":
        return jax.default_backend() == "tpu"
    return bool(flag)


def donation_supported() -> bool:
    """Whether input-buffer donation actually transfers ownership on the
    default backend (CPU ignores donation and warns)."""
    return jax.default_backend() in ("tpu", "gpu")


def _affinity_batch_impl(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
                         vm_mips, vm_bw, vm_price, gs_read: float,
                         gs_write: float, bp_ms: float,
                         use_pallas: bool = False) -> AffinityOut:
    if use_pallas:
        return AffinityOut(*affinity_pallas(
            size_mi, out_mb, budget, missing_mb, cont_ms, tier,
            vm_mips, vm_bw, vm_price, gs_read, gs_write, bp_ms))

    def one(s, o, b, m, c, t, mi, bw, pr):
        return affinity_ref(s, o, b, m, c, t, mi, bw, pr,
                            gs_read, gs_write, bp_ms)

    return jax.vmap(one)(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
                         vm_mips, vm_bw, vm_price)


_BATCH_STATIC = ("gs_read", "gs_write", "bp_ms", "use_pallas")


@partial(jax.jit, static_argnames=_BATCH_STATIC)
def affinity(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
             vm_mips, vm_bw, vm_price, gs_read: float, gs_write: float,
             bp_ms: float, use_pallas: bool = False) -> AffinityOut:
    """One scheduling cycle: task arrays ``[T]``, pairs ``[T, V]``, VM
    arrays ``[V]`` (a batch of one)."""
    args = (size_mi, out_mb, budget, missing_mb, cont_ms, tier,
            vm_mips, vm_bw, vm_price)
    out = _affinity_batch_impl(*(a[None] for a in args), gs_read, gs_write,
                               bp_ms, use_pallas)
    return AffinityOut(*(o[0] for o in out))


_affinity_batch_jit = jax.jit(_affinity_batch_impl,
                              static_argnames=_BATCH_STATIC)
# On accelerators the round buffers' device transfers are single-use:
# donating them lets XLA reuse the staging buffers for outputs instead of
# holding both alive across the call.
_affinity_batch_donated = jax.jit(_affinity_batch_impl,
                                  static_argnames=_BATCH_STATIC,
                                  donate_argnums=tuple(range(9)))


def affinity_batch(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
                   vm_mips, vm_bw, vm_price, gs_read: float, gs_write: float,
                   bp_ms: float, use_pallas: bool = False,
                   donate: bool = False) -> AffinityOut:
    """Batched affinity: every array carries a leading simulation dim ``B``.

    Task arrays are ``[B, T]``, pair arrays ``[B, T, V]``, VM arrays
    ``[B, V]`` (members may pool different VM fleets).  Inert members pad
    with ``tier = 0`` rows, which are infeasible by construction.

    ``donate=True`` routes through the donating jit (see
    :func:`donation_supported`); host-side round buffers stay reusable —
    only the on-device staging copies are consumed.
    """
    fn = _affinity_batch_donated if donate else _affinity_batch_jit
    return fn(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
              vm_mips, vm_bw, vm_price, gs_read=gs_read, gs_write=gs_write,
              bp_ms=bp_ms, use_pallas=use_pallas)
