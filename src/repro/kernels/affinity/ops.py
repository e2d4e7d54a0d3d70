"""jit'd dispatchers for the affinity scoring: Pallas kernel or jnp oracle.

Two entry points share one core:

* :func:`affinity` — one scheduling cycle, ``[T, V]`` pair arrays.
* :func:`affinity_batch` — a whole grid of independent simulations'
  cycles, ``[B, T, V]``.  This is what ``core.jax_engine`` drives: one
  device pass scores every member's auction round (the Pallas kernel
  carries the member dim as a grid axis; the oracle is vmapped over it).
  With ``packed=True`` it returns the four outputs as one array
  (:func:`pack`), which the host pulls in one transfer and reads back
  with :func:`unpack_host`.  Its ``live`` mask marks the rows and columns
  still in play, so that an auction's pair arrays can stay on the device
  (:func:`upload`) across its rounds.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

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


def pack(out: AffinityOut) -> jax.Array:
    """The four ``[B, T]`` outputs as one ``int32`` ``[4, B, T]`` array, in
    field order; the two float fields are carried bit for bit."""
    return jnp.stack([out.best_vm, out.best_tier,
                      jax.lax.bitcast_convert_type(out.est_finish, jnp.int32),
                      jax.lax.bitcast_convert_type(out.est_cost, jnp.int32)])


def unpack_host(packed: np.ndarray) -> AffinityOut:
    """Numpy views of a pulled :func:`pack` array, with the dtypes of
    :class:`AffinityOut`; nothing is copied."""
    return AffinityOut(packed[0], packed[1], packed[2].view(np.float32),
                       packed[3].view(np.float32))


def _affinity_batch_impl(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
                         vm_mips, vm_bw, vm_price, live, gs_read: float,
                         gs_write: float, bp_ms: float,
                         use_pallas: bool = False, packed: bool = False):
    if use_pallas:
        out = AffinityOut(*affinity_pallas(
            size_mi, out_mb, budget, missing_mb, cont_ms, tier,
            vm_mips, vm_bw, vm_price, live, gs_read, gs_write, bp_ms))
    else:
        T = tier.shape[1]
        tier = tier * live[:, :T, None] * live[:, None, T:]

        def one(s, o, b, m, c, t, mi, bw, pr):
            return affinity_ref(s, o, b, m, c, t, mi, bw, pr,
                                gs_read, gs_write, bp_ms)

        out = jax.vmap(one)(size_mi, out_mb, budget, missing_mb, cont_ms,
                            tier, vm_mips, vm_bw, vm_price)
    return pack(out) if packed else out


_STATIC = ("gs_read", "gs_write", "bp_ms", "use_pallas")
_BATCH_STATIC = _STATIC + ("packed",)


@partial(jax.jit, static_argnames=_STATIC)
def affinity(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
             vm_mips, vm_bw, vm_price, gs_read: float, gs_write: float,
             bp_ms: float, use_pallas: bool = False) -> AffinityOut:
    """One scheduling cycle: task arrays ``[T]``, pairs ``[T, V]``, VM
    arrays ``[V]`` (a batch of one)."""
    args = (size_mi, out_mb, budget, missing_mb, cont_ms, tier,
            vm_mips, vm_bw, vm_price)
    live = jnp.ones((1, sum(tier.shape)), jnp.int32)
    out = _affinity_batch_impl(*(a[None] for a in args), live, gs_read,
                               gs_write, bp_ms, use_pallas)
    return AffinityOut(*(o[0] for o in out))


_affinity_batch_jit = jax.jit(_affinity_batch_impl,
                              static_argnames=_BATCH_STATIC)
# On accelerators the upload's outputs are its own transfer buffers,
# donated and returned as they are: without donation XLA copies them.
_upload_jit = jax.jit(lambda *a: a)
_upload_donated = jax.jit(lambda *a: a, donate_argnums=tuple(range(9)))


def upload(*arrays) -> tuple:
    """The nine host arrays of an :func:`affinity_batch` call as device
    arrays, for every round of an auction to be handed.  It moves them
    through jit's own transfers, which cost a host less than
    ``jax.device_put``."""
    fn = _upload_donated if donation_supported() else _upload_jit
    return fn(*arrays)


@lru_cache(maxsize=None)
def all_live(B: int, n: int) -> jax.Array:
    """A resident all-live ``int32 [B, n]`` mask, one per bucket: the mask
    of an auction's first round, when every row is unplaced and every VM
    free, which then costs no transfer (nor, made by a transfer, a
    compile)."""
    return jax.device_put(np.ones((B, n), np.int32))


def affinity_batch(size_mi, out_mb, budget, missing_mb, cont_ms, tier,
                   vm_mips, vm_bw, vm_price, gs_read: float, gs_write: float,
                   bp_ms: float, use_pallas: bool = False,
                   packed: bool = False, live=None):
    """Batched affinity: every array carries a leading simulation dim ``B``.

    Task arrays are ``[B, T]``, pair arrays ``[B, T, V]``, VM arrays
    ``[B, V]`` (members may pool different VM fleets).  Inert members pad
    with ``tier = 0`` rows, which are infeasible by construction.

    ``live``: the ``int32`` ``[B, T + V]`` mask, 1 for a live task row (the
    first ``T`` columns) or VM column (the last ``V``) and 0 for a dead one;
    the tier is multiplied by both before scoring, so a dead row scores as
    an inert one and a dead column is out of every row's scope.  A live
    row's outputs equal those of the same row with the dead columns' tier
    zeroed on the host.  ``None`` means all live.  No input is donated:
    they may be device arrays (:func:`upload`) that the caller hands to
    every round of an auction.

    Returns an :class:`AffinityOut`, or with ``packed=True`` its
    :func:`pack`: one ``[4, B, T]`` array, so that the host pulls a round's
    outputs in one device-to-host transfer instead of four.
    """
    B, T, V = tier.shape
    if live is None:
        live = np.ones((B, T + V), np.int32)
    elif live.shape != (B, T + V):
        raise ValueError(f"live mask {live.shape} does not fit pair arrays "
                         f"{tier.shape}: want [B, T + V]")
    return _affinity_batch_jit(size_mi, out_mb, budget, missing_mb, cont_ms,
                               tier, vm_mips, vm_bw, vm_price, live,
                               gs_read=gs_read, gs_write=gs_write,
                               bp_ms=bp_ms, use_pallas=use_pallas,
                               packed=packed)
