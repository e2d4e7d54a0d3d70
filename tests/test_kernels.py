"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.affinity.ops import affinity, affinity_batch, unpack_host
from repro.kernels.affinity.ref import BIG
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_decode_ref, ssd_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("B,L,H,D,causal,dtype", [
    (2, 256, 4, 64, True, jnp.float32),
    (1, 128, 2, 128, False, jnp.float32),
    (2, 200, 3, 64, True, jnp.float32),       # non-multiple of block
    (1, 96, 1, 32, True, jnp.float32),
    (2, 256, 2, 64, True, jnp.bfloat16),
])
def test_flash_attention_sweep(B, L, H, D, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, L, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, L, H, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, L, H, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("B,L,H,P,N,Q", [
    (2, 128, 3, 32, 16, 32),
    (1, 256, 2, 64, 128, 64),
    (2, 64, 4, 16, 32, 16),
    (1, 128, 1, 64, 64, 128),
])
def test_ssd_kernel_sweep(B, L, H, P, N, Q):
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32)
    y_ref, s_ref = ssd_ref(x, dt, A, Bm, Cm, chunk=Q)
    y_pal, s_pal = ssd(x, dt, A, Bm, Cm, chunk=Q, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               atol=1e-4)


def test_ssd_chunked_equals_sequential_recurrence():
    B, L, H, P, N = 1, 64, 2, 16, 8
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32)
    y_ref, s_ref = ssd_ref(x, dt, A, Bm, Cm, chunk=16)
    state = jnp.zeros((B, H, N, P))
    ys = []
    for t in range(L):
        y, state = ssd_decode_ref(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t],
                                  state)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_ref),
                               atol=1e-4)


def _affinity_args(rng, lead, T, V):
    """Seeded random affinity inputs with leading dims ``lead``."""
    return (
        jnp.asarray(rng.uniform(10, 900, lead + (T,)), jnp.float32),
        jnp.asarray(rng.uniform(1, 150, lead + (T,)), jnp.float32),
        jnp.asarray(rng.uniform(5, 500, lead + (T,)), jnp.float32),
        jnp.asarray(rng.uniform(0, 200, lead + (T, V)), jnp.float32),
        jnp.asarray(rng.choice([0., 400., 10000.], lead + (T, V)),
                    jnp.float32),
        jnp.asarray(rng.choice([0, 1, 2, 3], lead + (T, V)), jnp.int32),
        jnp.asarray(rng.choice([2., 4., 8., 16.], lead + (V,)), jnp.float32),
        jnp.full(lead + (V,), 20.0, jnp.float32),
        jnp.asarray(rng.choice([1., 2., 4., 8.], lead + (V,)), jnp.float32),
    )


def _assert_bit_equal(r, p):
    for name, a, b in zip(r._fields, r, p):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a.view(np.uint32), b.view(np.uint32)), name


# (37, 100), (37, 2) and (40, 2048): T is not a multiple of the task block.
@pytest.mark.parametrize("T,V", [(16, 32), (37, 100), (64, 7), (1, 1),
                                 (37, 2), (40, 2048), (300, 2)])
def test_affinity_kernel_matches_ref(T, V):
    rng = np.random.default_rng(T * 1000 + V)
    args = _affinity_args(rng, (), T, V)
    r = affinity(*args, gs_read=50., gs_write=30., bp_ms=1000.,
                 use_pallas=False)
    p = affinity(*args, gs_read=50., gs_write=30., bp_ms=1000.,
                 use_pallas=True)
    _assert_bit_equal(r, p)


@pytest.mark.parametrize("B,T,V", [(4, 37, 100), (3, 20, 2)])
def test_affinity_batch_mixed_members_matches_ref(B, T, V):
    """A round buffer as ``multi_cycle`` fills it: live members, an inert
    padding member (``tier = 0`` everywhere) and rows no VM can afford."""
    rng = np.random.default_rng(B * 100 + T + V)
    args = list(_affinity_args(rng, (B,), T, V))
    args[5] = args[5].at[1].set(0)            # member 1 is inert padding
    args[2] = args[2].at[0, ::3].set(0.0)      # budget 0: all infeasible
    kw = dict(gs_read=50., gs_write=30., bp_ms=1000.)
    r = affinity_batch(*args, use_pallas=False, **kw)
    p = affinity_batch(*args, use_pallas=True, **kw)
    _assert_bit_equal(r, p)
    assert (np.asarray(p.best_vm[1]) == -1).all()
    assert (np.asarray(p.best_tier[0, ::3]) == 9).all()
    assert (np.asarray(p.best_vm[0, ::3]) == -1).all()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_affinity_batch_packed_views_equal_the_outputs(use_pallas):
    """``packed=True`` returns the four outputs as one ``int32 [4, B, T]``
    array whose host views are the fields of the unpacked call, float bits
    included, on a round buffer with an inert member, rows no VM can
    afford and ordinary rows."""
    B, T, V = 3, 37, 100
    rng = np.random.default_rng(7)
    args = list(_affinity_args(rng, (B,), T, V))
    args[5] = args[5].at[1].set(0)            # member 1 is inert padding
    args[2] = args[2].at[0, ::3].set(0.0)      # budget 0: all infeasible
    kw = dict(gs_read=50., gs_write=30., bp_ms=1000., use_pallas=use_pallas)
    want = affinity_batch(*args, **kw)
    packed = affinity_batch(*args, packed=True, **kw)
    assert packed.shape == (4, B, T) and packed.dtype == jnp.int32
    got = unpack_host(np.asarray(packed))
    for name, a, b in zip(want._fields, want, got):
        a = np.asarray(a)
        assert (b.dtype, b.shape) == (a.dtype, a.shape), name
        assert np.array_equal(a.view(np.int32), b.view(np.int32)), name
    for rows in (got[0][1], got[0][0, ::3]):   # no feasible VM
        assert (rows == -1).all()
    for field in got[1:]:
        want_none = 9 if field.dtype == np.int32 else BIG
        assert (field[1] == want_none).all()
        assert (field[0, ::3] == want_none).all()
    assert (got.best_vm[2] >= 0).any()         # ordinary rows place


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("B,T,V", [(3, 37, 100), (2, 16, 7)])
def test_masked_affinity_batch_equals_the_compacted_call(use_pallas, B, T, V):
    """``live=`` on resident pair arrays equals, on every live row and bit
    for bit, the call on only the live rows with the dead columns' tier
    zeroed on the host (what an auction round staged before the mask).
    Member 0 has left the auction: its mask row is all zero."""
    rng = np.random.default_rng(B * 1000 + T + V)
    args = [np.array(a) for a in _affinity_args(rng, (B,), T, V)]
    args[2][1, ::4] = 0.0                      # rows no VM can afford
    rows = rng.random((B, T)) < 0.6
    cols = rng.random((B, V)) < 0.7
    rows[0] = cols[0] = False
    live = np.concatenate([rows, cols], axis=1).astype(np.int32)
    kw = dict(gs_read=50., gs_write=30., bp_ms=1000., use_pallas=use_pallas)
    got = affinity_batch(*(jnp.asarray(a) for a in args), live=live, **kw)
    assert (np.asarray(got.best_vm[0]) == -1).all()

    # The compacted call: live rows packed to the front, inert padding after.
    Tc = max(int(rows.sum(1).max()), 1)
    pad = [np.zeros((B, Tc), np.float32), np.zeros((B, Tc), np.float32),
           np.full((B, Tc), -1.0, np.float32),
           np.zeros((B, Tc, V), np.float32), np.zeros((B, Tc, V), np.float32),
           np.zeros((B, Tc, V), np.int32)]
    for b in range(B):
        sel = np.flatnonzero(rows[b])
        for dst, src in zip(pad, args[:6]):
            dst[b, :len(sel)] = src[b, sel]
        pad[5][b, :len(sel)] *= cols[b][None, :]
    want = affinity_batch(*pad, *args[6:], **kw)
    for b in range(B):
        sel = np.flatnonzero(rows[b])
        for name, w, g in zip(want._fields, want, got):
            w = np.asarray(w)[b, :len(sel)]
            g = np.asarray(g)[b, sel]
            assert np.array_equal(w.view(np.uint32), g.view(np.uint32)), \
                (b, name)
    assert (np.asarray(got.best_vm)[rows] >= 0).any()
    dead = ~rows
    assert (np.asarray(got.best_vm)[dead] == -1).all()
    assert (np.asarray(got.best_tier)[dead] == 9).all()


def test_affinity_batch_refuses_a_mask_of_the_wrong_width():
    args = _affinity_args(np.random.default_rng(1), (2,), 8, 4)
    with pytest.raises(ValueError, match=r"\[B, T \+ V\]"):
        affinity_batch(*args, gs_read=50., gs_write=30., bp_ms=1000.,
                       live=np.ones((2, 8), np.int32))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_affinity_billing_periods_exact(use_pallas):
    """A pipeline of exactly k billing periods costs k periods, not k + 1:
    ``pipe / bp`` must not round up past an integer quotient (XLA turns
    the division by a constant into a multiplication by 0.001f)."""
    k = jnp.arange(1, 65, dtype=jnp.float32)           # pipe = k·1000 ms
    T, V = k.shape[0], 1
    r = affinity(k * 2.0, jnp.zeros(T), jnp.full((T,), 1e6),
                 jnp.zeros((T, V)), jnp.zeros((T, V)),
                 jnp.ones((T, V), jnp.int32), jnp.full((V,), 2.0),
                 jnp.full((V,), 20.0), jnp.full((V,), 3.0),
                 gs_read=50., gs_write=30., bp_ms=1000.,
                 use_pallas=use_pallas)
    assert np.array_equal(np.asarray(r.est_finish), np.asarray(k) * 1000.0)
    assert np.array_equal(np.asarray(r.est_cost), np.asarray(k) * 3.0)


def test_affinity_tier_priority():
    """A slower tier-1 VM must beat a faster tier-3 VM (Alg. 2 ordering)."""
    T, V = 1, 2
    size = jnp.asarray([100.0]); out_mb = jnp.asarray([10.0])
    budget = jnp.asarray([1e6])
    missing = jnp.asarray([[0.0, 0.0]])
    cont = jnp.asarray([[0.0, 0.0]])
    tier = jnp.asarray([[1, 3]], jnp.int32)
    mips = jnp.asarray([2.0, 16.0])       # tier-3 VM is 8× faster
    bw = jnp.full((V,), 20.0); price = mips / 2
    r = affinity(size, out_mb, budget, missing, cont, tier, mips, bw, price,
                 gs_read=50., gs_write=30., bp_ms=1000.)
    assert int(r.best_vm[0]) == 0
    assert int(r.best_tier[0]) == 1
