"""Batched (JAX) scheduling cycle ≡ sequential reference.

Equivalence holds while budgets avoid the tier-5 insufficiency fallback
(the auction resolves reuse globally; tier-5 interleaving differs), so
workloads here draw budgets from the upper half of [min, max].
"""
import numpy as np
import pytest

from repro.core.engine import SimEngine
from repro.core.jax_engine import BatchSimEngine
from repro.core.scheduler import EBPSM, EBPSM_NS, EBPSM_WS
from repro.core.types import PlatformConfig
from repro.kernels.affinity import ops
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()


def workload(seed):
    spec = WorkloadSpec(n_workflows=14, arrival_rate_per_min=6.0, seed=seed,
                        sizes=("small",), budget_lo=0.4, budget_hi=1.0)
    return generate_workload(CFG, spec)


@pytest.mark.parametrize("policy", [EBPSM, EBPSM_NS, EBPSM_WS],
                         ids=lambda p: p.name)
@pytest.mark.parametrize("seed", [0, 3])
def test_batched_equals_sequential(policy, seed):
    seq = SimEngine(CFG, policy, workload(seed), seed=0).run()
    bat = BatchSimEngine(CFG, [(policy, workload(seed), 0)],
                         batched=True).run()[0]
    assert [w.finish_ms for w in seq.workflows] == \
        [w.finish_ms for w in bat.workflows]
    np.testing.assert_allclose([w.cost for w in seq.workflows],
                               [w.cost for w in bat.workflows], rtol=1e-6)
    assert seq.vm_count_by_type == bat.vm_count_by_type


@pytest.mark.parametrize("policy", [EBPSM_NS, EBPSM_WS],
                         ids=lambda p: p.name)
def test_members_leaving_mid_auction_equal_sequential(policy, monkeypatch):
    """``multi_cycle`` calls whose members leave the fixed point at
    different rounds (``policy`` members early, EBPSM members later): the
    batch keeps its rows, a leaver's mask row is all zero, and every
    member's placements stay bit-exact."""
    spec = dict(n_workflows=8, arrival_rate_per_min=30.0, sizes=("medium",),
                budget_lo=0.4, budget_hi=1.0)
    # Each member gets a workload of its own: an engine writes budgets
    # into the tasks it runs.
    members = lambda: [(p, generate_workload(CFG, WorkloadSpec(seed=s,
                                                             **spec)))
                       for s in (2, 3) for p in (EBPSM, policy)]
    masks = []
    orig = ops.affinity_batch

    def recording(*args, **kw):
        # A member's row has real budgets; padding rows hold -1.
        masks.append((np.asarray(args[2])[:, 0] >= 0, kw["live"]))
        return orig(*args, **kw)

    monkeypatch.setattr(ops, "affinity_batch", recording)
    eng = BatchSimEngine(CFG, [(p, wl, 0) for p, wl in members()],
                         batched=True)
    results = eng.run()
    monkeypatch.setattr(ops, "affinity_batch", orig)
    left = [(member & ~live.any(1)).any() and live.any()
            for member, live in masks if isinstance(live, np.ndarray)]
    assert any(left), "no round had one member left and another still in"
    for (p, wl), res in zip(members(), results):
        ref = SimEngine(CFG, p, wl, seed=0).run()
        assert [w.finish_ms for w in ref.workflows] == \
            [w.finish_ms for w in res.workflows]
        assert [w.cost for w in ref.workflows] == \
            [w.cost for w in res.workflows]
        assert ref.vm_count_by_type == res.vm_count_by_type
        assert ref.vm_seconds_by_type == res.vm_seconds_by_type


def test_batched_trace_tiers_match():
    e1 = SimEngine(CFG, EBPSM, workload(7), seed=0, trace=True)
    e1.run()
    e2 = BatchSimEngine(CFG, [(EBPSM, workload(7), 0)], trace=True,
                        batched=True)
    e2.run()
    assert e1.trace_rows == e2.states[0].trace_rows


def test_data_index_consistent():
    eng = BatchSimEngine(CFG, [(EBPSM, workload(1), 0)], batched=True)
    eng.run()
    eng = eng.states[0]
    # the inverted index matches per-VM caches for every live VM
    for vm in eng.pool.vms:
        if vm.terminated_ms >= 0:
            continue
        for key in vm.data_cache:
            assert vm.vmid in eng.pool.data_index.get(key, set())
    for key, holders in eng.pool.data_index.items():
        for vid in holders:
            assert key in eng.pool.vms[vid].data_cache
