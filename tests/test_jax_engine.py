"""Batched JAX engine (core.jax_engine) ≡ sequential reference (SimEngine).

The parity suite draws budgets from the upper half of [min, max] — the
paper's "budgets always assumed sufficient" regime, where the auction's
fixed point provably equals the sequential interleaving (see
core.jax_cycles).  MSLBL members exercise the shared per-task path, so
their parity is unconditional.
"""
import numpy as np
import pytest

from repro.core.engine import SimEngine
from repro.core.jax_engine import BatchSimEngine, simulate_batch
from repro.core.scheduler import ALL_POLICIES, EBPSM, EBPSM_NC, MSLBL_MW
from repro.core.types import PlatformConfig
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()

POLICY_BY_NAME = {p.name: p for p in ALL_POLICIES}


def workload(seed, n=8, rate=6.0, budget_lo=0.5, budget_hi=1.0):
    spec = WorkloadSpec(n_workflows=n, arrival_rate_per_min=rate, seed=seed,
                        sizes=("small",), budget_lo=budget_lo,
                        budget_hi=budget_hi)
    return generate_workload(CFG, spec)


def assert_same(ref, res):
    assert [w.finish_ms for w in ref.workflows] == \
        [w.finish_ms for w in res.workflows]
    assert [w.cost for w in ref.workflows] == \
        [w.cost for w in res.workflows]
    assert ref.vm_count_by_type == res.vm_count_by_type
    assert ref.vm_seconds_by_type == res.vm_seconds_by_type


@pytest.mark.parametrize("policy", ALL_POLICIES, ids=lambda p: p.name)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_simulate_batch_matches_reference(policy, seed):
    """Bit-exact makespans/costs for every policy across ≥3 seeds, with
    the auction forced on (the batched engine's raison d'être)."""
    ref = SimEngine(CFG, policy, workload(seed), seed=seed).run()
    res = simulate_batch(CFG, policy, workload(seed), seed=seed,
                         batched=True).results[0]
    assert_same(ref, res)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_simulate_batch_auto_matches_reference(seed):
    """Default ("auto") batching: decisions match SimEngine path-for-path."""
    ref = SimEngine(CFG, EBPSM, workload(seed), seed=seed).run()
    res = simulate_batch(CFG, EBPSM, workload(seed), seed=seed).results[0]
    assert_same(ref, res)


def test_grid_members_are_independent():
    """A full policies × workloads × seeds grid in ONE lockstep run matches
    each member simulated alone — interleaving leaks no state."""
    grid = simulate_batch(CFG, ALL_POLICIES, [workload(0), workload(3)],
                          seed=[0, 5], batched=True)
    assert len(grid.entries) == len(ALL_POLICIES) * 2 * 2
    for e in grid.entries:
        ref = SimEngine(CFG, POLICY_BY_NAME[e.policy],
                        workload((0, 3)[e.workload]), seed=e.seed).run()
        assert_same(ref, e.result)


def test_trace_matches_reference():
    """Placement-level parity: same (time, task, tier, cost, vm) rows."""
    ref = SimEngine(CFG, EBPSM, workload(4), seed=0, trace=True)
    ref.run()
    eng = BatchSimEngine(CFG, [(EBPSM, workload(4), 0)], trace=True,
                         batched=True)
    eng.run()
    assert eng.states[0].trace_rows == ref.trace_rows


def test_workloads_not_mutated_by_grid():
    """simulate_batch deep-copies members; caller workflows stay pristine."""
    wl = workload(2)
    budgets_before = [[t.budget for t in wf.tasks] for wf in wl]
    simulate_batch(CFG, [EBPSM, EBPSM_NC], wl, seed=[0, 1])
    budgets_after = [[t.budget for t in wf.tasks] for wf in wl]
    assert budgets_before == budgets_after


def test_batched_calls_are_shared():
    """The whole grid's cycles ride a shared batched scoring pass: the
    number of device auction calls must be far below the per-member sum."""
    members = [(EBPSM, workload(s), s) for s in range(4)]
    eng = BatchSimEngine(CFG, members, batched=True)
    eng.run()
    solo_calls = 0
    for s in range(4):
        solo = BatchSimEngine(CFG, [(EBPSM, workload(s), s)], batched=True)
        solo.run()
        solo_calls += solo.batched_calls
    assert eng.batched_calls > 0
    assert eng.batched_calls < solo_calls


def test_mslbl_member_in_mixed_grid():
    """MSLBL members (sequential path) coexist with auctioned EBPSM
    members in one lockstep run."""
    grid = simulate_batch(CFG, [EBPSM, MSLBL_MW], workload(1), seed=2,
                          batched=True)
    for e in grid.entries:
        ref = SimEngine(CFG, POLICY_BY_NAME[e.policy], workload(1),
                        seed=2).run()
        assert_same(ref, e.result)


def test_stress_scale_parity_live_registry():
    """Stress-scale parity through the live-VM registry: a larger grid
    with deferred reaping (idle_threshold_ms > 0, including a shortened
    1 s threshold for extra reap/reuse churn) stays bit-exact between
    both engines, and every member's pool ends with clean registry
    invariants (terminated VMs pruned from every index)."""
    import dataclasses

    from repro.core.jax_engine import BatchSimEngine as _BSE

    ebpsm_1s = dataclasses.replace(EBPSM, name="EBPSM_1S",
                                   idle_threshold_ms=1_000)
    pols = (EBPSM, ebpsm_1s)
    wl_seeds = (9, 11)
    members, keys = [], []
    for pol in pols:
        for ws in wl_seeds:
            for s in (0, 3):
                members.append((pol, workload(ws, n=14, rate=20.0), s))
                keys.append((pol, ws, s))
    eng = _BSE(CFG, members, batched=True)
    results = eng.run()
    for (pol, ws, s), res in zip(keys, results):
        ref = SimEngine(CFG, pol, workload(ws, n=14, rate=20.0),
                        seed=s).run()
        assert_same(ref, res)
    for st in eng.states:
        st.pool.check_invariants()
        assert st.pool.n_live == 0
        assert st.pool.data_index == {}, "index not pruned after finalize"


@pytest.mark.parametrize("policy", [EBPSM, EBPSM_NC], ids=lambda p: p.name)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_insufficient_budget_tier5_parity(policy, seed):
    """Gate for the lowered auction threshold: with budgets drawn from the
    bottom of the range, cycles hit the insufficient-budget tier-5 rule
    (which may *reuse* an idle VM mid-cycle), and the auction must
    replicate that interleaving exactly — forced batched=True vs the
    sequential reference."""
    wl = workload(seed, n=10, rate=20.0, budget_lo=0.0, budget_hi=0.1)
    ref_eng = SimEngine(CFG, policy, workload(seed, n=10, rate=20.0,
                                              budget_lo=0.0, budget_hi=0.1),
                        seed=seed, trace=True)
    ref = ref_eng.run()
    eng = BatchSimEngine(CFG, [(policy, wl, seed)], trace=True, batched=True)
    res = eng.run()[0]
    assert_same(ref, res)
    assert eng.states[0].trace_rows == ref_eng.trace_rows
    # The low-budget regime must actually exercise tier 5 for the gate
    # to mean anything.
    tiers = {r[3] for r in ref_eng.trace_rows}
    assert 5 in tiers, f"workload never hit tier 5 (tiers seen: {tiers})"


def test_all_tasks_complete_batch():
    grid = simulate_batch(CFG, ALL_POLICIES, workload(6, n=6), seed=0)
    for e in grid.entries:
        assert len(e.result.workflows) == 6
        for w in e.result.workflows:
            assert w.finish_ms >= w.arrival_ms
            assert w.cost > 0


def test_online_mixed_tenant_stream_parity():
    """Bit-exact parity on an open multi-tenant stream (repro.tenants):
    heterogeneous apps incl. imported DAX/WfCommons traces, three arrival
    processes, per-QoS budgets — batched forced on, trace-row exact, with
    the predistributed-budget path the online harness uses."""
    from repro.core.jax_engine import predistribute_workload
    from repro.core.types import clone_workload
    from repro.tenants import (BRONZE, GOLD, SILVER, Diurnal,
                               MarkovModulated, Poisson, Tenant, TenantMix)

    mix = TenantMix((
        Tenant("astro", GOLD, apps=("montage", "trace:montage-18"),
               arrival=Poisson(10.0), n_workflows=5),
        Tenant("bio", SILVER, apps=("trace:epigenomics-20",),
               arrival=Diurnal(4.0, 14.0, period_s=240.0), n_workflows=3),
        Tenant("seis", BRONZE, apps=("sipht", "trace:seismology-9"),
               arrival=MarkovModulated(2.0, 18.0, mean_dwell_s=45.0),
               n_workflows=5),
    ))
    tw = mix.build(CFG, seed=0)
    for policy in (EBPSM, EBPSM_NC, MSLBL_MW):
        ref_eng = SimEngine(CFG, policy, clone_workload(tw.workflows),
                            seed=0, trace=True)
        ref = ref_eng.run()
        proto, spares = predistribute_workload(CFG, tw.workflows,
                                               policy.budget_mode)
        eng = BatchSimEngine(CFG, [(policy, clone_workload(proto), 0)],
                             trace=True, batched=True,
                             predistributed=[spares])
        res = eng.run()[0]
        assert_same(ref, res)
        assert eng.states[0].trace_rows == ref_eng.trace_rows
        assert res.peak_vms == ref.peak_vms
        assert res.mean_fleet_vms == ref.mean_fleet_vms
