"""Parity and edge-case tests for the array-path Algorithm 3.

``core.budget`` carries two bit-exact implementations of the per-finish
redistribution (scalar ``update_budget`` reference vs array
``update_budget_fast`` over a ``RedistState``) plus the pooled form
that absorbs chaos debt.  These tests pin:

* property-style randomized parity (spares and every task budget exactly
  equal, including chained updates and the ``budget_vec`` mirror);
* the edge cases the sweep regimes are built around — zero surplus, debt
  (negative surplus), a single unscheduled task, everyone topping out,
  and the zero-pool identity skip;
* engine-level parity: array vs forced-scalar hot path, and SimEngine
  vs BatchSimEngine cross-engine parity for every policy.
"""
import os

import numpy as np
import pytest

from repro.core import budget as bmod
from repro.core import cost_tables
from repro.core.engine import SimEngine, SimState
from repro.core.jax_engine import BatchSimEngine, simulate_batch
from repro.core.scheduler import ALL_POLICIES
from repro.core.types import PlatformConfig
from repro.workflows.dax import generate_workflow
from repro.workflows.workload import cell_workload

CFG = PlatformConfig()
EBPSM = next(p for p in ALL_POLICIES if p.name == "EBPSM")
EBPSM_NC = next(p for p in ALL_POLICIES if p.name == "EBPSM_NC")


def _prepared_wf(seed, n=40, app="montage", frac=0.6, rng=None):
    """Workflow with distributed budgets + a random scheduled subset.

    Returns (wf, spare, finished_tid, unscheduled_list).
    """
    rng = rng or np.random.default_rng(seed)
    wf = generate_workflow(app, 0, n, rng)
    lo, hi = bmod.min_max_workflow_cost(CFG, wf)
    spare = bmod.distribute_budget(CFG, wf, lo + frac * (hi - lo))
    nsched = int(rng.integers(1, wf.n_tasks + 1))
    sched = rng.choice(wf.n_tasks, size=nsched, replace=False).tolist()
    fin = int(sched[0])
    unscheduled = [t.tid for t in wf.tasks if t.tid not in set(sched)]
    return wf, spare, fin, unscheduled


def _assert_pair(wf_a, wf_b, spare_a, spare_b, unscheduled, rs=None):
    assert spare_a == spare_b
    for tid in unscheduled:
        assert wf_a.tasks[tid].budget == wf_b.tasks[tid].budget, tid
        if rs is not None:
            assert rs.budget_vec[tid] == wf_b.tasks[tid].budget, tid


# ---------------------------------------------------------------------------
# property-style parity: scalar oracle vs array path
# ---------------------------------------------------------------------------

def test_update_budget_parity_randomized():
    rng = np.random.default_rng(42)
    apps = ["montage", "sipht", "epigenome", "ligo", "cybershake"]
    for trial in range(60):
        n = int(rng.integers(5, 180)) if trial % 6 else \
            int(rng.integers(300, 700))
        wf, spare, fin, uns = _prepared_wf(
            trial, n, apps[trial % 5], float(rng.uniform(0, 1)), rng)
        wf2 = wf.clone()
        actual = float(rng.uniform(0, 2.5)) * max(wf.tasks[fin].budget, 1.0)

        spare_a = bmod.update_budget(CFG, wf, fin, actual, spare, uns)
        rs = bmod.RedistState(CFG, wf2, uns)
        spare_b = bmod.update_budget_fast(CFG, wf2, rs, fin, actual, spare)
        _assert_pair(wf, wf2, spare_a, spare_b, uns, rs)

        # Chained second update exercises mark_scheduled + the carried
        # budget_vec state (the mirror must stay exact across calls).
        if len(uns) > 1:
            fin2, uns2 = uns[0], uns[1:]
            actual2 = float(rng.uniform(0, 2.0)) \
                * max(wf.tasks[fin2].budget, 1.0)
            spare_a2 = bmod.update_budget(CFG, wf, fin2, actual2,
                                          spare_a, uns2)
            rs.mark_scheduled(fin2)
            spare_b2 = bmod.update_budget_fast(CFG, wf2, rs, fin2,
                                               actual2, spare_b)
            _assert_pair(wf, wf2, spare_a2, spare_b2, uns2, rs)


def test_update_budget_pooled_parity_randomized():
    rng = np.random.default_rng(7)
    for trial in range(40):
        n = int(rng.integers(5, 300))
        wf, spare, _fin, uns = _prepared_wf(
            trial, n, ["montage", "cybershake"][trial % 2],
            float(rng.uniform(0, 1)), rng)
        wf2 = wf.clone()
        surplus = float(rng.normal(0.0, 5.0))
        spare_a = bmod.update_budget_pooled_scalar(CFG, wf, surplus,
                                                   spare, uns)
        rs = bmod.RedistState(CFG, wf2, uns)
        spare_b = bmod.update_budget_pooled(CFG, wf2, rs, surplus, spare)
        _assert_pair(wf, wf2, spare_a, spare_b, uns, rs)


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------

def test_zero_surplus():
    """actual == headroom: the pool is exactly the unscheduled budgets."""
    wf, spare, fin, uns = _prepared_wf(3, 60, "montage", 0.4)
    wf2 = wf.clone()
    actual = wf.tasks[fin].budget + spare   # consumes headroom exactly
    pool_before = sum(wf.tasks[t].budget for t in uns)

    spare_a = bmod.update_budget(CFG, wf, fin, actual, spare, uns)
    rs = bmod.RedistState(CFG, wf2, uns)
    spare_b = bmod.update_budget_fast(CFG, wf2, rs, fin, actual, spare)
    _assert_pair(wf, wf2, spare_a, spare_b, uns, rs)
    pool_after = sum(wf.tasks[t].budget for t in uns) + spare_a
    assert pool_after <= pool_before + 1e-6      # conservation
    assert spare_a >= 0.0


def test_debt_negative_surplus():
    """Actual cost far above headroom: the debt drains the pool; when it
    exceeds the pool entirely, every unscheduled budget clamps to 0."""
    wf, spare, fin, uns = _prepared_wf(11, 50, "cybershake", 0.3)
    wf2 = wf.clone()
    pool = sum(wf.tasks[t].budget for t in uns) \
        + wf.tasks[fin].budget + spare
    actual = pool * 10.0 + 100.0                 # debt > whole pool

    spare_a = bmod.update_budget(CFG, wf, fin, actual, spare, uns)
    rs = bmod.RedistState(CFG, wf2, uns)
    spare_b = bmod.update_budget_fast(CFG, wf2, rs, fin, actual, spare)
    _assert_pair(wf, wf2, spare_a, spare_b, uns, rs)
    assert spare_a == 0.0
    assert all(wf.tasks[t].budget == 0.0 for t in uns)


def test_single_unscheduled_task():
    wf, spare, fin, _ = _prepared_wf(5, 30, "sipht", 0.5)
    uns = [t.tid for t in wf.tasks if t.tid != fin][:1]
    wf2 = wf.clone()
    actual = 0.5 * max(wf.tasks[fin].budget, 1.0)

    spare_a = bmod.update_budget(CFG, wf, fin, actual, spare, uns)
    rs = bmod.RedistState(CFG, wf2, uns)
    spare_b = bmod.update_budget_fast(CFG, wf2, rs, fin, actual, spare)
    _assert_pair(wf, wf2, spare_a, spare_b, uns, rs)
    # Alg 1 on one task: it can never exceed its top-tier cost.
    table = cost_tables.table_for(CFG, wf)
    assert wf.tasks[uns[0]].budget <= table.top_arr[uns[0]] + 1e-9
    assert spare_a >= 0.0


def test_all_tasks_topped_out():
    """A pool big enough to top everyone out pins every unscheduled
    budget at its top-tier cost, identically on both paths."""
    rng = np.random.default_rng(17)
    wf = generate_workflow("montage", 0, 120, rng)
    lo, hi = bmod.min_max_workflow_cost(CFG, wf)
    bmod.distribute_budget(CFG, wf, lo)
    uns = [t.tid for t in wf.tasks if t.tid != 0]
    wf2 = wf.clone()
    table = cost_tables.table_for(CFG, wf)
    huge = 10.0 * hi                              # tops out with room over

    spare_a = bmod.update_budget(CFG, wf, 0, 0.0, huge, uns)
    rs = bmod.RedistState(CFG, wf2, uns)
    spare_b = bmod.update_budget_fast(CFG, wf2, rs, 0, 0.0, huge)
    _assert_pair(wf, wf2, spare_a, spare_b, uns, rs)
    if table.tiers_monotone:
        for tid in uns:
            assert wf.tasks[tid].budget == table.top_arr[tid], tid
    assert spare_a > 0.0


def test_zero_pool_identity_skip():
    """Pool 0 over all-zero budgets: the array path returns without
    touching the tasks and agrees with the scalar result."""
    wf, _spare, fin, uns = _prepared_wf(23, 40, "ligo", 0.2)
    for t in wf.tasks:
        t.budget = 0.0
    wf2 = wf.clone()

    spare_a = bmod.update_budget(CFG, wf, fin, 5.0, 0.0, uns)
    rs = bmod.RedistState(CFG, wf2, uns)
    spare_b = bmod.update_budget_fast(CFG, wf2, rs, fin, 5.0, 0.0)
    assert spare_a == spare_b == 0.0
    assert all(wf2.tasks[t].budget == 0.0 for t in uns)
    assert not rs.budget_vec.any()


def test_empty_unscheduled_returns_pool():
    wf, spare, fin, _ = _prepared_wf(29, 20, "montage", 0.5)
    wf2 = wf.clone()
    actual = 0.25 * max(wf.tasks[fin].budget, 1.0)
    spare_a = bmod.update_budget(CFG, wf, fin, actual, spare, [])
    rs = bmod.RedistState(CFG, wf2, [])
    spare_b = bmod.update_budget_fast(CFG, wf2, rs, fin, actual, spare)
    assert spare_a == spare_b
    assert spare_a == max(wf.tasks[fin].budget + spare - actual, 0.0) \
        or spare_a >= 0.0


# ---------------------------------------------------------------------------
# engine-level parity
# ---------------------------------------------------------------------------

def _workload():
    return cell_workload(CFG, "montage", 6.0, (0.0, 0.25), seed=3,
                         n_workflows=10, sizes=("small", "medium"))


def _key(res):
    return [(w.wid, w.cost, w.finish_ms) for w in res.workflows]


def _run_engine(wl, scalar, monkeypatch, policy=EBPSM, seed=0):
    monkeypatch.setattr(bmod, "_ARRAY_REDIST", not scalar)
    wfs = [w.clone() for w in wl]
    return SimEngine(CFG, policy, wfs, seed=seed).run()


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("policy", [EBPSM, EBPSM_NC], ids=lambda p: p.name)
def test_engine_array_vs_scalar_parity(policy, seed, monkeypatch):
    wl = _workload()
    r_arr = _run_engine(wl, False, monkeypatch, policy, seed)
    r_sca = _run_engine(wl, True, monkeypatch, policy, seed)
    assert _key(r_arr) == _key(r_sca)


@pytest.mark.parametrize("policy", ALL_POLICIES, ids=lambda p: p.name)
def test_cross_engine_parity(policy):
    wl = _workload()
    seq = SimEngine(CFG, policy, [w.clone() for w in wl], seed=0).run()
    bat = simulate_batch(CFG, policy, [w.clone() for w in wl], seed=0)
    assert _key(bat.results[0]) == _key(seq)


def test_redistribute_mode_validated():
    """Algorithm 3 runs per task finish: ``BatchSimEngine`` accepts
    ``redistribute="finish"`` and refuses the retired ``"round"`` and
    anything else."""
    wl = _workload()[:1]
    BatchSimEngine(CFG, [(EBPSM, [wl[0].clone()], 0)],
                   redistribute="finish")
    for mode in ("round", "never"):
        with pytest.raises(ValueError, match="finish"):
            BatchSimEngine(CFG, [(EBPSM, [wl[0].clone()], 0)],
                           redistribute=mode)
