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
* the two routes of the SFTD sweep (``core.budget._bulk_sweep``): the
  exact chain and the loop, each on the inputs that pick it, against the
  reference ``distribute_budget``;
* engine-level parity: array vs forced-scalar hot path, and SimEngine
  vs BatchSimEngine cross-engine parity for every policy.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import budget as bmod
from repro.core import cost_tables
from repro.core.engine import SimEngine, new_profile
from repro.core.jax_engine import BatchSimEngine, simulate_batch
from repro.core.scheduler import ALL_POLICIES
from repro.core.types import PlatformConfig, Task, Workflow
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
# the SFTD sweep's two routes (core.budget._bulk_sweep)
# ---------------------------------------------------------------------------

U_ROWS = 80   # above _PY_DISTRIBUTE_MAX, so both sides sweep with numpy


def _synthetic_wf(tcr, cheap):
    """A workflow of ``len(cheap)`` tasks, ranked by tid, whose cost table
    holds the given tier costs ``tcr`` ``[U, K]`` and pass-1 column
    ``cheap``."""
    tcr = np.ascontiguousarray(tcr, np.float64)
    cheap = np.ascontiguousarray(cheap, np.float64)
    wf = Workflow(0, "montage", [Task(i, 1000.0, 1.0, rank=i)
                                 for i in range(cheap.size)])
    real = cost_tables.build_table(CFG, wf)
    est = real.est_full_cost.copy()
    est[:, 0] = cheap
    wf.cost_cache = dataclasses.replace(
        real, est_full_cost=est, tier_cost=tcr, cheap_arr=cheap,
        top_arr=np.ascontiguousarray(tcr[:, -1]), cheap_list=cheap.tolist(),
        tier_list=tcr.tolist(), top_list=tcr[:, -1].tolist(),
        tiers_monotone=bool((np.diff(tcr, axis=1) >= 0).all()))
    return wf


def _rows_vs_reference(wf, budget, rows=None):
    """``_distribute_rows`` against ``distribute_budget(presorted=True)``
    over the same rows and pool: the remainder and every budget equal to
    the bit.  Returns the reference's budgets and the route counters."""
    ref, arr = wf.clone(), wf.clone()
    rs = bmod.RedistState(CFG, arr, rows)
    order = rs.rows()
    rem_a = bmod.distribute_budget(CFG, ref, budget, task_ids=order.tolist(),
                                   presorted=True)
    prof = new_profile()
    rem_b = bmod._distribute_rows(CFG, arr, rs, order, budget, prof=prof)
    assert rem_a == rem_b
    got = [arr.tasks[t].budget for t in order.tolist()]
    want = [ref.tasks[t].budget for t in order.tolist()]
    assert got == want
    assert rs.budget_vec[order].tolist() == want
    return np.asarray(want), prof


def _ladder(k=4):
    """Dyadic tier costs (every sum below is exact): tier 0 in [1, 2],
    then steps of 0.5-1.75."""
    i = np.arange(U_ROWS)
    steps = [1.0 + (i % 5) * 0.25, 0.5 + (i % 3) * 0.25,
             0.75 + (i % 2) * 0.5, 1.0 + (i % 4) * 0.25][:k]
    return np.cumsum(np.stack(steps, axis=1), axis=1)


def _chain_deltas(tcr, alloc):
    """The reference's paid deltas while every row commits, pass by pass."""
    return np.concatenate([tcr[:, 1] - alloc,
                           *(tcr[:, k + 1] - tcr[:, k]
                             for k in range(1, tcr.shape[1] - 1))])


def _pool_at_a_delta(off):
    """The pool runs out at row 37 of pass 1, ``off`` from its delta."""
    def case():
        tcr = _ladder()
        d = _chain_deltas(tcr, tcr[:, 0])
        m = U_ROWS + 37
        return tcr, tcr[:, 0], float(d[:m + 1].sum()) + off, "chain"
    return case


def _dip_in_mid_pass():
    """After row 0 of pass 1 the remainder is 5e-10, under the sweep's
    1e-9 threshold; rows 1-3 (steps of 3e-10) still commit in that pass,
    and pass 2 never starts, though rows 0-3's next steps (3e-10) would
    pass its checks."""
    tcr = _ladder()
    tcr[:, 2] = tcr[:, 1] + 0.5
    tcr[0, 2] = tcr[0, 1] + (0.25 - 5e-10)
    tcr[1:4, 2] = tcr[1:4, 1] + 3e-10
    tcr[:, 3] = tcr[:, 2] + 1.0
    tcr[:4, 3] = tcr[:4, 2] + 3e-10
    pool = float((tcr[:, 1] - tcr[:, 0]).sum()) + 0.25
    return tcr, tcr[:, 0], pool, "chain"


def _under_tier_zero():
    """Pass 1 leaves every other row under tier 0's cost (the pass-1
    column is cheaper than the slowest tier)."""
    tcr = _ladder()
    cheap = tcr[:, 0] - np.where(np.arange(U_ROWS) % 2, 0.125, 0.0)
    d = _chain_deltas(tcr, cheap)
    return tcr, cheap, float(d[:U_ROWS + 50].sum()) + 0.0625, "chain"


def _two_tiers():
    tcr = _ladder(2)
    d = _chain_deltas(tcr, tcr[:, 0])
    return tcr, tcr[:, 0], float(d[:41].sum()) + 0.125, "chain"


def _tier_one_covered():
    tcr = _ladder()
    cheap = tcr[:, 0].copy()
    cheap[::10] = tcr[::10, 1]
    return tcr, cheap, 30.0, "loop"


def _zero_step():
    tcr = _ladder()
    tcr[::7, 2] = tcr[::7, 1]
    tcr[:, 3] = tcr[:, 2] + 1.0
    return tcr, tcr[:, 0], 70.0, "loop"


def _non_monotone():
    tcr = _ladder()
    tcr[3, 2] = tcr[3, 1] - 0.25
    return tcr, tcr[:, 0], 70.0, "loop"


def _every_row_tops_out():
    """The pool is exactly what every row needs to reach the top: the
    "everyone tops out" shortcut (which wants 1e-6 to spare) stays off,
    and every paid check of every pass passes."""
    tcr = _ladder()
    return tcr, tcr[:, 0], float((tcr[:, -1] - tcr[:, 0]).sum()), "chain"


CHAIN_CASES = {
    "pool_at_a_delta_minus_2e-9": _pool_at_a_delta(-2e-9),
    "pool_at_a_delta_minus_1e-9": _pool_at_a_delta(-1e-9),
    "pool_at_a_delta_plus_1e-9": _pool_at_a_delta(1e-9),
    "remainder_under_1e-9_mid_pass": _dip_in_mid_pass,
    "pass_one_under_tier_zero": _under_tier_zero,
    "two_tiers": _two_tiers,
    "tier_one_covered_at_entry": _tier_one_covered,
    "zero_step": _zero_step,
    "non_monotone_table": _non_monotone,
    "every_row_tops_out": _every_row_tops_out,
}


@pytest.mark.parametrize("name", CHAIN_CASES)
def test_sweep_routes_match_the_reference(name):
    """Each case takes the route it is built for and matches the
    reference to the bit."""
    tcr, cheap, extra, route = CHAIN_CASES[name]()
    wf = _synthetic_wf(tcr, cheap)
    want, prof = _rows_vs_reference(wf, float(cheap.sum()) + extra)
    assert (prof["sweeps_chain"], prof["sweeps_loop"]) == \
        ((1, 0) if route == "chain" else (0, 1))
    if name == "every_row_tops_out":
        assert want.tolist() == tcr[:, -1].tolist()
    if name == "remainder_under_1e-9_mid_pass":
        # Rows 0-3 reached tier 2, the rest stayed at tier 1.
        assert want[:4].tolist() == tcr[:4, 2].tolist()
        assert want[4:].tolist() == tcr[4:, 1].tolist()


def test_sweep_chain_parity_randomized():
    """Row sets shaped like a grid episode's sweeps (400-700 unscheduled
    rows of a 1,000-task Montage workflow, K = 4 tiers) at pools across
    the whole range: the chain takes them, bit-exact with the reference."""
    rng = np.random.default_rng(2024)
    wf = generate_workflow("montage", 0, 1000, rng)
    lo, hi = bmod.min_max_workflow_cost(CFG, wf)
    bmod.distribute_budget(CFG, wf, lo)
    assert cost_tables.table_for(CFG, wf).tier_cost.shape[1] == 4
    chained = 0
    for _ in range(30):
        n = int(rng.integers(400, 701))
        rows = np.sort(rng.choice(wf.n_tasks, n, replace=False)).tolist()
        table = cost_tables.table_for(CFG, wf)
        r = np.asarray(rows)
        need = float(table.cheap_arr[r].sum())
        top = float(table.top_arr[r].sum())
        budget = need + float(rng.uniform(0.0, 1.02)) * (top - need)
        _, prof = _rows_vs_reference(wf, budget, rows)
        chained += prof["sweeps_chain"]
    assert chained >= 25


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
