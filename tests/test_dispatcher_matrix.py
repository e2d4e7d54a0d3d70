"""Dispatcher matrix: every way a scheduling cycle can be scored must be
bit-exact with the sequential reference.

Axes covered:

* ``batched`` ∈ {False, True, "auto" (aggregate-round)} on randomized
  mixed grids — EBPSM family + MSLBL members, sufficient and
  insufficient budgets;
* ``use_pallas`` ∈ {False (jnp oracle), True (Pallas, interpreted on
  CPU)};
* ``select`` scalar loop (``REPRO_SCALAR_SELECT`` oracle) vs the
  vectorized numpy path;
* the small-subset pure-Python budget distribution vs the numpy branch;
* aggregate-round engagement itself: the auction must fire on rounds
  whose individual members sit below ``AUCTION_MIN_PAIRS_ROUND``, and a
  round parks or rides by its summed pairs against that threshold;
* the pooled ``StreamState``: a grid member's segment ends equal to a
  solo ``SimEngine``'s arrays.
"""
import random

import numpy as np
import pytest

import repro.core.budget as budget_mod
import repro.core.jax_engine as je
import repro.core.scheduler as sched
from repro.core import cost_tables
from repro.core.engine import SimEngine
from repro.core.jax_cycles import _RoundBuffers
from repro.core.jax_engine import BatchSimEngine
from repro.core.scheduler import (ALL_POLICIES, EBPSM, EBPSM_NC, EBPSM_NS,
                                  EBPSM_WS, MSLBL_MW, select)
from repro.core.types import PlatformConfig, StreamState
from repro.sim.cloud import VMPool
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()


def workload(seed, n=6, rate=12.0, budget_lo=0.5, budget_hi=1.0):
    spec = WorkloadSpec(n_workflows=n, arrival_rate_per_min=rate, seed=seed,
                        sizes=("small",), budget_lo=budget_lo,
                        budget_hi=budget_hi)
    return generate_workload(CFG, spec)


def assert_same(ref, res, what=""):
    assert [w.finish_ms for w in ref.workflows] == \
        [w.finish_ms for w in res.workflows], what
    assert [w.cost for w in ref.workflows] == \
        [w.cost for w in res.workflows], what
    assert ref.vm_count_by_type == res.vm_count_by_type, what
    assert ref.vm_seconds_by_type == res.vm_seconds_by_type, what


def _mixed_members(rng):
    """Randomized mixed grid: EBPSM family + MSLBL, a couple of
    insufficient-budget cells in the draw."""
    members = []
    pols = [EBPSM, EBPSM_NS, EBPSM_WS, EBPSM_NC, MSLBL_MW]
    for i in range(6):
        pol = pols[rng.randrange(len(pols))]
        lo, hi = (0.0, 0.1) if i % 3 == 0 else (0.5, 1.0)
        ws = rng.randrange(100)
        members.append(
            (pol, workload(ws, n=4 + i % 3, budget_lo=lo, budget_hi=hi),
             rng.randrange(5), ws, lo, hi))
    return members


@pytest.mark.parametrize("batched", [False, True, "auto"],
                         ids=["serial", "forced", "aggregate-auto"])
def test_dispatcher_matrix_randomized(batched, monkeypatch):
    """Mixed grids are bit-exact with per-member SimEngine references on
    every dispatcher path.  "auto" runs with a tiny aggregate threshold
    so the aggregate decision actually exercises the batched path."""
    if batched == "auto":
        monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", 16)
    members = _mixed_members(random.Random(1234))
    eng = BatchSimEngine(CFG, [(p, wl, s) for p, wl, s, *_ in members],
                         batched=batched)
    results = eng.run()
    # References run on identical fresh workloads (the draw is
    # deterministic in the rng seed).
    members2 = _mixed_members(random.Random(1234))
    for (pol, wl, seed, *_), res in zip(members2, results):
        ref = SimEngine(CFG, pol, wl, seed=seed).run()
        assert_same(ref, res, f"{pol.name} seed={seed} batched={batched}")


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_pallas_vs_jnp_paths(use_pallas):
    """Kernel backends are interchangeable: forced-batched grids match
    the sequential reference with the jnp oracle and with the Pallas
    kernel (interpreted off-TPU)."""
    wl = workload(3, n=5)
    eng = BatchSimEngine(CFG, [(EBPSM, wl, 0)], batched=True,
                         use_pallas=use_pallas)
    res = eng.run()[0]
    ref = SimEngine(CFG, EBPSM, workload(3, n=5), seed=0).run()
    assert_same(ref, res, f"use_pallas={use_pallas}")
    assert eng.batched_calls > 0


def _spy_rounds(monkeypatch):
    """Record each rendezvous round's per-member pairs and ride flags."""
    seen = []
    decide = BatchSimEngine._round_rides_kernel

    def spy(self, points, pairs):
        rides = decide(self, points, pairs)
        seen.append((list(pairs), list(rides)))
        return rides

    monkeypatch.setattr(BatchSimEngine, "_round_rides_kernel", spy)
    return seen


def test_aggregate_engagement_below_member_threshold(monkeypatch):
    """The aggregate-round dispatcher's reason to exist: rounds engage
    the kernel although every member's own pairs sit below the
    threshold — and stay bit-exact."""
    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", 64)
    seen = _spy_rounds(monkeypatch)
    members = [(EBPSM, workload(s, n=5), s) for s in range(4)]
    eng = BatchSimEngine(CFG, members, batched="auto")
    results = eng.run()
    assert eng.batched_calls > 0
    assert eng.batched_cycles > 0
    assert any(any(rides) and max(pairs) < 64 for pairs, rides in seen), \
        "some round must ride on its summed pairs alone"
    stats = eng.dispatch_stats()
    assert stats["batched_calls"] == eng.batched_calls
    for (pol, _, seed), res, s in zip(members, results, range(4)):
        ref = SimEngine(CFG, pol, workload(s, n=5), seed=seed).run()
        assert_same(ref, res)


_FIRST_ROUND_PAIRS = []


def _first_round_pairs(monkeypatch) -> int:
    """Summed pairs of the grid's first rendezvous round with pairs (the
    same whatever the threshold: every round before it has none)."""
    if not _FIRST_ROUND_PAIRS:
        with monkeypatch.context() as m:
            seen = _spy_rounds(m)
            members = [(EBPSM, workload(s, n=5), s) for s in range(4)]
            BatchSimEngine(CFG, members, batched="auto").run()
        _FIRST_ROUND_PAIRS.append(next(sum(p) for p, _ in seen if sum(p)))
    return _FIRST_ROUND_PAIRS[0]


@pytest.mark.parametrize("offset,side", [(1, "parked"), (0, "ridden"),
                                         (-1, "ridden")],
                         ids=["pairs-below", "pairs-at", "pairs-above"])
def test_round_threshold_decides_by_summed_pairs(offset, side, monkeypatch):
    """A round whose summed pairs lie below ``AUCTION_MIN_PAIRS_ROUND``
    parks; at or above it, it rides — read from ``ROUND_COUNTERS`` after
    that round, and the whole run stays bit-exact with ``SimEngine``."""
    pairs = _first_round_pairs(monkeypatch)
    assert pairs > 1
    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", pairs + offset)
    first = {}

    def hook(eng):
        s = eng.round_stats
        if not first and s["parked_rounds"] + s["ridden_rounds"] == 1:
            first.update(s)
        return False

    members = [(EBPSM, workload(s, n=5), s) for s in range(4)]
    eng = BatchSimEngine(CFG, members, batched="auto")
    results = eng.run(ckpt_hook=hook)
    assert first[side + "_rounds"] == 1
    assert first[side + "_pairs"] == pairs
    other = "parked" if side == "ridden" else "ridden"
    assert first[other + "_rounds"] == first[other + "_pairs"] == 0
    for (pol, _, seed), res, s in zip(members, results, range(4)):
        ref = SimEngine(CFG, pol, workload(s, n=5), seed=seed).run()
        assert_same(ref, res, f"threshold {pairs + offset}")


# ---------------------------------------------------------------------------
# select: scalar oracle vs vectorized path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", [EBPSM, EBPSM_NS, EBPSM_WS, EBPSM_NC,
                                    MSLBL_MW], ids=lambda p: p.name)
@pytest.mark.parametrize("seed", [0, 7])
def test_select_scalar_vs_vector_full_sim(policy, seed, monkeypatch):
    """Whole-simulation property: forcing every select through the
    vectorized path produces the trace the scalar oracle produces."""
    e1 = SimEngine(CFG, policy, workload(seed, n=8, rate=30.0), seed=seed,
                   trace=True)
    monkeypatch.setattr(sched, "_SCALAR_FORCED", True)
    e1.run()
    monkeypatch.setattr(sched, "_SCALAR_FORCED", False)
    monkeypatch.setattr(sched, "VECTOR_SELECT_MIN_VMS", 1)
    e2 = SimEngine(CFG, policy, workload(seed, n=8, rate=30.0), seed=seed,
                   trace=True)
    e2.run()
    assert e1.trace_rows == e2.trace_rows
    assert_same(e1.finalize(), e2.finalize())


def _random_pool(rng, n_vms, apps, keys):
    pool = VMPool(CFG)
    vms = []
    for i in range(n_vms):
        tag = rng.choice([None, ("wf", rng.randrange(3)),
                          ("app", rng.choice(apps))])
        vm = pool.provision(rng.randrange(len(CFG.vm_types)), 0, tag)
        pool.mark_idle(vm, 0)
        if rng.random() < 0.7:
            pool.activate_container(vm, rng.choice(apps), True)
        for key in rng.sample(keys, rng.randrange(len(keys))):
            vm.cache_put(CFG, key, rng.uniform(1, 600), pool.data_index)
        vms.append(vm)
    return pool, vms


@pytest.mark.parametrize("trial", range(12))
def test_select_scalar_vs_vector_random_pools(trial, monkeypatch):
    """Unit-level property test on synthetic pools: random caches,
    containers, sharing tags, budgets (incl. infeasible) — the scalar
    and vectorized paths agree on the placement decision."""
    rng = random.Random(1000 + trial)
    apps = ["montage", "sipht"]
    keys = [("out", 0, i) for i in range(6)] + [("ext", 1, 0)]
    pool, vms = _random_pool(rng, rng.randrange(1, 12), apps, keys)
    wl = workload(trial % 4, n=2)
    wf = wl[0]
    budget_mod.distribute_budget(CFG, wf, wf.budget)
    table = cost_tables.table_for(CFG, wf)
    for policy in (EBPSM, EBPSM_NS, EBPSM_WS, EBPSM_NC, MSLBL_MW):
        for task in wf.tasks[:4]:
            inputs = [(k, rng.uniform(0, 200)) for k in
                      rng.sample(keys, rng.randrange(1, 4))]
            budget = rng.choice([0.001, 0.5, 5.0, 500.0])
            args = (CFG, policy, task, wf.wid, wf.app, inputs, budget,
                    vms)
            monkeypatch.setattr(sched, "_SCALAR_FORCED", True)
            p_scalar = select(*args, table=table, pool=pool)
            monkeypatch.setattr(sched, "_SCALAR_FORCED", False)
            monkeypatch.setattr(sched, "VECTOR_SELECT_MIN_VMS", 1)
            p_vec = select(*args, table=table, pool=pool)
            key = lambda p: (p.vm.vmid if p.vm else None, p.new_vmt_idx,
                             p.tier, p.est_finish_ms, p.est_cost)
            assert key(p_scalar) == key(p_vec), \
                f"{policy.name} tid={task.tid} budget={budget}"


# ---------------------------------------------------------------------------
# budget distribution: pure-Python small path vs numpy branch
# ---------------------------------------------------------------------------


def test_distribute_small_vs_numpy_branch(monkeypatch):
    """The small-subset pure-Python distribution is bit-exact with the
    numpy branch on random subsets and budgets."""
    rng = random.Random(5)
    for seed in range(3):
        wl = workload(seed, n=3)
        for wf in wl:
            budget_mod.distribute_budget(CFG, wf, wf.budget)
            for _ in range(25):
                n = rng.randint(1, wf.n_tasks)
                ids = rng.sample(range(wf.n_tasks), n)
                b = rng.random() * max(wf.budget, 1.0) * 1.5
                saved = [t.budget for t in wf.tasks]

                monkeypatch.setattr(budget_mod, "_PY_DISTRIBUTE_MAX", -1)
                rem_np = budget_mod.distribute_budget(
                    CFG, wf, b, task_ids=list(ids))
                got_np = [t.budget for t in wf.tasks]

                for t, v in zip(wf.tasks, saved):
                    t.budget = v
                monkeypatch.setattr(budget_mod, "_PY_DISTRIBUTE_MAX",
                                    10 ** 9)
                rem_py = budget_mod.distribute_budget(
                    CFG, wf, b, task_ids=list(ids))
                got_py = [t.budget for t in wf.tasks]

                assert rem_np == rem_py
                assert got_np == got_py


# ---------------------------------------------------------------------------
# resident round buffers
# ---------------------------------------------------------------------------


def test_round_buffers_cover_and_reset():
    """A smaller round rides the resident covering bucket (no fresh
    allocation), and the used-region reset restores inert padding."""
    rb = _RoundBuffers()
    big = rb.get(4, 16, 16)
    tier_big = big[5]
    tier_big[:2, :8, :8] = 7   # simulate a round's writes
    # Smaller request within the cover slack: must reuse + reset.
    again = rb.get(4, 16, 8)
    assert again[5] is tier_big, "covering bucket should be reused"
    assert not tier_big.any(), "used region must be reset to inert 0"
    assert big[2][0, 0] == -1.0, "budget buffer resets to -1 sentinel"
    # Far-smaller request (beyond the slack): gets its own bucket so the
    # kernel does not waste compute on a mostly-inert giant tile.
    tiny = rb.get(1, 2, 2)
    assert tiny[5] is not tier_big
    assert tiny[5].shape == (1, 2, 2)


def test_round_buffers_lru_cap():
    """Total resident elements stay bounded; over-cap requests are
    one-shot and leave resident buckets alone."""
    class SmallRB(_RoundBuffers):
        MAX_RESIDENT_ELEMS = 2500   # fits one 1024- and one 2048-bucket,
                                    # but not both

    rb = SmallRB()
    a = rb.get(4, 16, 16)              # 1024 elems, resident
    rb.get(64, 64, 64)                 # over cap: one-shot
    assert (4, 16, 16) in rb.buckets
    assert (64, 64, 64) not in rb.buckets
    b = rb.get(2, 16, 16)              # fits under the (4,16,16) bucket
    assert b[5] is a[5]
    rb.get(4, 16, 32)                  # 2048 elems: evicts the LRU bucket
    assert (4, 16, 32) in rb.buckets
    assert (4, 16, 16) not in rb.buckets


# ---------------------------------------------------------------------------
# The pooled StreamState
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batched", [False, "auto"],
                         ids=["serial", "aggregate-auto"])
def test_pooled_segment_matches_solo_stream(batched, monkeypatch):
    """Each member's segment of the grid's pooled ``StreamState`` ends
    equal to the arrays of a solo ``SimEngine`` run of that member, on
    both dispatcher paths: the pooling is invisible to semantics."""
    if batched == "auto":
        monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", 16)
    members = _mixed_members(random.Random(4321))
    eng = BatchSimEngine(CFG, [(p, wl, s) for p, wl, s, *_ in members],
                         batched=batched)
    results = eng.run()
    fields = (StreamState.WF_FIELDS + StreamState.TASK_FIELDS
              + StreamState.POOL_FIELDS)
    wf_lo = task_lo = 0
    members2 = _mixed_members(random.Random(4321))
    for (pol, wl, seed, *_), res in zip(members2, results):
        ref = SimEngine(CFG, pol, wl, seed=seed)
        assert_same(ref.run(), res, f"{pol.name} seed={seed}")
        nw, nt = ref.stream.n_workflows, ref.stream.n_tasks
        for name, _ in fields:
            per_wf = (name, _) in StreamState.WF_FIELDS
            lo, hi = (wf_lo, wf_lo + nw) if per_wf else \
                (task_lo, task_lo + nt)
            assert np.array_equal(getattr(eng.stream, name)[lo:hi],
                                  getattr(ref.stream, name)), \
                f"{pol.name} seed={seed} {name}"
        wf_lo += nw
        task_lo += nt
    assert (wf_lo, task_lo) == (eng.stream.n_workflows,
                                eng.stream.n_tasks)
