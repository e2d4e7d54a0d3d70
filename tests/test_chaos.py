"""repro.chaos: deterministic fault injection through both engines.

The contract under test (PR 9):

* **determinism** — injection is a pure function of ``(seed, config)``:
  repeat runs produce identical results, counters and event streams;
* **parity** — ``SimEngine`` and
  ``BatchSimEngine`` agree bit-exactly under revocations, failures and
  stragglers, and a stream interrupted/resumed through a revocation
  round finishes bit-exact with the uninterrupted run;
* **zero-cost disabled** — ``chaos=None`` (or an all-zero config) is
  bit-identical to an engine built without the argument;
* **semantics** — retries are bounded by ``max_retries``, spot leases
  are billed at the discounted rate, wasted spend is absorbed by
  Algorithm 3 (scalar and vectorized redistribution agree), and the new
  obs kinds appear in the trace at schema v2.
"""
import pytest

from repro import ckpt
from repro.chaos import ChaosConfig, chaos_draws
from repro.core import budget as budget_mod
from repro.core.engine import SimEngine
from repro.core.jax_engine import BatchSimEngine, StreamInterrupted
from repro.core.scheduler import EBPSM, MSLBL_MW
from repro.core.types import PlatformConfig
from repro.obs.events import EVENT_SCHEMA_VERSION, EventLog
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()

CHAOS = ChaosConfig(spot_discount=0.6, revocation_rate=8.0, fail_prob=0.05,
                    max_retries=3, escalate_after=2, straggler_prob=0.1,
                    straggler_slowdown=4.0, straggler_factor=2.0, seed=0)


def workload(seed, n=8, rate=20.0):
    spec = WorkloadSpec(n_workflows=n, arrival_rate_per_min=rate, seed=seed,
                        sizes=("small",), budget_lo=0.5, budget_hi=1.0)
    return generate_workload(CFG, spec)


def signature(res):
    return ([(w.wid, w.finish_ms, w.cost) for w in res.workflows],
            res.vm_count_by_type, res.vm_seconds_by_type,
            (res.revocations, res.task_failures, res.task_retries,
             res.stragglers_detected, res.wasted_cost, res.spot_vms))


def run_one(policy=EBPSM, seed=0, chaos=CHAOS, **kw):
    eng = SimEngine(CFG, policy, workload(seed), seed=seed, chaos=chaos, **kw)
    return eng, eng.run()


# ---------------------------------------------------------------------------
# Config + draws
# ---------------------------------------------------------------------------


def test_chaos_config_validates_knobs():
    with pytest.raises(ValueError):
        ChaosConfig(spot_discount=1.5)
    with pytest.raises(ValueError):
        ChaosConfig(revocation_rate=-1.0)
    with pytest.raises(ValueError):
        ChaosConfig(fail_prob=2.0)
    with pytest.raises(ValueError):
        ChaosConfig(fail_prob=0.1, max_retries=-1)
    with pytest.raises(ValueError):
        ChaosConfig(straggler_prob=0.1, straggler_slowdown=0.5)
    assert not ChaosConfig().enabled          # all-zero = disabled
    assert CHAOS.enabled and CHAOS.spot_enabled


def test_chaos_draws_deterministic_and_none_when_disabled():
    assert chaos_draws(None, 100, 0) is None
    a = chaos_draws(CHAOS, 100, 3)
    b = chaos_draws(CHAOS, 100, 3)
    assert (a.fail_u == b.fail_u).all()
    assert (a.straggler == b.straggler).all()
    assert a.vm_lifetime_ms(7) == b.vm_lifetime_ms(7)
    # A failed attempt past the table width never fails again: the
    # retry bound is structural, not probabilistic.
    assert not a.fails(0, CHAOS.max_retries)
    # Different sim seed, different draws.
    c = chaos_draws(CHAOS, 100, 4)
    assert (a.fail_u != c.fail_u).any()


# ---------------------------------------------------------------------------
# Zero-cost disabled + determinism
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("off", [None, ChaosConfig()],
                         ids=["none", "all-zero"])
def test_chaos_disabled_is_bit_exact_benign(off):
    _, base = run_one(chaos=None)
    eng = SimEngine(CFG, EBPSM, workload(0), seed=0)   # no chaos arg at all
    assert signature(eng.run()) == signature(base)
    _, res = run_one(chaos=off)
    assert signature(res) == signature(base)
    assert res.revocations == 0 and res.spot_vms == 0


@pytest.mark.parametrize("policy", [EBPSM, MSLBL_MW], ids=lambda p: p.name)
def test_chaos_deterministic_across_repeat_runs(policy):
    _, a = run_one(policy)
    _, b = run_one(policy)
    assert signature(a) == signature(b)
    # And the injection actually fired.
    assert a.revocations > 0
    assert a.task_retries > 0
    assert a.stragglers_detected > 0
    assert a.wasted_cost > 0
    assert a.spot_vms > 0


def test_chaos_seed_changes_injection():
    _, a = run_one()
    _, b = run_one(chaos=ChaosConfig(**{**CHAOS.knobs(), "seed": 1}))
    assert signature(a) != signature(b)


# ---------------------------------------------------------------------------
# Engine parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", [EBPSM, MSLBL_MW], ids=lambda p: p.name)
def test_chaos_engine_parity_sim_vs_batch(policy):
    _, ref = run_one(policy)
    beng = BatchSimEngine(CFG, [(policy, workload(0), 0)], chaos=CHAOS)
    assert signature(beng.run()[0]) == signature(ref)


def test_chaos_scalar_vs_vector_redistribution(monkeypatch):
    """Wasted-spend absorption takes the same Algorithm-3 result whether
    the pooled vectorized update or the scalar reference runs it."""
    _, vec = run_one()
    monkeypatch.setattr(budget_mod, "_ARRAY_REDIST", False)
    _, sca = run_one()
    assert signature(sca) == signature(vec)


# ---------------------------------------------------------------------------
# Interrupt / resume through revocation rounds
# ---------------------------------------------------------------------------


def _chaos_members():
    return [(EBPSM, workload(0), 0), (MSLBL_MW, workload(1), 1)]


@pytest.mark.parametrize("cut_round", [1, 4])
def test_chaos_interrupt_resume_bit_exact(cut_round, tmp_path):
    ref = BatchSimEngine(CFG, _chaos_members(), chaos=CHAOS)
    want = [signature(r) for r in ref.run()]
    assert ref.states[0].revocations > 0     # the cut spans real churn

    eng = BatchSimEngine(CFG, _chaos_members(), chaos=CHAOS)
    cut = {}

    def hook(e):
        if e.rounds >= cut_round:
            cut["snap"] = e.snapshot()
            return True
        return False

    with pytest.raises(StreamInterrupted):
        eng.run(ckpt_hook=hook)
    # Round-trip the snapshot through the on-disk stream format too.
    ckpt.save_stream(str(tmp_path), 0, cut["snap"])
    back, _, _ = ckpt.restore_stream(str(tmp_path))
    eng2 = BatchSimEngine(CFG, _chaos_members(), chaos=CHAOS)
    eng2.load_snapshot(back)
    assert [signature(r) for r in eng2.run()] == want


# ---------------------------------------------------------------------------
# Semantics: retries, spot billing, events
# ---------------------------------------------------------------------------


def test_retries_bounded_and_all_tasks_finish():
    heavy = ChaosConfig(fail_prob=0.3, max_retries=2, seed=0)
    eng, res = run_one(chaos=heavy)
    assert res.task_failures > 0
    for (wid, tid), attempts in eng.task_attempts.items():
        assert attempts <= heavy.max_retries + 1
    # Every workflow still completed (failures only delay, never strand).
    for w in res.workflows:
        assert w.finish_ms >= w.arrival_ms


def test_spot_discount_reduces_cost_without_revocation():
    """Pure spot (no churn) bills busy-periods at the discounted rate —
    strictly cheaper in aggregate.  (Schedules may legitimately diverge
    from benign: EBPSM's budget updates see the cheaper actual spend and
    can afford faster VM types downstream.)"""
    _, base = run_one(chaos=None)
    spot = ChaosConfig(spot_discount=0.5, seed=0)
    _, res = run_one(chaos=spot)
    assert res.spot_vms > 0 and res.revocations == 0
    assert sum(w.cost for w in res.workflows) < \
        sum(w.cost for w in base.workflows)


def test_chaos_event_kinds_in_trace():
    elog = EventLog()
    eng = SimEngine(CFG, EBPSM, workload(0), seed=0, chaos=CHAOS,
                    events=elog)
    eng.run()
    assert EVENT_SCHEMA_VERSION == 2
    kinds = set(elog.kind[:elog.total].tolist())
    from repro.obs.events import (STRAGGLER_DETECT, TASK_FAIL, TASK_RETRY,
                                  VM_REVOKE)
    assert {VM_REVOKE, TASK_FAIL, TASK_RETRY, STRAGGLER_DETECT} <= kinds
