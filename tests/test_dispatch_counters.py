"""The dispatcher's round counters (``jax_engine.ROUND_COUNTERS``): every
rendezvous round with pairs is counted once, with its summed pairs, as
parked (kept off the kernel) or ridden; the zero-pair cycles are counted
and timed apart.  The counters
are exact, whatever the order of the members."""
import numpy as np
import pytest

import repro.core.jax_engine as je
from repro.core.jax_engine import ROUND_COUNTERS, BatchSimEngine
from repro.core.scheduler import EBPSM, EBPSM_NS, EBPSM_WS, MSLBL_MW
from repro.core.types import PlatformConfig
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()
# Under the default threshold every round of so small a grid is parked;
# at this one about a third of the grid's rounds with pairs ride.
THRESHOLD = 24


def _members():
    pols = (EBPSM, EBPSM_NS, EBPSM_WS, MSLBL_MW, EBPSM, EBPSM_NS)
    return [(pol, generate_workload(CFG, WorkloadSpec(
                n_workflows=4, arrival_rate_per_min=6.0, seed=200 + i,
                sizes=("small",), budget_lo=0.5, budget_hi=1.0)), i)
            for i, pol in enumerate(pols)]


def _run(batched, order=None, **kw):
    members = _members()
    if order is not None:
        members = [members[k] for k in order]
    eng = BatchSimEngine(CFG, members, batched=batched, **kw)
    results = eng.run()
    return eng, results


@pytest.mark.parametrize("batched", ["auto", True])
def test_each_round_with_pairs_is_parked_or_ridden(monkeypatch, batched):
    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", THRESHOLD)
    eng, _ = _run(batched)
    c = eng.dispatch_stats()
    assert c["parked_pairs"] + c["ridden_pairs"] == sum(eng.round_pairs)
    assert c["parked_rounds"] + c["ridden_rounds"] == \
        sum(1 for p in eng.round_pairs if p) <= c["rounds"]
    assert c["ridden_rounds"] == c["batched_calls"] > 0
    assert c["ridden_pairs"] > 0
    if batched is True:
        assert c["parked_pairs"] == c["parked_rounds"] == 0
    else:
        assert c["parked_pairs"] > 0 and c["parked_rounds"] > 0
    if batched == "auto":
        # One decision per round: the parked sums lie under the threshold,
        # the ridden ones at or above it, one batched call each.
        parked = [p for p in eng.round_pairs if p < THRESHOLD]
        ridden = [p for p in eng.round_pairs if p >= THRESHOLD]
        assert c["parked_pairs"] == sum(parked)
        assert c["ridden_pairs"] == sum(ridden)
        assert c["parked_rounds"] == sum(1 for p in parked if p)
        assert c["ridden_rounds"] == c["batched_calls"] == len(ridden)


def test_every_parked_round_of_a_small_grid_stays_off_the_kernel():
    eng, _ = _run("auto")
    c = eng.dispatch_stats()
    assert max(eng.round_pairs) < je.AUCTION_MIN_PAIRS_ROUND
    assert c["ridden_pairs"] == c["ridden_rounds"] == c["batched_calls"] == 0
    assert c["parked_pairs"] == sum(eng.round_pairs) > 0
    assert c["parked_rounds"] == sum(1 for p in eng.round_pairs if p)


def test_the_counters_do_not_change_with_member_order(monkeypatch):
    """Two seeds put the members in two orders: the answers, the round
    counters and the cycle counts are the same."""
    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", THRESHOLD)
    n = len(_members())
    got = []
    for seed in (1, 2):
        order = np.random.default_rng(seed).permutation(n).tolist()
        eng, results = _run("auto", order)
        by_member = {order[m]: [(w.wid, w.finish_ms, w.cost)
                                for w in r.workflows]
                     for m, r in enumerate(results)}
        c = eng.dispatch_stats()
        got.append((by_member, {k: c[k] for k in ROUND_COUNTERS},
                    c["batched_cycles"], c["serial_cycles"]))
    assert got[0] == got[1]


def test_the_profile_block_repeats_the_counters_and_times_zero_pairs_apart(
        monkeypatch):
    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", THRESHOLD)
    eng, _ = _run("auto", profile=True)
    c = eng.dispatch_stats()
    prof = c["profile"]
    for k in ROUND_COUNTERS:
        assert prof[k] == c[k]
    assert 0.0 < prof["zero_pair_s"] < prof["round.serial_s"]
    assert 0 < prof["zero_pair_n"] < prof["round.serial_n"]


def test_the_counters_survive_a_snapshot(monkeypatch):
    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", THRESHOLD)
    want = _run("auto")[0].dispatch_stats()
    eng = BatchSimEngine(CFG, _members(), batched="auto")
    cut = {}

    def hook(e):
        if e.round_stats["ridden_pairs"] and e.round_stats["parked_pairs"]:
            cut["snap"] = e.snapshot()
            return True
        return False

    with pytest.raises(je.StreamInterrupted):
        eng.run(ckpt_hook=hook)
    resumed = BatchSimEngine(CFG, _members(), batched="auto")
    resumed.load_snapshot(cut["snap"])
    resumed.run()
    got = resumed.dispatch_stats()
    assert {k: got[k] for k in ROUND_COUNTERS} == \
        {k: want[k] for k in ROUND_COUNTERS}


def test_a_snapshot_without_the_counters_resumes(monkeypatch):
    """A snapshot written before the round counters and the zero-pair
    keys existed resumes with profiling on; the counters then count the rounds
    after the cut."""
    import pickle

    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", THRESHOLD)
    eng = BatchSimEngine(CFG, _members(), batched="auto", profile=True)
    cut = {}

    def hook(e):
        if e.rounds == 20:
            cut["snap"] = e.snapshot()
            return True
        return False

    with pytest.raises(je.StreamInterrupted):
        eng.run(ckpt_hook=hook)
    snap = cut["snap"]
    residue = pickle.loads(snap["residue"])
    del residue["counters"]["round_stats"]
    del residue["counters"]["profile"]["zero_pair_s"]
    del residue["counters"]["profile"]["zero_pair_n"]
    snap = {**snap, "residue": pickle.dumps(residue)}
    resumed = BatchSimEngine(CFG, _members(), batched="auto", profile=True)
    resumed.load_snapshot(snap)
    resumed.run()
    got = resumed.dispatch_stats()
    later = resumed.round_pairs[20:]
    assert got["parked_pairs"] + got["ridden_pairs"] == sum(later) > 0
    assert got["parked_rounds"] + got["ridden_rounds"] == \
        sum(1 for p in later if p)
    assert got["profile"]["zero_pair_s"] <= got["profile"]["round.serial_s"]
