"""The profile block's phases: one bracket helper (``engine.phase``) that
times a phase, counts it and spans it on the profiler's clock; the
engine phases of ``BatchSimEngine`` rounds and ``multi_cycle`` kernel
rounds; the always-on kernel counters; snapshots."""
import jax
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core import jax_cycles as jc
from repro.core import jax_engine
from repro.core.engine import (ENGINE_PHASES, MEMBER_PHASES,
                               new_engine_profile, new_profile, phase)
from repro.core.jax_cycles import KERNEL_COUNTERS
from repro.core.jax_engine import BatchSimEngine, StreamInterrupted
from repro.core.scheduler import EBPSM, EBPSM_NS, MSLBL_MW
from repro.core.types import PlatformConfig
from repro.kernels.affinity import ops
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()


class SpanLog:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs every enter
    and exit as ("+"/"-", name)."""

    def __init__(self):
        self.log = []
        outer = self

        class Span:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.log.append(("+", self.name))

            def __exit__(self, *exc):
                outer.log.append(("-", self.name))

        self.Span = Span

    def entered(self):
        return [n for sign, n in self.log if sign == "+"]

    def nested(self) -> bool:
        """Every exit closes the span entered last."""
        stack = []
        for sign, name in self.log:
            if sign == "+":
                stack.append(name)
            elif not stack or stack.pop() != name:
                return False
        return not stack


@pytest.fixture
def spans(monkeypatch):
    log = SpanLog()
    monkeypatch.setattr(engine_mod, "_SPAN", log.Span)
    return log


def _members():
    spec = dict(arrival_rate_per_min=12.0, sizes=("small",), budget_lo=0.5,
                budget_hi=1.0)
    return [(pol, generate_workload(CFG, WorkloadSpec(
                n_workflows=5, seed=100 + i, **spec)), i)
            for i, pol in enumerate((EBPSM, EBPSM_NS, MSLBL_MW, EBPSM))]


def _engine(**kw):
    return BatchSimEngine(CFG, _members(), batched=True, **kw)


def _signatures(results):
    return [([(w.wid, w.finish_ms, w.cost) for w in r.workflows],
             r.vm_count_by_type, r.vm_seconds_by_type) for r in results]


def _counts(prof):
    """The block's counts: brackets and kernel rounds."""
    keys = [*MEMBER_PHASES.values(), *ENGINE_PHASES.values(),
            *KERNEL_COUNTERS]
    return {k: prof[k] for k in keys}


def test_profile_off_keeps_no_block_and_enters_no_span(spans):
    eng = _engine(profile=False)
    eng.run()
    assert eng.profile is None
    assert all(st.profile is None for st in eng.states)
    assert spans.log == []
    stats = eng.dispatch_stats()
    assert "profile" not in stats
    assert stats["kernel_calls"] > 0    # the counters are always on


def test_a_phase_adds_seconds_a_count_and_one_span(spans):
    prof = new_engine_profile()
    assert set(prof) == {n + "_s" for n in ENGINE_PHASES} \
        | set(ENGINE_PHASES.values()) | {"zero_pair_s", "zero_pair_n"}
    for _ in range(2):
        phase(prof, "auction.pull").close()
    assert prof["auction.pull_n"] == 2
    assert prof["auction.pull_s"] > 0.0
    assert spans.log == [("+", "repro.auction.pull"),
                         ("-", "repro.auction.pull")] * 2
    assert sum(v for k, v in prof.items() if k != "auction.pull_s"
               and k != "auction.pull_n") == 0.0


def test_nested_phases_nest_and_the_outer_holds_the_inner(spans):
    eng_block, member = new_engine_profile(), new_profile()
    outer = phase(eng_block, "round.members")
    for _ in range(3):
        phase(member, "select").close()
    inner = phase(member, "redistribute")
    inner.close()
    outer.close()
    assert spans.nested()
    assert spans.entered() == ["repro.round.members"] + [
        "repro.select"] * 3 + ["repro.redistribute"]
    assert member["selects"] == 3 and member["redistributions"] == 1
    assert eng_block["round.members_s"] >= member["select_s"] \
        + member["redistribute_s"]


def test_engine_phases_count_the_rounds_and_hold_the_member_phases(spans):
    eng = _engine(profile=True)
    eng.run()
    stats = eng.dispatch_stats()
    prof = stats["profile"]
    assert spans.nested()
    assert all(n.startswith("repro.") for n in spans.entered())
    counts = {n: prof[c] for n, c in {**MEMBER_PHASES,
                                      **ENGINE_PHASES}.items()}
    assert len(spans.entered()) == sum(counts.values())
    assert counts["round.members"] == eng.rounds
    assert counts["auction.build"] == eng.batched_cycles > 0
    assert counts["round.serial"] == eng.serial_cycles
    assert counts["round.apply"] == eng.batched_calls > 0
    for name in ("auction.stage", "auction.dispatch", "auction.pull",
                 "auction.commit"):
        assert counts[name] == stats["kernel_calls"]
    for k in KERNEL_COUNTERS:
        assert prof[k] == stats[k]
    # Every member phase runs inside an engine phase.
    member_s = sum(prof[n + "_s"] for n in MEMBER_PHASES)
    engine_s = sum(prof[n + "_s"] for n in ENGINE_PHASES)
    assert 0.0 < member_s <= engine_s <= eng.wall_s


def test_kernel_counters_count_what_the_kernel_is_handed(monkeypatch):
    handed = []
    orig = ops.affinity_batch

    def counting(*args, **kw):
        handed.append((args[3].size, sum(a.nbytes for a in args)))
        return orig(*args, **kw)

    monkeypatch.setattr(ops, "affinity_batch", counting)
    eng = _engine()
    eng.run()
    stats = eng.dispatch_stats()
    assert stats["kernel_calls"] == len(handed) > 0
    assert stats["kernel_pairs"] == sum(p for p, _ in handed)
    assert stats["staged_bytes"] == sum(b for _, b in handed)
    assert 0 < stats["real_pairs"] <= stats["kernel_pairs"]


def test_each_kernel_round_pulls_its_outputs_in_one_transfer(monkeypatch):
    returned = []
    orig = ops.affinity_batch

    def recording(*args, **kw):
        out = orig(*args, **kw)
        returned.append(out)
        return out

    monkeypatch.setattr(ops, "affinity_batch", recording)
    eng = _engine(profile=True)
    eng.run()
    stats = eng.dispatch_stats()
    assert stats["pull_transfers"] == stats["kernel_calls"] \
        == len(returned) > 0
    assert stats["profile"]["pull_transfers"] == stats["pull_transfers"]
    assert all(isinstance(out, jax.Array) and out.shape[0] == 4
               for out in returned)


def test_an_auction_uploads_its_pairs_once_then_sends_a_mask(monkeypatch):
    """An auction uploads its nine arrays once and hands the same device
    arrays to every kernel round; its first round goes with the bucket's
    resident all-live mask, and each later round sends only its live
    mask."""
    calls = []          # (auction, positional arrays, keyword arguments, out)
    auction = [0]
    orig_cycle, orig = jax_engine.multi_cycle, ops.affinity_batch

    def counting_cycle(*args, **kw):
        auction[0] += 1
        return orig_cycle(*args, **kw)

    def recording(*args, **kw):
        out = orig(*args, **kw)
        calls.append((auction[0], args, kw, out))
        return out

    monkeypatch.setattr(jax_engine, "multi_cycle", counting_cycle)
    monkeypatch.setattr(ops, "affinity_batch", recording)
    eng = _engine(profile=True)
    eng.run()
    stats = eng.dispatch_stats()
    rounds = {}
    for a, args, kw, out in calls:
        rounds.setdefault(a, []).append((args, kw, out))
    assert stats["kernel_calls"] == len(calls)
    assert max(len(r) for r in rounds.values()) > 1   # multi-round auctions
    sent = 0
    for r in rounds.values():
        (dev, kw, _), later = r[0], r[1:]
        assert len(dev) == 9
        assert all(isinstance(a, jax.Array) for a in dev)
        B, T, V = dev[3].shape
        assert kw["live"] is ops.all_live(B, T + V)
        for args, kw, out in later:
            assert type(kw["live"]) is np.ndarray
            assert all(a is b for a, b in zip(args, dev))
            # Rows placed earlier, and members that left, score as inert.
            best = ops.unpack_host(np.asarray(out)).best_vm
            assert (best[kw["live"][:, :T] == 0] == -1).all()
        # Every round places a task of each member still in, or the member
        # leaves: the live rows shrink from the first round's real rows
        # (padding rows hold budget -1).
        live_rows = [int((np.asarray(dev[2]) >= 0).sum())] + [
            int(kw["live"][:, :T].sum()) for _, kw, _ in later]
        assert all(a > b for a, b in zip(live_rows, live_rows[1:]))
        sent += sum(a.nbytes for a in dev) \
            + sum(kw["live"].nbytes for _, kw, _ in later)
    n = len(rounds)
    assert stats["h2d_transfers"] == 9 * n + stats["kernel_calls"] - n
    assert stats["h2d_bytes"] == sent
    assert stats["profile"]["h2d_transfers"] == stats["h2d_transfers"]


def _restaging_multi_cycle(cfg, requests, use_pallas="auto", stats=None,
                           prof=None):
    """The fixed point as it ran while every round restaged its pairs:
    each round packs the unplaced rows of the members still in the loop
    into fresh buffers, with the taken VMs' tier zeroed on the host, and
    calls the kernel without a mask; its outputs go back to task index."""
    pallas = ops.resolve_use_pallas(use_pallas)
    while True:
        active = []
        for r in requests:
            if not r.active:
                continue
            if len(r.unplaced) * int(r.avail.sum()) < jc.AUCTION_TAIL_PAIRS:
                r.finish_serial()
            else:
                active.append(r)
        if not active:
            break
        bufs = jc._RoundBuffers._alloc(
            jc._p2(len(active)), max(jc._p2(len(r.unplaced)) for r in active),
            max(jc._p2(r.V) for r in active))
        for b, r in enumerate(active):
            sel, n, V = r.unplaced, len(r.unplaced), r.V
            for buf, src in zip(bufs[:3], (r.size, r.out_mb, r.budget)):
                buf[b, :n] = src[sel]
            for buf, src in zip(bufs[3:6], (r.missing, r.cont, r.tier)):
                buf[b, :n, :V] = src[sel]
            bufs[5][b, :n, :V] *= r.avail[None, :]
            for buf, src in zip(bufs[6:], (r.mips, r.bw, r.price)):
                buf[b, :V] = src
        stats["kernel_calls"] += 1
        stats["real_pairs"] += sum(len(r.unplaced) * r.V for r in active)
        out = ops.unpack_host(np.asarray(ops.affinity_batch(
            *bufs, gs_read=cfg.gs_read_mbps, gs_write=cfg.gs_write_mbps,
            bp_ms=float(cfg.billing_period_ms), use_pallas=pallas,
            packed=True)))
        for b, r in enumerate(active):
            by_task = []
            for field in out:
                full = np.zeros(r.T, field.dtype)
                full[r.unplaced] = field[b, :len(r.unplaced)]
                by_task.append(full)
            r.commit(*by_task)
    return [r.placements for r in requests]


def test_resident_pairs_reach_the_fixed_point_of_restaged_rounds(
        monkeypatch):
    """The same kernel rounds, the same real pairs and the same results as
    rounds that restage the unplaced rows every time."""
    runs = []
    for cycle in (jax_engine.multi_cycle, _restaging_multi_cycle):
        monkeypatch.setattr(jax_engine, "multi_cycle", cycle)
        eng = _engine()
        results = eng.run()
        stats = eng.dispatch_stats()
        runs.append((_signatures(results), stats["kernel_calls"],
                     stats["real_pairs"]))
    assert runs[0] == runs[1]
    assert runs[0][1] > 0


def test_results_are_identical_with_profile_on_and_off():
    runs = []
    for profile in (False, True):
        eng = _engine(profile=profile)
        results = eng.run()
        stats = eng.dispatch_stats()
        stats.pop("profile", None)
        runs.append((_signatures(results), stats))
    assert runs[0] == runs[1]


def test_the_engine_block_survives_a_snapshot():
    ref = _engine(profile=True)
    ref.run()
    want = ref.dispatch_stats()

    eng = _engine(profile=True)
    cut = {}

    def hook(e):
        if e.rounds >= 4:
            cut["snap"] = e.snapshot()
            cut["profile"] = dict(e.profile)
            return True
        return False

    with pytest.raises(StreamInterrupted):
        eng.run(ckpt_hook=hook)
    assert cut["profile"]["round.members_n"] == 4

    resumed = _engine(profile=True)
    resumed.load_snapshot(cut["snap"])
    assert resumed.profile == cut["profile"]
    resumed.run()
    got = resumed.dispatch_stats()
    assert _counts(got["profile"]) == _counts(want["profile"])
    for k in KERNEL_COUNTERS:
        assert got[k] == want[k]
