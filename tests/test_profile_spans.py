"""The profile block's phases: one bracket helper (``engine.phase``) that
times a phase, counts it and spans it on the profiler's clock; the
engine phases of ``BatchSimEngine`` rounds and ``multi_cycle`` kernel
rounds; the always-on kernel counters; snapshots."""
import jax
import pytest

from repro.core import engine as engine_mod
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
    """The block's counts: brackets, Algorithm-3 events, kernel rounds."""
    keys = [*MEMBER_PHASES.values(), *ENGINE_PHASES.values(),
            "redistribute_events", *KERNEL_COUNTERS]
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
