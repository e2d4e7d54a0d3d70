"""repro.tenants: trace importers, arrival processes, tenant/QoS mixes."""
import dataclasses
import os

import numpy as np
import pytest

from repro.core.engine import SimEngine
from repro.core.jax_engine import BatchSimEngine
from repro.core.scheduler import EBPSM
from repro.core.types import PlatformConfig, Task, Workflow
from repro.tenants import (BRONZE, GOLD, SILVER, Diurnal, MarkovModulated,
                           Poisson, Tenant, TenantMix, TraceReplay,
                           assign_budgets_uniform, bundled_trace,
                           bundled_trace_names, ideal_makespan_ms,
                           infer_family, load_dax, load_trace,
                           load_wfcommons)
from repro.tenants.traces import DATA_DIR
from repro.workflows.dax import TRACE_CALIBRATION

CFG = PlatformConfig()


# ---------------------------------------------------------------------------
# Workflow.validate: malformed inputs must raise clear ValueErrors
# ---------------------------------------------------------------------------


def _chain(n=3):
    tasks = [Task(tid=i, size_mi=10.0, out_mb=1.0) for i in range(n)]
    for i in range(n - 1):
        tasks[i].children.append(i + 1)
        tasks[i + 1].parents.append(i)
    return Workflow(wid=0, app="t", tasks=tasks)


def test_validate_accepts_wellformed():
    _chain().validate()


def test_validate_rejects_empty():
    with pytest.raises(ValueError, match="empty"):
        Workflow(wid=0, app="t", tasks=[]).validate()


def test_validate_rejects_cycle():
    wf = _chain(3)
    wf.tasks[2].children.append(0)
    wf.tasks[0].parents.append(2)
    with pytest.raises(ValueError, match="cycle"):
        wf.validate()


def test_validate_rejects_out_of_range_parent():
    wf = _chain(2)
    wf.tasks[0].parents.append(7)
    with pytest.raises(ValueError, match="outside"):
        wf.validate()


def test_validate_rejects_dangling_edges():
    wf = _chain(3)
    wf.tasks[2].parents.append(0)      # 0 never lists 2 as a child
    with pytest.raises(ValueError, match="dangling"):
        wf.validate()
    wf2 = _chain(3)
    wf2.tasks[0].children.append(2)    # 2 never lists 0 as a parent
    with pytest.raises(ValueError, match="dangling"):
        wf2.validate()


def test_validate_rejects_tid_mismatch():
    wf = _chain(2)
    wf.tasks[1].tid = 5
    with pytest.raises(ValueError, match="tid"):
        wf.validate()


# ---------------------------------------------------------------------------
# Trace importers
# ---------------------------------------------------------------------------


def test_bundled_traces_round_trip_deterministically():
    """Same bytes in → identical Workflow, for every bundled trace."""
    names = bundled_trace_names()
    assert len(names) >= 3
    for name in names:
        a, b = bundled_trace(name), bundled_trace(name)
        assert a == b
        assert a is not b
        a.validate()


def test_dax_import_structure_and_calibration():
    wf = bundled_trace("montage-18")
    assert wf.app == "montage"
    assert wf.n_tasks == 18
    # runtime seconds × montage reference MIPS.
    cal = TRACE_CALIBRATION["montage"]
    assert wf.tasks[0].size_mi == pytest.approx(12.40 * cal.mips)
    # mProjectPP stages its sky tile + shared header from global storage.
    assert wf.tasks[0].ext_in_mb == pytest.approx(31.3)
    # Interior tasks read parent outputs, not external staging.
    assert wf.tasks[4].ext_in_mb == 0.0
    assert wf.tasks[4].parents == [0, 1]
    # mAdd's mosaic output.
    assert wf.tasks[15].out_mb == pytest.approx(122.0)
    assert wf.exit_tasks() == [17]


def test_wfcommons_import_both_spellings():
    epi = bundled_trace("epigenomics-20")     # schema 1.4 "tasks"+parents
    assert epi.app == "epigenome"
    assert epi.n_tasks == 20
    assert len(epi.entry_tasks()) == 1
    seis = bundled_trace("seismology-9")      # legacy "jobs"+children
    assert seis.app == "seismology"
    assert seis.n_tasks == 9
    assert len(seis.entry_tasks()) == 8
    assert seis.tasks[8].parents == list(range(8))


def test_importer_rejects_cycle():
    doc = """{"name": "bad", "workflow": {"tasks": [
        {"name": "a", "runtime": 1, "parents": ["b"]},
        {"name": "b", "runtime": 1, "parents": ["a"]}]}}"""
    with pytest.raises(ValueError, match="cycle"):
        load_wfcommons(doc)


def test_importer_rejects_dangling_parent():
    doc = """{"name": "bad", "workflow": {"tasks": [
        {"name": "a", "runtime": 1, "parents": ["ghost"]}]}}"""
    with pytest.raises(ValueError, match="unknown"):
        load_wfcommons(doc)


def test_importer_rejects_empty_and_malformed():
    with pytest.raises(ValueError, match="no tasks"):
        load_wfcommons('{"name": "x", "workflow": {"tasks": []}}')
    with pytest.raises(ValueError, match="malformed"):
        load_wfcommons('{nope')
    with pytest.raises(ValueError, match="malformed"):
        load_dax("<adag><job </adag>")
    with pytest.raises(ValueError, match="adag"):
        load_dax("<notadax/>")
    with pytest.raises(ValueError, match="duplicate"):
        load_dax('<adag><job id="J1" runtime="1"/>'
                 '<job id="J1" runtime="1"/></adag>')
    with pytest.raises(ValueError, match="names no job"):
        load_dax('<adag><job id="J1" runtime="1"/>'
                 '<child ref="J9"><parent ref="J1"/></child></adag>')


def test_importer_rejects_hostile_fields():
    """NaN / negative / non-numeric runtimes and sizes, self-edges —
    descriptive ValueErrors, never a silent clip or a mid-sim crash."""
    with pytest.raises(ValueError, match="non-finite"):
        load_wfcommons('{"workflow": {"tasks": ['
                       '{"name": "a", "runtime": NaN}]}}')
    with pytest.raises(ValueError, match="negative"):
        load_wfcommons('{"workflow": {"tasks": ['
                       '{"name": "a", "runtime": -3.0}]}}')
    with pytest.raises(ValueError, match="non-numeric"):
        load_wfcommons('{"workflow": {"tasks": ['
                       '{"name": "a", "runtime": "soon"}]}}')
    with pytest.raises(ValueError, match="negative"):
        load_wfcommons('{"workflow": {"tasks": [{"name": "a", "runtime": 1,'
                       ' "files": [{"name": "f", "sizeInBytes": -5}]}]}}')
    with pytest.raises(ValueError, match="self-edge"):
        load_wfcommons('{"workflow": {"tasks": ['
                       '{"name": "a", "runtime": 1, "parents": ["a"]}]}}')
    with pytest.raises(ValueError, match="self-edge"):
        load_wfcommons('{"workflow": {"jobs": ['
                       '{"name": "a", "runtime": 1, "children": ["a"]}]}}')
    with pytest.raises(ValueError, match="duplicate"):
        load_wfcommons('{"workflow": {"tasks": [{"name": "a", "runtime": 1},'
                       ' {"name": "a", "runtime": 2}]}}')
    with pytest.raises(ValueError, match="not a list"):
        load_wfcommons('{"workflow": {"tasks": ['
                       '{"name": "a", "runtime": 1, "files": 7}]}}')
    with pytest.raises(ValueError, match="non-numeric"):
        load_dax('<adag><job id="J1" runtime="soon"/></adag>')
    with pytest.raises(ValueError, match="negative"):
        load_dax('<adag><job id="J1" runtime="1">'
                 '<uses file="f" link="output" size="-9"/></job></adag>')
    with pytest.raises(ValueError, match="self-edge"):
        load_dax('<adag><job id="J1" runtime="1"/>'
                 '<child ref="J1"><parent ref="J1"/></child></adag>')


def _mutate(data: bytes, rng: np.random.default_rng) -> bytes:
    """One seeded mutation: truncate, delete a span, duplicate a span,
    or flip bytes — the classic fuzz moves over trace bytes."""
    n = len(data)
    op = rng.integers(0, 4)
    if op == 0:                                    # truncate
        return data[:rng.integers(0, n)]
    i = int(rng.integers(0, n))
    j = min(n, i + int(rng.integers(1, 64)))
    if op == 1:                                    # delete span
        return data[:i] + data[j:]
    if op == 2:                                    # duplicate span
        return data[:j] + data[i:j] + data[j:]
    flipped = bytearray(data)                      # flip bytes
    for k in range(i, j):
        flipped[k] ^= int(rng.integers(1, 256))
    return bytes(flipped)


@pytest.mark.parametrize("name", ["montage-18", "epigenomics-20",
                                  "seismology-9", "cybershake-12"])
def test_fuzzed_traces_fail_closed(name):
    """Seeded mutation fuzz over every bundled trace: each mutant either
    parses into a *valid* Workflow or raises ValueError — no other
    exception type, no invalid DAG, ever escapes the importer."""
    for ext in (".dax", ".json"):
        path = os.path.join(DATA_DIR, name + ext)
        if os.path.exists(path):
            break
    with open(path, "rb") as f:
        pristine = f.read()
    loader = load_wfcommons if ext == ".json" else load_dax
    rng = np.random.default_rng(0xF022 + len(name))
    for trial in range(60):
        mutant = _mutate(pristine, rng)
        try:
            wf = loader(mutant, name=f"{name}#{trial}")
        except ValueError:
            continue
        wf.validate()                    # parsed → must be a legal DAG
        for t in wf.tasks:
            assert t.size_mi >= 0 and t.out_mb >= 0 and t.ext_in_mb >= 0


def test_load_trace_dispatches_on_extension():
    wf = load_trace(os.path.join(DATA_DIR, "montage-18.dax"))
    assert wf.n_tasks == 18
    with pytest.raises(ValueError, match="extension"):
        load_trace("/tmp/foo.csv")
    with pytest.raises(ValueError, match="no bundled trace"):
        bundled_trace("no-such-trace")


def test_infer_family():
    assert infer_family("Montage") == "montage"
    assert infer_family("1000genome-chr21") == "epigenome"
    assert infer_family("unknown-app") is None


# ---------------------------------------------------------------------------
# Arrival processes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("proc", [
    Poisson(6.0),
    MarkovModulated(1.0, 12.0, mean_dwell_s=30.0),
    Diurnal(2.0, 10.0, period_s=300.0),
    TraceReplay(times_ms=(0, 500, 2_000, 9_000)),
], ids=lambda p: type(p).__name__)
def test_arrivals_deterministic_sorted_nonnegative(proc):
    a = proc.arrival_times_ms(40, np.random.default_rng(7))
    b = proc.arrival_times_ms(40, np.random.default_rng(7))
    assert a == b
    assert a == sorted(a)
    assert a[0] == 0
    assert len(a) == 40
    assert proc.mean_rate_per_min() > 0


def test_poisson_rate_roughly_matches():
    times = Poisson(6.0).arrival_times_ms(600, np.random.default_rng(0))
    rate = 599 / (times[-1] / 60_000.0)
    assert 5.0 < rate < 7.0


def test_trace_replay_loops_past_trace_end():
    proc = TraceReplay(times_ms=(0, 1_000, 3_000))
    times = proc.arrival_times_ms(7, np.random.default_rng(0))
    assert times[:3] == [0, 1000, 3000]
    assert times[3] > times[2]
    assert times == sorted(times)


# ---------------------------------------------------------------------------
# Tenant / TenantMix
# ---------------------------------------------------------------------------

TINY_MIX = TenantMix((
    Tenant("gold-astro", GOLD, apps=("montage", "trace:montage-18"),
           arrival=Poisson(8.0), n_workflows=4),
    Tenant("silver-bio", SILVER, apps=("trace:epigenomics-20",),
           arrival=Diurnal(3.0, 12.0, period_s=240.0), n_workflows=3),
    Tenant("bronze-seis", BRONZE, apps=("sipht", "trace:seismology-9"),
           arrival=MarkovModulated(2.0, 16.0, mean_dwell_s=45.0),
           n_workflows=4),
))


def test_tenant_rejects_bad_specs():
    with pytest.raises(ValueError, match="unknown app"):
        Tenant("t", GOLD, apps=("not-a-family",), arrival=Poisson(1.0))
    with pytest.raises(ValueError, match="arrival"):
        Tenant("t", GOLD, apps=("montage",))
    with pytest.raises(ValueError, match="apps or stream"):
        Tenant("t", GOLD)
    with pytest.raises(ValueError, match="duplicate tenant"):
        TenantMix((Tenant("t", GOLD, apps=("montage",),
                          arrival=Poisson(1.0)),
                   Tenant("t", BRONZE, apps=("sipht",),
                          arrival=Poisson(1.0))))


def test_mix_build_is_deterministic_and_well_formed():
    tw1 = TINY_MIX.build(CFG, seed=3)
    tw2 = TINY_MIX.build(CFG, seed=3)
    assert [w.arrival_ms for w in tw1.workflows] == \
        [w.arrival_ms for w in tw2.workflows]
    assert [w.budget for w in tw1.workflows] == \
        [w.budget for w in tw2.workflows]
    assert tw1.tenant_of == tw2.tenant_of
    # Engine invariants: wid == position, arrival-sorted.
    assert [w.wid for w in tw1.workflows] == list(range(11))
    arr = [w.arrival_ms for w in tw1.workflows]
    assert arr == sorted(arr)
    # Every tenant contributed its quota.
    names = list(tw1.tenant_of.values())
    assert names.count("gold-astro") == 4
    assert names.count("silver-bio") == 3
    assert names.count("bronze-seis") == 4
    assert tw1.qos_of == {"gold-astro": "gold", "silver-bio": "silver",
                          "bronze-seis": "bronze"}
    for wf in tw1.workflows:
        wf.validate()
        assert wf.budget > 0
    # Different seed, different draws.
    tw3 = TINY_MIX.build(CFG, seed=4)
    assert [w.budget for w in tw3.workflows] != \
        [w.budget for w in tw1.workflows]


def test_mix_budgets_respect_qos_interval():
    from repro.core.budget import min_max_workflow_cost
    tw = TINY_MIX.build(CFG, seed=0)
    for wf in tw.workflows:
        lo, hi = min_max_workflow_cost(CFG, wf)
        t = next(t for t in TINY_MIX.tenants
                 if t.name == tw.tenant_of[wf.wid])
        blo, bhi = t.qos.budget_interval
        u = (wf.budget - lo) / max(hi - lo, 1e-9)
        assert blo - 1e-9 <= u <= bhi + 1e-9


def test_mix_stream_runs_through_both_engines():
    """A trace-bearing merged stream simulates end-to-end, and renumbered
    trace clones keep their caches coherent (every task completes)."""
    tw = TINY_MIX.build(CFG, seed=0)
    res = SimEngine(CFG, EBPSM, tw.workflows, seed=0).run()
    assert len(res.workflows) == 11
    for w in res.workflows:
        assert w.finish_ms >= w.arrival_ms
        assert w.cost > 0
    assert res.peak_vms > 0
    assert res.mean_fleet_vms > 0


def test_ideal_makespan_is_positive_critical_path():
    wf = bundled_trace("seismology-9")
    ideal = ideal_makespan_ms(CFG, wf)
    # Fan-in DAG: ideal ≥ slowest decon + the sift wrapper lower bounds.
    assert ideal > 0
    chain = bundled_trace("epigenomics-20")
    assert ideal_makespan_ms(CFG, chain) > ideal


def test_assign_budgets_uniform_bounds():
    from repro.core.budget import min_max_workflow_cost
    wf = bundled_trace("montage-18")
    assign_budgets_uniform(CFG, [wf], np.random.default_rng(0), 0.0, 1.0)
    lo, hi = min_max_workflow_cost(CFG, wf)
    assert lo - 1e-9 <= wf.budget <= hi + 1e-9


# ---------------------------------------------------------------------------
# profile=True per-phase counters (core.engine satellite)
# ---------------------------------------------------------------------------


def test_profile_counters_opt_in(monkeypatch):
    tw = TINY_MIX.build(CFG, seed=0)
    eng = SimEngine(CFG, EBPSM, tw.workflows, seed=0)
    assert eng.profile is None           # off by default
    # The per-engine kwarg opts in without touching os.environ ...
    members = [(EBPSM, TenantMix(TINY_MIX.tenants[:1]).build(
        CFG, seed=0).workflows, 0)]
    beng = BatchSimEngine(CFG, members, batched="auto", profile=True)
    ref = SimEngine(CFG, EBPSM, TenantMix(TINY_MIX.tenants[:1]).build(
        CFG, seed=0).workflows, seed=0, profile=True)
    res_b = beng.run()[0]
    res_r = ref.run()
    # Profiling must not perturb results.
    assert [w.finish_ms for w in res_b.workflows] == \
        [w.finish_ms for w in res_r.workflows]
    stats = beng.dispatch_stats()
    prof = stats["profile"]
    assert prof["redistributions"] > 0
    assert prof["redistribute_s"] > 0.0
    assert prof["distributions"] == 4    # one Algorithm-1 run per workflow
    assert prof["selects"] > 0
    assert 0.0 <= prof["redistribute_share_of_wall"] <= 1.0
    assert ref.profile is not None and ref.profile["redistributions"] > 0
    # ... while REPRO_PROFILE=1 stays the ambient default source.
    monkeypatch.setenv("REPRO_PROFILE", "1")
    env_eng = SimEngine(CFG, EBPSM, tw.workflows, seed=0)
    assert env_eng.profile is not None
    assert SimEngine(CFG, EBPSM, tw.workflows, seed=0,
                     profile=False).profile is None


# ---------------------------------------------------------------------------
# Review-driven regressions
# ---------------------------------------------------------------------------


def test_dax_dedups_repeated_edge_declarations():
    doc = """<adag name="dup">
      <job id="J0" runtime="1"><uses file="a" link="output" size="1000000"/></job>
      <job id="J1" runtime="1"><uses file="a" link="input" size="1000000"/></job>
      <child ref="J1"><parent ref="J0"/><parent ref="J0"/></child>
      <child ref="J1"><parent ref="J0"/></child>
    </adag>"""
    wf = load_dax(doc)
    assert wf.tasks[1].parents == [0]
    assert wf.tasks[0].children == [1]


def test_stream_tenant_applies_start_ms():
    def stream(n, seed):
        wfs = [_chain(2) for _ in range(n)]
        for i, wf in enumerate(wfs):
            wf.wid = i
            wf.arrival_ms = i * 1_000
        return wfs

    mix = TenantMix((
        dataclasses.replace(
            Tenant("late", GOLD, stream=stream, n_workflows=3),
            start_ms=60_000),
    ))
    tw = mix.build(CFG, seed=0)
    assert [w.arrival_ms for w in tw.workflows] == [60_000, 61_000, 62_000]


def test_arrival_processes_reject_bad_rates():
    with pytest.raises(ValueError, match="> 0"):
        Poisson(0.0)
    with pytest.raises(ValueError, match=">= 0"):
        MarkovModulated(-1.0, 5.0)
    with pytest.raises(ValueError, match="at least one"):
        MarkovModulated(0.0, 0.0)
    with pytest.raises(ValueError, match="dwell"):
        MarkovModulated(1.0, 5.0, mean_dwell_s=0.0)
    with pytest.raises(ValueError, match="base <= peak"):
        Diurnal(5.0, 2.0)
    with pytest.raises(ValueError, match="period"):
        Diurnal(1.0, 2.0, period_s=0.0)


def test_interrupted_poisson_silent_state_works():
    """quiet_rate=0 is the textbook IPP: silence between bursts, not a
    crash."""
    proc = MarkovModulated(0.0, 20.0, mean_dwell_s=30.0)
    a = proc.arrival_times_ms(50, np.random.default_rng(1))
    b = proc.arrival_times_ms(50, np.random.default_rng(1))
    assert a == b == sorted(a)
    assert len(a) == 50
    # Bursty: some inter-arrival gap spans a whole silent dwell.
    gaps = np.diff(a)
    assert gaps.max() > 10 * np.median(gaps[gaps > 0])
