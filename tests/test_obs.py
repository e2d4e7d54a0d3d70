"""repro.obs — event bus, time-series, and trace-export gates.

The contract under test (PR 8):

* :class:`repro.obs.events.EventLog` — append/growth, ring wrap-around,
  chronological views, pickling;
* zero cost when disabled — a run without ``events=`` allocates nothing
  in the obs layer and leaves ``elog is None``;
* event-count invariants — the log agrees with the aggregate
  ``SimResult``/``CellMetrics`` numbers it shadows (placements ==
  scheduled tasks, provisions == fleet, event-derived peak == reported
  peak via the shared ``peak_and_mean`` reconstruction);
* byte-determinism — the same cell + seed exports identical Perfetto
  JSON and JSONL bytes across repeat runs, a member alone or pooled,
  and a checkpoint/resume cut mid-stream;
* the exp harness merge — ``--workers`` events blocks equal serial
  (asserted in ``tests/test_exp.py::test_run_grid_workers_matches_serial``).
"""
import dataclasses
import pickle
import tracemalloc

import numpy as np
import pytest

from repro.core.engine import SimEngine
from repro.core.jax_engine import BatchSimEngine, StreamInterrupted
from repro.core.scheduler import EBPSM, MSLBL_MW
from repro.core.types import PlatformConfig
from repro.exp.run import run_online
from repro.exp.scenarios import ONLINE_SCENARIOS
from repro.obs import events as ev
from repro.obs import export as ex
from repro.obs import timeseries as ts
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()


def workload(seed, n=6, rate=12.0):
    spec = WorkloadSpec(n_workflows=n, arrival_rate_per_min=rate, seed=seed,
                        sizes=("small",), budget_lo=0.5, budget_hi=1.0)
    return generate_workload(CFG, spec)


# ---------------------------------------------------------------------------
# EventLog mechanics
# ---------------------------------------------------------------------------


def test_eventlog_append_and_growth():
    log = ev.EventLog()
    for i in range(3000):                      # crosses the 1024 → 2048 grow
        log.append(ev.TASK_READY, i, a=i, x=i * 0.5)
    assert len(log) == log.total == 3000
    assert log.dropped == 0
    arrays = log.to_arrays()
    assert arrays["t"].tolist() == list(range(3000))
    assert arrays["a"][2999] == 2999 and arrays["x"][1] == 0.5
    assert log.counts() == {"task_ready": 3000}


def test_eventlog_ring_keeps_most_recent():
    log = ev.EventLog(capacity=4)
    for i in range(6):
        log.append(ev.TASK_READY, i, a=10 + i)
    assert log.total == 6 and len(log) == 4 and log.dropped == 2
    arrays = log.to_arrays()                   # chronological despite wrap
    assert arrays["t"].tolist() == [2, 3, 4, 5]
    assert arrays["a"].tolist() == [12, 13, 14, 15]
    assert [r["t_ms"] for r in log.rows()] == [2, 3, 4, 5]


def test_eventlog_capacity_validated():
    with pytest.raises(ValueError):
        ev.EventLog(capacity=0)


def test_eventlog_pickle_roundtrip():
    log = ev.EventLog(capacity=3)
    for i in range(5):
        log.append(ev.VM_PROVISION, i, a=i, b=1)
    back = pickle.loads(pickle.dumps(log))
    assert back.total == 5 and back.dropped == 2
    assert back.to_arrays()["t"].tolist() == [2, 3, 4]
    back.append(ev.VM_REAP, 9, a=0)            # still appendable after load
    assert back.total == 6


def test_resolve_events(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert ev.resolve_events(None) is None
    assert ev.resolve_events(False) is None
    assert isinstance(ev.resolve_events(True), ev.EventLog)
    log = ev.EventLog()
    assert ev.resolve_events(log) is log       # pass-through, not a copy
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert isinstance(ev.resolve_events(None), ev.EventLog)
    assert ev.resolve_events(False) is None    # explicit False beats env


def test_events_block_sums_logs():
    a, b = ev.EventLog(), ev.EventLog(capacity=2)
    a.append(ev.TASK_READY, 0)
    for i in range(3):
        b.append(ev.TASK_READY, i)
    blk = ev.events_block([a, None, b])
    assert blk["enabled"] and blk["total"] == 4 and blk["dropped"] == 1
    assert blk["by_kind"] == {"task_ready": 3}   # rings report what they hold
    off = ev.events_block([None, None])
    assert off == {"enabled": False, "total": 0, "by_kind": {}, "dropped": 0}


# ---------------------------------------------------------------------------
# Time series
# ---------------------------------------------------------------------------


def test_step_series_coalesces_ties():
    s = ts.step_series("q", [5, 1, 5], [1.0, 1.0, -1.0])
    assert s.t_ms.tolist() == [1, 5]
    assert s.v.tolist() == [1.0, 1.0]          # same-t deltas coalesce
    assert s.at(0) == 0.0 and s.at(3) == 1.0 and s.final() == 1.0


def test_peak_and_mean_matches_hand_computation():
    # [0,30] + [10,15] + [20,25]: peak 2, vm-time 40 over horizon 30.
    peak, mean = ts.peak_and_mean([0, 10, 20], [30, 15, 25])
    assert peak == 2
    assert mean == pytest.approx(40.0 / 30.0)
    assert ts.peak_and_mean([], []) == (0, 0.0)


def test_sample_step_hold():
    s = ts.step_series("s", [10, 20], [2.0, 3.0])
    grid = np.array([0, 10, 15, 20, 99], np.int64)
    assert ts.sample(s, grid).tolist() == [0.0, 2.0, 2.0, 5.0, 5.0]


def test_series_from_empty_log():
    log = ev.EventLog()
    for series in (ts.fleet_series(log), ts.busy_series(log),
                   ts.utilization_series(log),
                   ts.cumulative_cost_series(log),
                   ts.cumulative_budget_series(log)):
        assert len(series.t_ms) == 0
        assert series.final() == 0.0 and series.at(10_000) == 0.0
    assert ts.queue_depth_series(log)["all"].final() == 0.0
    summary = ts.cell_summary(log)
    assert summary["peak_vms"] == 0 and summary["horizon_ms"] == 0
    assert summary["t_ms"] == []
    assert all(v == [] for v in summary["series"].values())


def test_series_from_dropped_ring_residue():
    """A ring that overwrote every provision but kept the reaps still
    yields a well-formed (if negative-going) step series — derivation
    never crashes on truncated logs, it just reflects what survived."""
    log = ev.EventLog(capacity=2)
    log.append(ev.VM_PROVISION, 10, a=0)
    log.append(ev.VM_REAP, 50, a=0)
    log.append(ev.VM_REAP, 60, a=1)            # evicts the provision
    assert log.dropped == 1
    fleet = ts.fleet_series(log)
    assert fleet.t_ms.tolist() == [50, 60]
    assert fleet.v.tolist() == [-1.0, -2.0]
    summary = ts.cell_summary(log)
    assert summary["horizon_ms"] == 60


def test_single_event_series():
    log = ev.EventLog()
    log.append(ev.VM_PROVISION, 1_000, a=0)
    fleet = ts.fleet_series(log)
    assert fleet.t_ms.tolist() == [1_000]
    assert fleet.at(999) == 0.0 and fleet.at(1_000) == 1.0
    assert fleet.final() == 1.0
    util = ts.utilization_series(log)
    assert util.at(1_000) == 0.0               # fleet without busy VMs


def test_peak_and_mean_zero_length_leases():
    assert ts.peak_and_mean([0], [0]) == (0, 0.0)
    # A zero-length lease at t>0 contributes no area and no concurrency
    # (the end's -1 sorts before the start's +1 at the same ms).
    peak, mean = ts.peak_and_mean([5, 0], [5, 10])
    assert peak == 1
    assert mean == pytest.approx(1.0)
    assert ts.peak_and_mean([], []) == (0, 0.0)


def test_fleet_series_counts_revocations_as_closes():
    log = ev.EventLog()
    log.append(ev.VM_PROVISION, 0, a=0)
    log.append(ev.VM_PROVISION, 10, a=1)
    log.append(ev.VM_REVOKE, 20, a=0, d=1, x=0.5)
    log.append(ev.VM_REAP, 30, a=1)
    fleet = ts.fleet_series(log)
    assert fleet.at(15) == 2.0
    assert fleet.at(20) == 1.0                 # revocation closes the lease
    assert fleet.final() == 0.0
    cost = ts.cumulative_cost_series(log)
    assert cost.final() == pytest.approx(0.5)  # sunk spend counted


def test_series_from_engine_log_match_result():
    eng = SimEngine(CFG, EBPSM, workload(3, n=5), seed=0, events=True)
    res = eng.run()
    fleet = ts.fleet_series(eng.elog)
    assert int(fleet.v.max()) == res.peak_vms
    assert fleet.final() == 0.0                # finalize reaps every VM
    busy = ts.busy_series(eng.elog)
    assert busy.final() == 0.0 and busy.v.min() >= 0.0
    util = ts.utilization_series(eng.elog)
    assert 0.0 <= util.v.max() <= 1.0
    cost = ts.cumulative_cost_series(eng.elog)
    assert cost.final() == pytest.approx(
        sum(w.cost for w in res.workflows))
    summary = ts.cell_summary(eng.elog)
    assert summary["peak_vms"] == res.peak_vms
    assert set(summary["series"]) == {"fleet", "busy", "utilization",
                                      "cumulative_cost",
                                      "cumulative_budget"}
    n = len(summary["t_ms"])
    assert all(len(v) == n for v in summary["series"].values())


# ---------------------------------------------------------------------------
# Engine emission invariants
# ---------------------------------------------------------------------------


def test_event_counts_match_result_aggregates():
    wl = workload(1, n=8)
    eng = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0, events=True)
    res = eng.run()
    counts = eng.elog.counts()
    n_tasks = sum(w.n_tasks for w in res.workflows)
    assert counts["task_place"] == counts["task_start"] == \
        counts["task_finish"] == n_tasks
    assert counts["task_ready"] == n_tasks
    assert counts["wf_arrive"] == counts["wf_done"] == len(res.workflows)
    assert counts["vm_provision"] == counts["vm_reap"] == res.total_vms
    assert counts["budget_distribute"] == len(res.workflows)
    # Every event timestamp is within the simulated horizon.
    arrays = eng.elog.to_arrays()
    assert arrays["t"].min() >= 0
    assert arrays["t"].max() <= eng.now


def test_events_do_not_perturb_results():
    wl = workload(2, n=6)
    plain = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0).run()
    traced = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0,
                       events=True).run()
    assert [(w.wid, w.finish_ms, w.cost) for w in traced.workflows] == \
        [(w.wid, w.finish_ms, w.cost) for w in plain.workflows]
    assert traced.vm_count_by_type == plain.vm_count_by_type


def test_disabled_path_allocates_nothing_in_obs():
    wl = workload(4, n=4)
    warm = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0)
    warm.run()                                  # warm caches outside tracing
    eng = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0)
    assert eng.elog is None and eng.profile is None
    # The event bus itself must not allocate when disabled.  (The shared
    # peak_and_mean reconstruction in obs/timeseries.py still runs once
    # in finalize — that path predates the event log and is exempt.)
    obs_filter = tracemalloc.Filter(True, "*repro/obs/events.py")
    tracemalloc.start()
    try:
        eng.run()
        snap = tracemalloc.take_snapshot().filter_traces([obs_filter])
        obs_bytes = sum(stat.size for stat in snap.statistics("filename"))
    finally:
        tracemalloc.stop()
    assert obs_bytes == 0


def test_dispatch_stats_events_block():
    members = [(EBPSM, workload(5, n=4), 0), (MSLBL_MW, workload(6, n=4), 1)]
    eng = BatchSimEngine(CFG, members, events=True)
    eng.run()
    blk = eng.dispatch_stats()["events"]
    assert blk["enabled"] and blk["dropped"] == 0
    assert blk["total"] == sum(blk["by_kind"].values())
    # The driver's last round is an empty termination probe (no member
    # yields a point) and emits no GRID_ROUND.
    assert blk["by_kind"]["grid_round"] == eng.rounds - 1
    off = BatchSimEngine(CFG, [(EBPSM, workload(5, n=3), 0)])
    off.run()
    assert off.dispatch_stats()["events"] == {
        "enabled": False, "total": 0, "by_kind": {}, "dropped": 0}


# ---------------------------------------------------------------------------
# Export determinism
# ---------------------------------------------------------------------------


def _trace_bytes(events_log, **kw):
    return (ex._dumps(ex.chrome_trace(events_log, **kw)),
            ex.events_jsonl(events_log))


def test_export_bytes_identical_across_runs_and_layouts():
    """Repeat runs of one grid engine and a member pooled beside another
    export byte-identical traces."""
    runs = {}
    for name in ("solo1", "solo2"):
        eng = BatchSimEngine(CFG, [(EBPSM, workload(7, n=5), 0)],
                             events=True)
        eng.run()
        runs[name] = _trace_bytes(eng.states[0].elog, label="cell")
    eng = BatchSimEngine(CFG, [(EBPSM, workload(3, n=4), 1),
                               (EBPSM, workload(7, n=5), 0)], events=True)
    eng.run()
    runs["pooled"] = _trace_bytes(eng.states[1].elog, label="cell")
    assert runs["solo1"] == runs["solo2"]      # repeat-run determinism
    assert runs["solo1"] == runs["pooled"]     # pooled-segment independence


def test_chrome_trace_structure():
    eng = SimEngine(CFG, EBPSM, workload(8, n=4), seed=0, events=True,
                    trace=True)
    res = eng.run()
    tenant_of = {w.wid: ("even" if w.wid % 2 == 0 else "odd")
                 for w in res.workflows}
    doc = ex.chrome_trace(eng.elog, label="unit",
                          vm_type_names=[t.name for t in CFG.vm_types],
                          tenant_of=tenant_of,
                          qos_of={"even": "gold", "odd": "silver"})
    assert doc["metadata"]["schema"] == ex.TRACE_SCHEMA
    evs = doc["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert len(slices) == sum(w.n_tasks for w in res.workflows)
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in slices)
    assert {e["cat"] for e in slices} == {"even", "odd"}
    assert all(e["args"]["qos"] in ("gold", "silver") for e in slices)
    assert all("tier" in e["args"] and "est_cost" in e["args"]
               for e in slices)
    names = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
    assert len(names) == res.total_vms
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"fleet", "busy", "cumulative_cost",
            "cumulative_budget"} <= counters
    assert any(c.startswith("queue_depth") for c in counters)


def test_events_jsonl_shape():
    eng = SimEngine(CFG, EBPSM, workload(9, n=3), seed=0, events=True)
    eng.run()
    text = ex.events_jsonl(eng.elog, label="u")
    lines = text.splitlines()
    import json
    header = json.loads(lines[0])
    assert header["schema"] == ex.EVENTS_SCHEMA
    assert header["version"] == ev.EVENT_SCHEMA_VERSION
    assert header["n_events"] == len(lines) - 1 == len(eng.elog)
    assert header["dropped"] == 0
    kinds = {json.loads(l)["kind"] for l in lines[1:]}
    assert kinds <= set(ev.KIND_NAMES.values())


# ---------------------------------------------------------------------------
# Harness-level trace determinism (uninterrupted vs checkpoint/resume)
# ---------------------------------------------------------------------------


def _tiny_online():
    base = ONLINE_SCENARIOS["online-smoke"]
    return dataclasses.replace(base, name="online-smoke",
                               policies=("EBPSM", "MSLBL_MW"))


def _read_all(trace_dir):
    out = {}
    for p in sorted(trace_dir.iterdir()):
        out[p.name] = p.read_bytes()
    return out


def test_run_online_trace_deterministic_and_resume_identical(tmp_path):
    """The acceptance gate: the same scenario + seed writes byte-identical
    trace files across repeat runs AND across a mid-stream checkpoint cut
    resumed in a fresh process state."""
    scen = _tiny_online()
    d_ref = tmp_path / "ref"
    d_rep = tmp_path / "rep"
    d_res = tmp_path / "res"
    run_online(scen, trace_dir=str(d_ref))
    run_online(scen, trace_dir=str(d_rep))
    ref = _read_all(d_ref)
    assert ref and set(n for n in ref if n.endswith(".trace.json"))
    assert ref == _read_all(d_rep)

    ck = tmp_path / "ck"
    with pytest.raises(StreamInterrupted):
        run_online(scen, trace_dir=str(d_res), ckpt_dir=str(ck),
                   ckpt_every_s=0.0, stop_after_ckpts=2)
    got = run_online(scen, trace_dir=str(d_res), ckpt_dir=str(ck),
                     resume=True)
    assert _read_all(d_res) == ref
    assert got["dispatch"]["events"]["enabled"]


def test_written_traces_pass_validator(tmp_path):
    import os
    import subprocess
    import sys
    scen = _tiny_online()
    run_online(scen, trace_dir=str(tmp_path / "t"))
    checker = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_trace.py")
    proc = subprocess.run(
        [sys.executable, checker, str(tmp_path / "t")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
