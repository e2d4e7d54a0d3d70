"""repro.obs.monitor / slo / report — the live SLO monitor gates.

The contract under test (PR 10):

* zero cost when disabled — ``monitor=None`` without ``REPRO_MONITOR=1``
  leaves ``eng.monitor is None``; an events-on run with the monitor off
  allocates nothing in ``obs/monitor.py`` (the hot path is one ``sub is
  not None`` check in ``EventLog.append``);
* streaming aggregates agree with the event log they fold (events seen,
  placements, completions, arrivals) and never perturb results;
* determinism — ``monitor.json`` and the HTML dashboard are
  byte-identical across SimEngine vs BatchSimEngine, a member alone or
  pooled beside another, repeat runs, and an interrupt/resume cut mid-stream
  (the monitor rides the pickled ``elog.sub`` in stream snapshots);
* alert mechanics — burn-rate algebra, the threshold+MAD rule, and
  fire/clear hysteresis on a synthetic event stream;
* the chaos gate — ``online-chaos-smoke`` fires the ``budget_burn`` and
  ``straggler_spike`` detectors (the CI alert floors) while the benign
  detectors stay quiet on clean streams;
* the exp harness — ``dispatch_stats()["monitor"]`` blocks are
  integer-only and merge exactly across worker chunks; written reports
  pass ``tools/check_report.py``.
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
from repro.obs import monitor as mon_mod
from repro.obs import report as rep
from repro.obs import slo
from repro.obs.monitor import Monitor, MonitorConfig
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()


def workload(seed, n=6, rate=12.0):
    spec = WorkloadSpec(n_workflows=n, arrival_rate_per_min=rate, seed=seed,
                        sizes=("small",), budget_lo=0.5, budget_hi=1.0)
    return generate_workload(CFG, spec)


# ---------------------------------------------------------------------------
# Enable/disable plumbing
# ---------------------------------------------------------------------------


def test_monitor_disabled_by_default(monkeypatch):
    monkeypatch.delenv("REPRO_MONITOR", raising=False)
    eng = SimEngine(CFG, EBPSM, workload(0, n=3), seed=0)
    assert eng.monitor is None and eng.elog is None
    eng.run()
    assert eng.monitor is None


def test_resolve_monitor(monkeypatch):
    monkeypatch.delenv("REPRO_MONITOR", raising=False)
    assert mon_mod.resolve_monitor(None) is None
    assert mon_mod.resolve_monitor(False) is None
    assert isinstance(mon_mod.resolve_monitor(True), Monitor)
    m = Monitor()
    assert mon_mod.resolve_monitor(m) is m          # pass-through
    monkeypatch.setenv("REPRO_MONITOR", "1")
    assert isinstance(mon_mod.resolve_monitor(None), Monitor)
    assert mon_mod.resolve_monitor(False) is None   # explicit False beats env


def test_repro_monitor_env_enables(monkeypatch):
    monkeypatch.setenv("REPRO_MONITOR", "1")
    eng = SimEngine(CFG, EBPSM, workload(0, n=3), seed=0)
    assert eng.monitor is not None
    assert eng.elog is not None                     # monitor implies events
    assert eng.elog.sub is eng.monitor
    eng.run()
    assert eng.monitor.ticks > 0
    assert eng.monitor.finalized_ms == eng.now


def test_monitor_off_allocates_nothing_in_monitor_module():
    wl = workload(4, n=4)
    warm = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0,
                     events=True)
    warm.run()                                  # warm caches outside tracing
    eng = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0,
                    events=True)
    assert eng.monitor is None and eng.elog is not None
    mon_filter = tracemalloc.Filter(True, "*repro/obs/monitor.py")
    tracemalloc.start()
    try:
        eng.run()
        snap = tracemalloc.take_snapshot().filter_traces([mon_filter])
        mon_bytes = sum(stat.size for stat in snap.statistics("filename"))
    finally:
        tracemalloc.stop()
    assert mon_bytes == 0


# ---------------------------------------------------------------------------
# Aggregate invariants
# ---------------------------------------------------------------------------


def test_monitor_counts_match_event_log():
    eng = SimEngine(CFG, EBPSM, workload(1, n=8), seed=0, monitor=True)
    res = eng.run()
    m, counts = eng.monitor, eng.elog.counts()
    assert m.events_seen == eng.elog.total
    assert m.placements == counts["task_place"]
    assert m.completions == counts["wf_done"] == len(res.workflows)
    assert m.arrivals == counts["wf_arrive"]
    assert m.churn == counts["vm_provision"] + counts["vm_reap"]
    assert m.fleet == 0 and m.busy == 0 and m.queue == 0  # post-finalize
    assert m.cost == pytest.approx(sum(w.cost for w in res.workflows))
    # The sampled series cover the horizon and end on the final state.
    s = m.series()
    assert int(s["t_ms"][-1]) == eng.now
    assert int(s["fleet"][-1]) == 0
    assert float(s["cum_cost"][-1]) == pytest.approx(m.cost)
    assert all(len(v) == len(s["t_ms"]) for v in s.values())


def test_monitor_does_not_perturb_results():
    wl = workload(2, n=6)
    plain = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0).run()
    mon = SimEngine(CFG, EBPSM, [w.clone() for w in wl], seed=0,
                    monitor=True).run()
    assert [(w.wid, w.finish_ms, w.cost) for w in mon.workflows] == \
        [(w.wid, w.finish_ms, w.cost) for w in plain.workflows]
    assert mon.vm_count_by_type == plain.vm_count_by_type


# ---------------------------------------------------------------------------
# Determinism across engines, layouts, repeats
# ---------------------------------------------------------------------------


def _report_bytes(m, label="cell"):
    return rep.monitor_json(m, label), rep.dashboard_html(m, label)


def test_reports_identical_across_engines_and_layouts():
    """Repeat runs of one grid engine, a member pooled beside another and
    the sequential oracle write byte-identical reports."""
    runs = {}
    seq = SimEngine(CFG, EBPSM, workload(7, n=5), seed=0, monitor=True)
    seq.run()
    runs["seq"] = _report_bytes(seq.monitor)
    for name in ("solo1", "solo2"):
        eng = BatchSimEngine(CFG, [(EBPSM, workload(7, n=5), 0)],
                             monitor=True)
        eng.run()
        runs[name] = _report_bytes(eng.states[0].monitor)
    eng = BatchSimEngine(CFG, [(EBPSM, workload(3, n=4), 1),
                               (EBPSM, workload(7, n=5), 0)], monitor=True)
    eng.run()
    runs["pooled"] = _report_bytes(eng.states[1].monitor)
    assert runs["solo1"] == runs["solo2"]      # repeat-run determinism
    assert runs["solo1"] == runs["pooled"]     # pooled-segment independence
    assert runs["solo1"] == runs["seq"]        # sequential-oracle parity


def test_monitor_pickles_with_event_log():
    eng = SimEngine(CFG, EBPSM, workload(3, n=4), seed=0, monitor=True)
    eng.run()
    back = pickle.loads(pickle.dumps(eng.elog))
    assert isinstance(back.sub, Monitor)
    assert _report_bytes(back.sub) == _report_bytes(eng.monitor)
    # Pre-monitor pickles (no ``sub`` key) restore with sub = None.
    state = eng.elog.__getstate__()
    state.pop("sub")
    old = ev.EventLog.__new__(ev.EventLog)
    old.__setstate__(state)
    assert old.sub is None


# ---------------------------------------------------------------------------
# Alert mechanics (synthetic streams)
# ---------------------------------------------------------------------------


def test_burn_rate_algebra():
    assert slo.burn_rate(1.0, 0.9) == 0.0
    assert slo.burn_rate(0.9, 0.9) == pytest.approx(1.0)
    assert slo.burn_rate(0.8, 0.9) == pytest.approx(2.0)
    assert slo.burn_rate(0.9, 1.0) == pytest.approx(100.0)  # degenerate tgt


def test_mad_fire_rule():
    hist = np.array([1.0] * 20)
    assert not slo.mad_fire(hist, 1.0, k=6.0, min_abs=2.0, min_samples=12)
    assert slo.mad_fire(hist, 4.0, k=6.0, min_abs=2.0, min_samples=12)
    # All-quiet history (MAD = 0): the absolute floor keeps small ticks
    # from flagging.
    assert not slo.mad_fire(hist, 2.5, k=6.0, min_abs=2.0, min_samples=12)
    # Too little history never fires.
    assert not slo.mad_fire(hist[:5], 99.0, k=6.0, min_abs=2.0,
                            min_samples=12)


def test_target_for_falls_back_to_all():
    assert slo.target_for("gold").budget_met == 0.90
    assert slo.target_for("nonesuch") == slo.DEFAULT_TARGETS["all"]


def _synthetic_monitor():
    return Monitor(MonitorConfig(sample_ms=1_000, short_window_ms=5_000,
                                 long_window_ms=10_000))


def test_budget_burn_fires_and_clears():
    m = _synthetic_monitor()
    t = 0
    # Phase 1: every other task fails — wasted/spend far over the 4% fire
    # threshold on both windows.
    for i in range(40):
        t = i * 500
        kind = ev.TASK_FAIL if i % 2 else ev.TASK_FINISH
        m.on_event(kind, t, 0, i, 0, 0, 0.5, 0.0)
    # Phase 2: clean finishes only; the windows slide past the failures
    # and the short-window fraction drops below the 1% clear threshold.
    for i in range(40, 140):
        t = i * 500
        m.on_event(ev.TASK_FINISH, t, 0, i, 0, 0, 0.5, 0.0)
    m.finalize(t)
    burns = [a for a in m.alerts if a.kind == slo.ALERT_BUDGET_BURN]
    assert len(burns) == 1
    a = burns[0]
    assert a.scope == "platform" and not a.open
    assert 0 < a.fired_ms < a.cleared_ms <= t
    assert a.value >= m.cfg.waste_frac_fire


def test_straggler_spike_fires_and_clears():
    m = _synthetic_monitor()
    for i in range(4):
        m.on_event(ev.STRAGGLER_DETECT, 1_000 + i * 100, 0, i, 0, 0,
                   0.0, 0.0)
    for i in range(30):
        m.on_event(ev.TASK_FINISH, 2_000 + i * 1_000, 0, i, 0, 0, 0.1, 0.0)
    m.finalize(32_000)
    spikes = [a for a in m.alerts if a.kind == slo.ALERT_STRAGGLER_SPIKE]
    assert len(spikes) == 1 and not spikes[0].open
    assert spikes[0].value >= m.cfg.straggler_fire


def test_alert_gate_hysteresis():
    g = slo.AlertGate(slo.ALERT_BUDGET_BURN, "platform")
    alerts = []
    g.step(alerts, 10, fire=False, clear=True, value=0.0, threshold=1.0)
    assert alerts == []
    g.step(alerts, 20, fire=True, clear=False, value=2.0, threshold=1.0)
    g.step(alerts, 30, fire=True, clear=False, value=3.0, threshold=1.0)
    assert len(alerts) == 1 and alerts[0].open      # no re-fire while open
    g.step(alerts, 40, fire=False, clear=True, value=0.0, threshold=1.0)
    assert alerts[0].cleared_ms == 40 and not alerts[0].open
    g.step(alerts, 50, fire=True, clear=False, value=2.0, threshold=1.0)
    assert len(alerts) == 2                          # re-arms after clear


def test_tick_before_event_boundary():
    """A sample at boundary B records state from events with t < B."""
    m = _synthetic_monitor()
    m.on_event(ev.TASK_READY, 500, 0, 0, 0, 0, 0.0, 0.0)
    m.on_event(ev.TASK_READY, 1_000, 0, 1, 0, 0, 0.0, 0.0)  # flushes t=1000
    assert m.ticks == 1
    assert int(m.s_gauges[0, 2]) == 1   # only the t=500 READY is sampled
    m.finalize(1_500)
    s = m.series()
    assert s["t_ms"].tolist() == [1_000, 1_500]
    assert s["queue"].tolist() == [1, 2]


# ---------------------------------------------------------------------------
# Chaos separation: detectors fire on chaos, stay quiet on benign streams
# ---------------------------------------------------------------------------


def _chaos_scenario(**kw):
    base = ONLINE_SCENARIOS["online-chaos-smoke"]
    return dataclasses.replace(base, **kw)


def test_chaos_smoke_fires_alert_floors():
    scen = _chaos_scenario(policies=("EBPSM",))
    art = run_online(scen, monitor=True)
    blk = art["dispatch"]["monitor"]
    assert blk["enabled"] and blk["members"] == 1
    by_kind = blk["alerts_by_kind"]
    for kind, floor in scen.alert_floors.items():
        assert by_kind.get(kind, 0) >= floor, (kind, by_kind)
    assert art["alert_floors"] == scen.alert_floors
    # Per-cell alert tallies land on the rows too.
    row = art["cells"][0]
    assert row["alerts_total"] == sum(by_kind.values())
    assert sum(row["alerts_by_kind"].values()) == row["alerts_total"]


def test_benign_stream_keeps_chaos_detectors_quiet():
    base = ONLINE_SCENARIOS["online-smoke"]
    scen = dataclasses.replace(base, policies=("EBPSM",))
    art = run_online(scen, monitor=True)
    by_kind = art["dispatch"]["monitor"]["alerts_by_kind"]
    assert by_kind.get("budget_burn", 0) == 0
    assert by_kind.get("straggler_spike", 0) == 0


# ---------------------------------------------------------------------------
# Harness integration: resume identity, merged blocks, validator
# ---------------------------------------------------------------------------


def _read_all(d):
    return {p.name: p.read_bytes() for p in sorted(d.iterdir())}


def test_reports_identical_across_interrupt_resume(tmp_path):
    """The acceptance gate: dashboards and monitor.json from a stream
    interrupted mid-flight and resumed are byte-identical with an
    uninterrupted run (the monitor rides the snapshot's elog residue)."""
    scen = _chaos_scenario(policies=("EBPSM", "MSLBL_MW"))
    d_ref, d_res, ck = tmp_path / "ref", tmp_path / "res", tmp_path / "ck"
    run_online(scen, report_dir=str(d_ref))
    ref = _read_all(d_ref)
    assert any(n.endswith(".monitor.json") for n in ref)
    with pytest.raises(StreamInterrupted):
        run_online(scen, report_dir=str(d_res), ckpt_dir=str(ck),
                   ckpt_every_s=0.0, stop_after_ckpts=2)
    got = run_online(scen, report_dir=str(d_res), ckpt_dir=str(ck),
                     resume=True)
    assert _read_all(d_res) == ref
    assert got["dispatch"]["monitor"]["enabled"]


def test_monitor_block_integer_only_and_merge_exact():
    eng = BatchSimEngine(
        CFG, [(EBPSM, workload(5, n=4), 0), (MSLBL_MW, workload(6, n=4), 1)],
        monitor=True)
    eng.run()
    blk = eng.dispatch_stats()["monitor"]
    for key, v in blk.items():
        if key == "alerts_by_kind":
            assert all(isinstance(n, int) for n in v.values())
        elif key != "enabled":
            assert isinstance(v, int), key
    # Splitting members across chunks and merging the blocks reproduces
    # the single-block numbers exactly (the serial-vs-workers CI gate).
    solo = [mon_mod.monitor_block([st.monitor]) for st in eng.states]
    assert mon_mod.merge_monitor_blocks(solo) == blk
    off = mon_mod.monitor_block([None, None])
    assert off["enabled"] is False and off["alerts_total"] == 0


def test_written_reports_pass_validator(tmp_path):
    import os
    import subprocess
    import sys
    scen = _chaos_scenario(policies=("EBPSM",))
    run_online(scen, report_dir=str(tmp_path / "r"))
    checker = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_report.py")
    proc = subprocess.run(
        [sys.executable, checker, str(tmp_path / "r"),
         "--require-alert", "budget_burn",
         "--require-alert", "straggler_spike"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # A corrupted document fails it.
    bad = tmp_path / "r" / "bad.monitor.json"
    bad.write_text('{"schema": "nope"}')
    proc = subprocess.run(
        [sys.executable, checker, str(tmp_path / "r")],
        capture_output=True, text=True)
    assert proc.returncode == 1
    # An empty directory is its own error.
    (tmp_path / "empty").mkdir()
    proc = subprocess.run(
        [sys.executable, checker, str(tmp_path / "empty")],
        capture_output=True, text=True)
    assert proc.returncode == 2


def test_dashboard_and_payload_shape(tmp_path):
    eng = SimEngine(CFG, EBPSM, workload(9, n=5), seed=0, monitor=True)
    eng.run()
    pay = rep.monitor_payload(eng.monitor, label="unit")
    assert pay["schema"] == rep.MONITOR_SCHEMA
    assert pay["version"] == rep.MONITOR_SCHEMA_VERSION
    assert set(pay["samples"]["series"]) >= set(mon_mod.SERIES_NAMES)
    assert pay["qos"] == ["all"]
    assert "all" in pay["slo"]
    html = rep.dashboard_html(eng.monitor, label="unit")
    assert html.startswith("<!DOCTYPE html>")
    assert rep.DASHBOARD_MARKER in html
    assert "<script" not in html                    # static, no scripts
    jp, hp = rep.write_cell_report(str(tmp_path), "unit", eng.monitor)
    assert open(jp).read().rstrip("\n") == rep.monitor_json(
        eng.monitor, "unit")
    assert rep.DASHBOARD_MARKER in open(hp).read()
