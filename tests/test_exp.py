"""repro.exp: scenario registry, metrics collection, grid harness."""
import json

import numpy as np
import pytest

from repro.core.engine import SimEngine
from repro.core.scheduler import EBPSM
from repro.core.types import PlatformConfig
from repro.exp import run as exp_run
from repro.exp.metrics import CellMetrics, aggregate_by_policy, format_row
from repro.exp.scenarios import SCENARIOS, Scenario, get_scenario
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()

TINY = Scenario(
    name="unit-tiny",
    description="unit-test grid",
    apps=("montage",),
    rates=(6.0,),
    budget_intervals=((0.5, 1.0),),
    policies=("EBPSM", "MSLBL_MW"),
    seeds=(0,),
    n_workflows=4,
    sizes=("small",),
    ebpsm_budget_met_floor=0.5,
)


def test_registry_contains_paper_grids():
    for name in ("paper", "paper-smoke"):
        s = get_scenario(name)
        assert s.n_cells == s.n_workload_cells * len(s.policies)
    assert SCENARIOS["paper"].apps == (
        "cybershake", "epigenome", "ligo", "montage", "sipht")
    assert len(SCENARIOS["paper"].budget_intervals) == 4
    with pytest.raises(SystemExit):
        get_scenario("no-such-grid")


def test_workload_cells_are_deterministic_and_distinct():
    s = get_scenario("paper-smoke")
    cells = list(s.workload_cells())
    assert len(cells) == s.n_workload_cells
    assert [c.index for c in cells] == list(range(len(cells)))
    seeds = {c.workload_seed for c in cells}
    assert len(seeds) == len(cells)  # no two cells share a workload draw
    assert list(s.workload_cells())[0].workload_seed == cells[0].workload_seed


def test_cell_metrics_from_result():
    wl = generate_workload(CFG, WorkloadSpec(
        n_workflows=4, arrival_rate_per_min=6.0, sizes=("small",),
        seed=1, budget_lo=0.5, budget_hi=1.0))
    eng = SimEngine(CFG, EBPSM, wl, seed=0, trace=True)
    res = eng.run()
    m = CellMetrics.from_result("EBPSM", res, eng.trace_rows)
    assert m.n_workflows == 4
    assert m.mean_makespan_s > 0
    assert 0.0 <= m.budget_met <= 1.0
    assert 0.0 <= m.utilization <= 1.0
    assert 0.0 <= m.data_cache_hit_rate <= 1.0
    assert 0.0 <= m.container_hit_rate <= 1.0
    assert sum(m.tier_hist.values()) == sum(w.n_tasks for w in wl)
    d = m.to_dict()
    assert d["policy"] == "EBPSM"
    assert "locality_hit_rate" in d
    assert "EBPSM" in format_row(m)
    agg = aggregate_by_policy([m, m])
    assert agg["EBPSM"]["cells"] == 2
    assert agg["EBPSM"]["mean_makespan_s"] == pytest.approx(m.mean_makespan_s)


def test_container_warmth_classified_by_state_not_delay():
    """Cold provisions must be counted as cold even when the config makes
    the init and full-provision delays coincide (classification reads the
    VM's pre-activation state, not the returned ms)."""
    cfg = CFG.with_(container_download_ms=0)
    wl = generate_workload(cfg, WorkloadSpec(
        n_workflows=4, arrival_rate_per_min=6.0, sizes=("small",),
        seed=2, budget_lo=0.5, budget_hi=1.0))
    res = SimEngine(cfg, EBPSM, wl, seed=0).run()
    assert res.container_cold > 0  # every first activation is a download
    assert res.container_warm + res.container_init + res.container_cold \
        == sum(w.n_tasks for w in wl)


def test_run_grid_end_to_end(tmp_path):
    art = exp_run.run_grid(TINY, cells_per_batch=2)
    assert art["bench"] == "paper_grid"
    assert len(art["cells"]) == TINY.n_cells == 2
    for row in art["cells"]:
        assert row["app"] == "montage"
        assert row["policy"] in ("EBPSM", "MSLBL_MW")
        for key in ("mean_makespan_s", "mean_cost_budget_ratio",
                    "budget_met", "utilization", "data_cache_hit_rate",
                    "container_hit_rate"):
            assert np.isfinite(row[key])
    assert set(art["summary_by_policy"]) == {"EBPSM", "MSLBL_MW"}
    assert art["ebpsm_vs_mslbl_makespan_ratio"] is not None

    jpath = tmp_path / "BENCH_paper_grid.json"
    jpath.write_text(json.dumps(art))
    assert json.loads(jpath.read_text())["scenario"] == "unit-tiny"

    mpath = tmp_path / "paper_grid.md"
    exp_run.write_report(art, str(mpath))
    text = mpath.read_text()
    assert "Summary by policy" in text and "MSLBL_MW" in text


def test_check_floors_flags_regressions():
    art = exp_run.run_grid(TINY, cells_per_batch=2)
    assert exp_run.check_floors(art) == []  # healthy grid passes
    # Budget-met floor violation on an EBPSM cell is reported with its
    # coordinates; MSLBL cells are never floor-gated.
    bad = json.loads(json.dumps(art))
    for row in bad["cells"]:
        if row["policy"] == "EBPSM":
            row["budget_met"] = 0.0
    fails = exp_run.check_floors(bad)
    assert fails and "budget-met" in fails[0]
    # Losing the headline makespan win is a failure too.
    worse = json.loads(json.dumps(art))
    worse["ebpsm_vs_mslbl_makespan_ratio"] = 1.2
    assert any("beats" in f or "ratio" in f
               for f in exp_run.check_floors(worse))


def test_grid_matches_sequential_reference():
    """The harness's batched cells equal a sequential SimEngine run of the
    same predistributed clone — the exp subsystem inherits engine parity."""
    from repro.core.jax_engine import predistribute_workload
    from repro.core.types import clone_workload
    from repro.exp.scenarios import POLICY_BY_NAME
    from repro.workflows.workload import cell_workload

    cell = next(iter(TINY.workload_cells()))
    wl = cell_workload(CFG, cell.app, cell.rate, cell.budget_interval,
                       cell.workload_seed, TINY.n_workflows, TINY.sizes)
    art = exp_run.run_grid(TINY, cells_per_batch=1)
    for pol_name in TINY.policies:
        pol = POLICY_BY_NAME[pol_name]
        proto, spares = predistribute_workload(CFG, wl, pol.budget_mode)
        ref = SimEngine(CFG, pol, clone_workload(proto), seed=cell.seed,
                        predistributed=spares).run()
        row = next(r for r in art["cells"] if r["policy"] == pol_name)
        mks = np.array([w.makespan_ms for w in ref.workflows], np.float64)
        assert row["mean_makespan_s"] == pytest.approx(
            float(mks.mean()) / 1000.0, rel=1e-12)
        assert row["budget_met"] == pytest.approx(ref.budget_met_fraction)


def test_simulate_batch_reports_the_grid_dispatch():
    """``simulate_batch`` returns its engine's dispatch counters, and the
    same cell through ``run_grid`` reports the same ones."""
    from repro.core.jax_engine import simulate_batch
    from repro.exp.scenarios import POLICY_BY_NAME
    from repro.workflows.workload import cell_workload

    cell = next(iter(TINY.workload_cells()))
    wl = cell_workload(CFG, cell.app, cell.rate, cell.budget_interval,
                       cell.workload_seed, TINY.n_workflows, TINY.sizes)
    br = simulate_batch(CFG, [POLICY_BY_NAME[p] for p in TINY.policies], wl,
                        seed=cell.seed, batched=True)
    art = exp_run.run_grid(TINY, batched=True)
    assert br.dispatch["batched_calls"] > 0
    for k in ("rounds", "batched_calls", "batched_cycles", "serial_cycles"):
        assert br.dispatch[k] == art["dispatch"][k], k


def test_run_grid_workers_refused_on_tpu(monkeypatch):
    """One process per chip: a worker pool cannot open the TPU the
    parent holds, so run_grid refuses workers > 1 on a TPU backend."""
    monkeypatch.setattr(exp_run.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(exp_run, "use_compile_cache", lambda: None)
    with pytest.raises(RuntimeError, match="belongs to the process"):
        exp_run.run_grid(TINY, workers=2)
    with pytest.raises(RuntimeError, match="belongs to the process"):
        exp_run.main(["--grid", "paper-smoke", "--workers", "2",
                      "--out", "/nonexistent"])


def test_run_grid_workers_matches_serial():
    """--workers fans cell batches across a spawn pool; rows and
    summaries must equal the serial run exactly (cells are independent
    and regenerate deterministically in-worker).  Dispatch stats are
    chunking-dependent in general; with cells_per_batch=1 the chunking
    coincides, so they must match too."""
    two = Scenario(
        name="unit-two-cells",
        description="two-cell workers grid",
        apps=("montage", "sipht"),
        rates=(6.0,),
        budget_intervals=((0.5, 1.0),),
        policies=("EBPSM", "MSLBL_MW"),
        seeds=(0,),
        n_workflows=3,
        sizes=("small",),
    )
    serial = exp_run.run_grid(two, cells_per_batch=1, events=True,
                              monitor=True)
    par = exp_run.run_grid(two, cells_per_batch=1, workers=2, events=True,
                           monitor=True)
    assert par["workers"] == 2
    assert par["cells"] == serial["cells"]
    assert par["summary_by_policy"] == serial["summary_by_policy"]
    # Dispatch equality now also covers the merged obs events block and
    # the live-monitor block (_merge_stats sums by-kind counts and the
    # integer-only monitor tallies across worker processes).
    assert par["dispatch"] == serial["dispatch"]
    ev = par["dispatch"]["events"]
    assert ev["enabled"] and ev["total"] > 0 and ev["dropped"] == 0
    assert ev["by_kind"]["task_start"] == ev["by_kind"]["task_finish"]
    mon = par["dispatch"]["monitor"]
    assert mon["enabled"] and mon["members"] == two.n_cells
    assert 0 < mon["events"] <= ev["total"]
    assert mon["samples"] > 0


# ---------------------------------------------------------------------------
# Online (open-stream) scenarios — repro.tenants harness
# ---------------------------------------------------------------------------

from repro.exp.scenarios import ONLINE_SCENARIOS, OnlineScenario  # noqa: E402
from repro.tenants import GOLD, SILVER, Poisson, Tenant, TenantMix  # noqa: E402

TINY_ONLINE = OnlineScenario(
    name="unit-online-tiny",
    description="unit-test online stream",
    mix=TenantMix((
        Tenant("a", GOLD, apps=("montage", "trace:montage-18"),
               arrival=Poisson(10.0), n_workflows=4),
        Tenant("b", SILVER, apps=("trace:seismology-9",),
               arrival=Poisson(6.0), n_workflows=3),
    )),
    policies=("EBPSM", "MSLBL_MW"),
    seeds=(0,),
    warmup_s=5.0,
    ebpsm_budget_met_floor=0.0,
)


def test_online_registry():
    for name in ("online-smoke", "online-heavy"):
        sc = exp_run.get_scenario(name)
        assert isinstance(sc, OnlineScenario)
        assert sc.n_cells == len(sc.seeds) * len(sc.policies)
        assert sc.mix.n_workflows > 0
    assert ONLINE_SCENARIOS["online-smoke"].warmup_s > 0
    # Closed grids still resolve to Scenario.
    assert isinstance(exp_run.get_scenario("paper-smoke"), Scenario)


def test_run_online_end_to_end(tmp_path):
    art = exp_run.run_online(TINY_ONLINE)
    assert art["bench"] == "paper_grid"          # same artifact schema
    assert art["scenario_kind"] == "online"
    assert art["warmup_s"] == 5.0
    assert len(art["cells"]) == TINY_ONLINE.n_cells == 2
    assert [t["name"] for t in art["tenants"]] == ["a", "b"]
    for row in art["cells"]:
        assert row["app"] == "mixed"
        assert row["n_workflows"] + row["n_warmup_excluded"] == 7
        # Per-tenant extensions present and sane.
        assert set(row["by_tenant"]) <= {"a", "b"}
        assert set(row["by_qos"]) <= {"gold", "silver"}
        assert row["p95_slowdown"] >= row["p50_slowdown"] > 0
        assert 0 < row["jain_fairness"] <= 1.0 + 1e-9
        assert row["peak_vms"] > 0
        assert row["mean_fleet_vms"] > 0
        for stats in row["by_tenant"].values():
            assert stats["n"] > 0
            assert stats["p95_slowdown"] >= stats["p50_slowdown"]
    # Round-trips through the shared report writer + floor gate.
    mpath = tmp_path / "paper_grid.md"
    exp_run.write_report(art, str(mpath))
    assert "mixed" in mpath.read_text()
    assert exp_run.check_floors(art) == []


def test_run_online_is_deterministic():
    a = exp_run.run_online(TINY_ONLINE)
    b = exp_run.run_online(TINY_ONLINE)
    ka = [{k: v for k, v in row.items()} for row in a["cells"]]
    kb = [{k: v for k, v in row.items()} for row in b["cells"]]
    assert ka == kb


def test_online_warmup_truncation_counts():
    no_warm = OnlineScenario(
        name="t", description="t", mix=TINY_ONLINE.mix,
        policies=("EBPSM",), seeds=(0,), warmup_s=0.0)
    art = exp_run.run_online(no_warm)
    row = art["cells"][0]
    assert row["n_warmup_excluded"] == 0
    assert row["n_workflows"] == 7


def test_check_floors_rejects_empty_post_warmup_cells():
    """A warm-up window that swallows the whole stream must fail the
    gate loudly, not pass vacuously with budget_met=1.0."""
    all_warm = OnlineScenario(
        name="t", description="t", mix=TINY_ONLINE.mix,
        policies=("EBPSM",), seeds=(0,), warmup_s=1e6,
        ebpsm_budget_met_floor=0.5)
    art = exp_run.run_online(all_warm)
    assert art["cells"][0]["n_workflows"] == 0
    fails = exp_run.check_floors(art)
    assert fails and "no post-warmup workflows" in fails[0]


def test_check_floors_alert_gating():
    """Declared alert floors require the monitor: a monitoring-disabled
    run fails (never passes vacuously), an under-floor kind fails, and a
    monitored run meeting the floors passes."""
    scen = OnlineScenario(
        name="t", description="t", mix=TINY_ONLINE.mix,
        policies=("EBPSM",), seeds=(0,), warmup_s=0.0,
        alert_floors={"budget_burn": 1})
    art = exp_run.run_online(scen)                # monitor off
    fails = exp_run.check_floors(art)
    assert fails and "monitoring disabled" in fails[0]
    art = exp_run.run_online(scen, monitor=True)  # benign stream: 0 burns
    fails = exp_run.check_floors(art)
    assert fails and "alert floor" in fails[0]
    ok = json.loads(json.dumps(art))
    ok["dispatch"]["monitor"]["alerts_by_kind"]["budget_burn"] = 2
    assert exp_run.check_floors(ok) == []


def test_artifact_warns_on_dropped_events():
    """Satellite: a ring-truncated event log surfaces as a loud warning
    in the artifact (and from there on stdout)."""
    art = exp_run.run_online(TINY_ONLINE)
    assert art["warnings"] == []
    stats = {"events": {"enabled": True, "total": 10, "by_kind": {},
                        "dropped": 7}}
    fake = exp_run._artifact(TINY_ONLINE, art["cells"], stats,
                             wall_s=1.0, workers=1, use_pallas=False)
    assert len(fake["warnings"]) == 1
    assert "dropped 7 events" in fake["warnings"][0]


def test_warmup_truncates_tier_hist_too():
    """tier_hist must count only placements made by post-warmup
    workflows — cold-start placements are excluded from every metric."""
    from repro.exp.metrics import CellMetrics
    wl = generate_workload(CFG, WorkloadSpec(
        n_workflows=6, arrival_rate_per_min=2.0, sizes=("small",),
        seed=5, budget_lo=0.5, budget_hi=1.0))
    eng = SimEngine(CFG, EBPSM, wl, seed=0, trace=True)
    res = eng.run()
    cut = wl[3].arrival_ms           # exclude the first three arrivals
    m = CellMetrics.from_result("EBPSM", res, eng.trace_rows,
                                warmup_ms=cut)
    kept = [w for w in res.workflows if w.arrival_ms >= cut]
    assert m.n_warmup_excluded == 6 - len(kept) > 0
    assert sum(m.tier_hist.values()) == \
        sum(wl[w.wid].n_tasks for w in kept)
