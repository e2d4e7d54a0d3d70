"""The harness on the CPU: finding parts by name, the end-to-end
arithmetic, the trace reduction, the kernel's bytes, the refusal without a
chip, and a whole tiny run of each traffic mix."""
from __future__ import annotations

import copy
import json
import os
import shutil
import statistics
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import pytest

from bench import generator as gen
from bench import harness as H
from bench import kernel_cost, spec
from bench import trace as tr

BENCH = Path(spec.BENCH_DIR)
ROOT = BENCH.parent
RECORDED = BENCH / "tests" / "data" / "trace_grid-montage.json"


def tiny(cell: spec.Cell) -> spec.Cell:
    """The cell at a size a test run can hold: one small workflow per
    stream."""
    c = copy.deepcopy(cell)
    c.conf["workload"]["workflows_per_cell"] = 1
    c.conf["workload"]["sizes"] = ["small"]
    return c


def test_cells_and_metrics_are_found_by_name():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], bench)
        assert cell.conf["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"tasks_per_s",
                                                        "setup_s"}
        assert cell.per_layer
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    rounds = {m["name"] for m in spec.resolve("platform-montage",
                                              bench).end_to_end}
    assert {"round_p95_ms", "round_p50_ms"} <= rounds
    assert "round_p95_ms" not in {m["name"] for m in spec.resolve(
        "grid-montage", bench).end_to_end}


# Cells of PERF.md's Open questions: a configuration and a traffic mix
# already in bench/, or one of them with its numbers changed, as a later
# PR would add it in a file of its own.
LATER_CELLS = [
    ("platform-montage-cells", "paper-montage", "platform", {}, 4),
    ("grid-montage-live", "paper-montage-live", "grid", {}, 20),
    ("grid-montage-lowrate", "paper-montage", "grid",
     {"rate_per_min": 0.5}, 40),
    ("platform-montage-uncut", "paper-montage-live", "platform",
     {"workflows_per_cell": 100, "budget_intervals": [
         [0.0, 0.25], [0.25, 0.5], [0.5, 0.75], [0.75, 1.0]]}, 4),
    ("grid-mix", "paper-montage", "grid",
     {"app": ["cybershake", "epigenome", "ligo", "montage", "sipht"]}, 40),
]


@pytest.mark.parametrize("name,config,traffic,change,members", LATER_CELLS)
def test_a_later_cell_composes_from_data(name, config, traffic, change,
                                         members):
    """Composed without adding the cell or editing code, and run whole at
    a tiny size."""
    from bench.run import run
    bench = spec.load_benchmark()
    cell = spec.compose(name, config, traffic, bench)
    cell.conf["workload"].update(change)
    streams = gen.streams(gen.platform_config(cell.conf),
                          cell.conf["workload"], cell.traffic)
    assert len(H.member_plan(cell, streams, 9)) == members
    assert {len(s.workload) for s in streams} == {
        cell.conf["workload"]["workflows_per_cell"]}
    assert {m["name"] for m in cell.end_to_end} == {"tasks_per_s",
                                                    "setup_s"}
    out = run(tiny(cell), 2**31 + 13, 0.01, False,
              t_start=time.perf_counter())
    assert out["correct"] is True and out["attempted"] > 0


def test_a_five_application_grid_switches_containers(monkeypatch):
    """The composed mix, run whole at 4 small and medium workflows per
    stream: streams mix applications, and an EBPSM member's VMs hold the
    containers of two of them, as the pool's ``app_image`` index reads at
    some round."""
    from bench.run import run
    from repro.core import jax_engine
    cell = spec.compose("grid-mix", "paper-montage", "grid",
                        spec.load_benchmark())
    cell.conf["workload"].update(app=LATER_CELLS[-1][3]["app"],
                                 sizes=["small", "medium"])
    streams = gen.streams(gen.platform_config(cell.conf),
                          cell.conf["workload"], cell.traffic)
    assert max(len({wf[1] for wf in s.workload}) for s in streams) >= 2
    most_images = {}

    class Watched(jax_engine.BatchSimEngine):
        def run(self, ckpt_hook=None):
            def hook(engine):
                for st in engine.states:
                    held = Counter(v for vids in st.pool.app_image.values()
                                   for v in vids)
                    name = st.policy.name
                    most_images[name] = max([most_images.get(name, 0),
                                             *held.values()])
                return ckpt_hook(engine)
            return super().run(ckpt_hook=hook)

    monkeypatch.setattr(jax_engine, "BatchSimEngine", Watched)
    out = run(cell, 2**31 + 17, 0.01, False, t_start=time.perf_counter())
    assert out["correct"] is True
    assert out["checks"]["mismatches"] == {"value": 0, "limit": 0}
    assert max(v for k, v in most_images.items()
               if k.startswith("EBPSM")) >= 2


def test_member_order_changes_no_answer_and_no_work():
    """Two seeds put the grid's members in two orders: every member's
    answer and the engine's dispatch counters are the same."""
    cell = tiny(spec.resolve("grid-montage"))
    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    kernel = H.KernelCalls()
    try:
        got = []
        for seed in (1, 2):
            plan = H.member_plan(cell, streams, seed)
            ep = H.run_episode(cfg, cell, streams, plan, kernel)
            answer = H.program_answer(ep.results)
            got.append(({plan[m]: answer(m) for m in range(len(plan))},
                        ep.dispatch, sorted(ep.kernel_shapes)))
    finally:
        kernel.close()
    assert got[0] == got[1]


def test_metric_readers_read_nothing_from_an_empty_run():
    bench = spec.load_benchmark()
    empty = {"timed_s": 0.0, "profile": {}, "trace": None,
             "window_compiles": 0, "kernel_calls": 0,
             "dispatch": {"rounds": 0, "batched_calls": 0,
                          "batched_cycles": 0, "serial_cycles": 0}}
    for m in bench["per_layer"]:
        v = spec.metric_reader(m["name"])(empty)
        assert v is None or (m["name"] == "window_compiles" and v == 0)


def _episode(seconds, tasks, rounds):
    return H.Episode(seconds=seconds, tasks=tasks, rounds_s=rounds,
                     digest=(), results=[], dispatch={}, kernel_shapes=[])


def test_tasks_per_s_is_all_work_over_all_time():
    eps = [_episode(2.0, 1000, [0.001]), _episode(6.0, 1000, [0.001])]
    assert H.tasks_per_s(eps) == 2000 / 8.0   # not the mean of 500 and 166


def test_round_quantiles_pool_every_round_of_every_episode():
    a = [0.001] * 90
    b = [0.010] * 10
    q = H.round_quantiles_ms([_episode(1, 1, a), _episode(1, 1, b)])
    pooled = statistics.quantiles([r * 1e3 for r in a + b], n=100,
                                  method="inclusive")
    assert q["round_p50_ms"] == pytest.approx(1.0)
    assert q["round_p95_ms"] == pytest.approx(pooled[94])
    assert q["round_p95_ms"] == pytest.approx(10.0)


def test_trace_reduction_on_a_synthetic_trace():
    dev, host = tr.DEVICE_PLANE, "/host:CPU"
    events = [
        (host, "python", "bench.episode", 0.0, 1000.0),
        (host, "python", "bench.round", 100.0, 400.0),
        (host, "python", "bench.round", 500.0, 500.0),
        (dev, tr.DEVICE_LINE, "fusion.1", 100.0, 100.0),
        (dev, tr.DEVICE_LINE, "affinity_argmin", 150.0, 100.0),  # overlaps
        (dev, tr.DEVICE_LINE, "affinity_argmin", 800.0, 50.0),
        (dev, "XLA Modules", "jit_x", 100.0, 800.0),  # not an op line
    ]
    r = tr.reduce(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["kernel_s"] == pytest.approx(150e-9)
    assert r["kernel_calls"] == 2
    assert r["top_ops"][0] == ["affinity_argmin", pytest.approx(150e-9)]
    assert r["idle_gaps"][0] == ["bench.round", pytest.approx(550e-9)]
    assert r["idle_gaps"][1] == ["bench.round", pytest.approx(150e-9)]
    assert r["idle_gaps"][2] == ["bench.episode", pytest.approx(100e-9)]
    assert tr.reduce([e for e in events if e[0] != dev]) is None


def test_trace_reduction_on_the_recorded_chip_trace():
    """300 device ops and the host spans around them, recorded on a TPU v5
    lite in a ``grid-montage`` traced episode."""
    events = tr.load(RECORDED)
    r = tr.reduce(events)
    ops = tr.device_ops(events)
    assert r["device_ops"] == len(ops) == 300
    assert r["kernel_calls"] == 9
    assert r["kernel_s"] == pytest.approx(23.236e-6)
    assert r["window_s"] == pytest.approx(0.87844937)
    assert r["busy_s"] == pytest.approx(58.063e-6)
    assert r["busy_s"] <= sum(e[4] for e in ops) / 1e9
    assert r["top_ops"][0] == ["affinity_argmin", pytest.approx(23.236e-6)]
    assert r["idle_gaps"][0][0] == "bench.round"
    rec = {"trace": r, "least_kernel_s": 0.5 * r["kernel_s"],
           "traced_kernel_calls": r["kernel_calls"]}
    assert spec.metric_reader("affinity_roofline")(rec) == pytest.approx(50.)
    rec["traced_kernel_calls"] = 8   # shapes that do not pair with events
    assert spec.metric_reader("affinity_roofline")(rec) is None
    idle = spec.metric_reader("device_idle_share")(rec)
    assert idle == pytest.approx(100 * (1 - 58.063e-6 / 0.87844937))


def test_affinity_roofline_bytes_and_ops():
    assert kernel_cost.affinity_bytes(1, 1, 1) == 12 + 12 + 12 + 16
    assert kernel_cost.affinity_bytes(8, 1024, 1024) == (
        12 * 8 * 1024 * 1024 + 28 * 8 * 1024 + 12 * 8 * 1024)
    peak = spec.peaks("TPU v5 lite")
    t = kernel_cost.least_seconds([(8, 1024, 1024)], peak)
    assert t == pytest.approx(kernel_cost.affinity_bytes(8, 1024, 1024)
                              / 819e9)   # bound by bytes
    with pytest.raises(SystemExit):
        spec.peaks("TPU v9 imaginary")


def _bench_cmd(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grid-montage",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_backend():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    assert "not beside the benchmark" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["grid-montage", "platform-montage"])
def test_a_tiny_run_of_each_traffic_mix(name):
    from bench.run import run
    cell = tiny(spec.resolve(name))
    out = run(cell, 2**31 + 11, 0.01, False, t_start=time.perf_counter())
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["checks"]["mismatches"] == {"value": 0, "limit": 0}
    assert out["attempted"] > 0 and out["failed"] == 0
    json.dumps(out)
