"""The program's phase spans and kernel counters as the benchmark reads
them: the readers of the per-layer metrics that come from the engines'
profile blocks, and the idle time put down to the innermost span
(``bench/spans.py``), on a synthetic trace and a recorded chip trace."""
from __future__ import annotations

import copy
import time
from pathlib import Path

import pytest

from bench import generator as gen
from bench import harness as H
from bench import spans as sp
from bench import spec
from bench import trace as tr

RECORDED = Path(spec.BENCH_DIR) / "tests" / "data" / \
    "trace_platform-montage.json"
SPAN_SHARES = ("member_loop_share", "auction_build_share",
               "auction_stage_share", "auction_dispatch_share",
               "auction_pull_share", "auction_commit_share")
NEW_METRICS = SPAN_SHARES + ("kernel_pair_occupancy", "staged_mb_per_call")

DEV, HOST = tr.DEVICE_PLANE, "/host:CPU"
SYNTHETIC = [
    (HOST, "main", "bench.episode", 0.0, 1000.0),
    (HOST, "episode", "bench.round", 100.0, 400.0),
    (HOST, "episode", "bench.round", 500.0, 500.0),
    (HOST, "episode", "repro.round.members", 120.0, 180.0),
    (HOST, "episode", "repro.select", 150.0, 50.0),
    (HOST, "episode", "repro.auction.dispatch", 310.0, 90.0),
    (HOST, "episode", "repro.auction.pull", 400.0, 80.0),
    (HOST, "episode", "PjitFunction(affinity)", 310.0, 60.0),  # not a span
    (DEV, tr.DEVICE_LINE, "fusion.1", 160.0, 20.0),
    (DEV, tr.DEVICE_LINE, "affinity_argmin", 450.0, 20.0),
]


def test_idle_time_by_innermost_span_on_a_synthetic_trace():
    """Idle [0,160], [180,450], [470,1000] (ns); the pull opens as the
    dispatch closes."""
    by = sp.idle_by_span(SYNTHETIC)
    want = {"bench.round": 550, "repro.round.members": 130,
            "bench.episode": 100, "repro.auction.dispatch": 90,
            "repro.auction.pull": 60, "repro.select": 30}
    assert list(by) == list(want)             # descending
    assert by == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    r = sp.reduce(SYNTHETIC)
    assert r["idle_s"] == pytest.approx(960e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["idle_s"] + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["idle_attributed_share"] == pytest.approx(100 * 310 / 960)
    assert [g[0] for g in r["idle_gaps"]] == [
        "bench.round", "repro.auction.dispatch", "bench.episode"]
    # The benchmark's own reduction still sees only the bench.* spans.
    assert [g[0] for g in tr.reduce(SYNTHETIC)["idle_gaps"]] == [
        "bench.round", "bench.round", "bench.episode"]


def test_idle_outside_every_span_and_nothing_to_read():
    events = [(HOST, "main", "bench.episode", 0.0, 100.0),
              (HOST, "episode", "repro.select", 100.0, 50.0),
              (DEV, tr.DEVICE_LINE, "fusion.1", 40.0, 20.0)]
    assert sp.idle_by_span(events) == {
        "bench.episode": pytest.approx(80e-9)}
    assert sp.attributed_share({}) is None
    assert sp.reduce([e for e in events if e[0] != DEV]) is None
    assert sp.idle_by_span(events[1:]) is None      # no episode span


def test_the_recorded_platform_trace_puts_its_idle_time_on_the_program():
    """300 device ops of a traced ``platform-montage`` episode on a TPU v5
    lite from its first auction on, with the spans that overlap them."""
    events = tr.load(RECORDED)
    assert len(tr.device_ops(events)) == 300
    r = sp.reduce(events)
    assert r["window_s"] == pytest.approx(0.04473701)
    assert r["busy_s"] == pytest.approx(37.051e-6)
    assert r["idle_attributed_share"] == pytest.approx(98.715, abs=1e-3)
    assert r["idle_attributed_share"] >= 90.0
    assert {"repro.auction.dispatch", "repro.auction.pull",
            "repro.auction.stage"} <= set(r["idle_by_span"])
    assert all(g[0].startswith("repro.") for g in r["idle_gaps"])
    old = tr.reduce(events)
    assert old["busy_s"] == pytest.approx(r["busy_s"])
    assert old["window_s"] == pytest.approx(r["window_s"])


def test_trim_keeps_ops_from_the_first_auction_and_cuts_the_spans():
    events = SYNTHETIC + [(HOST, "episode", "repro.auction.build",
                           300.0, 5.0)]
    cut = sp.trim(events, 1)
    assert [e[2] for e in tr.device_ops(cut)] == ["affinity_argmin"]
    assert {e[2]: (e[3], e[4]) for e in sp.host_spans(cut)} == {
        "bench.episode": (300.0, 170.0), "bench.round": (300.0, 170.0),
        "repro.auction.build": (300.0, 5.0),
        "repro.auction.dispatch": (310.0, 90.0),
        "repro.auction.pull": (400.0, 70.0)}
    assert len(cut) == 6


def _record(profile, timed_s=2.0):
    return {"timed_s": timed_s, "profile": profile, "trace": None,
            "window_compiles": 0, "kernel_calls": 0, "dispatch": {}}


def test_readers_of_the_profile_block():
    prof = {"round.members_s": 0.5, "round.serial_s": 0.1,
            "round.apply_s": 0.2, "auction.build_s": 0.1,
            "auction.stage_s": 0.05, "auction.dispatch_s": 0.3,
            "auction.pull_s": 0.2, "auction.commit_s": 0.15,
            "auction.tail_s": 0.05, "kernel_calls": 4, "real_pairs": 300,
            "kernel_pairs": 1200, "staged_bytes": 8_000_000}
    got = {m: spec.metric_reader(m)(_record(prof)) for m in NEW_METRICS}
    assert got == {"member_loop_share": pytest.approx(40.0),
                   "auction_build_share": pytest.approx(5.0),
                   "auction_stage_share": pytest.approx(2.5),
                   "auction_dispatch_share": pytest.approx(15.0),
                   "auction_pull_share": pytest.approx(10.0),
                   "auction_commit_share": pytest.approx(10.0),
                   "kernel_pair_occupancy": pytest.approx(25.0),
                   "staged_mb_per_call": pytest.approx(2.0)}
    # A program without the spans and counters reads nothing.
    older = {"select_s": 0.1, "redistribute_s": 0.2}
    assert all(spec.metric_reader(m)(_record(older)) is None
               for m in NEW_METRICS)


def _tiny_grid():
    """The grid at the least size at which auctions ride the kernel."""
    cell = copy.deepcopy(spec.resolve("grid-montage"))
    cell.conf["workload"]["workflows_per_cell"] = 3
    cell.conf["workload"]["sizes"] = ["small"]
    return cell


def test_the_program_counts_the_calls_the_harness_sees():
    cell = _tiny_grid()
    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    kernel = H.KernelCalls()
    try:
        ep = H.run_episode(cfg, cell, streams, H.member_plan(cell, streams,
                                                             3),
                           kernel, profile=True)
    finally:
        kernel.close()
    prof = ep.dispatch["profile"]
    assert prof["kernel_calls"] == len(ep.kernel_shapes) > 0
    assert prof["kernel_pairs"] == sum(b * t * v
                                       for b, t, v in ep.kernel_shapes)
    assert 0 < prof["real_pairs"] <= prof["kernel_pairs"]


def test_a_tiny_traced_run_reports_the_span_shares():
    from bench.run import run
    out = run(_tiny_grid(), 2**31 + 17, 0.01, True,
              t_start=time.perf_counter())
    assert out["correct"] is True
    got = {m: out["metrics"][m]["value"] for m in NEW_METRICS}
    assert all(v > 0 for v in got.values())
    assert sum(got[m] for m in SPAN_SHARES) <= 100.0
