"""The ``grid-montage-lowrate`` cell and the readers of the dispatcher's
round counters.  The cell's configuration is ``paper-montage`` at the
paper's lowest rate, so its streams are the grid's draw for draw; each
reader on a hand-built record, on nothing, and on a real episode."""
from __future__ import annotations

import json

import pytest

import repro.core.jax_engine as je
from bench import generator as gen
from bench import harness as H
from bench import spec
from bench.tests.test_bench_harness import tiny

NAMES = ("parked_pair_share", "serial_us_per_pair", "auction_us_per_pair")


def _conf(name):
    return json.loads((spec.ROOT / "bench" / "configs" /
                       f"{name}.json").read_text())


def test_the_lowrate_config_is_the_grid_config_at_the_lowest_rate():
    """Every platform field, guarantee and size as in ``paper-montage``:
    only the rate and the words that name it differ."""
    low, grid = _conf("paper-montage-lowrate"), _conf("paper-montage")
    assert low["workload"]["rate_per_min"] == 0.5
    assert grid["workload"]["rate_per_min"] == 12.0
    words = ("name", "source", "assumed")
    for c in (low, grid):
        c["workload"].pop("rate_per_min")
        c["reduced"]["workflows_per_cell"].pop("why")
        for k in words:
            c.pop(k)
    assert low == grid
    entry = {c["name"]: c for c in spec.load_benchmark()["configs"]}
    assert entry["paper-montage-lowrate"]["reduced"] == ["workflows_per_cell"]


def test_the_lowrate_streams_are_the_grid_streams_spread_out():
    """The same workflows, tasks and budgets; each arrival 24 times later,
    within the integer-ms rounding."""
    low, grid = (spec.resolve(c) for c in ("grid-montage-lowrate",
                                          "grid-montage"))
    assert low.traffic == grid.traffic
    got = [gen.streams(gen.platform_config(c.conf), c.conf["workload"],
                       c.traffic) for c in (low, grid)]
    assert len(got[0]) == len(got[1]) == 8
    for s_low, s_grid in zip(*got):
        assert s_low.degradation_seed == s_grid.degradation_seed
        for a, b in zip(s_low.workload, s_grid.workload, strict=True):
            assert (a[0], a[1], a[3], a[4]) == (b[0], b[1], b[3], b[4])
            assert abs(a[2] - 24 * b[2]) < 24
        assert s_low.workload[-1][2] > 20 * s_grid.workload[-1][2] > 0


def _record(**profile):
    return {"timed_s": 10.0, "profile": profile, "trace": None,
            "window_compiles": 0, "kernel_calls": 0,
            "dispatch": {"rounds": 0, "batched_calls": 0,
                         "batched_cycles": 0, "serial_cycles": 0}}


AUCTION = {"auction.build_s": 0.5, "auction.stage_s": 0.25,
           "auction.dispatch_s": 1.0, "auction.pull_s": 0.5,
           "auction.commit_s": 0.5, "auction.tail_s": 0.25,
           "round.apply_s": 1.0}


@pytest.mark.parametrize("name,profile,want", [
    ("parked_pair_share", {"parked_pairs": 400, "ridden_pairs": 600},
     40.0),
    ("serial_us_per_pair", {"round.serial_s": 2.5, "zero_pair_s": 0.5,
                            "parked_pairs": 400_000}, 5.0),
    ("auction_us_per_pair", {**AUCTION, "ridden_pairs": 1_000_000}, 4.0),
])
def test_each_round_reader_reads_its_record(name, profile, want):
    read = spec.metric_reader(name)
    assert read(_record(**profile)) == pytest.approx(want)
    assert read(_record()) is None


@pytest.mark.parametrize("name,profile", [
    ("parked_pair_share", {"parked_pairs": 0, "ridden_pairs": 0}),
    ("serial_us_per_pair", {"round.serial_s": 2.5, "zero_pair_s": 2.5,
                            "parked_pairs": 0}),
    ("auction_us_per_pair", {**AUCTION, "ridden_pairs": 0}),
])
def test_a_round_reader_reads_nothing_without_its_pairs(name, profile):
    assert spec.metric_reader(name)(_record(**profile)) is None


def test_the_round_readers_are_in_the_cells_they_name():
    """Each round metric is composed into the cells its entry lists, and
    each of those cells reports the metric it moves."""
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert entries[name]["moves"] == "tasks_per_s"
        for c in entries[name]["workloads"]:
            cell = spec.resolve(c, bench)
            assert name in {m["name"] for m in cell.per_layer}
            assert "tasks_per_s" in {m["name"] for m in cell.end_to_end}


def test_the_round_readers_read_a_real_episode(monkeypatch):
    """A small grid episode's record, built as a traced run builds it,
    gives every round metric, with rounds on both sides of the
    threshold."""
    from bench.run import _record as bench_record

    threshold = 64
    monkeypatch.setattr(je, "AUCTION_MIN_PAIRS_ROUND", threshold)
    cell = tiny(spec.resolve("grid-montage"))
    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    plan = H.member_plan(cell, streams, 2**31 + 29)
    kernel = H.KernelCalls()
    try:
        ep = H.run_episode(cfg, cell, streams, plan, kernel, profile=True)
    finally:
        kernel.close()
    rec = bench_record([ep], H.CompileClock(), None, None, None)
    got = {n: spec.metric_reader(n)(rec) for n in NAMES}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["parked_pair_share"] < 100.0
    assert ep.dispatch["batched_calls"] > 0
