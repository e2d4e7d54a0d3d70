"""The reader of ``sweep_chain_share``: the share of Algorithm 3's SFTD
sweeps that took the exact chain, from the members' profile blocks."""
from __future__ import annotations

import copy

import pytest

from bench import generator as gen
from bench import harness as H
from bench import spec

METRIC = "sweep_chain_share"
CELLS = ("grid-montage", "platform-montage", "grid-montage-lowrate")


def _record(profile):
    return {"timed_s": 2.0, "profile": profile, "trace": None,
            "window_compiles": 0, "kernel_calls": 0, "dispatch": {}}


@pytest.mark.parametrize("profile,want", [
    ({"sweeps_chain": 45, "sweeps_loop": 5}, 90.0),
    ({"sweeps_chain": 7, "sweeps_loop": 0}, 100.0),
    ({"sweeps_chain": 0, "sweeps_loop": 3}, 0.0),
    # A program that does not count its sweeps, or swept nothing.
    ({"redistributions": 12.0}, None),
    ({"sweeps_chain": 0, "sweeps_loop": 0}, None),
])
def test_the_reader_reads_its_counters(profile, want):
    got = spec.metric_reader(METRIC)(_record(profile))
    assert got == (None if want is None else pytest.approx(want))


def test_the_metric_is_composed_into_all_three_cells():
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    assert entry["moves"] == "tasks_per_s"
    assert tuple(entry["workloads"]) == CELLS
    for name in CELLS:
        cell = spec.resolve(name, bench)
        assert METRIC in {m["name"] for m in cell.per_layer}
        assert "tasks_per_s" in {m["name"] for m in cell.end_to_end}


def test_a_small_grid_counts_its_sweeps():
    """One 100-task Montage workflow per stream: Algorithm 3's calls over
    more than 64 rows sweep, and the monotone tables take the chain."""
    cell = copy.deepcopy(spec.resolve("grid-montage"))
    cell.conf["workload"]["workflows_per_cell"] = 1
    cell.conf["workload"]["sizes"] = ["medium"]
    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    kernel = H.KernelCalls()
    try:
        ep = H.run_episode(cfg, cell, streams,
                           H.member_plan(cell, streams, 2**31 + 7), kernel,
                           profile=True)
    finally:
        kernel.close()
    prof = ep.dispatch["profile"]
    swept = prof["sweeps_chain"] + prof["sweeps_loop"]
    assert 0 < swept <= prof["redistributions"]
    assert spec.metric_reader(METRIC)(_record(prof)) > 90.0
