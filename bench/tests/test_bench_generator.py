"""The benchmark's copied generators give the workloads the program's own
generators give today, and every seed simulates the same streams."""
from __future__ import annotations

import pytest

from bench import generator as gen
from bench import harness as H
from bench import spec
from repro.core.types import PlatformConfig
from repro.workflows.workload import cell_workload

CFG = PlatformConfig()


def test_config_files_pin_the_program_defaults():
    for name in ("paper-montage", "paper-montage-live"):
        assert gen.platform_config(spec.load_config(name)) == CFG


@pytest.mark.parametrize("app", ["montage", "cybershake", "epigenome",
                                 "ligo", "sipht"])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_paper_stream_equals_cell_workload(app, seed):
    sizes = ("small", "medium", "large") if app == "montage" \
        else ("small",)
    want = cell_workload(CFG, app, 12.0, (0.25, 0.5), seed, 4, sizes)
    got = gen.paper_stream(CFG, app, 12.0, (0.25, 0.5), seed, 4, sizes)
    assert gen.to_plain(got) == gen.to_plain(want)


@pytest.mark.parametrize("cell", ["grid-montage", "platform-montage"])
def test_every_seed_simulates_the_same_streams(cell):
    """The traffic fixes the workflows and the degradation; ``--seed``
    only orders the members."""
    c = spec.resolve(cell)
    n = c.traffic["streams_per_group"]
    a = gen.streams(CFG, c.conf["workload"], c.traffic)
    b = gen.streams(CFG, c.conf["workload"], c.traffic)
    assert a == b
    assert [s.degradation_seed for s in a] == [
        c.traffic["degradation_seed"] + d for d in range(n)] * (len(a) // n)
    assert len({s.workload for s in a}) == len(a)
    plans = [H.member_plan(c, a, seed) for seed in (3, 4, 2**33 + 1)]
    assert all(sorted(p) == sorted(plans[0]) for p in plans)
    assert len(plans[0]) == len(a) * len(c.traffic["policies"])


def test_the_seed_orders_the_grid_members():
    c = spec.resolve("grid-montage")
    a = gen.streams(CFG, c.conf["workload"], c.traffic)
    assert len({tuple(H.member_plan(c, a, seed))
                for seed in (3, 4, 2**33 + 1)}) == 3


def test_plain_round_trip_drops_memos():
    wfs = gen.paper_stream(CFG, "montage", 12.0, (0.0, 0.25), 3, 2,
                           ("small",))
    assert any(w.cost_cache is not None for w in wfs)  # budgets built it
    fresh = gen.from_plain(gen.to_plain(wfs))
    assert gen.to_plain(fresh) == gen.to_plain(wfs)
    assert all(w.cost_cache is None and w.rank_cache is None for w in fresh)
    assert all(t.inputs_cache is None for w in fresh for t in w.tasks)
