"""The benchmark's copied generators give the workloads the program's own
generators give today, and every seed simulates the same streams."""
from __future__ import annotations

import hashlib

import pytest

from bench import generator as gen
from bench import harness as H
from bench import spec
from repro.core.types import PlatformConfig
from repro.workflows.dax import APP_NAMES
from repro.workflows.workload import (WorkloadSpec, cell_workload,
                                      generate_workload)

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


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_paper_stream_of_the_five_applications_equals_generate_workload(
        seed):
    sizes = ("small", "medium")
    want = generate_workload(CFG, WorkloadSpec(
        n_workflows=10, arrival_rate_per_min=12.0, apps=APP_NAMES,
        sizes=sizes, seed=seed, budget_lo=0.25, budget_hi=0.5))
    got = gen.paper_stream(CFG, list(APP_NAMES), 12.0, (0.25, 0.5), seed,
                           10, sizes)
    assert gen.to_plain(got) == gen.to_plain(want)
    assert len({wf.app for wf in got}) >= 2


# sha256 of the streams each configuration simulates at its own traffic,
# taken before a configuration's ``app`` could list several applications.
STREAM_DIGESTS = {
    "grid-montage":
        "33cbf619e8b315e855eac6eefe4b365c0acd02485cc1cab6c9ef4e03f42196b7",
    "platform-montage":
        "8d0015071e46da6e15705ec5ab9004ae911e47831021379af90f08d270827ec3",
    "grid-montage-lowrate":
        "192e96f16a57077d05271801c7408b6d7f230fbb4ff9751cc99881a6b5d5441a",
}


@pytest.mark.parametrize("cell", sorted(STREAM_DIGESTS))
def test_each_configuration_simulates_the_streams_it_always_did(cell):
    c = spec.resolve(cell)
    streams = gen.streams(gen.platform_config(c.conf), c.conf["workload"],
                          c.traffic)
    plain = repr([(s.workload, s.degradation_seed) for s in streams])
    assert hashlib.sha256(plain.encode()).hexdigest() == STREAM_DIGESTS[cell]


@pytest.mark.parametrize("app", ["blast", ["montage", "Montage"], []])
def test_an_unknown_application_is_refused(app):
    with pytest.raises(ValueError, match="known applications"):
        gen.paper_stream(CFG, app, 12.0, (0.0, 0.25), 1, 2, ("small",))
    c = spec.resolve("grid-montage")
    with pytest.raises(ValueError, match="known applications"):
        gen.streams(CFG, dict(c.conf["workload"], app=app), c.traffic)
