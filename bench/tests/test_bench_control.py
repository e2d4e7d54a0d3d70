"""``correct`` comes out false for the control and for a broken timed path,
at a size a test run can hold (the chip readings are in PERF.md)."""
from __future__ import annotations

import time

import pytest

from bench import harness as H
from bench import spec
from bench.control import readings
from bench.tests.test_bench_harness import tiny
from repro.core import engine as engine_mod
from repro.core import jax_engine


@pytest.mark.parametrize("name", ["grid-montage", "platform-montage"])
def test_the_float32_control_fails_where_the_program_passes(name):
    """At a size where float32 flips a 1 ms rounding on these seeds: three
    medium workflows per stream."""
    cell = spec.resolve(name)
    w = cell.conf["workload"]
    w["workflows_per_cell"], w["sizes"] = 3, ["medium"]
    got = list(readings(cell, [5], [5, 6], require_tpu=False))
    assert [r["mismatches"] for r in got if r["kind"] == "program"] == [0]
    assert all(r["mismatches"] > 0 for r in got if r["kind"] == "control")


def _run(cell):
    from bench.run import run
    return run(cell, 77, 0.01, False, t_start=time.perf_counter())


@pytest.mark.parametrize("name", ["grid-montage", "platform-montage"])
def test_an_answer_altered_where_it_is_produced(name, monkeypatch):
    finalize = engine_mod.SimState.finalize

    def altered(self, wall_s=0.0):
        res = finalize(self, wall_s)
        res.workflows[0].finish_ms += 1
        return res

    monkeypatch.setattr(engine_mod.SimState, "finalize", altered)
    out = _run(tiny(spec.resolve(name)))
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0


@pytest.mark.parametrize("name", ["grid-montage", "platform-montage"])
def test_a_step_that_returns_its_state_unchanged(name, monkeypatch):
    """A dispatched task whose pipeline never starts: the step leaves the
    member's state as it was, and its workflow never finishes."""
    start = engine_mod.SimState._start_pipeline

    def unchanged(self, wid, tid, vm, triggered_provision):
        if (wid, tid) == (0, 0):
            return
        start(self, wid, tid, vm, triggered_provision)

    monkeypatch.setattr(engine_mod.SimState, "_start_pipeline", unchanged)
    out = _run(tiny(spec.resolve(name)))
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0


def test_half_of_the_batch_left_out(monkeypatch):
    """The grid engine simulates only the first half of its members; the
    others' answers never come.  (Each ``platform-montage`` engine has one
    member.)"""
    init = jax_engine.BatchSimEngine.__init__

    def half(self, cfg, members, *a, predistributed=None, **kw):
        n = len(members) // 2
        init(self, cfg, members[:n], *a,
             predistributed=predistributed[:n], **kw)

    monkeypatch.setattr(jax_engine.BatchSimEngine, "__init__", half)
    cell = tiny(spec.resolve("grid-montage"))
    out = _run(cell)
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0


def test_program_answer_of_a_missing_member_is_empty():
    answer = H.program_answer([])
    assert answer(3) == ({}, {})
