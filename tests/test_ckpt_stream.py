"""Stream checkpoints: versioned snapshots, disk round-trips, resume.

The contract under test (PR 7):

* ``SimState.snapshot`` / ``load_snapshot`` — a cut of one simulation;
  an older v2 cut that banked round-mode Algorithm-3 surplus is refused;
* ``BatchSimEngine.snapshot`` / ``load_snapshot`` — the whole grid at a
  rendezvous-round boundary; a fresh engine restored from the cut and
  run to completion is bit-exact with the uninterrupted run, wherever
  the cut lands;
* ``repro.ckpt`` ``save_stream`` / ``restore_stream`` — the atomic
  on-disk form (named ``.npy`` arrays + residue blob + manifest), which
  refuses params checkpoints and newer schema versions;
* ``repro.exp.run.run_online`` — the CLI-level resume: an interrupted
  ``--ckpt-every-s`` stream resumed from disk reassembles the identical
  artifact rows and dispatch stats.
"""
import dataclasses

import numpy as np
import pytest

from repro import ckpt
from repro.core.engine import STREAM_SNAPSHOT_VERSION, SimEngine
from repro.core.jax_engine import BatchSimEngine, StreamInterrupted
from repro.core.scheduler import EBPSM, EBPSM_NS, MSLBL_MW
from repro.core.types import PlatformConfig
from repro.exp.run import run_online
from repro.exp.scenarios import ONLINE_SCENARIOS
from repro.workflows.workload import WorkloadSpec, generate_workload

CFG = PlatformConfig()


def workload(seed, n=6, rate=12.0, budget_lo=0.5, budget_hi=1.0):
    spec = WorkloadSpec(n_workflows=n, arrival_rate_per_min=rate, seed=seed,
                        sizes=("small",), budget_lo=budget_lo,
                        budget_hi=budget_hi)
    return generate_workload(CFG, spec)


def _members(seeds=(0, 1, 2)):
    pols = (EBPSM, EBPSM_NS, MSLBL_MW)
    return [(pols[i % len(pols)], workload(100 + i, n=5), s)
            for i, s in enumerate(seeds)]


def _signatures(results):
    return [
        ([(w.wid, w.finish_ms, w.cost) for w in res.workflows],
         res.vm_count_by_type, res.vm_seconds_by_type)
        for res in results
    ]


# ---------------------------------------------------------------------------
# Disk format
# ---------------------------------------------------------------------------


# The rounds of a full run of the ``_members()`` grid.
_GRID_ROUNDS = []


def _grid_rounds() -> int:
    if not _GRID_ROUNDS:
        eng = BatchSimEngine(CFG, _members())
        eng.run()
        _GRID_ROUNDS.append(eng.rounds)
    return _GRID_ROUNDS[0]


@pytest.mark.parametrize("cut_round", [1, 3, "late"])
def test_save_restore_stream_roundtrip(tmp_path, cut_round):
    """A grid cut at a rendezvous round round-trips through
    ``save_stream`` / ``restore_stream``: every named array with its
    dtype and values, the residue bytes, the member count, the version
    and the meta."""
    if cut_round == "late":
        cut_round = _grid_rounds() - 1
        assert cut_round > 3
    snap = _interrupt_at(BatchSimEngine(CFG, _members()), cut_round)
    meta = {"scenario": "x", "rows": [{"a": 0.125}]}
    ckpt.save_stream(str(tmp_path), 4, snap, meta=meta)
    assert ckpt.latest_step(str(tmp_path)) == 4
    back, step, meta2 = ckpt.restore_stream(str(tmp_path))
    assert step == 4 and meta2 == meta
    assert back["residue"] == snap["residue"]
    assert back["n_members"] == snap["n_members"] == 3
    assert back["version"] == snap["version"]
    assert set(back["arrays"]) == set(snap["arrays"])
    for name, arr in snap["arrays"].items():
        got = back["arrays"][name]
        assert got.dtype == arr.dtype and np.array_equal(got, arr), name


def test_restore_stream_refuses_params_dir(tmp_path):
    ckpt.save(str(tmp_path), 1, {"w": np.ones(3)})
    with pytest.raises(ValueError, match="params"):
        ckpt.restore_stream(str(tmp_path))


def test_restore_stream_refuses_newer_schema(tmp_path):
    snap = {"arrays": {"a": np.zeros(1)}, "residue": b"",
            "version": ckpt.STREAM_SCHEMA_VERSION + 1}
    ckpt.save_stream(str(tmp_path), 1, snap)
    with pytest.raises(ValueError, match="newer"):
        ckpt.restore_stream(str(tmp_path))


# ---------------------------------------------------------------------------
# Engine-level cuts
# ---------------------------------------------------------------------------


def _interrupt_at(engine, round_n):
    """Run until round ``round_n``, return the snapshot taken there."""
    cut = {}

    def hook(eng):
        if eng.rounds >= round_n:
            cut["snap"] = eng.snapshot()
            return True
        return False

    with pytest.raises(StreamInterrupted):
        engine.run(ckpt_hook=hook)
    return cut["snap"]


@pytest.mark.parametrize("cut_round", [0, 2, 6])
def test_interrupt_resume_bit_exact(cut_round):
    """A grid cut at any rendezvous round and resumed in a fresh engine
    finishes bit-exact with the uninterrupted run — including trace rows
    and fleet (vm-seconds) stats."""
    ref = BatchSimEngine(CFG, _members(), trace=True)
    want = _signatures(ref.run())

    eng = BatchSimEngine(CFG, _members(), trace=True)
    snap = _interrupt_at(eng, cut_round)

    eng2 = BatchSimEngine(CFG, _members(), trace=True)
    eng2.load_snapshot(snap)
    got = _signatures(eng2.run())
    assert got == want
    assert [st.trace_rows for st in eng2.states] == \
        [st.trace_rows for st in ref.states]


def test_interrupt_resume_through_disk(tmp_path):
    """Same cut, but the snapshot round-trips through save_stream /
    restore_stream — the exact path ``repro.exp.run --resume`` takes."""
    ref = BatchSimEngine(CFG, _members())
    want = _signatures(ref.run())

    eng = BatchSimEngine(CFG, _members())
    snap = _interrupt_at(eng, 3)
    ckpt.save_stream(str(tmp_path), 0, snap, meta={"seed_index": 0})
    back, _, meta = ckpt.restore_stream(str(tmp_path))
    assert meta == {"seed_index": 0}

    eng2 = BatchSimEngine(CFG, _members())
    eng2.load_snapshot(back)
    assert _signatures(eng2.run()) == want


def test_load_snapshot_rejects_member_count_mismatch():
    eng = BatchSimEngine(CFG, _members((0, 1, 2)))
    snap = _interrupt_at(eng, 1)
    other = BatchSimEngine(CFG, _members((0, 1)))
    with pytest.raises(ValueError, match="members"):
        other.load_snapshot(snap)


def test_simstate_snapshot_version_gate():
    st = SimEngine(CFG, EBPSM, workload(7, n=3), seed=0)
    snap = st.snapshot()
    assert snap["version"] == STREAM_SNAPSHOT_VERSION
    snap["version"] = 99
    fresh = SimEngine(CFG, EBPSM, workload(7, n=3), seed=0)
    with pytest.raises(ValueError):
        fresh.load_snapshot(snap)


def test_v2_snapshot_with_banked_round_surplus_refused():
    """An older v2 cut carries the round-mode columns ``pending_surplus``
    and ``pending_events``.  With none banked it restores (the columns
    are ignored) and finishes bit-exact; with a finish banked it came from
    the retired round mode and is refused."""
    eng = BatchSimEngine(CFG, _members())
    want = _signatures(BatchSimEngine(CFG, _members()).run())
    snap = _interrupt_at(eng, 3)
    for i in range(3):
        n_wf = len(snap["arrays"][f"m{i:04d}.spare"])
        snap["arrays"][f"m{i:04d}.pending_surplus"] = np.zeros(n_wf)
        snap["arrays"][f"m{i:04d}.pending_events"] = np.zeros(n_wf, np.int64)
    eng2 = BatchSimEngine(CFG, _members())
    eng2.load_snapshot(snap)
    assert _signatures(eng2.run()) == want
    snap["arrays"]["m0001.pending_events"][0] = 2
    snap["arrays"]["m0001.pending_surplus"][0] = 0.5
    with pytest.raises(ValueError, match="round-mode"):
        BatchSimEngine(CFG, _members()).load_snapshot(snap)


# ---------------------------------------------------------------------------
# Harness-level resume (run_online)
# ---------------------------------------------------------------------------


def _tiny_online():
    base = ONLINE_SCENARIOS["online-smoke"]
    return dataclasses.replace(base, name="online-smoke",
                               policies=("EBPSM", "MSLBL_MW"))


def test_run_online_resume_row_identical(tmp_path):
    """Interrupted-then-resumed run_online reassembles the identical
    artifact: same cell rows, same dispatch stats."""
    scen = _tiny_online()
    want = run_online(scen)

    with pytest.raises(StreamInterrupted):
        run_online(scen, ckpt_dir=str(tmp_path), ckpt_every_s=0.0,
                   stop_after_ckpts=2)
    got = run_online(scen, ckpt_dir=str(tmp_path), resume=True)
    assert got["cells"] == want["cells"]
    assert got["dispatch"] == want["dispatch"]


def test_run_online_resume_rejects_wrong_scenario(tmp_path):
    scen = _tiny_online()
    with pytest.raises(StreamInterrupted):
        run_online(scen, ckpt_dir=str(tmp_path), ckpt_every_s=0.0,
                   stop_after_ckpts=1)
    other = dataclasses.replace(scen, name="not-the-same")
    with pytest.raises(SystemExit, match="scenario"):
        run_online(other, ckpt_dir=str(tmp_path), resume=True)
