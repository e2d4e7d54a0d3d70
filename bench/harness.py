"""One run of one cell: set-up, warm-up, the measured window of whole
episodes, the per-layer record, and the check against the reference.

An episode is one complete simulation of the cell's streams through the
program's normal path: ``predistribute_workload`` once per budget mode per
stream, ``clone_workload`` per member (as ``exp.run._grid_batch`` builds
members), then ``BatchSimEngine(...).run()`` — one engine for all members
(a grid), or one per stream when the traffic's ``engine_per_stream`` says
so (independent live platforms, run one after another).  Its timed part
runs from the first ``predistribute_workload`` to the return of the last
``run()``; the fresh
workflow objects it starts from are built from plain data before the clock
starts, so no cost table or other memo carries over between episodes.

Rounds are timed through ``run(ckpt_hook=...)``, which the engine calls at
the top of every rendezvous round: a round runs from one call to the next,
and the last round of an episode ends when ``run()`` returns.
"""
from __future__ import annotations

import dataclasses
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import generator as gen
from . import reference
from .spec import Cell


class CompileClock:
    """Counts, while :attr:`on`, XLA compile requests (JAX's
    backend-compile events, which also wrap a persistent-cache load) and
    how many of them the persistent cache served."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.on = False
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if self.on and event == self.BACKEND:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if self.on and event == self.CACHE_HIT:
            self.cache_hits += 1


class KernelCalls:
    """Wraps ``repro.kernels.affinity.ops.affinity_batch`` (which
    ``core.jax_cycles.multi_cycle`` calls through the module) and records
    the ``(B, T, V)`` shape each call is handed, and the shapes of the
    calls during which ``clock`` saw a backend compile."""

    def __init__(self, clock: Optional[CompileClock] = None):
        from repro.kernels.affinity import ops
        self.ops = ops
        self.orig = ops.affinity_batch
        self.shapes: List[tuple] = []
        self.compiled: List[tuple] = []

        def wrapper(*args, **kw):
            shape = tuple(args[3].shape)
            self.shapes.append(shape)
            before = clock.compiles if clock is not None else 0
            out = self.orig(*args, **kw)
            if clock is not None and clock.compiles > before:
                self.compiled.append(shape)
            return out

        ops.affinity_batch = wrapper

    def close(self) -> None:
        self.ops.affinity_batch = self.orig


@dataclasses.dataclass
class Episode:
    seconds: float
    tasks: int
    rounds_s: List[float]
    digest: tuple
    results: list
    dispatch: dict
    kernel_shapes: List[tuple]


def policies_by_name() -> Dict[str, object]:
    from repro.core.scheduler import ALL_POLICIES
    return {p.name: p for p in ALL_POLICIES}


def member_plan(cell: Cell, streams: Sequence[gen.Stream], seed: int):
    """(stream index, policy name) per member: ``_grid_batch``'s members,
    in an order drawn from ``seed``.  Members are independent simulations,
    so the order changes where each sits in the batch, not the work."""
    plan = [(i, p) for i in range(len(streams))
            for p in cell.traffic["policies"]]
    order = np.random.default_rng([seed, 1]).permutation(len(plan))
    return [plan[k] for k in order]


def digest(results) -> tuple:
    return tuple(
        None if r is None else
        (tuple(sorted((w.wid, w.finish_ms, w.cost) for w in r.workflows)),
         tuple(sorted(r.vm_count_by_type.items())))
        for r in results)


def run_episode(cfg, cell: Cell, streams: Sequence[gen.Stream],
                plan: Sequence[tuple], kernel: KernelCalls,
                profile: bool = False,
                on_round: Optional[Callable[[], None]] = None) -> Episode:
    """One episode of the members ``plan`` lists, in a new thread."""
    out: list = []

    def target():
        try:
            out.append(_episode(cfg, cell, streams, plan, kernel, profile,
                                on_round))
        except BaseException as e:  # re-raised in the caller's thread
            out.append(e)

    t = threading.Thread(target=target, name="bench-episode")
    t.start()
    t.join()
    if isinstance(out[0], BaseException):
        raise out[0]
    return out[0]


def _episode(cfg, cell, streams, plan, kernel, profile, on_round) -> Episode:
    from repro.core.jax_engine import BatchSimEngine, predistribute_workload
    from repro.core.types import clone_workload

    pols = policies_by_name()
    fresh = [gen.from_plain(s.workload) for s in streams]
    if cell.traffic["engine_per_stream"]:
        firsts = list(dict.fromkeys(i for i, _ in plan))
        groups = [[m for m, (i, _) in enumerate(plan) if i == s]
                  for s in firsts]
    else:
        groups = [list(range(len(plan)))]
    stamps: List[List[float]] = []

    def hook(_engine) -> bool:
        stamps[-1].append(time.perf_counter())
        if on_round is not None:
            on_round()
        return False

    n_shapes = len(kernel.shapes)
    results: list = [None] * len(plan)
    engines = []
    t0 = time.perf_counter()
    for group in groups:
        members, pre = [], []
        protos: Dict[tuple, tuple] = {}
        for m in group:
            i, name = plan[m]
            pol = pols[name]
            key = (i, pol.budget_mode)
            if key not in protos:
                protos[key] = predistribute_workload(cfg, fresh[i],
                                                     pol.budget_mode)
            proto, spares = protos[key]
            members.append((pol, clone_workload(proto),
                            streams[i].degradation_seed))
            pre.append(spares)
        engine = BatchSimEngine(
            cfg, members, use_pallas="auto", batched="auto",
            predistributed=pre,
            redistribute=cell.conf["guarantees"]["budget_redistribution"],
            profile=profile, events=False, monitor=False)
        stamps.append([])
        for m, r in zip(group, engine.run(ckpt_hook=hook)):
            results[m] = r
        stamps[-1].append(time.perf_counter())
        engines.append(engine)
    t1 = time.perf_counter()
    return Episode(
        seconds=t1 - t0,
        tasks=sum(streams[i].n_tasks for i, _ in plan),
        rounds_s=[r for s in stamps for r in np.diff(s).tolist()],
        digest=digest(results),
        results=results,
        dispatch=_merged_dispatch(engines),
        kernel_shapes=kernel.shapes[n_shapes:],
    )


COUNTERS = ("rounds", "batched_calls", "batched_cycles", "serial_cycles")


def _merged_dispatch(engines) -> dict:
    """The engines' ``dispatch_stats()`` counters summed, with their
    ``REPRO_PROFILE`` seconds summed under ``profile`` when on."""
    stats = [e.dispatch_stats() for e in engines]
    out = {k: sum(s[k] for s in stats) for k in COUNTERS}
    profs = [s["profile"] for s in stats if "profile" in s]
    if profs:
        out["profile"] = {k: sum(p[k] for p in profs) for k in profs[0]}
    return out


def tasks_per_s(episodes: Sequence[Episode]) -> float:
    """Every task of every member of every timed episode over the summed
    timed seconds."""
    return sum(e.tasks for e in episodes) / sum(e.seconds for e in episodes)


def round_quantiles_ms(episodes: Sequence[Episode]) -> Dict[str, float]:
    """p50 and p95 over every round of every timed episode (the
    ``statistics.quantiles`` inclusive method)."""
    rounds = [r * 1e3 for e in episodes for r in e.rounds_s]
    q = statistics.quantiles(rounds, n=100, method="inclusive")
    return {"round_p50_ms": q[49], "round_p95_ms": q[94]}


def program_answer(results) -> Callable[[int], tuple]:
    """Member ``m``'s answer from the program's ``SimResult`` list: each
    workflow's (finish ms, cost), and per VM type (VMs leased, busy ms,
    leased ms) — the program sums seconds per VM, each an exact
    ``ms / 1000``, so rounding back to ms is exact."""
    def answer(m: int) -> tuple:
        if m >= len(results) or results[m] is None:
            return {}, {}   # an answer that never came
        r = results[m]
        fleet = {name: (n, round(r.vm_busy_seconds_by_type[name] * 1000),
                        round(r.vm_seconds_by_type[name] * 1000))
                 for name, n in r.vm_count_by_type.items()}
        return {w.wid: (w.finish_ms, w.cost) for w in r.workflows}, fleet
    return answer


def check(cell: Cell, streams: Sequence[gen.Stream], plan: Sequence[tuple],
          answer: Callable[[int], tuple], seed: int) -> Dict[str, int]:
    """The reference over a sample of ``plan``'s members drawn from
    ``seed``: the member-workflows whose finish time or cost differ or are
    missing, and the VM types whose count, busy time or leased time
    differ, in ``answer(m)``."""
    k = min(cell.traffic["check_members"], len(plan))
    pick = sorted(np.random.default_rng([seed, 2]).choice(
        len(plan), k, replace=False).tolist())
    bad = compared = 0
    for m in pick:
        i, name = plan[m]
        want, fleet = reference.simulate(
            cell.conf, name, streams[i].workload,
            streams[i].degradation_seed)
        got, got_fleet = answer(m)
        compared += len(want)
        bad += sum(got.get(wid) != v for wid, v in want.items())
        bad += len(set(got) - set(want))
        bad += sum(got_fleet.get(k) != v for k, v in fleet.items())
    return {"mismatches": bad, "compared": compared, "members": k}
