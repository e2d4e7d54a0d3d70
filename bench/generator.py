"""The benchmark's one workload generator, driven by a configuration's
``workload`` block and a traffic file.

The DAG generators and the Poisson workload stream of the paper's §5 are
copies of the program's (``repro.workflows.dax``,
``repro.workflows.workload``), kept here so that a change to the program
cannot move the yardstick.  They build the program's ``Workflow``/``Task``
types and call its ``min_max_workflow_cost``, which belongs to the
program's semantics.  They reproduce ``cell_workload`` and
``generate_workload`` draw for draw
(``bench/tests/test_bench_generator.py``).

A configuration's ``workload.app`` names one application, or lists
several: each workflow of a stream then draws its application from the
list, in the order given, as ``generate_workload`` draws from
``WorkloadSpec.apps``.  One name draws exactly as a one-element list.

A cell's streams are fixed by its traffic file: the workflows come from
its ``workload_seed``, the per-task CPU and bandwidth degradation from its
``degradation_seed``.  So every run simulates the same work, and
``--seed`` only sets the order of the members (``harness.member_plan``)
and the sample the reference checks.  When the seed drew the workflows, a
platform episode's kernel calls ranged from 192 to 431 across seeds and
its time on a TPU v5 lite host by 40 %, while two runs of one seed agreed
within 2 %; with the degradation alone drawn from the seed, one Montage
platform's kernel calls still ranged by 10 %.

A workload leaves here as plain data (:func:`to_plain`): every number the
program and the reference need, and nothing either of them computed.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple, Union

import numpy as np

from repro.core import budget as budget_mod
from repro.core.types import MS, PlatformConfig, Task, VMType, Workflow

SIZE_CLASSES = {"small": 50, "medium": 100, "large": 1000}

# Plain task: (size_mi, out_mb, ext_in_mb, parents, children, shared_in).
PlainTask = Tuple[float, float, float, Tuple[int, ...], Tuple[int, ...],
                  Tuple[Tuple[str, float], ...]]
# Plain workflow: (wid, app, arrival_ms, budget, tasks).
PlainWorkflow = Tuple[int, str, int, float, Tuple[PlainTask, ...]]


# ---------------------------------------------------------------------------
# Platform configuration
# ---------------------------------------------------------------------------


def platform_config(conf: dict) -> PlatformConfig:
    """The program's ``PlatformConfig`` with every field pinned by the
    configuration file."""
    p = dict(conf["platform"])
    p["vm_types"] = tuple(VMType(**v) for v in p["vm_types"])
    return PlatformConfig(**p)


# ---------------------------------------------------------------------------
# DAG generators (copied from repro.workflows.dax)
# ---------------------------------------------------------------------------


def _mk(rng, sizes_mi, out_mb):
    s = max(rng.normal(sizes_mi[0], sizes_mi[1]), sizes_mi[0] * 0.1)
    d = max(rng.normal(out_mb[0], out_mb[1]), out_mb[0] * 0.1)
    return float(s), float(d)


def _build(wid, app, spec, edges) -> Workflow:
    tasks = [Task(tid=i, size_mi=s, out_mb=o, ext_in_mb=e)
             for i, (s, o, e) in enumerate(spec)]
    for u, v in edges:
        tasks[u].children.append(v)
        tasks[v].parents.append(u)
    wf = Workflow(wid=wid, app=app, tasks=tasks)
    wf.validate()
    return wf


def montage(wid, n, rng) -> Workflow:
    k = max(3, (n - 5) // 3)
    spec, edges, proj = [], [], []
    for _ in range(k):
        s, o = _mk(rng, (20, 5), (40, 10))
        proj.append(len(spec))
        spec.append((s, o, 30.0))
    diff = []
    for i in range(k):
        s, o = _mk(rng, (10, 3), (2, 0.5))
        d = len(spec)
        diff.append(d)
        spec.append((s, o, 0.0))
        edges.append((proj[i], d))
        edges.append((proj[(i + 1) % k], d))
    s, o = _mk(rng, (15, 4), (1, 0.2))
    concat = len(spec)
    spec.append((s, o, 0.0))
    edges += [(d, concat) for d in diff]
    s, o = _mk(rng, (15, 4), (1, 0.2))
    bg_model = len(spec)
    spec.append((s, o, 0.0))
    edges.append((concat, bg_model))
    backs = []
    for i in range(k):
        s, o = _mk(rng, (10, 3), (40, 10))
        b = len(spec)
        backs.append(b)
        spec.append((s, o, 0.0))
        edges.append((bg_model, b))
        edges.append((proj[i], b))
    s, o = _mk(rng, (20, 5), (5, 1))
    imgtbl = len(spec)
    spec.append((s, o, 0.0))
    edges += [(b, imgtbl) for b in backs]
    s, o = _mk(rng, (60, 15), (120, 30))
    madd = len(spec)
    spec.append((s, o, 0.0))
    edges.append((imgtbl, madd))
    s, o = _mk(rng, (15, 4), (20, 5))
    shrink = len(spec)
    spec.append((s, o, 0.0))
    edges.append((madd, shrink))
    s, o = _mk(rng, (10, 2), (5, 1))
    jpeg = len(spec)
    spec.append((s, o, 0.0))
    edges.append((shrink, jpeg))
    return _build(wid, "montage", spec, edges)


def cybershake(wid, n, rng) -> Workflow:
    pairs = max(2, (n - 2) // 4)
    spec, edges, synths, peaks = [], [], [], []
    for _ in range(pairs):
        s, o = _mk(rng, (110, 25), (150, 40))
        sgt = len(spec)
        spec.append((s, o, 120.0))
        for _ in range(2):
            s2, o2 = _mk(rng, (450, 100), (180, 50))
            syn = len(spec)
            synths.append(syn)
            spec.append((s2, o2, 0.0))
            edges.append((sgt, syn))
            s3, o3 = _mk(rng, (30, 8), (1, 0.3))
            pk = len(spec)
            peaks.append(pk)
            spec.append((s3, o3, 0.0))
            edges.append((syn, pk))
    s, o = _mk(rng, (40, 10), (60, 15))
    zipseis = len(spec)
    spec.append((s, o, 0.0))
    edges += [(x, zipseis) for x in synths]
    s, o = _mk(rng, (30, 8), (10, 3))
    zippsa = len(spec)
    spec.append((s, o, 0.0))
    edges += [(x, zippsa) for x in peaks]
    return _build(wid, "cybershake", spec, edges)


def epigenome(wid, n, rng) -> Workflow:
    lanes = max(2, (n - 4) // 4)
    spec, edges = [], []
    s, o = _mk(rng, (60, 10), (15, 3))
    split = len(spec)
    spec.append((s, o, 25.0))
    maps = []
    for _ in range(lanes):
        prev = split
        for mi, mb in [((90, 20), (10, 2)), ((45, 10), (10, 2)),
                       ((45, 10), (8, 2)), ((900, 180), (8, 2))]:
            s2, o2 = _mk(rng, mi, mb)
            t = len(spec)
            spec.append((s2, o2, 0.0))
            edges.append((prev, t))
            prev = t
        maps.append(prev)
    s, o = _mk(rng, (120, 25), (20, 4))
    merge = len(spec)
    spec.append((s, o, 0.0))
    edges += [(m, merge) for m in maps]
    s, o = _mk(rng, (60, 12), (10, 2))
    index = len(spec)
    spec.append((s, o, 0.0))
    edges.append((merge, index))
    s, o = _mk(rng, (90, 18), (15, 3))
    pileup = len(spec)
    spec.append((s, o, 0.0))
    edges.append((index, pileup))
    return _build(wid, "epigenome", spec, edges)


def ligo(wid, n, rng) -> Workflow:
    groups = max(2, (n - 2) // 10)
    per = 4
    spec, edges = [], []
    for _ in range(groups):
        insp = []
        for _ in range(per):
            s, o = _mk(rng, (70, 15), (25, 6))
            tb = len(spec)
            spec.append((s, o, 30.0))
            s2, o2 = _mk(rng, (320, 70), (30, 8))
            ins = len(spec)
            spec.append((s2, o2, 0.0))
            edges.append((tb, ins))
            insp.append(ins)
        s3, o3 = _mk(rng, (25, 6), (8, 2))
        th = len(spec)
        spec.append((s3, o3, 0.0))
        edges += [(i, th) for i in insp]
        insp2 = []
        for _ in range(per):
            s4, o4 = _mk(rng, (20, 5), (6, 2))
            tb2 = len(spec)
            spec.append((s4, o4, 0.0))
            edges.append((th, tb2))
            s5, o5 = _mk(rng, (280, 60), (25, 6))
            ins2 = len(spec)
            spec.append((s5, o5, 0.0))
            edges.append((tb2, ins2))
            insp2.append(ins2)
        s6, o6 = _mk(rng, (25, 6), (8, 2))
        th2 = len(spec)
        spec.append((s6, o6, 0.0))
        edges += [(i, th2) for i in insp2]
    return _build(wid, "ligo", spec, edges)


def sipht(wid, n, rng) -> Workflow:
    patsers = max(2, (n - 8) // 2)
    spec, edges, pats = [], [], []
    for _ in range(patsers):
        s, o = _mk(rng, (25, 6), (1.5, 0.4))
        pats.append(len(spec))
        spec.append((s, o, 2.0))
    s, o = _mk(rng, (15, 4), (2, 0.5))
    pconc = len(spec)
    spec.append((s, o, 0.0))
    edges += [(p, pconc) for p in pats]
    tools = []
    for mi in [(120, 25), (90, 20), (160, 30), (90, 20), (60, 15)]:
        s2, o2 = _mk(rng, mi, (4, 1))
        tools.append(len(spec))
        spec.append((s2, o2, 3.0))
    s3, o3 = _mk(rng, (220, 45), (6, 1.5))
    srna = len(spec)
    spec.append((s3, o3, 0.0))
    edges += [(t, srna) for t in tools + [pconc]]
    s4, o4 = _mk(rng, (110, 22), (4, 1))
    annot = len(spec)
    spec.append((s4, o4, 0.0))
    edges.append((srna, annot))
    return _build(wid, "sipht", spec, edges)


APP_GENERATORS = {"cybershake": cybershake, "epigenome": epigenome,
                  "ligo": ligo, "montage": montage, "sipht": sipht}


def assign_budgets_uniform(cfg, wfs, rng, lo, hi) -> None:
    """§5 budgets: uniform over the ``[lo, hi]`` slice of each workflow's
    ``[min_cost, max_cost]`` (the program's estimate of that range)."""
    for wf in wfs:
        cmin, cmax = budget_mod.min_max_workflow_cost(cfg, wf)
        wf.budget = cmin + rng.uniform(lo, hi) * (cmax - cmin)


# ---------------------------------------------------------------------------
# Workload streams
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Stream:
    """One workload stream: the workflows every member of it simulates."""

    workload: Tuple[PlainWorkflow, ...]
    degradation_seed: int

    @property
    def n_tasks(self) -> int:
        return sum(len(wf[4]) for wf in self.workload)


def paper_stream(cfg: PlatformConfig, app: Union[str, Sequence[str]],
                 rate: float, interval: Tuple[float, float], seed: int,
                 n: int, sizes: Sequence[str]) -> List[Workflow]:
    """One §5 grid cell's workload: a Poisson stream with budgets from one
    quarter of the cost range, each workflow's application drawn from
    ``app``, one name or several in the order given.  One name gives
    ``cell_workload``'s stream, a list ``generate_workload``'s with
    ``apps`` that list."""
    apps = (app,) if isinstance(app, str) else tuple(app)
    if not apps or any(a not in APP_GENERATORS for a in apps):
        raise ValueError(f"workload app {app!r} is not one or more of the "
                         f"known applications {sorted(APP_GENERATORS)}")
    rng = np.random.default_rng(seed)
    inter_ms = 60.0 * MS / rate
    lo, hi = interval
    t = 0.0
    out: List[Workflow] = []
    for wid in range(n):
        name = apps[int(rng.integers(len(apps)))]
        size = SIZE_CLASSES[sizes[int(rng.integers(len(sizes)))]]
        wf = APP_GENERATORS[name](wid, size, rng)
        wf.arrival_ms = int(t)
        assign_budgets_uniform(cfg, [wf], rng, lo, hi)
        out.append(wf)
        t += rng.exponential(inter_ms)
    return out


def streams(cfg: PlatformConfig, workload: dict,
            traffic: dict) -> List[Stream]:
    """Every workload stream of one cell: one per (budget interval, d) for
    ``d`` below the traffic's ``streams_per_group``, numbered as
    ``Scenario.workload_cells`` numbers them.  Stream ``d`` of a group
    draws its workflows with the program's per-cell workload seed
    ``7919 (workload_seed + d + 1) + index`` and its degradation from
    ``degradation_seed + d``; ``workload["app"]`` goes to
    :func:`paper_stream` as it stands, one name or a list."""
    n = traffic["streams_per_group"]
    w = traffic["workload_seed"]
    out: List[Stream] = []
    for interval in workload["budget_intervals"]:
        for d in range(n):
            wfs = paper_stream(
                cfg, workload["app"], workload["rate_per_min"],
                tuple(interval), 7919 * (w + d + 1) + len(out),
                workload["workflows_per_cell"], workload["sizes"])
            out.append(Stream(to_plain(wfs), traffic["degradation_seed"] + d))
    return out


# ---------------------------------------------------------------------------
# Plain data
# ---------------------------------------------------------------------------


def to_plain(wfs: Sequence[Workflow]) -> Tuple[PlainWorkflow, ...]:
    return tuple(
        (wf.wid, wf.app, int(wf.arrival_ms), float(wf.budget),
         tuple((float(t.size_mi), float(t.out_mb), float(t.ext_in_mb),
                tuple(t.parents), tuple(t.children),
                tuple((str(n), float(mb)) for n, mb in t.shared_in))
               for t in wf.tasks))
        for wf in wfs)


def from_plain(plain: Sequence[PlainWorkflow]) -> List[Workflow]:
    """Fresh program objects: no cost table, rank list or input list
    carried over from any earlier run."""
    return [
        Workflow(wid=wid, app=app, budget=budget, arrival_ms=arrival,
                 tasks=[Task(tid, s, o, e, list(p), list(c), list(sh))
                        for tid, (s, o, e, p, c, sh) in enumerate(tasks)])
        for wid, app, arrival, budget, tasks in plain]

