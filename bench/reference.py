"""Plain reference of the simulated WaaS platform: the paper's semantics,
written from the paper and the configuration file, importing nothing of
the program.

One call simulates one member (policy, workload, degradation seed) with a
heap-ordered integer-millisecond event loop and a per-task Algorithm 2
scan over the idle VMs — no auction, no kernel, no batching, no cost-table
memo shared between runs.  It returns what the benchmark compares: each
workflow's finish time and cost, and the VMs leased per type.

Semantics (arXiv:1903.01113 §3-§5, with the configuration's cloud model):

* Eqs. (1)-(5): transfer-in ``d/b + d/GS_r``, runtime ``S/p``, write-back
  ``d/b + d/GS_w``, each ``ceil`` to integer ms after a relative backoff of
  ``ceil_tolerance``; cost ``ceil(duration / bp) * price``.  The scheduler
  estimates on advertised capacity; pipelines run on capacity degraded by
  per-task draws from the configuration's variation model.
* Algorithm 1: Deadline-Top-Level levels, estimated execution order ``S``
  (level, EFT on ``vm_types[0]``, tid); pass 1 gives each task its
  cheapest-type conservative cost while the budget lasts; pass 2 (SFTD)
  sweeps ``S`` raising tasks one VM-type tier per visit.
* Algorithm 2: per ready task in (ready time, wid, tid) order, the lowest
  feasible tier among idle VMs in the policy's sharing scope — 1 all
  inputs cached, 2 container active, 3 any — by (finish, vmid); else the
  fastest new VM type the sub-budget affords (tier 4); else the cheapest
  action over reuse and a new cheapest VM (tier 5).
* Algorithm 3: at every task finish, the finished task's sub-budget plus
  the spare absorb its actual cost and the surplus or debt is
  redistributed over the unscheduled tasks by Algorithm 1.
* MSLBL_MW: sub-budget ``c_min + b (c_max - c_min)``; unspent sub-budget
  rolls into one spare pot per workflow; idle VMs are released after
  every cycle.  EBPSM variants release a VM idle for ``idle_threshold``.
"""
from __future__ import annotations

import heapq
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

ARRIVAL, FINISH, VM_READY, REAP = 0, 1, 2, 3
PROVISIONING, IDLE, BUSY, TERMINATED = 1, 2, 3, 4

# The five policies of the paper (§5): containers, sharing scope, data
# locality tiers, idle threshold (ms), budget handling.
POLICIES = {
    "EBPSM": (True, "global", True, 5_000, "ebpsm"),
    "EBPSM_NS": (True, "workflow", True, 5_000, "ebpsm"),
    "EBPSM_WS": (False, "app", True, 5_000, "ebpsm"),
    "EBPSM_NC": (False, "global", True, 5_000, "ebpsm"),
    "MSLBL_MW": (False, "global", False, 0, "mslbl"),
}


class _VM:
    __slots__ = ("vmid", "type", "status", "tag", "ready_ms", "epoch",
                 "active", "images", "data", "cached_mb", "lease_ms",
                 "busy_ms")

    def __init__(self, vmid, vtype, tag, now, ready_ms):
        self.vmid = vmid
        self.lease_ms = now
        self.busy_ms = 0
        self.type = vtype
        self.status = PROVISIONING
        self.tag = tag
        self.ready_ms = ready_ms
        self.epoch = 0
        self.active = None
        self.images: Dict[str, bool] = {}
        self.data: Dict[tuple, float] = {}
        self.cached_mb = 0.0


class Platform:
    """The configuration's cloud model and cost arithmetic, in ``num``:
    ``float`` (the configuration's float64) or ``np.float32`` for the
    control."""

    def __init__(self, conf: dict, num=float):
        p = conf["platform"]
        g = conf["guarantees"]
        self.num = num
        self.types = p["vm_types"]
        self.mips = [num(t["mips"]) for t in self.types]
        self.bw = [num(t["bandwidth_mbps"]) for t in self.types]
        self.price = [num(t["cost_per_bp"]) for t in self.types]
        self.storage = [num(t["storage_mb"]) for t in self.types]
        self.bp = p["billing_period_ms"]
        self.prov = p["vm_provision_delay_ms"]
        self.c_init = p["container_init_ms"]
        self.c_prov = p["container_download_ms"] + p["container_init_ms"]
        self.gsr = num(p["gs_read_mbps"])
        self.gsw = num(p["gs_write_mbps"])
        self.cache_slots = p["cache_slots"]
        self.image_slots = p["image_slots"]
        self.deg = ((p["cpu_degradation_mean"], p["cpu_degradation_std"],
                     p["cpu_degradation_max"]),
                    (p["bw_degradation_mean"], p["bw_degradation_std"],
                     p["bw_degradation_max"]))
        self.tol = num(1.0 - g["ceil_tolerance"])
        self.budget_slack = g["budget_slack"]
        n = len(self.types)
        self.speed_desc = sorted(range(n), key=lambda i: self.mips[i],
                                 reverse=True)
        self.speed_asc = sorted(range(n), key=lambda i: self.mips[i])
        self.cheapest_price = min(range(n), key=lambda i: self.price[i])
        self.slowest = min(range(n), key=lambda i: self.mips[i])
        self.fastest = max(range(n), key=lambda i: self.mips[i])

    def ceil_ms(self, x: float) -> int:
        return int(math.ceil(x * self.tol))

    def xfer(self, mb: float, bw: float, gs: float) -> int:
        return self.ceil_ms(1000.0 * (mb / bw + mb / gs)) if mb > 0.0 else 0

    def runtime(self, size: float, mips: float) -> int:
        return self.ceil_ms(1000.0 * size / mips)

    def bill(self, dur_ms: int, price: float) -> float:
        return ((max(dur_ms, 0) + self.bp - 1) // self.bp) * price


class _Workflow:
    """Static per-workflow estimates (advertised capacity)."""

    def __init__(self, plat: Platform, wf):
        num = plat.num
        self.wid, self.app, self.arrival, budget, tasks = wf
        self.budget = num(budget)
        tasks = [(num(s), num(o), num(e), par, ch,
                  tuple((n, num(mb)) for n, mb in sh))
                 for s, o, e, par, ch, sh in tasks]
        self.n = len(tasks)
        self.size = [t[0] for t in tasks]
        self.out = [t[1] for t in tasks]
        self.parents = [t[3] for t in tasks]
        self.children = [t[4] for t in tasks]
        self.inputs = []
        self.in_total = []
        for tid, (s, o, e, par, ch, sh) in enumerate(tasks):
            ins = []
            if e > 0:
                ins.append((("ext", self.wid, tid), e))
            for name, mb in sh:
                ins.append((("shared", name, 0), mb))
            for q in par:
                ins.append((("out", self.wid, q), tasks[q][1]))
            self.inputs.append(ins)
            self.in_total.append(e + sum(mb for _, mb in sh)
                                 + sum(tasks[q][1] for q in par))
        K = len(plat.types)
        self.rt_out = [[plat.runtime(self.size[t], plat.mips[i])
                        + plat.xfer(self.out[t], plat.bw[i], plat.gsw)
                        for i in range(K)] for t in range(self.n)]
        self.proc = [[plat.xfer(self.in_total[t], plat.bw[i], plat.gsr)
                      + self.rt_out[t][i] for i in range(K)]
                     for t in range(self.n)]
        self.full_cost = [[plat.bill(self.proc[t][i] + plat.prov
                                     + plat.c_prov, plat.price[i])
                           for i in range(K)] for t in range(self.n)]
        self.sub = [0.0] * self.n
        self.rank = [0] * self.n

    def topo(self) -> List[int]:
        indeg = [len(p) for p in self.parents]
        heap = [i for i, d in enumerate(indeg) if d == 0]
        heapq.heapify(heap)
        out = []
        while heap:
            u = heapq.heappop(heap)
            out.append(u)
            for c in self.children[u]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    heapq.heappush(heap, c)
        return out

    def execution_order(self, plat: Platform) -> List[int]:
        level = [0] * self.n
        eft = [0] * self.n
        for t in self.topo():
            if self.parents[t]:
                level[t] = 1 + max(level[q] for q in self.parents[t])
            eft[t] = max((eft[q] for q in self.parents[t]), default=0) \
                + self.proc[t][0]
        order = sorted(range(self.n), key=lambda t: (level[t], eft[t], t))
        for r, t in enumerate(order):
            self.rank[t] = r
        return order

    def distribute(self, plat: Platform, budget: float,
                   order: Sequence[int]) -> float:
        """Algorithm 1 over ``order``; sets sub-budgets, returns spare."""
        alloc = []
        running = 0.0
        for t in order:
            w = self.full_cost[t][0]
            running += w
            alloc.append(min(w, max(budget - (running - w), 0.0)))
        remaining = max(budget - float(np.sum(np.asarray(alloc))), 0.0)
        tiers = [[self.full_cost[t][i] for i in plat.speed_asc]
                 for t in order]
        K = len(plat.speed_asc)
        level = []
        for u, a in enumerate(alloc):
            k = 0
            for j in range(K - 1, -1, -1):
                if a >= tiers[u][j] - 1e-9:
                    k = j
                    break
            level.append(k)
        changed = True
        while remaining > 1e-9 and changed:
            changed = False
            for u in range(len(alloc)):
                k = level[u]
                if k + 1 >= K:
                    continue
                delta = tiers[u][k + 1] - alloc[u]
                if 0 < delta <= remaining + 1e-9:
                    alloc[u] = tiers[u][k + 1]
                    level[u] = k + 1
                    remaining -= delta
                    changed = True
                elif delta <= 0:
                    level[u] = k + 1
                    changed = True
        for u, t in enumerate(order):
            self.sub[t] = alloc[u]
        return max(remaining, 0.0)

    def distribute_mslbl(self, plat: Platform) -> None:
        self.execution_order(plat)
        c_min = np.array([c[plat.slowest] for c in self.full_cost])
        c_max = np.array([c[plat.fastest] for c in self.full_cost])
        lo, hi = float(c_min.sum()), float(c_max.sum())
        level = 1.0 if hi - lo < 1e-9 else (self.budget - lo) / (hi - lo)
        level = min(max(level, 0.0), 1.0)
        for t in range(self.n):
            self.sub[t] = float(c_min[t] + level * (c_max[t] - c_min[t]))


def degradation(plat: Platform, n_tasks: int, seed: int):
    """Per-task (cpu, bw_in, bw_out) degradation: clipped normals drawn in
    that order from the seed."""
    rng = np.random.default_rng(seed)
    out = []
    for mean, std, hi in (plat.deg[0], plat.deg[1], plat.deg[1]):
        d = np.clip(rng.normal(mean, std, n_tasks), 0.0, hi).tolist()
        out.append([plat.num(x) for x in d] if plat.num is not float else d)
    return out


def simulate(conf: dict, policy: str, workload, seed: int, num=float):
    """Run one member; returns ``({wid: (finish_ms, cost)}, {type: (VMs
    leased, busy ms, leased ms)})``.
    ``num=np.float32`` computes every estimate, budget and cost in float32
    (the control)."""
    plat = Platform(conf, num)
    use_cont, scope, locality, idle_ms, mode = POLICIES[policy]
    wfs = [_Workflow(plat, wf) for wf in workload]
    base, acc = {}, 0
    for w in wfs:
        base[w.wid] = acc
        acc += w.n
    cpu_deg, bwi_deg, bwo_deg = degradation(plat, acc, seed)
    by_wid = {w.wid: w for w in wfs}

    events: list = []
    seq = [0]

    def push(t, kind, payload):
        heapq.heappush(events, (t, seq[0], kind, payload))
        seq[0] += 1

    vms: List[_VM] = []
    idle: Dict[int, _VM] = {}
    bound: Dict[int, Tuple[int, int]] = {}
    running: Dict[Tuple[int, int], Tuple[_VM, float]] = {}
    queue: list = []
    spare: Dict[int, float] = {}
    cost: Dict[int, float] = {}
    finish: Dict[int, int] = {}
    unscheduled: Dict[int, set] = {}
    pending: Dict[int, List[int]] = {}
    fleet = [[0, 0, 0] for _ in plat.types]   # VMs, busy ms, leased ms

    def tag_of(w):
        return None if scope == "global" else (
            ("wf", w.wid) if scope == "workflow" else ("app", w.app))

    def cache_put(vm, key, mb):
        if mb <= 0 or key in vm.data:
            return
        vm.data[key] = mb
        vm.cached_mb += mb
        while (vm.cached_mb > plat.storage[vm.type]
               or len(vm.data) > plat.cache_slots) and vm.data:
            old = next(iter(vm.data))
            vm.cached_mb -= vm.data.pop(old)

    def container_ms(vm, app):
        if not use_cont or vm.active == app:
            return 0
        return plat.c_init if app in vm.images else plat.c_prov

    def close(vm, now):
        vm.status = TERMINATED
        idle.pop(vm.vmid, None)
        fleet[vm.type][1] += vm.busy_ms
        fleet[vm.type][2] += now - vm.lease_ms

    def set_idle(vm, now):
        vm.status = IDLE
        vm.epoch += 1
        idle[vm.vmid] = vm
        if idle_ms > 0:
            push(now + idle_ms, REAP, (vm.vmid, vm.epoch))

    def start(now, w, tid, vm, provisioned):
        gid = base[w.wid] + tid
        c_ms = container_ms(vm, w.app)
        if use_cont:
            if w.app not in vm.images:
                vm.images[w.app] = True
            vm.active = w.app
            while len(vm.images) > plat.image_slots:
                old = next(iter(vm.images))
                del vm.images[old]
                if vm.active == old:
                    vm.active = None
        missing = 0.0
        fetch = []
        for key, mb in w.inputs[tid]:
            if key not in vm.data:
                missing += mb
                fetch.append((key, mb))
        for key, mb in fetch:
            cache_put(vm, key, mb)
        v = vm.type
        in_ms = plat.xfer(missing, plat.bw[v] * (1.0 - bwi_deg[gid]),
                          plat.gsr)
        rt_ms = plat.runtime(w.size[tid], plat.mips[v] * (1.0 - cpu_deg[gid]))
        out_ms = plat.xfer(w.out[tid], plat.bw[v] * (1.0 - bwo_deg[gid]),
                           plat.gsw)
        pipe = c_ms + in_ms + rt_ms + out_ms
        vm.busy_ms += pipe
        billed = pipe + (plat.prov if provisioned else 0)
        running[(w.wid, tid)] = (vm, plat.bill(billed, plat.price[v]))
        push(now + pipe, FINISH, (w.wid, tid))

    def select(w, tid, budget, pool):
        """Algorithm 2 for one task: (vm or None, new type, est cost)."""
        tag = tag_of(w)
        scoped = [vm for vm in pool if vm.tag == tag]
        ins = w.inputs[tid]
        total_in = sum(mb for _, mb in ins)
        limit = budget + plat.budget_slack
        best = {}
        for vm in scoped:
            v = vm.type
            c_ms = container_ms(vm, w.app)
            if locality:
                missing = 0.0
                have_all = True
                for key, mb in ins:
                    if key not in vm.data:
                        missing += mb
                        if mb > 0:
                            have_all = False
                tier = 1 if have_all else (
                    2 if use_cont and vm.active == w.app else 3)
            else:
                missing, tier = total_in, 3
            pipe = c_ms + plat.xfer(missing, plat.bw[v], plat.gsr) \
                + w.rt_out[tid][v]
            c = plat.bill(pipe, plat.price[v])
            if c > limit:
                continue
            if tier not in best or pipe < best[tier][0]:
                best[tier] = (pipe, c, vm)
        for tier in (1, 2, 3):
            if tier in best:
                return best[tier][2], None, best[tier][1]
        c_new = plat.c_prov if use_cont else 0
        for i in plat.speed_desc:
            c = plat.bill(w.proc[tid][i] + c_new + plat.prov, plat.price[i])
            if c <= limit:
                return None, i, c
        i = plat.cheapest_price
        pipe = w.proc[tid][i] + c_new
        pick = (plat.bill(pipe + plat.prov, plat.price[i]),
                plat.prov + pipe, 1 << 30, None, i)
        for vm in scoped:
            v = vm.type
            missing = sum(mb for key, mb in ins if key not in vm.data) \
                if locality else total_in
            pipe = container_ms(vm, w.app) \
                + plat.xfer(missing, plat.bw[v], plat.gsr) + w.rt_out[tid][v]
            cand = (plat.bill(pipe, plat.price[v]), pipe, vm.vmid, vm, None)
            if cand[:3] < pick[:3]:
                pick = cand
        return pick[3], pick[4], pick[0]

    def algorithm3(w, tid, actual):
        order = sorted(unscheduled[w.wid], key=lambda t: w.rank[t])
        pool = sum([w.sub[t] for t in order]) if order else 0.0
        headroom = w.sub[tid] + spare[w.wid]
        if actual <= headroom:
            pool += headroom - actual
        else:
            pool -= actual - headroom
        pool = max(pool, 0.0)
        spare[w.wid] = w.distribute(plat, pool, order) if order else pool

    for w in wfs:
        push(w.arrival, ARRIVAL, (w.wid,))
    now = 0
    while events:
        now = events[0][0]
        cycle = False
        while events and events[0][0] == now:
            _, _, kind, payload = heapq.heappop(events)
            if kind == ARRIVAL:
                w = by_wid[payload[0]]
                cost[w.wid] = 0.0
                finish[w.wid] = 0
                unscheduled[w.wid] = set(range(w.n))
                pending[w.wid] = [len(p) for p in w.parents]
                if mode == "mslbl":
                    w.distribute_mslbl(plat)
                    spare[w.wid] = 0.0
                else:
                    spare[w.wid] = w.distribute(plat, w.budget,
                                                w.execution_order(plat))
                for t in range(w.n):
                    if not w.parents[t]:
                        heapq.heappush(queue, (now, w.wid, t))
                cycle = True
            elif kind == FINISH:
                wid, tid = payload
                w = by_wid[wid]
                vm, actual = running.pop((wid, tid))
                cache_put(vm, ("out", wid, tid), w.out[tid])
                set_idle(vm, now)
                bound.pop(vm.vmid, None)
                cost[wid] += actual
                finish[wid] = max(finish[wid], now)
                if mode == "mslbl":
                    spare[wid] += w.sub[tid] - actual
                else:
                    algorithm3(w, tid, actual)
                for c in w.children[tid]:
                    pending[wid][c] -= 1
                    if pending[wid][c] == 0:
                        heapq.heappush(queue, (now, wid, c))
                cycle = True
            elif kind == VM_READY:
                vm = vms[payload[0]]
                if vm.status == PROVISIONING:
                    wid, tid = bound[vm.vmid]
                    vm.status = BUSY
                    start(now, by_wid[wid], tid, vm, True)
            elif kind == REAP:
                vm = vms[payload[0]]
                if vm.status == IDLE and vm.epoch == payload[1]:
                    close(vm, now)
        if not cycle:
            continue
        pool = [idle[k] for k in sorted(idle)]
        while queue:
            _, wid, tid = heapq.heappop(queue)
            w = by_wid[wid]
            budget = w.sub[tid]
            if mode == "mslbl" and spare[wid] > 0:
                budget += spare[wid]
            vm, new_type, est = select(w, tid, budget, pool)
            if mode == "mslbl":
                used = max(0.0, est - w.sub[tid])
                spare[wid] -= min(used, max(spare[wid], 0.0))
            unscheduled[wid].discard(tid)
            if vm is not None:
                vm.status = BUSY
                idle.pop(vm.vmid)
                pool = [v for v in pool if v is not vm]
                bound[vm.vmid] = (wid, tid)
                start(now, w, tid, vm, False)
            else:
                vm = _VM(len(vms), new_type, tag_of(w), now,
                         now + plat.prov)
                vms.append(vm)
                fleet[new_type][0] += 1
                bound[vm.vmid] = (wid, tid)
                push(vm.ready_ms, VM_READY, (vm.vmid,))
        if idle_ms == 0:
            for k in sorted(idle):
                close(idle[k], now)
    for vm in vms:
        if vm.status != TERMINATED:
            close(vm, now)
    results = {w.wid: (finish[w.wid], float(cost[w.wid])) for w in wfs}
    return results, {t["name"]: tuple(fleet[i])
                     for i, t in enumerate(plat.types)}
