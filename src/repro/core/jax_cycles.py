"""Batched scheduling cycles: Algorithm 2 as a JAX computation.

The sequential reference processes the ready queue task-by-task, scoring
every idle VM per task (O(T·V) Python).  This module scores ALL pairs at
once with the affinity kernel (jnp oracle or the Pallas kernel) and
resolves VM conflicts with an auction: every unplaced task picks its best
VM; the earliest task in queue order wins each VM; losers retry against
the shrunken pool.  Because pair scores are static within a cycle (caches
only change when pipelines start), the fixed point equals the sequential
outcome exactly — property-tested in tests/test_jax_cycles.py.

Tier encoding per (task, VM): 0 = out of scope (busy/wrong owner),
1 = all inputs cached, 2 = container active, 3 = idle.  Provisioning
(tier 4/5) can't conflict and stays in the per-task fallback.

Pair arrays are built from the :class:`~repro.sim.cloud.VMPool`
live-state registry, not from per-VM Python calls: VM-type attributes
are vmid-indexed gathers, container-delay vectors come from the pool's
incremental ``app_image`` / ``app_active`` sets, and sharing-scope masks
from ``tag_members`` — each computed once per distinct app/tag per
cycle.  An auction's first kernel round writes every member's pair arrays
into resident padded ``[B, T, V]`` host buffers (:class:`_RoundBuffers`),
which cross to the device once and stay there; since the scores are
static, every later round of that auction sends only a small live mask
(the rows still unplaced and the VMs still free), which the kernel folds
into the tier.

One driver consumes the auction: :func:`multi_cycle` runs many
independent simulations' cycles at once (``core.jax_engine.BatchSimEngine``;
a single simulation is a one-member grid): each round stacks every active
member's proposal into one ``[B, T, V]`` tensor and scores it with a
single kernel call whose grid runs over the member dim.

Tuning knobs (see the README "Tuning knobs" table): ``AUCTION_TAIL_PAIRS``
(=192) drains a member's auction tail through per-task ``select`` once
its remaining queue×pool product drops below it — identical outcomes
(the fixed point *is* the sequential interleaving), it just stops paying
per-round kernel dispatch for a handful of pairs.  The threshold that
decides whether a cycle rides this module at all
(``AUCTION_MIN_PAIRS_ROUND``) lives in ``core.jax_engine``.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..kernels.affinity import ops as aff_ops
from ..sim.cloud import VM, VMPool
from .engine import phase
from .scheduler import Placement, Policy, select
from .types import PlatformConfig, Task


def build_pair_arrays(cfg: PlatformConfig, policy: Policy,
                      tasks: Sequence[Tuple[Task, str, object, List]],
                      vms: Sequence[VM],
                      pool: VMPool):
    """tasks: [(task, app, owner_tag, inputs)] in queue order; ``vms`` are
    idle VMs in ascending-vmid order (the auction's column space)."""
    T, V = len(tasks), len(vms)
    size = np.empty(T, np.float32)
    out_mb = np.empty(T, np.float32)
    budget = np.empty(T, np.float32)
    missing = np.zeros((T, V), np.float32)
    cont = np.zeros((T, V), np.float32)
    tier = np.zeros((T, V), np.int32)

    ids = np.fromiter((vm.vmid for vm in vms), np.int64, V)
    vm_ids = {vmid: j for j, vmid in enumerate(ids.tolist())}
    # vmid-indexed gathers from the pool's static per-VM attribute arrays.
    mips = pool.mips[ids]
    bw = pool.bandwidth[ids]
    price = pool.price[ids]

    # Per-(vm, app) container state from the pool's incremental app
    # indexes — O(|holders|) per distinct app, no per-VM Python calls.
    cont_by_app = {}
    for app in {app for _, app, _, _ in tasks}:
        is_active = np.zeros(V, bool)
        if not policy.use_containers:
            cvec = np.zeros(V, np.float32)
        else:
            cvec = np.full(V, cfg.container_provision_ms, np.float32)
            for vid in pool.app_image.get(app, ()):
                j = vm_ids.get(vid)
                if j is not None:
                    cvec[j] = cfg.container_init_ms
            for vid in pool.app_active.get(app, ()):
                j = vm_ids.get(vid)
                if j is not None:
                    cvec[j] = 0.0
                    is_active[j] = True
        cont_by_app[app] = (cvec, is_active)

    # Sharing-scope masks, one per distinct owner tag this cycle.
    scope_by_tag = {}
    for tag in {tag for _, _, tag, _ in tasks}:
        s = np.zeros(V, bool)
        for vid in pool.tag_members.get(tag, ()):
            j = vm_ids.get(vid)
            if j is not None:
                s[j] = True
        scope_by_tag[tag] = s

    data_index = pool.data_index
    for i, (task, app, tag, inputs) in enumerate(tasks):
        size[i] = task.size_mi
        out_mb[i] = task.out_mb
        budget[i] = task.budget
        scope = scope_by_tag[tag]
        cvec, is_active = cont_by_app[app]
        cont[i] = cvec
        if policy.locality_tiers:
            have_all = scope.copy()
            miss = np.zeros(V, np.float32)
            for key, mb in inputs:
                holders = data_index.get(key, ())
                hold = np.zeros(V, bool)
                for vid in holders:
                    j = vm_ids.get(vid)
                    if j is not None:
                        hold[j] = True
                miss += np.where(hold, 0.0, mb)
                if mb > 0:
                    have_all &= hold
            missing[i] = miss
            t = np.where(have_all, 1,
                         np.where(is_active & policy.use_containers, 2, 3))
        else:
            missing[i] = sum(mb for _, mb in inputs)
            t = np.full(V, 3, np.int32)
        tier[i] = np.where(scope, t, 0)
    return (size, out_mb, budget, missing, cont, tier, mips, bw, price)


# Below this remaining queue×pool pair product a request finishes its
# auction serially instead of riding further kernel rounds — the commit
# rule's conflict tails otherwise pay per-round device dispatch for a
# handful of pairs.  Serial and kernel resolution are bit-exact.
AUCTION_TAIL_PAIRS = 192


def _p2(n: int) -> int:
    """Next power of two ≥ max(n, 2) — shape buckets so the jitted kernel
    is reused across cycles instead of recompiling per shape (padding
    rows/cols are tier-0 ⇒ infeasible ⇒ inert)."""
    return 1 << max(n - 1, 1).bit_length()


class _RoundBuffers:
    """Resident padded pair buffers for auction rounds, bucketed by
    power-of-two ``(Bp, Tp, Vp)`` shape.

    The old cache held exactly ONE bucket: mixed-size rounds (a big
    round followed by small ones, the normal shape of the aggregate
    dispatcher) thrashed it — every bucket flip reallocated and refilled
    nine arrays, and the jitted kernel re-traced.  Now:

    * multiple buckets stay resident (dict, LRU-evicted once the summed
      ``B·T·V`` exceeds ``MAX_RESIDENT_ELEMS``), each traced once;
    * a round reuses the smallest resident bucket that covers its shape
      (up to ``COVER_SLACK``× element blowup — padding is inert, and
      riding a slightly-larger resident bucket beats allocating and
      tracing a new one), growing buckets geometrically via the
      power-of-two dims;
    * resets clear only the region the bucket's previous round actually
      wrote (tracked per bucket), not the whole allocation — small
      rounds in a big bucket pay memsets proportional to their own size.

    The cache is thread-local (each thread driving engines gets its own
    buffers — rounds from concurrent runs never interleave on shared
    arrays); over-cap outliers allocate fresh per round rather than
    pinning hundreds of MB at module scope.
    """

    __slots__ = ("buckets", "used", "lru")

    # Largest summed B·T·V kept alive between rounds (~4M pair elements
    # ⇒ ≲50 MB across the six [B,T,V] arrays).
    MAX_RESIDENT_ELEMS = 1 << 22
    # Max element blowup tolerated when riding a larger resident bucket.
    COVER_SLACK = 4

    def __init__(self):
        self.buckets = {}   # (Bp, Tp, Vp) -> bufs tuple
        self.used = {}      # (Bp, Tp, Vp) -> (B, T, V) region to reset
        self.lru = []       # keys, most-recently-used last

    @staticmethod
    def _alloc(Bp: int, Tp: int, Vp: int):
        return (
            np.zeros((Bp, Tp), np.float32),        # size
            np.zeros((Bp, Tp), np.float32),        # out_mb
            np.full((Bp, Tp), -1.0, np.float32),   # budget (inert: -1)
            np.zeros((Bp, Tp, Vp), np.float32),    # missing
            np.zeros((Bp, Tp, Vp), np.float32),    # cont
            np.zeros((Bp, Tp, Vp), np.int32),      # tier (inert: 0)
            np.ones((Bp, Vp), np.float32),         # mips (no div-by-zero)
            np.ones((Bp, Vp), np.float32),         # bw
            np.ones((Bp, Vp), np.float32),         # price
        )

    @staticmethod
    def _reset(bufs, region) -> None:
        B, T, V = region
        if B == 0:
            return
        size, out_mb, budget, missing, cont, tier, mips, bw, price = bufs
        size[:B, :T] = 0.0
        out_mb[:B, :T] = 0.0
        budget[:B, :T] = -1.0
        missing[:B, :T, :V] = 0.0
        cont[:B, :T, :V] = 0.0
        tier[:B, :T, :V] = 0
        mips[:B, :V] = 1.0
        bw[:B, :V] = 1.0
        price[:B, :V] = 1.0

    def _touch(self, key) -> None:
        if self.lru and self.lru[-1] == key:
            return
        try:
            self.lru.remove(key)
        except ValueError:
            pass
        self.lru.append(key)

    def get(self, Bp: int, Tp: int, Vp: int):
        req = Bp * Tp * Vp
        best = None
        for key in self.buckets:
            if key[0] >= Bp and key[1] >= Tp and key[2] >= Vp:
                if best is None or (key[0] * key[1] * key[2]
                                    < best[0] * best[1] * best[2]):
                    best = key
        if best is not None \
                and best[0] * best[1] * best[2] <= self.COVER_SLACK * req:
            bufs = self.buckets[best]
            self._reset(bufs, self.used[best])
            # Upper bound of what this round may write (propose_into
            # writes member rows within the requested dims only).
            self.used[best] = (Bp, Tp, Vp)
            self._touch(best)
            return bufs
        bufs = self._alloc(Bp, Tp, Vp)
        if req <= self.MAX_RESIDENT_ELEMS:
            key = (Bp, Tp, Vp)
            self.buckets[key] = bufs
            self.used[key] = (Bp, Tp, Vp)
            self._touch(key)
            total = sum(k[0] * k[1] * k[2] for k in self.buckets)
            while total > self.MAX_RESIDENT_ELEMS and len(self.lru) > 1:
                old = self.lru.pop(0)
                total -= old[0] * old[1] * old[2]
                del self.buckets[old]
                del self.used[old]
        # else: one-shot buffers — leave resident buckets intact.
        return bufs


class _ThreadLocalBuffers(threading.local):
    def __init__(self):
        self.rb = _RoundBuffers()


_ROUND_BUFFERS = _ThreadLocalBuffers()

# Mesh placement seam for the round buffers (stubbed: TPU tuning is a
# later ROADMAP item).  ``parallel.sharding.round_buffer_placement`` is
# imported lazily — sharding pulls in the model registry, which has no
# business on the simulation hot path.
_ROUND_BUFFER_MESH = None
_ROUND_BUFFER_PLACEMENT = None


def set_round_buffer_mesh(mesh) -> None:
    """Install a device mesh for future round-buffer placement.  With
    ``mesh=None`` (the default state) buffers stay host-staged numpy;
    with a mesh, the replicated placement is computed and recorded but
    — today — only consulted by tests: each auction's ``[B, T, V]``
    stacks go to the default device, and placing them on the mesh is the
    deferred TPU-tuning work."""
    global _ROUND_BUFFER_MESH, _ROUND_BUFFER_PLACEMENT
    _ROUND_BUFFER_MESH = mesh
    if mesh is None:
        _ROUND_BUFFER_PLACEMENT = None
        return
    from ..parallel.sharding import round_buffer_placement
    _ROUND_BUFFER_PLACEMENT = round_buffer_placement(mesh)


class CycleRequest:
    """One simulation's auction state inside a (possibly multi-sim) cycle.

    Owns the pair arrays, the queue-order task list, the availability
    mask, and the serial-dictatorship commit rule.  ``multi_cycle`` only
    orchestrates rounds; all per-simulation semantics live here.
    """

    def __init__(self, cfg: PlatformConfig, policy: Policy,
                 tasks, vms: Sequence[VM], pool: VMPool,
                 tables: Optional[Sequence] = None):
        self.cfg = cfg
        self.policy = policy
        self.pool = pool
        self.tables = tables   # per-task CostTables for serial resolution
        self.tasks = list(tasks)
        self.vms = list(vms)
        T, V = len(tasks), len(vms)
        self.T, self.V = T, V
        self.col = {vm.vmid: j for j, vm in enumerate(self.vms)}
        self.placements: List[Optional[Placement]] = [None] * T
        self.unplaced: List[int] = list(range(T)) if V else []
        self.avail = np.ones(V, bool)
        self.stalled = False
        if T and V:
            (self.size, self.out_mb, self.budget, self.missing, self.cont,
             self.tier, self.mips, self.bw, self.price) = build_pair_arrays(
                cfg, policy, tasks, vms, pool)

    @property
    def active(self) -> bool:
        return bool(self.unplaced) and bool(self.avail.any()) \
            and not self.stalled

    def propose_into(self, bufs, b: int) -> None:
        """Write this member's pair arrays, every task row in queue order,
        into batch row ``b`` of the shared resident buffers (already reset
        to inert padding), before any commit: every task is unplaced and
        every VM free."""
        size, out_mb, budget, missing, cont, tier, mips, bw, price = bufs
        T, V = self.T, self.V
        size[b, :T] = self.size
        out_mb[b, :T] = self.out_mb
        budget[b, :T] = self.budget
        missing[b, :T, :V] = self.missing
        cont[b, :T, :V] = self.cont
        tier[b, :T, :V] = self.tier
        mips[b, :V] = self.mips
        bw[b, :V] = self.bw
        price[b, :V] = self.price

    def live_into(self, live, Tp: int) -> None:
        """Write this member's row of a round's live mask: 1 for each task
        still unplaced (columns ``[0, Tp)``, by task index) and each VM
        still free (columns ``[Tp, Tp + V)``)."""
        live[self.unplaced] = 1
        live[Tp:Tp + self.V] = self.avail

    def _select_serial(self, ti: int) -> Placement:
        """The per-task reference rule for task ``ti`` against the
        auction's *current* availability set — the same ``select`` call,
        at the same point in the serial order, the sequential reference
        makes.  Used both for kernel-infeasible rows (insufficient-budget
        tier-4/5 resolution) and for the serial tail drain."""
        task, app, tag, inputs = self.tasks[ti]
        avail = [vm for j, vm in enumerate(self.vms) if self.avail[j]]
        return select(self.cfg, self.policy, task, -1, app, inputs,
                      task.budget, avail, owner_tag=tag, pool=self.pool,
                      table=self.tables[ti] if self.tables else None)

    def finish_serial(self) -> None:
        """Drain every remaining unplaced task with the per-task
        reference rule, in queue order, against the live availability
        set.  The auction's fixed point *is* sequential per-task
        processing (the property the whole module rests on), so the tail
        is bit-exact either way — and a few Python selects beat a long
        conflict tail of near-empty kernel rounds."""
        for ti in self.unplaced:
            p = self._select_serial(ti)
            self.placements[ti] = p
            if p.vm is not None:
                self.avail[self.col[p.vm.vmid]] = False
        self.unplaced = []

    def commit(self, best, tiers, fins, costs_) -> None:
        """Serial-dictatorship prefix commit over the kernel's outputs,
        indexed by task index: the winner of each VM is its
        earliest claimant, and only winners EARLIER than the first loser
        commit this round.  A later round-1 winner could otherwise steal
        the VM an earlier loser takes next — exactly the interleaving
        the sequential reference produces.

        Tasks with no feasible VM (best < 0) resolve *in serial position*
        through :meth:`_select_serial` — the insufficient-budget
        tier-5 rule may take an idle VM, in which case every later task
        this round is deferred (``halted``) and re-auctions against the
        shrunken pool, exactly as the sequential reference would see it."""
        claims: dict = {}
        for ti in self.unplaced:
            j = int(best[ti])
            if j >= 0 and j not in claims:
                claims[j] = ti
        losers = [ti for ti in self.unplaced
                  if int(best[ti]) >= 0 and claims[int(best[ti])] != ti]
        first_loser = min(losers) if losers else None
        next_unplaced = []
        committed = False
        halted = False
        for ti in self.unplaced:
            j = int(best[ti])
            if halted or (first_loser is not None and ti > first_loser):
                next_unplaced.append(ti)
                continue
            if j < 0:
                p = self._select_serial(ti)
                self.placements[ti] = p
                committed = True
                if p.vm is not None:
                    # Tier-5 reuse consumed a VM the kernel scored as
                    # infeasible; later tasks must re-auction without it.
                    self.avail[self.col[p.vm.vmid]] = False
                    halted = True
                continue
            if claims[j] == ti:
                self.placements[ti] = Placement(
                    self.vms[j], None, int(tiers[ti]),
                    int(fins[ti]), float(costs_[ti]))
                self.avail[j] = False
                committed = True
            else:
                next_unplaced.append(ti)
        self.unplaced = next_unplaced
        self.stalled = not committed


# The kernel-round counters ``multi_cycle`` adds to a ``stats`` sink.
KERNEL_COUNTERS = ("kernel_calls", "real_pairs", "kernel_pairs",
                   "staged_bytes", "pull_transfers", "h2d_transfers",
                   "h2d_bytes")


def multi_cycle(cfg: PlatformConfig, requests: Sequence[CycleRequest],
                use_pallas: object = "auto",
                stats: Optional[Dict[str, int]] = None,
                prof: Optional[Dict[str, float]] = None,
                ) -> List[List[Optional[Placement]]]:
    """Run every request's auction to its fixed point, scoring all active
    members' rounds with ONE batched kernel call per round.

    Members are independent simulations, so rounds interleave freely; a
    member drops out as soon as it has no unplaced task, no available VM,
    or a round commits nothing.  The first kernel round fills the resident
    power-of-two ``(B, T, V)`` buffers (``_RoundBuffers``) with every
    member's whole queue, so the batched kernel recompiles per bucket, not
    per round, and uploads them (``aff_ops.upload``); with them goes the
    bucket's resident all-live mask.  Every later round hands the kernel
    the same device arrays and sends only an ``int32 [B, T + V]`` live
    mask (``CycleRequest.live_into``): the unplaced rows and free VMs of
    each member still in the loop, all zero for a member that has left it.
    The bucket and each member's batch row stay fixed for the call, and
    the outputs are read by task index.

    Requests whose remaining task×VM pair product drops below
    ``AUCTION_TAIL_PAIRS`` leave the fixed point and drain serially
    (:meth:`CycleRequest.finish_serial`, bit-exact): conflict tails
    otherwise stretch into dozens of near-empty kernel rounds whose
    dispatch overhead dwarfs the scoring they do.

    ``use_pallas``: False / True / "auto" (Pallas on TPU, jnp elsewhere).

    ``stats``: optional sink of :data:`KERNEL_COUNTERS`, added to per
    kernel round: the round itself, its real pairs (unplaced tasks × VMs
    of each active request), the pairs of the buffers handed to the
    kernel (the resident bucket, which may be larger than the round) and
    the bytes of those nine arrays, the device-to-host transfers that
    bring its outputs back (one: the kernel returns them packed), and the
    host-to-device arrays sent and their bytes (the nine on an auction's
    first round, the live mask on each later one).
    ``prof``: optional engine profile block (``engine.new_engine_profile``)
    that times each round's ``auction.*`` phases.
    """
    pallas = aff_ops.resolve_use_pallas(use_pallas)
    dev = None     # the auction's nine device arrays, from its first round
    row = {}       # request -> its batch row, fixed at the first round
    while True:
        active = []
        for r in requests:
            if not r.active:
                continue
            if len(r.unplaced) * int(r.avail.sum()) < AUCTION_TAIL_PAIRS:
                ph = phase(prof, "auction.tail") if prof is not None \
                    else None
                r.finish_serial()
                if ph is not None:
                    ph.close()
            else:
                active.append(r)
        if not active:
            break
        ph = phase(prof, "auction.stage") if prof is not None else None
        if dev is None:
            Tp = max(_p2(r.T) for r in active)
            Vp = max(_p2(r.V) for r in active)
            # Batch dim rounds to 1, 2, 4, … (a solo auction stays
            # unpadded); rows beyond the active members keep the inert
            # padding.
            Bp = 1 << max(len(active) - 1, 0).bit_length()
            bufs = _ROUND_BUFFERS.rb.get(Bp, Tp, Vp)
            for b, r in enumerate(active):
                r.propose_into(bufs, b)
                row[r] = b
            dev = aff_ops.upload(*bufs)
            Bp, Tp, Vp = bufs[3].shape
            # Before any commit every row is unplaced and every VM free.
            live, sent = aff_ops.all_live(Bp, Tp + Vp), bufs
        else:
            live = np.zeros((Bp, Tp + Vp), np.int32)
            for r in active:
                r.live_into(live[row[r]], Tp)
            sent = (live,)
        if ph is not None:
            ph.close()
        if stats is not None:
            stats["kernel_calls"] += 1
            stats["real_pairs"] += sum(len(r.unplaced) * r.V
                                       for r in active)
            stats["kernel_pairs"] += dev[3].size
            stats["staged_bytes"] += sum(a.nbytes for a in dev)
            stats["h2d_transfers"] += len(sent)
            stats["h2d_bytes"] += sum(a.nbytes for a in sent)
        ph = phase(prof, "auction.dispatch") if prof is not None else None
        res = aff_ops.affinity_batch(
            *dev,
            gs_read=cfg.gs_read_mbps, gs_write=cfg.gs_write_mbps,
            bp_ms=float(cfg.billing_period_ms), use_pallas=pallas,
            packed=True, live=live)
        if ph is not None:
            ph.close()
            ph = phase(prof, "auction.pull")
        best, tiers, fins, costs_ = aff_ops.unpack_host(np.asarray(res))
        if stats is not None:
            stats["pull_transfers"] += 1
        if ph is not None:
            ph.close()
            ph = phase(prof, "auction.commit")
        for r in active:
            b = row[r]
            r.commit(best[b], tiers[b], fins[b], costs_[b])
        if ph is not None:
            ph.close()
    return [r.placements for r in requests]

