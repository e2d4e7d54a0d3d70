"""Chip smoke test: drive the scheduler's main path once on one TPU chip.

    python chip_smoke.py

Three phases, each checked against the repository's own oracles:

* kernel — the compiled Pallas affinity kernel against the jnp oracle
  (``affinity_ref``) on seeded random pairs at ``(B, T, V) = (8, 1024,
  1024)``; indices bit-equal, finish and cost estimates bit-equal;
* grid — one ``paper`` cell at the paper's own size (montage, 12 wf/min,
  budget interval (0.25, 0.5), seed 0, 100 workflows of all three sizes,
  all five policies) through ``simulate_batch``;
* stream — the ``online-heavy`` tenant mix through ``run_online``, then
  each seed through ``simulate_batch``, which must report the same
  dispatch counters.

The grid and stream phases keep ``use_pallas="auto"`` and check every
policy's per-workflow finish times and costs, and the VM counts, of the
``simulate_batch`` run whose counters they print against
``SimEngine(batched=False)`` — the host-only sequential oracle, which
never calls the kernel.  Wall and compile seconds and the compile count
are set-up information, not device metrics.

Exits non-zero, printing no result, when JAX finds no TPU or when any phase
fails.  The last line of stdout is the JSON result.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


class _CompileClock:
    """Sums JAX's compile-duration events (tracing, lowering, backend
    compile) and counts backend compiles between :meth:`lap` calls."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration
            self.compiles += event == self.BACKEND

    def lap(self) -> tuple[float, int]:
        out = (self.total, self.compiles)
        self.total, self.compiles = 0.0, 0
        return out


def _phase_done(name: str, t0: float, clock: _CompileClock) -> None:
    secs, n = clock.lap()
    print(f"[{name}] set-up timings: wall {time.perf_counter() - t0:.3f} s, "
          f"compile {secs:.3f} s over {n} XLA compiles (host clock)",
          flush=True)


def kernel_phase(cfg, B: int = 8, T: int = 1024, V: int = 1024,
                 seed: int = 0) -> None:
    """Compiled Pallas kernel vs ``affinity_ref`` on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.affinity import ops as aff_ops

    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    args = (
        f32(rng.uniform(10, 900, (B, T))),               # size_mi
        f32(rng.uniform(1, 150, (B, T))),                # out_mb
        f32(rng.uniform(5, 500, (B, T))),                # budget
        f32(rng.uniform(0, 200, (B, T, V))),             # missing_mb
        f32(rng.choice([0., 400., 10000.], (B, T, V))),  # cont_ms
        jnp.asarray(rng.choice([0, 1, 2, 3], (B, T, V)), jnp.int32),
        f32(rng.choice([2., 4., 8., 16.], (B, V))),      # vm_mips
        f32(rng.choice([10., 20., 25.], (B, V))),        # vm_bw
        f32(rng.choice([1., 2., 4., 8.], (B, V))),       # vm_price
    )
    kw = dict(gs_read=cfg.gs_read_mbps, gs_write=cfg.gs_write_mbps,
              bp_ms=float(cfg.billing_period_ms))
    kernel = jax.jit(lambda *a: aff_ops.affinity_batch(
        *a, use_pallas=True, **kw)).lower(*args).compile()
    pal = kernel(*args)
    ref = aff_ops.affinity_batch(*args, use_pallas=False, **kw)
    bad = {}
    for name, a, b in zip(ref._fields, pal, ref):
        a, b = np.asarray(a), np.asarray(b)
        bad[name] = int((a.view(np.uint32) != b.view(np.uint32)).sum())
    n_none = int((np.asarray(ref.best_vm) < 0).sum())
    print(f"[kernel] (B, T, V) = ({B}, {T}, {V}), seed {seed}: "
          f"no-feasible-VM rows {n_none}; elements that differ from "
          f"affinity_ref: {bad}", flush=True)
    if any(bad.values()):
        _fail(f"kernel differs from affinity_ref: {bad}")
    if "tpu_custom_call" not in kernel.as_text():
        _fail("the compiled affinity program holds no tpu_custom_call")
    print("[kernel] the compiled program holds the kernel (tpu_custom_call)",
          flush=True)


COUNTERS = ("rounds", "batched_calls", "batched_cycles", "serial_cycles")


def _mismatches(ref, res) -> int:
    """Count the oracle disagreements of one simulation: workflows whose
    finish time or cost differ, plus one if the VM counts differ."""
    n = sum(a.finish_ms != b.finish_ms or a.cost != b.cost
            for a, b in zip(ref.workflows, res.workflows))
    n += len(ref.workflows) != len(res.workflows)
    n += ref.vm_count_by_type != res.vm_count_by_type
    return n


def _report(name: str, use_pallas, dispatch) -> object:
    """Print the dispatch counters; returns the ``batched`` mode the
    parity run must use so that it drives the kernel."""
    from repro.kernels.affinity import ops as aff_ops

    pallas = aff_ops.resolve_use_pallas(use_pallas)
    print(f"[{name}] use_pallas={use_pallas} resolves to "
          f"{'the Pallas kernel' if pallas else 'the jnp oracle'}; "
          + ", ".join(f"{k} {dispatch[k]}" for k in COUNTERS), flush=True)
    if not pallas:
        _fail(f"{name}: use_pallas did not resolve to the Pallas kernel")
    if dispatch["batched_calls"] > 0:
        return "auto"
    print(f"[{name}] the auto dispatcher never called the kernel at this "
          f"size; the parity run uses batched=True", flush=True)
    return True


def parity(name: str, cfg, policies, wl, seed, br, chaos=None) -> int:
    """Each policy's ``simulate_batch`` entry against
    ``SimEngine(batched=False)``, per workflow; returns the mismatches."""
    from repro.core.engine import SimEngine
    from repro.core.types import clone_workload

    total = 0
    for pol, entry in zip(policies, br.entries):
        ref = SimEngine(cfg, pol, clone_workload(wl), seed=seed,
                        batched=False, chaos=chaos).run()
        n = _mismatches(ref, entry.result)
        total += n
        print(f"[{name}] parity {pol.name} seed {seed} ({len(wl)} "
              f"workflows): {n} mismatches vs SimEngine(batched=False)",
              flush=True)
    return total


def grid_phase(cfg) -> None:
    from repro.core.jax_engine import simulate_batch
    from repro.exp.scenarios import POLICY_BY_NAME, get_scenario
    from repro.workflows.workload import cell_workload

    sc = dataclasses.replace(get_scenario("paper"), apps=("montage",),
                             rates=(12.0,), budget_intervals=((0.25, 0.5),),
                             seeds=(0,))
    cell = next(sc.workload_cells())
    wl = cell_workload(cfg, cell.app, cell.rate, cell.budget_interval,
                       cell.workload_seed, sc.n_workflows, sc.sizes)
    print(f"[grid] cell {cell.app} {cell.rate:g} wf/min budget "
          f"{cell.budget_interval} seed {cell.seed}: {len(wl)} workflows, "
          f"{sum(w.n_tasks for w in wl)} tasks", flush=True)
    policies = [POLICY_BY_NAME[p] for p in sc.policies]
    br = simulate_batch(cfg, policies, wl, seed=cell.seed)
    if _report("grid", "auto", br.dispatch) is True:
        br = simulate_batch(cfg, policies, wl, seed=cell.seed, batched=True)
    if n := parity("grid", cfg, policies, wl, cell.seed, br):
        _fail(f"grid: {n} mismatches against SimEngine")


def stream_phase(cfg) -> None:
    from repro.core.jax_engine import simulate_batch
    from repro.exp.run import run_online
    from repro.exp.scenarios import POLICY_BY_NAME, get_scenario

    sc = get_scenario("online-heavy")
    art = run_online(sc)
    batched = _report("stream", art["use_pallas"], art["dispatch"])
    policies = [POLICY_BY_NAME[p] for p in sc.policies]
    seen = dict.fromkeys(COUNTERS, 0)
    total = 0
    for seed in sc.seeds:
        wl = sc.mix.build(cfg, seed).workflows
        br = simulate_batch(cfg, policies, wl, seed=seed, batched=batched,
                            chaos=sc.chaos)
        for k in COUNTERS:
            seen[k] += br.dispatch[k]
        total += parity("stream", cfg, policies, wl, seed, br, sc.chaos)
    print(f"[stream] parity runs' counters: {seen}", flush=True)
    if batched == "auto" and any(seen[k] != art["dispatch"][k]
                                 for k in COUNTERS):
        _fail("stream: the parity runs' counters differ from run_online's")
    if total:
        _fail(f"stream: {total} mismatches against SimEngine")


def main() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}; run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"JAX found no TPU (default device is {dev.platform!r}); "
              f"this script needs one TPU chip")
    from repro.core.types import PlatformConfig
    from repro.launch.cache import use_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {use_compile_cache()}",
          flush=True)
    clock = _CompileClock()
    cfg = PlatformConfig()
    for name, phase in (("kernel", lambda: kernel_phase(cfg)),
                        ("grid", lambda: grid_phase(cfg)),
                        ("stream", lambda: stream_phase(cfg))):
        t0 = time.perf_counter()
        phase()
        _phase_done(name, t0, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
