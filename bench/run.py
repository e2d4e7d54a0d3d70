"""Benchmark of the simulated multi-tenant WaaS platform on one TPU chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once: builds the cell's workload
streams, puts its members in an order drawn from ``--seed``, warms up with one untimed episode, then runs whole
episodes back to back until their timed seconds reach ``--seconds`` (the
last episode finishes).  With ``--trace 0`` it reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from the
window's counters and from a device trace of one extra episode.  Either
way it checks every episode against the first and a seed-drawn sample of
members against the plain reference (``bench/reference.py``).

The last line of stdout is the JSON result; the numbers compared and their
limits are the last lines of stderr.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for, or when the
program (``src/repro``) is not beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    raise SystemExit(1)


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it itself), else ``.jax_cache/`` in the checkout.  Every
    compile is cached, however short: the affinity buckets compile in
    about 0.2 s each, under JAX's default threshold of 1 s."""
    import os

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)


def run(cell, seed: int, seconds: float, trace: bool,
        t_start: float = T_START) -> dict:
    """Everything after the look for a chip: set-up, warm-up, window,
    per-layer record, checks.  Returns the result object."""
    import jax

    from bench import generator as gen
    from bench import harness as H
    from bench import spec

    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    plan = H.member_plan(cell, streams, seed)
    clock = H.CompileClock()
    kernel = H.KernelCalls(clock)
    try:
        warm = H.run_episode(cfg, cell, streams, plan, kernel, profile=trace)
        setup_s = time.perf_counter() - t_start
        window = []
        clock.on = True
        while sum(e.seconds for e in window) < seconds:
            window.append(H.run_episode(cfg, cell, streams, plan, kernel,
                                        profile=trace))
        clock.on = False
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        device_out = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": int(stats.get("peak_bytes_in_use",
                                                         0))}
        traced = reduced = None
        if trace:
            traced, reduced = _traced_episode(cfg, cell, streams, plan,
                                              kernel)
    finally:
        kernel.close()

    episodes = [warm] + window + ([traced] if traced else [])
    differing = sum(e.digest != warm.digest for e in episodes)
    checked = H.check(cell, streams, plan,
                      H.program_answer(window[-1].results), seed)
    print(f"episodes: {len(window)} timed ({sum(e.seconds for e in window):.3f}"
          f" s), {len(plan)} members, {window[0].tasks} member-tasks and "
          f"{sum(len(e.rounds_s) for e in window)} rounds in the window; "
          f"set-up {setup_s:.3f} s; window compile requests "
          f"{clock.compiles} ({clock.cache_hits} served by the persistent "
          f"cache), in kernel buckets (B, T, V) "
          f"{sorted(set(kernel.compiled))}",
          flush=True)

    metrics = {}
    if not trace:
        values = {"tasks_per_s": H.tasks_per_s(window), "setup_s": setup_s}
        values.update(H.round_quantiles_ms(window))
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        record = _record(window, clock, reduced, traced,
                         spec.peaks(device_out["kind"])
                         if device_out["platform"] == "tpu" else None)
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]

    checks = {"mismatches": {"value": checked["mismatches"], "limit": 0},
              "episodes_differing": {"value": differing, "limit": 0}}
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(window) * sum(len(streams[i].workload)
                                       for i, _ in plan),
        "failed": checked["mismatches"],
        "metrics": metrics,
        "device": device_out,
    }
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    print(f"reference: {checked['members']} members, {checked['compared']} "
          f"workflows compared", flush=True)
    return out


def _traced_episode(cfg, cell, streams, plan, kernel):
    """One extra episode under the profiler, with ``bench.episode`` and
    ``bench.round`` host spans; returns it and the reduced trace."""
    from jax import profiler

    from bench import harness as H
    from bench import trace as tr

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    span = [None]

    def on_round():
        if span[0] is not None:
            span[0].__exit__(None, None, None)
        span[0] = profiler.TraceAnnotation("bench.round")
        span[0].__enter__()

    try:
        profiler.start_trace(log_dir, profiler_options=tr.profile_options())
        try:
            with profiler.TraceAnnotation("bench.episode"):
                ep = H.run_episode(cfg, cell, streams, plan, kernel,
                                   profile=True, on_round=on_round)
                if span[0] is not None:
                    span[0].__exit__(None, None, None)
        finally:
            profiler.stop_trace()
        events = tr.events_from_xplane(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return ep, tr.reduce(events)


def _record(window, clock, reduced, traced, peak):
    """What the per-layer readers read."""
    from bench import harness as H
    from bench import kernel_cost

    dispatch = {k: sum(e.dispatch[k] for e in window) for k in H.COUNTERS}
    profile = {}
    for e in window:
        for k, v in (e.dispatch.get("profile") or {}).items():
            profile[k] = profile.get(k, 0.0) + v
    rec = {
        "timed_s": sum(e.seconds for e in window),
        "dispatch": dispatch,
        "profile": profile,
        "window_compiles": clock.compiles,
        "kernel_calls": sum(len(e.kernel_shapes) for e in window),
        "trace": reduced,
        "peak": peak,
    }
    if reduced is not None and peak is not None:
        rec["least_kernel_s"] = kernel_cost.least_seconds(
            traced.kernel_shapes, peak)
        rec["traced_kernel_calls"] = len(traced.kernel_shapes)
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail(f"--seed must be >= 0, got {args.seed}")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is not beside the benchmark: no {ROOT / 'src'}"
             f"/repro")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import spec

    cell = spec.resolve(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (default device is {devices[0].platform!r});"
             f" this benchmark measures the chip and has no CPU fallback")
    if len(devices) < cell.chips:
        fail(f"cell {cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}")
    cache = use_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}; jax "
          f"{jax.__version__}; compile cache {cache}", flush=True)
    out = run(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
