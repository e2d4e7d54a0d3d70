"""Benchmark driver — one module per paper table/figure + the bridge +
roofline.  ``python -m benchmarks.run [--full] [--only NAME]``.

Prints ``name,seconds,key=value...`` lines and writes one CSV per bench
into artifacts/bench/.
"""
from __future__ import annotations

import argparse
import inspect
import time
import traceback

from repro.launch.cache import use_compile_cache

from . import (bench_container_delay, bench_cost_ratio,
               bench_cpu_degradation, bench_grid_wall, bench_makespan,
               bench_prov_delay, bench_roofline, bench_sched_throughput,
               bench_stream_scale, bench_waas_ml)
from .common import print_rows, write_json

BENCHES = {
    "makespan": (bench_makespan, "Fig3+4 makespan/budget/VMs vs rate"),
    "cpu_degradation": (bench_cpu_degradation, "Fig5-6 CPU degradation"),
    "prov_delay": (bench_prov_delay, "Fig7-8 provisioning delay"),
    "container_delay": (bench_container_delay, "Fig9 container delay"),
    "cost_ratio": (bench_cost_ratio, "Table3 violated cost/budget"),
    "sched_throughput": (bench_sched_throughput, "Alg2 kernel throughput"),
    "grid_wall": (bench_grid_wall, "paper-smoke grid end-to-end wall"),
    "stream_scale": (bench_stream_scale,
                     "SoA vs object state at open-stream member scale"),
    "waas_ml": (bench_waas_ml, "WaaS->ML bridge platform"),
    "roofline": (bench_roofline, "roofline from dry-run artifacts"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale workloads (1000 workflows)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    if args.only and args.only not in BENCHES:
        raise SystemExit(
            f"unknown benchmark {args.only!r}; choose from {sorted(BENCHES)}")
    use_compile_cache()

    failures = []
    for name, (mod, desc) in BENCHES.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            rows = mod.run(full=args.full)
            dt = time.time() - t0
            print(f"\n### {name},{dt:.1f}s — {desc} ({len(rows)} rows)")
            print_rows(name, rows[:24])
            if hasattr(mod, "artifact"):
                if "full" in inspect.signature(mod.artifact).parameters:
                    art = mod.artifact(rows, full=args.full)
                else:
                    art = mod.artifact(rows)
                path = write_json(f"BENCH_{name}", art)
                print(f"artifact: {path}")
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"### {name} FAILED: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks complete; CSVs in artifacts/bench/")


if __name__ == "__main__":
    main()
