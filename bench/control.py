"""Readings for the limit of ``correct``: the program as the configuration
states it, and the control, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 ... --control-seeds 7 8 9

For each ``--seeds`` seed it runs one episode of the cell on the chip and
compares a seed-drawn sample of members with the plain reference, exactly
as ``bench/run.py`` does after its window.  For each ``--control-seeds``
seed it puts the control in the program's place: the reference itself,
computed in float32, the precision below the configuration's float64
(``guarantees.precision``).  One JSON line per reading.  ``bench/run.py``
never runs the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(cell, seeds, control_seeds, require_tpu: bool = True):
    import jax
    import numpy as np

    from bench import generator as gen
    from bench import harness as H
    from bench import reference

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("bench/control.py: JAX found no TPU")
    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    kernel = H.KernelCalls()
    try:
        for seed in seeds:
            plan = H.member_plan(cell, streams, seed)
            t0 = time.perf_counter()
            ep = H.run_episode(cfg, cell, streams, plan, kernel)
            t1 = time.perf_counter()
            checked = H.check(cell, streams, plan,
                              H.program_answer(ep.results), seed)
            yield {"kind": "program", "seed": seed, **checked,
                   "episode_s": t1 - t0,
                   "reference_s": time.perf_counter() - t1}
    finally:
        kernel.close()
    for seed in control_seeds:
        plan = H.member_plan(cell, streams, seed)

        def control(m, streams=streams, plan=plan):
            i, name = plan[m]
            return reference.simulate(cell.conf, name, streams[i].workload,
                                      streams[i].degradation_seed,
                                      num=np.float32)

        t0 = time.perf_counter()
        checked = H.check(cell, streams, plan, control, seed)
        yield {"kind": "control", "seed": seed, **checked,
               "reference_s": time.perf_counter() - t0}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import spec
    from bench.run import use_compile_cache

    cell = spec.resolve(args.workload)
    use_compile_cache()
    for r in readings(cell, args.seeds, args.control_seeds):
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
