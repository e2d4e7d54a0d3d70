"""CI gate over the makespan benchmark artifact.

``python -m benchmarks.check_speedup [--floor F] [--path P]``

``BENCH_makespan.json`` — the batched engine must stay at or above the
speedup floor vs the sequential reference, with parity intact.

Exit non-zero when the artifact is missing, the speedup regressed below
its floor, or parity broke.  The default floor leaves headroom for
shared-runner noise.  Speed is measured on the chip (``bench/``); the
Algorithm-3 share of a cell's time is its ``host_redistribute_share``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

DEFAULT_PATH = "artifacts/bench/BENCH_makespan.json"
DEFAULT_FLOOR = 0.95


def _check_makespan(path: pathlib.Path, floor: float) -> None:
    if not path.exists():
        sys.exit(f"missing benchmark artifact: {path}")
    art = json.loads(path.read_text())
    speedup = float(art.get("speedup_batched_vs_ref", 0.0))
    bit_exact = bool(art.get("bit_exact", False))
    print(
        f"batched-vs-reference speedup {speedup:.3f} (floor {floor}), "
        f"bit_exact={bit_exact}, grid_members={art.get('grid_members')}"
    )
    if not bit_exact:
        sys.exit("FAIL: batched engine lost bit-exact parity with reference")
    if speedup < floor:
        sys.exit(
            f"FAIL: speedup {speedup:.3f} regressed below floor {floor}"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--path", default=DEFAULT_PATH)
    ap.add_argument("--floor", type=float, default=DEFAULT_FLOOR)
    args = ap.parse_args()

    _check_makespan(pathlib.Path(args.path), args.floor)
    print("benchmark gate OK")


if __name__ == "__main__":
    main()
