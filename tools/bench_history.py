"""Benchmark trend table — ingest ``BENCH_*.json`` artifacts.

``python tools/bench_history.py [DIR ...] [--out FILE] [--check]``

Scans the given directories (default: ``artifacts/bench`` and
``artifacts/exp``) for the benchmark artifacts the suite emits
(``benchmarks/run.py``, ``repro.exp.run``) and prints one markdown
trend table: current headline numbers next to the reference each
artifact records (the sequential oracle's wall, the CI floors), with the
delta.

Default mode is informational (always exits 0).  ``--check`` turns the
table into a regression gate: exit 1 when any *dimensionless* metric
(speedups, shares, ratios — never absolute walls, which don't compare
across machines) regresses beyond ``--tolerance`` (default 25%)
relative to its recorded reference.  The bench-smoke CI job runs it in
this mode so a silent perf slide fails the build instead of scrolling
by in a log.  ``--out`` additionally writes the table to a file (CI
appends it to the job summary).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

DEFAULT_DIRS = ("artifacts/bench", "artifacts/exp")


def _get(d: Dict, *path):
    for key in path:
        if not isinstance(d, dict) or key not in d:
            return None
        d = d[key]
    return d


def _fmt(v: Optional[float], unit: str = "") -> str:
    if v is None:
        return "n/a"
    if isinstance(v, float):
        return f"{v:.4g}{unit}"
    return f"{v}{unit}"


def _delta(cur: Optional[float], ref: Optional[float],
           lower_is_better: bool = False) -> str:
    if cur is None or ref is None or not ref:
        return ""
    pct = (cur - ref) / ref * 100.0
    arrow = ""
    if abs(pct) >= 0.05:
        better = (pct < 0) if lower_is_better else (pct > 0)
        arrow = " ✓" if better else " ✗"
    return f"{pct:+.1f}%{arrow}"


def rows_for(doc: Dict, path: str) -> List[Dict]:
    """Structured metric rows for one artifact.  ``gate=True`` rows are
    dimensionless (machine-portable) and participate in ``--check``;
    absolute wall times stay informational."""
    bench = doc.get("bench", os.path.basename(path))
    out: List[Dict] = []

    def row(metric, cur, ref, ref_label, lower_is_better=False, unit="",
            gate=False):
        out.append({"bench": bench, "metric": metric, "cur": cur,
                    "ref": ref, "ref_label": ref_label,
                    "lower_is_better": lower_is_better, "unit": unit,
                    "gate": gate})

    if bench == "makespan":
        row("batched vs ref speedup", _get(doc, "speedup_batched_vs_ref"),
            1.0, "sequential oracle", gate=True)
        row("batched wall", _get(doc, "batched_wall_s"),
            _get(doc, "ref_wall_s"), "sequential oracle",
            lower_is_better=True, unit="s")
    elif bench == "paper_grid":
        row("grid wall", _get(doc, "wall_s"), None, "", unit="s")
        row("EBPSM/MSLBL makespan ratio",
            _get(doc, "ebpsm_vs_mslbl_makespan_ratio"), 1.0,
            "MSLBL parity", lower_is_better=True, gate=True)
        met = _get(doc, "summary_by_policy", "EBPSM", "budget_met_min")
        row("EBPSM budget-met (min)", met,
            _get(doc, "ebpsm_budget_met_floor"), "CI floor", gate=True)
    else:
        # Unknown artifact: surface its scalar numerics so new benches
        # show up in the trend table without a code change here.
        for key in sorted(doc):
            v = doc[key]
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row(key, v, None, "")
    return out


def collect_rows(dirs: List[str]) -> "tuple[List[str], List[Dict]]":
    """(file list, structured rows) for every artifact under ``dirs``.
    Unreadable artifacts produce a row with ``metric='unreadable'``."""
    files: List[str] = []
    for d in dirs:
        files.extend(sorted(glob.glob(os.path.join(d, "BENCH_*.json"))))
    rows: List[Dict] = []
    for path in files:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            rows.append({"bench": os.path.basename(path),
                         "metric": f"unreadable ({e})", "cur": None,
                         "ref": None, "ref_label": "",
                         "lower_is_better": False, "unit": "",
                         "gate": False})
            continue
        rows.extend(rows_for(doc, path))
    return files, rows


def build_table(files: List[str], rows: List[Dict],
                dirs: List[str]) -> str:
    lines = ["| bench | metric | current | reference | ref source | delta |",
             "|---|---|---|---|---|---|"]
    for r in rows:
        lines.append(
            "| " + " | ".join([
                r["bench"], r["metric"], _fmt(r["cur"], r["unit"]),
                _fmt(r["ref"], r["unit"]), r["ref_label"],
                _delta(r["cur"], r["ref"], r["lower_is_better"])]) + " |")
    if not files:
        return ("bench_history: no BENCH_*.json artifacts under "
                + ", ".join(dirs)
                + " (run benchmarks/run.py or repro.exp.run first)\n")
    header = (f"### Benchmark trend ({len(rows)} metrics from "
              f"{len(files)} artifact(s))\n\n")
    return header + "\n".join(lines) + "\n"


def regressions(rows: List[Dict], tolerance: float) -> List[str]:
    """Gate-row regressions beyond ``tolerance`` (relative, against the
    recorded reference, oriented per row)."""
    fails: List[str] = []
    for r in rows:
        if not r["gate"] or r["cur"] is None or not r["ref"]:
            continue
        cur, ref = float(r["cur"]), float(r["ref"])
        rel = (cur - ref) / abs(ref)
        worse = rel if r["lower_is_better"] else -rel
        if worse > tolerance:
            fails.append(
                f"{r['bench']}: {r['metric']} = {cur:.4g} vs reference "
                f"{ref:.4g} ({r['ref_label']}): {rel:+.1%} is past the "
                f"{tolerance:.0%} tolerance")
    return fails


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dirs", nargs="*", default=list(DEFAULT_DIRS),
                    help="directories to scan for BENCH_*.json "
                         f"(default: {' '.join(DEFAULT_DIRS)})")
    ap.add_argument("--out", default=None,
                    help="also write the markdown table to this file")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when a dimensionless metric (speedup, "
                         "share, ratio) regresses beyond --tolerance vs "
                         "its recorded reference (absolute walls are "
                         "never gated — they don't compare across "
                         "machines)")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="relative regression tolerance for --check "
                         "(default 0.25 = 25%%)")
    args = ap.parse_args(argv)
    dirs = args.dirs or list(DEFAULT_DIRS)
    files, rows = collect_rows(dirs)
    table = build_table(files, rows, dirs)
    print(table, end="")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(table)
    if args.check:
        fails = regressions(rows, args.tolerance)
        if fails:
            print(f"\nbench_history --check: {len(fails)} regression(s):")
            for line in fails:
                print(f"  {line}")
            return 1
        n_gated = sum(1 for r in rows
                      if r["gate"] and r["cur"] is not None and r["ref"])
        print(f"\nbench_history --check: {n_gated} gated metric(s) within "
              f"{args.tolerance:.0%} of reference")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
