"""The device's idle time put down to the host span it falls under.

``bench/trace.py`` labels the longest idle gaps of a traced episode by the
innermost ``bench.*`` span around them.  This module also reads the
program's own phase spans, ``repro.*``, which an engine records while it
runs with ``profile=True`` (``core.engine.phase``), and puts every idle
second of the episode down to the innermost span open at that moment, in
one sweep over the sorted span boundaries.  It works on the same plain
event lists as ``bench/trace.py``.

    python3 bench/spans.py --workload <cell> --seed <n>
        [--compare <pairs>] [--save <path> [--ops <n>]]

runs on the TPU: the cell's set-up and one untimed warm-up episode as
``bench/run.py`` makes them, then one traced episode with the program's
spans, and prints one JSON line: busy and window seconds, idle seconds by
innermost span, the share of idle time under a ``repro.*`` span, and the
longest idle gaps labelled by both kinds of span.  ``--compare`` first
times that many pairs of untraced episodes, profile off then on, for the
cost of the program's phase brackets.  ``--save`` writes a trimmed copy of
the trace, as the recorded traces kept with the tests: ``--ops`` device
ops from the first auction on, with the spans cut to their extent.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Optional, Sequence

if __package__ in (None, ""):
    sys.path[:0] = [str(Path(__file__).resolve().parent.parent / "src"),
                    str(Path(__file__).resolve().parent.parent)]

from bench import trace as tr  # noqa: E402

PREFIXES = ("bench.", "repro.")
PROGRAM = "repro."
OUTSIDE = "outside spans"


def host_spans(events: Sequence[tr.Event]) -> List[tr.Event]:
    return [e for e in events if e[0].startswith("/host:")
            and e[2].startswith(PREFIXES)]


def _window(events):
    ops = tr.device_ops(events)
    spans = host_spans(events)
    episodes = [e for e in spans if e[2] == "bench.episode"]
    if not ops or not episodes:
        return None
    w0 = min(e[3] for e in episodes)
    w1 = max(e[3] + e[4] for e in episodes)
    busy = tr._union([(max(e[3], w0), min(e[3] + e[4], w1)) for e in ops
                      if e[3] + e[4] > w0 and e[3] < w1])
    gaps, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return w0, w1, busy, gaps, spans


def idle_by_span(events: Sequence[tr.Event]) -> Optional[Dict[str, float]]:
    """Idle seconds of the ``bench.episode`` window by the innermost span
    open (the one entered last; of two entered together, the shorter), in
    descending order; None without a device op or an episode span."""
    w = _window(events)
    return None if w is None else _idle_by_span(*w)


def _idle_by_span(w0, w1, busy, gaps, spans) -> Dict[str, float]:
    marks = []
    for i, s in enumerate(spans):
        a, b = max(s[3], w0), min(s[3] + s[4], w1)
        if b > a:
            marks += [(a, 1, i), (b, 0, i)]
    marks.sort()             # at one instant, spans close before others open
    open_: Dict[int, tuple] = {}
    out: Dict[str, float] = {}
    g = 0

    def credit(a: float, b: float) -> None:
        nonlocal g
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        idle = 0.0
        j = g
        while j < len(gaps) and gaps[j][0] < b:
            idle += min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
        if idle > 0:
            name = max(open_.values())[2] if open_ else OUTSIDE
            out[name] = out.get(name, 0.0) + idle / 1e9

    t = w0
    for at, opens, i in marks:
        if at > t:
            credit(t, at)
            t = at
        if opens:
            s = spans[i]
            open_[i] = (s[3], -s[4], s[2])
        else:
            del open_[i]
    credit(t, w1)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def attributed_share(by_span: Dict[str, float]) -> Optional[float]:
    """Percent of the idle seconds that fall under a ``repro.*`` span."""
    total = sum(by_span.values())
    if not total:
        return None
    return 100.0 * sum(v for k, v in by_span.items()
                       if k.startswith(PROGRAM)) / total


def reduce(events: Sequence[tr.Event], n_gaps: int = 10
           ) -> Optional[Dict[str, object]]:
    """Busy and window seconds, idle seconds by innermost span, the share
    under the program's spans, and the ``n_gaps`` longest idle gaps
    labelled by the innermost span at their midpoint."""
    w = _window(events)
    if w is None:
        return None
    w0, w1, busy, gaps, spans = w
    by_span = _idle_by_span(*w)

    def label(t: float) -> str:
        inner = [sp for sp in spans if sp[3] <= t <= sp[3] + sp[4]]
        return min(inner, key=lambda sp: sp[4])[2] if inner else OUTSIDE

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "idle_s": sum(by_span.values()),
        "idle_attributed_share": attributed_share(by_span),
        "idle_by_span": by_span,
        "idle_gaps": [[label(0.5 * (s + e)), (e - s) / 1e9]
                      for s, e in longest],
    }


def trim(events: Sequence[tr.Event], n_ops: int) -> List[tr.Event]:
    """The first ``n_ops`` device ops from the start of the first
    ``repro.auction.build`` span on, with the host spans that overlap
    them cut to that interval (``bench.episode`` becomes the interval)."""
    spans = host_spans(events)
    a = min(s[3] for s in spans if s[2] == PROGRAM + "auction.build")
    ops = sorted((e for e in tr.device_ops(events) if e[3] >= a),
                 key=lambda e: e[3])[:n_ops]
    b = max(e[3] + e[4] for e in ops)
    cut = []
    for s in spans:
        s0, s1 = max(s[3], a), min(s[3] + s[4], b)
        if s1 > s0:
            cut.append((s[0], s[1], s[2], s0, s1 - s0))
    return sorted([list(e) for e in ops] + [list(s) for s in cut],
                  key=lambda e: (e[3], -e[4]))


def traced_events(cfg, cell, streams, plan, kernel) -> List[tr.Event]:
    """One episode under the profiler, with the ``bench.episode`` and
    ``bench.round`` spans of ``bench/run.py`` and the program's own."""
    from jax import profiler

    from bench import harness as H

    log_dir = tempfile.mkdtemp(prefix="bench-spans-")
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
                H.run_episode(cfg, cell, streams, plan, kernel, profile=True,
                              on_round=on_round)
                if span[0] is not None:
                    span[0].__exit__(None, None, None)
        finally:
            profiler.stop_trace()
        return tr.events_from_xplane(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--compare", type=int, default=0)
    ap.add_argument("--save")
    ap.add_argument("--ops", type=int, default=300)
    args = ap.parse_args(argv)
    import jax

    from bench import generator as gen
    from bench import harness as H
    from bench import spec
    from bench.run import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench/spans.py: JAX found no TPU")
    use_compile_cache()
    cell = spec.resolve(args.workload)
    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    plan = H.member_plan(cell, streams, args.seed)
    kernel = H.KernelCalls()
    out: Dict[str, object] = {"workload": cell.name, "seed": args.seed}
    try:
        H.run_episode(cfg, cell, streams, plan, kernel)
        pairs = [[H.run_episode(cfg, cell, streams, plan, kernel,
                                profile=p).seconds for p in (False, True)]
                 for _ in range(args.compare)]
        if pairs:
            out["episode_s_profile_off_on"] = pairs
        events = traced_events(cfg, cell, streams, plan, kernel)
    finally:
        kernel.close()
    out.update(reduce(events) or {})
    if args.save:
        Path(args.save).parent.mkdir(parents=True, exist_ok=True)
        Path(args.save).write_text(json.dumps(trim(events, args.ops)))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
