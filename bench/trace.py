"""Device trace: record one episode with the JAX profiler and reduce it.

The reduction works on a plain list of events ``[plane, line, name,
start_ns, dur_ns]`` (:func:`events_from_xplane`), so that the same code
reads a trace just recorded and the small recorded trace kept with the
tests (the first 300 device ops of a traced ``grid-montage`` episode on a
TPU v5 lite, with the ``bench.*`` host spans cut to their extent).  Device ops are the events of the ``XLA Ops`` line of the first TPU
plane; host spans are the harness's own ``bench.*`` annotations.
"""
from __future__ import annotations

import glob
import json
import os
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

KERNEL = "affinity_argmin"
DEVICE_PLANE = "/device:TPU:0"
DEVICE_LINE = "XLA Ops"
SPAN_PREFIX = "bench."

Event = Tuple[str, str, str, float, float]


def profile_options():
    from jax import profiler
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0   # host spans only: no per-call tracing
    opts.host_tracer_level = 2
    return opts


def events_from_xplane(log_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax import profiler
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    data = profiler.ProfileData.from_file(files[-1])
    out: List[Event] = []
    for plane in data.planes:
        keep_plane = plane.name.startswith("/device:") \
            or plane.name.startswith("/host:")
        if not keep_plane:
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, op_name(ev.name),
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%affinity_argmin.1 = (s32[8,512,1]...) custom-call(...)``); keep
    the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def device_ops(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if e[0] == DEVICE_PLANE and e[1] == DEVICE_LINE]


def host_spans(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if e[0].startswith("/host:")
            and e[2].startswith(SPAN_PREFIX)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(events: Sequence[Event]) -> Optional[Dict[str, object]]:
    """Busy and window seconds, kernel device seconds and calls, the top
    device ops and the longest idle gaps labelled by the innermost
    ``bench.*`` host span around them.  None when the trace holds no
    device op or no episode span."""
    ops = device_ops(events)
    spans = host_spans(events)
    episodes = [e for e in spans if e[2] == SPAN_PREFIX + "episode"]
    if not ops or not episodes:
        return None
    w0 = min(e[3] for e in episodes)
    w1 = max(e[3] + e[4] for e in episodes)
    clipped = [(max(e[3], w0), min(e[3] + e[4], w1)) for e in ops
               if e[3] + e[4] > w0 and e[3] < w1]
    busy = _union(clipped)
    busy_ns = sum(e - s for s, e in busy)
    by_name: Dict[str, float] = {}
    for e in ops:
        base = re.sub(r"\.\d+$", "", e[2])   # copy-start.4 -> copy-start
        by_name[base] = by_name.get(base, 0.0) + e[4]
    kernel = [e for e in ops if KERNEL in e[2]]
    gaps = []
    prev = w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)

    def label(t: float) -> str:
        inner = [sp for sp in spans if sp[3] <= t <= sp[3] + sp[4]]
        if not inner:
            return "outside bench spans"
        return min(inner, key=lambda sp: sp[4])[2]

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "device_ops": len(ops),
        "kernel_s": sum(e[4] for e in kernel) / 1e9,
        "kernel_calls": len(kernel),
        "top_ops": [[n, t / 1e9] for n, t in
                    sorted(by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[label(0.5 * (s + e)), (e - s) / 1e9]
                      for s, e in gaps[:10]],
    }


def load(path: Path) -> List[Event]:
    return [tuple(e) for e in json.loads(Path(path).read_text())]
