"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- device busy time: the union of the intervals in which an operation ran on
  a device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  averaged over the devices;
- time per program: the summed durations of each program's events on the
  ``XLA Modules`` line, keyed by its name without the ``(<id>)`` suffix;
- the operations that took most time;
- the idle gaps between busy intervals, each named by the innermost of the
  benchmark's host spans (``TraceAnnotation``) open at its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

TOP = 10


def _device_planes(pd):
    return [p for p in pd.planes
            if re.fullmatch(r"/device:TPU:\d+", p.name)]


def _line(plane, name):
    return next((ln for ln in plane.lines if ln.name == name), None)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """An operation's HLO name (``%fusion.12``) without its text."""
    return name.split(" = ", 1)[0]


def _host_spans(pd, names):
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name in names:
                    out.append((ev.start_ns, ev.end_ns, ev.name))
    return out


def reduce(pd, window_s: float, span_names) -> dict:
    """The trace's numbers (see the module docstring). ``window_s`` is the
    length of the traced window on the host clock; ``span_names`` are the
    benchmark's own host spans."""
    planes = _device_planes(pd)
    busy, modules, ops = [], {}, {}
    first = None
    for plane in planes:
        op_line = _line(plane, "XLA Ops") or _line(plane, "XLA Modules")
        ivs = []
        if op_line is not None:
            for ev in op_line.events:
                ivs.append((ev.start_ns, ev.end_ns))
                op = _op_name(ev.name)
                ops[op] = ops.get(op, 0.0) + ev.duration_ns * 1e-9
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first is None:
            first = merged
        mod_line = _line(plane, "XLA Modules")
        if mod_line is not None:
            for ev in mod_line.events:
                k = _module_name(ev.name)
                sec, n = modules.get(k, (0.0, 0))
                modules[k] = (sec + ev.duration_ns * 1e-9, n + 1)

    gaps: dict = {}
    spans = sorted(_host_spans(pd, set(span_names)))
    starts = [sp[0] for sp in spans]
    for (_, e0), (s1, _) in zip(first or [], (first or [])[1:]):
        mid = (e0 + s1) / 2
        # the benchmark's spans do not nest: the one that started last
        # before the midpoint is the only one that can be open at it
        i = bisect.bisect_right(starts, mid) - 1
        name = spans[i][2] if i >= 0 and spans[i][1] >= mid else "other"
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0) * 1e-9
    return {
        "devices": len(planes),
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_s,
        "modules": modules,
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:TOP],
    }


def find_xplane(directory: str) -> str | None:
    hits = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def reduce_dir(directory: str, window, span_names) -> dict:
    """Reduce the newest trace under ``directory``; ``window`` is the
    (start, end) of the traced window on ``time.perf_counter``."""
    from jax.profiler import ProfileData

    window_s = window[1] - window[0]
    path = find_xplane(directory)
    if path is None:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s,
                "modules": {}, "device_ops": [], "idle_gaps": []}
    return reduce(ProfileData.from_file(path), window_s, span_names)
