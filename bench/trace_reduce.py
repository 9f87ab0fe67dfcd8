"""Reduce a JAX profiler trace to device busy time, per-operation device
time and host-attributed idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
Device planes are those named ``/device:<platform>:<n>``; on each, the
operations are the events of the ``XLA Ops`` line (every event of the plane
where that line is missing).  Host planes carry one line per thread, whose
events are the host's own spans (``jax.profiler.TraceAnnotation``) and the
runtime's.  All times are seconds; the window is the span named
``window_span`` on the host, or the whole trace.

* busy: length of the union of a device's operation intervals inside the
  window, averaged over the devices;
* op_seconds: summed duration of each operation name over all devices;
* idle_gaps: every stretch inside the window in which device 0 ran
  nothing, attributed to the shortest host span that covers its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

DEVICE_LINE = "XLA Ops"


def latest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _device_planes(planes):
    return [p for p in planes if p["name"].startswith("/device:") and p["ops"]]


def planes_from_profile(path: str) -> list[dict]:
    """The trace as plain data: [{"name", "ops": [(name, start_s, end_s)],
    "threads": [[(name, start_s, end_s)], ...]}]."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        by_name = {line.name: line for line in lines}
        entry = {"name": plane.name, "ops": [], "threads": []}
        if plane.name.startswith("/device:"):
            chosen = [by_name[DEVICE_LINE]] if DEVICE_LINE in by_name else lines
            for line in chosen:
                for e in line.events:
                    entry["ops"].append(
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    )
        elif plane.name.startswith("/host:"):
            for line in lines:
                entry["threads"].append(
                    [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    ]
                )
        out.append(entry)
    return out


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _window(planes, window_span: str | None):
    spans = [
        (s, e)
        for p in planes
        for thread in p["threads"]
        for name, s, e in thread
        if name == window_span
    ]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    times = [t for p in planes for op in p["ops"] for t in op[1:]]
    times += [t for p in planes for th in p["threads"] for ev in th for t in ev[1:]]
    return min(times), max(times)


class _HostIndex:
    """Shortest host span covering an instant, over all threads."""

    def __init__(self, planes, lo: float, hi: float):
        self.threads = []
        for p in planes:
            for thread in p["threads"]:
                evs = sorted(
                    (s, e, name) for name, s, e in thread if e > lo and s < hi and e > s
                )
                if evs:
                    self.threads.append(([s for s, _, _ in evs], evs))

    def label(self, t: float, max_walk: int = 256) -> str:
        best = None
        for starts, evs in self.threads:
            i = bisect.bisect_right(starts, t) - 1
            walked = 0
            while i >= 0 and walked < max_walk:
                s, e, name = evs[i]
                if e >= t and (best is None or e - s < best[0]):
                    best = (e - s, name)
                    break
                i -= 1
                walked += 1
        return best[1] if best else "no host span"


def reduce_planes(planes, window_span: str | None = None, top: int = 10) -> dict:
    devices = _device_planes(planes)
    if not devices:
        return {}
    lo, hi = _window(planes, window_span)
    busy = []
    op_seconds: dict[str, float] = defaultdict(float)
    for p in devices:
        for name, s, e in p["ops"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_seconds[name] += d
        busy.append(sum(e - s for s, e in _union([op[1:] for op in p["ops"]], lo, hi)))
    first = _union([op[1:] for op in devices[0]["ops"]], lo, hi)
    gaps, t = [], lo
    for s, e in first + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    index = _HostIndex(planes, lo, hi)
    gap_seconds: dict[str, float] = defaultdict(float)
    for s, e in gaps:
        gap_seconds[index.label((s + e) / 2)] += e - s
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "devices": len(devices),
        "op_seconds": dict(op_seconds),
        "device_ops": [[k, v] for k, v in rank(op_seconds)],
        "idle_gaps": [[k, v] for k, v in rank(gap_seconds)],
    }


def reduce_trace(log_dir: str, window_span: str | None = None) -> dict:
    return reduce_planes(planes_from_profile(latest_xplane(log_dir)), window_span)


def op_seconds_matching(trace: dict, pattern: str) -> float:
    """Device seconds of the operations whose name matches ``pattern``."""
    import re

    rx = re.compile(pattern)
    return sum(v for k, v in trace.get("op_seconds", {}).items() if rx.search(k))
