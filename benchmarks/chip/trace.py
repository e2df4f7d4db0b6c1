"""Profiler trace → device busy and idle time over the traced window.

``reduce`` reads the ``.xplane.pb`` that ``jax.profiler`` writes. The
device's busy time is the union of the intervals in which an operation
ran on a device plane (``/device:TPU:<n>``), on its ``XLA Ops`` line
where the plane has one, clipped to the window that the benchmark marks
with a host span named ``bench.window``. Busy time is averaged over the
device planes; the idle share is ``1 - busy_s / window_s``. Each idle gap
is named by the innermost ``bench.*`` host span that covers its middle:
what the host was doing while the device waited.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(text: str) -> str:
    """``%fusion.10 = f32[...] fusion(...)`` → ``fusion.10``: a TPU trace
    names each op by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def _planes(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path).planes


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def reduce_planes(planes) -> Optional[Dict]:
    """``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps`` (the
    top ten) of one trace, with every op's seconds (``op_s``) and HLO
    text (``op_text``) by name; None where the trace holds no window span
    or no device plane."""
    window = None
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:TPU"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices.append([(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name)
                            for ln in ops for ev in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith("bench."):
                        spans.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns,
                                       ev.name))
    if window is None or not devices:
        return None
    w0, w1 = window
    busy_ns = 0.0
    op_ns: Dict[str, float] = defaultdict(float)
    op_text: Dict[str, str] = {}
    gap_ns: Dict[str, float] = defaultdict(float)
    for events in devices:
        clipped = [(max(s, w0), min(e, w1), name) for s, e, name in events
                   if e > w0 and s < w1]
        merged = union((s, e) for s, e, _ in clipped)
        busy_ns += sum(e - s for s, e in merged)
        for s, e, text in clipped:
            name = op_name(text)
            op_ns[name] += e - s
            op_text.setdefault(name, text)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gap_ns[_label((s + e) / 2, spans)] += e - s
    n = len(devices)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": _top(op_ns, n),
        "idle_gaps": _top(gap_ns, n),
        "op_s": {name: v / n / 1e9 for name, v in op_ns.items()},
        "op_text": op_text,
    }


def _label(t: float, spans: List[Tuple[float, float, str]]) -> str:
    covering = [(e - s, name) for s, e, name in spans if s <= t <= e]
    return min(covering)[1] if covering else "unattributed"


def _top(ns: Dict[str, float], n_devices: int) -> List[List]:
    ranked = sorted(ns.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, v / n_devices / 1e9] for name, v in ranked]


def reduce(trace_dir: str) -> Optional[Dict]:
    path = find_xplane(trace_dir)
    return None if path is None else reduce_planes(_planes(path))
