"""Profiler trace → device busy and idle time over the traced window.

``reduce`` reads the ``.xplane.pb`` that ``jax.profiler`` writes. The
device's busy time is the union of the intervals in which an operation
ran on a device plane (``/device:TPU:<n>``), on its ``XLA Ops`` line
where the plane has one, clipped to the window that the benchmark marks
with a host span named ``bench.window``. Busy time is averaged over the
device planes; the idle share is ``1 - busy_s / window_s``. Each idle gap
is named by the innermost ``bench.*`` host span that covers its middle:
what the host was doing while the device waited. ``reduce`` adds what
``scopes`` reads of the program's own names from the same trace.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10
# the HLO opcodes that move data between chips
COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start|-done)?")
# ops whose events span the events of the ops they hold
CONTAINERS = ("while", "call", "conditional")


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


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def reduce_planes(planes) -> Optional[Dict]:
    """``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps`` (the
    top ten) of one trace, with every op's seconds (``op_s``), events
    (``op_n``) and HLO text (``op_text``) by name, seconds and events
    averaged over the device planes; None where the trace holds no
    window span or no device plane."""
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
    op_count: Dict[str, int] = defaultdict(int)
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
            op_count[name] += 1
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
        "op_n": {name: v / n for name, v in op_count.items()},
        "op_text": op_text,
    }


def _label(t: float, spans: List[Tuple[float, float, str]]) -> str:
    covering = [(e - s, name) for s, e, name in spans if s <= t <= e]
    return min(covering)[1] if covering else "unattributed"


def _top(ns: Dict[str, float], n_devices: int) -> List[List]:
    ranked = sorted(ns.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, v / n_devices / 1e9] for name, v in ranked]


def opcode(text: str) -> str:
    """The opcode of an op from its HLO text (``%x = f32[8]{0} fusion(...``
    → ``fusion``): the first word after the result's shape that opens a
    parenthesis."""
    found = re.search(r"\s([a-z][a-z0-9-]*)\(", text.partition(" = ")[2])
    return found.group(1) if found else ""


def collectives(summary: Dict, shape: Optional[str] = None
                ) -> Tuple[float, float]:
    """Device seconds and calls, a device, of the ops that exchange data
    between chips (a ``COLLECTIVE`` opcode); with ``shape`` (``[n]``), of
    those alone whose HLO text holds it. An asynchronous collective's
    ``-done`` half adds its seconds but is not a call."""
    secs = calls = 0.0
    for name, text in summary["op_text"].items():
        code = opcode(text)
        if COLLECTIVE.fullmatch(code) and (shape is None or shape in text):
            secs += summary["op_s"][name]
            if not code.endswith("-done"):
                calls += summary["op_n"][name]
    return secs, calls


def reduce(trace_dir: str) -> Optional[Dict]:
    """``reduce_planes`` of the trace in ``trace_dir``, with what
    ``scopes.reduce_planes`` reads of the program's own names merged in:
    ``scope_s``, ``scope_n``, ``unscoped_ops``, ``span_s``, ``span_n``."""
    from benchmarks.chip import scopes

    path = find_xplane(trace_dir)
    if path is None:
        return None
    with open(path, "rb") as f:
        xspace = f.read()
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_serialized_xspace(xspace).planes)
    summary = reduce_planes(planes)
    if summary is not None:
        named = scopes.reduce_planes(planes, scopes.op_paths(xspace))
        summary.update((k, v) for k, v in named.items() if k != "idle_gaps")
    return summary
