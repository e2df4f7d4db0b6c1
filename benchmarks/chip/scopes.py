"""Profiler trace → device seconds by named scope, host seconds by the
program's own spans.

The program names its work on both sides of one trace (DESIGN.md §10):
device scopes (``jax.named_scope``: ``sample.hop<l>``, ``gather.features``,
``gather.labels``, ``model.fwd_bwd``, ``model.update``) ride each HLO op's
``op_name``, which a TPU trace keeps as the ``tf_op`` stat of each op's
event metadata; host spans (``jax.profiler`` annotations named
``flex.<layer>.<what>``) sit on the host planes, on the device's clock.
JAX's ``ProfileData`` gives an event's own stats but not its metadata's,
so ``op_paths`` reads those from the serialized trace itself.

``reduce`` reads the same ``.xplane.pb`` as ``trace.reduce``, over the same
window (the host span ``bench.window``) and the same device ops, and adds:

- ``scope_s`` and ``scope_n``: device seconds and op events in the
  window by the first component of the first scope name in each op's
  path (``sample``, ``gather``, ``model``), each op counted once, both
  averaged over the device planes; ops with no scope under ``unscoped``;
- ``unscoped_ops``: the ten largest of those, by op name;
- ``span_s`` and ``span_n``: seconds (clipped to the window) and count of
  each ``flex.*`` host span that overlaps the window;
- ``idle_gaps``: the device's idle time in the window by the innermost
  ``bench.*`` or ``flex.*`` span over it, top ten: each gap is cut at the
  spans' edges, and each piece goes to the span covering its middle (a
  gap that runs from one span into the next is split between them).
"""

from __future__ import annotations

import re
from bisect import bisect_left, bisect_right
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks.chip import trace

SCOPE_STAT = "tf_op"
DEVICE_PLANE = "/device:TPU"
SPAN_PREFIX = "flex."
LABEL_PREFIXES = ("bench.", SPAN_PREFIX)
UNSCOPED = "unscoped"

# a scope name is dotted identifiers; transforms (``jit(f)``,
# ``transpose(jvp())``), primitives and argument paths are not
_SCOPE = re.compile(r"[A-Za-z_]\w*(\.\w+)+")


def scope_of(path: str) -> str:
    """``jit(f)/sample.hop0/jit(_take)/gather:`` → ``sample``."""
    for part in path.split("/"):
        if _SCOPE.fullmatch(part):
            return part.split(".", 1)[0]
    return UNSCOPED


# ---------------------------------------------- the XSpace's event metadata
def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: a varint as an int,
    any other field as a slice of ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} before byte {i}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_value(entry):
    """The value (field 2) of a protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``{device plane: {op event name: its SCOPE_STAT}}`` from a
    serialized XSpace (xplane.proto: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4, .stat_metadata 5, both maps; XEventMetadata.name 2,
    .stats 5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1,
    .str_value 5, .ref_value 7, the id of an interned string)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        name, events, interned = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
                if not name.startswith(DEVICE_PLANE):
                    break
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                interned[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not name.startswith(DEVICE_PLANE):
            continue
        paths = out.setdefault(name, {})
        for md in events:
            op, path = "", None
            for f, v in _fields(md):
                if f == 2:
                    op = _text(v)
                elif f == 5:
                    stat = dict(_fields(v))
                    if interned.get(stat.get(1)) == SCOPE_STAT:
                        path = (_text(stat[5]) if 5 in stat
                                else interned.get(stat.get(7), ""))
            if path is not None:
                paths[op] = path
    return out


# ------------------------------------------------------------ the reduction
def label_times(times: List[float], spans: List[Tuple[float, float, str]]
                ) -> List[str]:
    """The innermost (shortest) span covering each of the sorted
    ``times``, as ``trace`` labels a gap, in one sweep."""
    spans = sorted(spans)
    out: List[str] = []
    active: List[Tuple[float, float, str]] = []
    i = 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] >= t]
        out.append(min((e - s, name) for s, e, name in active)[1]
                   if active else "unattributed")
    return out


def reduce_planes(planes, paths: Dict[str, Dict[str, str]]
                  ) -> Optional[Dict]:
    """``scope_s``, ``scope_n``, ``unscoped_ops``, ``span_s``, ``span_n``
    and ``idle_gaps`` of one trace, its ops' paths given by ``op_paths``;
    None where it holds no window span or no device plane."""
    window = None
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == trace.OPS_LINE] or lines
            path = paths.get(plane.name, {})
            devices.append([(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name, path.get(ev.name, ""))
                            for ln in ops for ev in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == trace.WINDOW_SPAN:
                        window = iv
                    elif ev.name.startswith(LABEL_PREFIXES):
                        spans.append(iv + (ev.name,))
    if window is None or not devices:
        return None
    w0, w1 = window
    spans = [sp for sp in spans if sp[1] > w0 and sp[0] < w1]
    cuts = sorted({x for sp in spans for x in sp[:2]})
    scope_ns: Dict[str, float] = defaultdict(float)
    scope_count: Dict[str, int] = defaultdict(int)
    unscoped_ns: Dict[str, float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    for events in devices:
        clipped = [(max(s, w0), min(e, w1), text, path)
                   for s, e, text, path in events if e > w0 and s < w1]
        for s, e, text, path in clipped:
            scope = scope_of(path)
            scope_ns[scope] += e - s
            scope_count[scope] += 1
            if scope == UNSCOPED:
                unscoped_ns[trace.op_name(text)] += e - s
        merged = trace.union((s, e) for s, e, _, _ in clipped)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        pieces = []
        for s, e in zip(edges[0::2], edges[1::2]):
            bounds = [s] + cuts[bisect_right(cuts, s):bisect_left(cuts, e)]
            pieces += [(a, b) for a, b in zip(bounds, bounds[1:] + [e])
                       if b > a]
        for (s, e), name in zip(pieces, label_times(
                [(s + e) / 2 for s, e in pieces], spans)):
            gap_ns[name] += e - s
    span_ns: Dict[str, float] = defaultdict(float)
    span_n: Dict[str, int] = defaultdict(int)
    for s, e, name in spans:
        if name.startswith(SPAN_PREFIX):
            span_ns[name] += min(e, w1) - max(s, w0)
            span_n[name] += 1
    n = len(devices)
    return {
        "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
        "scope_n": {k: v / n for k, v in scope_count.items()},
        "unscoped_ops": trace._top(unscoped_ns, n),
        "span_s": {k: v / 1e9 for k, v in span_ns.items()},
        "span_n": dict(span_n),
        "idle_gaps": trace._top(gap_ns, n),
    }


def reduce(trace_dir: str) -> Optional[Dict]:
    path = trace.find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        xspace = f.read()
    return reduce_planes(ProfileData.from_serialized_xspace(xspace).planes,
                         op_paths(xspace))
