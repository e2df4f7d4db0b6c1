"""Kernels: a chip's superstep scatter as a share (%) of its HBM
bandwidth. Bytes: the chip's arcs, a target id and a value each (8 B),
times the supersteps of the traced window (one exchange of the
length-n message buffer each). Time: the device time, a chip, of every
op whose HLO text holds an array of the fragment's length (``[arcs]`` or
``[1,arcs]``): the gather of the sources' values, their mask, the sort
and the scatter into the message buffer; the loops that hold them
(``trace.CONTAINERS``) are not counted twice. Nothing found, nothing
returned."""

import re

from benchmarks.chip import flops, trace


def read(run, suffix):
    t = run.trace_summary
    if not t:
        return None
    _, steps = trace.collectives(t, f"[{run.dataset['n']}]")
    arcs = len(run.dataset["indices"]) // run.cell.chips
    edge = re.compile(rf"\[(1,)?{arcs}\]")
    secs = sum(s for name, s in t["op_s"].items()
               if edge.search(t["op_text"][name])
               and trace.opcode(t["op_text"][name]) not in trace.CONTAINERS)
    if not steps or secs <= 0:
        return None
    return 100.0 * steps * 8 * arcs / secs / flops.peak(
        run.device_kind, "hbm_bytes_per_s")
