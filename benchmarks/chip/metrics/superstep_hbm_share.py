"""Analytics: the traced window's share (%) of its chips' HBM bandwidth,
from the bytes the supersteps in it have to move
(``flops.grape_superstep_bytes``). A superstep is one exchange of the
length-n message buffer between chips: one collective call a chip of an
array ``[n]`` (the residual's scalar exchange is not one). It bounds
``scatter_roofline``."""

from benchmarks.chip import flops, trace


def read(run, suffix):
    t = run.trace_summary
    if not t or t["window_s"] <= 0:
        return None
    _, steps = trace.collectives(t, f"[{run.dataset['n']}]")
    if not steps:
        return None
    moved = steps * flops.grape_superstep_bytes(
        run.dataset["n"], len(run.dataset["indices"]))
    return 100.0 * moved / t["window_s"] / (
        run.cell.chips * flops.peak(run.device_kind, "hbm_bytes_per_s"))
