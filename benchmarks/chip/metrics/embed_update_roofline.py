"""Kernels: the sparse embedding update's share (%) of the chip's HBM
bandwidth, from the trace.

Bytes: the distinct embedding rows the traced window's steps updated
(the program's count, summed by the runner as ``embed_rows``), each read
and written once (``flops_rgcn.embed_update_bytes``). Time: the device
time of the program's scope ``embed.update`` in that window
(``scope_s["embed"]``). Nothing found, nothing returned."""

from benchmarks.chip import flops, flops_rgcn


def read(run, suffix):
    t, rows = run.trace_summary, run.extra.get("embed_rows")
    secs = (t or {}).get("scope_s", {}).get("embed", 0.0)
    if not rows or secs <= 0:
        return None
    moved = flops_rgcn.embed_update_bytes(
        rows, run.cell.config["graph"]["feature_dim"])
    return 100.0 * moved / secs / flops.peak(run.device_kind,
                                             "hbm_bytes_per_s")
