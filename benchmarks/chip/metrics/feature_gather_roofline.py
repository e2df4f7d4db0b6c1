"""Kernels: the feature gather's share (%) of the chip's HBM bandwidth,
from the trace.

Bytes: the rows that a step has to read from the feature table, every
frontier of the sampled batch (``flops.sage_gather_bytes``), times the
steps of the traced window. Time: the device time, in that window, of
every operation whose HLO text names the padded feature table's shape
(``f32[n + 1, feature_dim]``): the gathers that read it and any copy of
the table. Nothing found, nothing returned."""

from benchmarks.chip import flops


def read(run, suffix):
    t, steps = run.trace_summary, run.extra.get("window_steps")
    if not t or not steps:
        return None
    graph, job = run.cell.config["graph"], run.cell.mix
    table = f"f32[{run.dataset['n'] + 1},{graph['feature_dim']}]"
    secs = sum(s for name, s in t["op_s"].items()
               if table in t["op_text"][name])
    if secs <= 0:
        return None
    moved = steps * flops.sage_gather_bytes(job["batch_size"], job["fanouts"],
                                            graph["feature_dim"])
    return 100.0 * moved / secs / flops.peak(run.device_kind,
                                             "hbm_bytes_per_s")
