"""Learning: the whole R-GCN step's share (%) of the chip's bf16 peak, from
the products a step needs in the dense form (``flops_rgcn.py``) times the
steps per second of the window."""

from benchmarks.chip import flops, flops_rgcn
from benchmarks.chip.runners.rgcn_train import model_dims, n_relations


def read(run, suffix):
    steps = run.extra.get("window_steps")
    if not steps or run.window_s <= 0:
        return None
    job, cfg = run.cell.mix, run.cell.config
    per_step = flops_rgcn.rgcn_train_flops(
        job["batch_size"], job["fanouts"], model_dims(cfg, job),
        n_relations(cfg))
    return 100.0 * per_step * steps / run.window_s / flops.peak(
        run.device_kind)
