"""Learning: the whole step's share (%) of the chip's bf16 peak, from the
GraphSAGE products that a step needs (``flops.py``) times the steps per
second of the window. It bounds the kernels' roofline shares: a kernel
taken off the path leaves its own share silent, not this one."""

from benchmarks.chip import flops


def read(run, suffix):
    steps = run.extra.get("window_steps")
    if not steps or run.window_s <= 0:
        return None
    job, graph = run.cell.mix, run.cell.config["graph"]
    per_step = flops.sage_train_flops(job["batch_size"], job["fanouts"],
                                      graph["feature_dim"], job["hidden"],
                                      graph["n_classes"])
    return 100.0 * per_step * steps / run.window_s / flops.peak(
        run.device_kind)
