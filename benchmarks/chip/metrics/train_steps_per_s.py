"""Fused GraphSAGE steps completed over the window's seconds; each step
ends when its loss reaches the host."""


def read(run, suffix):
    steps = run.extra.get("window_steps")
    return steps / run.window_s if steps and run.window_s > 0 else None
