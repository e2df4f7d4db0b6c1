"""Operations of a step, from its shapes, and the chip's peaks.

Kept with the benchmark so that every PR counts the same way. Of the
operations only the matrix products count: sampling, gathers, the
neighbour mean and the loss are left out, so a share of the peak
computed from these counts is a lower bound of what the step issues.
Of the bytes only the feature rows a step must read count, and of a
GRAPE superstep only its arcs' targets and values and its vertices'
state.
"""

from __future__ import annotations

import json
import os
from typing import Sequence

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def sage_forward_flops(batch: int, fanouts: Sequence[int], feature_dim: int,
                       hidden: int, n_classes: int) -> int:
    """Mean-aggregator GraphSAGE on one sampled batch: layer ``l`` updates
    the frontiers at depth ``0 .. k-l-1`` (depth ``d`` holds
    ``batch·∏fanouts[:d]`` rows) with two products of its input width by
    its output width (self and neighbour mean); then the output layer."""
    k = len(fanouts)
    rows = [batch]
    for f in fanouts:
        rows.append(rows[-1] * f)
    dims = [feature_dim] + [hidden] * k
    flops = 0
    for layer in range(k):
        updated = sum(rows[:k - layer])
        flops += updated * 2 * (2 * dims[layer]) * dims[layer + 1]
    return flops + 2 * batch * hidden * n_classes


def sage_train_flops(*args, **kwargs) -> int:
    """Forward and backward: the backward pass takes twice the forward's
    products (the gradients of the inputs and of the weights)."""
    return 3 * sage_forward_flops(*args, **kwargs)


def sage_gather_bytes(batch: int, fanouts: Sequence[int],
                      feature_dim: int) -> int:
    """Bytes a step has to read from the float32 feature table: one row
    for every vertex of every frontier, ``batch·∏fanouts[:d]`` rows at
    depth ``d``. Writing the gathered rows out, and reading the sampled
    ids, are not counted."""
    rows, total = batch, batch
    for f in fanouts:
        rows *= f
        total += rows
    return total * feature_dim * 4


def grape_superstep_bytes(n_vertices: int, arcs: int) -> int:
    """Bytes one GRAPE superstep has to move: each arc's target id and
    value (4 B each), and each vertex's state read and written (4 B
    each). The arcs' sources, the mask and the message buffer's
    read-modify-write are not counted."""
    return 8 * arcs + 8 * n_vertices


def peak(device_kind: str, what: str = "bf16_flops_per_s") -> float:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return float(table[device_kind][what])
