"""Operations and bytes of an R-GCN training step, from its shapes.

Kept with the benchmark so that every PR counts the same way, beside
``flops.py``. Of the operations only the matrix products of the dense
form count: per layer, each updated row takes one product by its node
type's root weight and one by each relation's weight, whatever the
relations its draws hold. Sampling, gathers, the per-relation means and
the loss are left out.
"""

from __future__ import annotations

from typing import Sequence


def rgcn_forward_flops(batch: int, fanouts: Sequence[int],
                       dims: Sequence[int], n_relations: int) -> int:
    """Layer ``l`` (widths ``dims[l] → dims[l + 1]``, the last the
    classes) updates the frontiers at depth ``0 .. k-l-1``, depth ``d``
    holding ``batch·∏fanouts[:d]`` rows, each with ``1 + n_relations``
    products of ``dims[l]`` by ``dims[l + 1]``."""
    k = len(fanouts)
    rows = [batch]
    for f in fanouts:
        rows.append(rows[-1] * f)
    return sum(sum(rows[:k - l]) * 2 * dims[l] * dims[l + 1]
               * (1 + n_relations) for l in range(k))


def rgcn_train_flops(*args, **kwargs) -> int:
    """Forward and backward: the backward pass takes twice the forward's
    products (the gradients of the inputs and of the weights)."""
    return 3 * rgcn_forward_flops(*args, **kwargs)


def embed_update_bytes(rows: int, dim: int) -> int:
    """Bytes a sparse update of ``rows`` distinct float32 rows of width
    ``dim`` has to move: each row read once and written once, the least
    any implementation can move. The gradients and indices read are not
    counted."""
    return rows * dim * 4 * 2
