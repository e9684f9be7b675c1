"""Operations that the language-model cells' kernels must do, from shapes
alone, so that a share of the chip's peak can be checked by hand. What is
counted is what the algorithm needs: a product recomputed in the backward
pass (rematerialisation, the flash kernel's scores) is not counted, as in
a model's FLOP/s utilization, so a share reads under what the unit does.
"""

from __future__ import annotations


def expert_products_flops(held_rows: int, dim: int, ffn: int) -> int:
    """The held experts' grouped products over ``held_rows`` routed rows,
    forward and backward: three matrices (gate, up, down) of ``dim x
    ffn``, 2 operations a multiply-add, and three products a matrix
    (forward, the input's gradient, the weight's gradient).
    ``3 * 3 * 2 * dim * ffn * held_rows``. The padding rows of the sorted
    buffer are work the chip does and the algorithm does not need."""
    return 3 * 3 * 2 * dim * ffn * held_rows
