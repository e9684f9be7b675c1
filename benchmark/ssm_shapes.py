"""Operations and bytes that the one-mixer-a-block cell's own parts must
do, from shapes alone, so that a share of the chip's peak can be checked by
hand. What is counted is what the algorithm needs, as ``lm_shapes`` and
``attn_shapes`` count: a product recomputed in the backward pass is time
and not operations, so a share reads under what the unit does.
"""

from __future__ import annotations


def expert_products_flops(held_rows: int, dim: int, ffn: int) -> int:
    """The held experts' grouped products over ``held_rows`` routed rows,
    forward and backward, for an expert of the ``relu2`` form: TWO
    matrices (up, down) of ``dim x ffn``, 2 operations a multiply-add, and
    three products a matrix (forward, the input's gradient, the weight's
    gradient): ``2 * 3 * 2 * dim * ffn * held_rows``.
    (``lm_shapes.expert_products_flops`` counts a gate matrix as well and
    would read half again too high here.)"""
    return 2 * 3 * 2 * dim * ffn * held_rows


def scan_flops(sequences: int, positions: int, heads: int, head_dim: int,
               groups: int, state: int, chunk: int) -> int:
    """The chunked state-space scan's four products, forward and backward,
    over ``sequences`` x ``positions``. A chunk of Q positions has ``Q (Q +
    1) / 2`` live (i >= j) pairs. Forward, a chunk: ``C B^T`` once a group
    (``2 state`` a pair), the masked product with ``Xd`` (``2 head_dim`` a
    pair and head), the chunk's state ``B^T Xd`` and ``C H`` (each ``2 Q
    state head_dim`` a head). Each product has two more behind it in the
    backward pass (one a factor): times 3."""
    pairs = chunk * (chunk + 1) // 2
    a_chunk = (2 * state * pairs * groups + 2 * head_dim * pairs * heads
               + 2 * 2 * chunk * state * head_dim * heads)
    return 3 * sequences * (positions // chunk) * a_chunk


def scan_bytes(sequences: int, positions: int, heads: int, head_dim: int,
               groups: int, state: int, operand_bytes: int = 2) -> int:
    """What the scan must read and write, forward and backward: ``x`` and
    ``y`` [positions, heads, head_dim], ``B`` and ``C`` [positions, groups,
    state] in the operands' width and ``dt`` [positions, heads] in float32,
    once forward (read x, B, C, dt; write y) and twice backward (read them
    and ``dy``; write the four gradients). The chunks' states (positions /
    chunk of heads x head_dim x state) stay out: a kernel may keep them on
    the chip."""
    wide = positions * (heads * head_dim + 2 * groups * state) * operand_bytes
    steps = positions * heads * 4
    out = positions * heads * head_dim * operand_bytes
    return sequences * ((wide + steps + out) + 2 * (wide + steps + out))
