"""Operations that a causal and a banded grouped-query attention core
must do, forward and backward, from shapes alone, so that a kernel's
share of the chip's peak can be checked by hand. What is counted is what
the algorithm needs: the scores and probabilities that the backward
kernels recompute, and the forward pass run again for rematerialisation,
are time and not operations, so a share reads under what the unit does.
Grouping changes no count: every query head still meets every live key.
"""

from __future__ import annotations

from typing import Optional


def live_positions(positions: int, window: Optional[int] = None) -> int:
    """The (query, key) position pairs with ``0 <= i - j`` and, under a
    ``window``, ``i - j < window``: the triangle ``S (S + 1) / 2``, or the
    band ``W (W + 1) / 2 + (S - W) W`` where ``W < S``."""
    s = positions
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def core_flops(sequences: int, heads: int, positions: int, head_dim: int,
               window: Optional[int] = None) -> int:
    """One attention core over ``sequences`` x ``heads`` query heads,
    forward and backward: two products forward (``Q K^T``, ``P V``) and
    four backward (``dV = P^T dO``, ``dP = dO V^T``, ``dQ = dS K``, ``dK =
    dS^T Q``), each 2 operations a multiply-add over ``head_dim`` for
    every live position pair: ``12 * head_dim * live``."""
    return (sequences * heads * 12 * head_dim
            * live_positions(positions, window))
