"""The table's update rules in NumPy float32, and how a sparse batch of
row deltas meets them. Copied from ``chip_smoke.py`` (``_np_dedupe``,
``_np_adagrad_rows``, ``_np_default_rows``), which states them against
the reference implementation; ``benchmark/tests`` holds the two copies
together. Nothing here imports the program."""

from __future__ import annotations

from typing import Tuple

import numpy as np

ADAGRAD_EPS = 1e-10


def dedupe(ids: np.ndarray, vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Duplicate ids in one call sum their deltas (float64 accumulate, one
    cast) before the updater sees them."""
    uids, inv = np.unique(ids, return_inverse=True)
    acc = np.zeros((uids.size, vals.shape[1]), np.float64)
    np.add.at(acc, inv.reshape(-1), vals.astype(np.float64))
    return uids, acc.astype(np.float32)


def adagrad_rows(data, g_sqr, ids, vals, lr: float, rho: float,
                 eps: float = ADAGRAD_EPS) -> None:
    """adagrad, in place: G += d^2 / lr^2 ; data -= d * rho / (sqrt(G) + eps)."""
    uids, d = dedupe(ids, vals)
    lr, rho = np.float32(lr), np.float32(rho)
    g_sqr[uids] += np.square(d) / np.square(lr)
    data[uids] -= d * rho / (np.sqrt(g_sqr[uids]) + np.float32(eps))


def default_rows(data, ids, vals) -> None:
    """default (plain SGD push), in place: data += delta."""
    uids, d = dedupe(ids, vals)
    data[uids] += d


def adagrad_step(g, g_sqr_old, lr: float, rho: float,
                 eps: float = ADAGRAD_EPS):
    """What one AdaGrad application subtracts from a value whose gradient
    is ``g`` and whose history is ``g_sqr_old`` (elementwise, float64).
    Monotone in ``g``; the drivers use it to turn a tolerance on the
    gradient into one on the updated value."""
    g = np.asarray(g, np.float64)
    hist = np.asarray(g_sqr_old, np.float64) + np.square(g) / lr ** 2
    return g * rho / (np.sqrt(hist) + eps)
