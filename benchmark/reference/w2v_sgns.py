"""Skip-gram with negative sampling, plainly: float32 ``jax.numpy`` at
``highest`` matmul precision, gradients by autodiff of the written-down
objective (Mikolov et al. 2013, eq. 4), duplicates accumulated and the
SGD push applied in NumPy. Independent of ``multiverso_tpu``: the
program's steps derive their gradients by hand, this file does not.

Objective of one batch, summed over its pairs (c, x) with negatives n:

    L = - sum_pairs [ log s(v_c . u_x) + w * sum_n log s(-v_c . u_n) ]

``w`` is 1 for per-pair negatives ``[B, K]``. For a pool ``[K']`` shared
by the whole batch (the program's ``shared_negatives``) every pair meets
every pool word and ``w = negative / K'`` rescales to the published
objective. The program ascends with ``lr`` on this sum (not its mean) and
reports ``L / B`` as the loss; so does this file.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import rules


def _objective(v, up, un, neg_weight):
    pos = jnp.sum(v * up, axis=-1)
    if un.ndim == 2:                          # shared pool [K', D]
        neg = v @ un.T
    else:                                     # per pair [B, K, D]
        neg = jnp.einsum("bd,bkd->bk", v, un)
    return -(jnp.sum(jax.nn.log_sigmoid(pos))
             + neg_weight * jnp.sum(jax.nn.log_sigmoid(-neg)))


_value_and_grad = jax.jit(jax.value_and_grad(_objective, argnums=(0, 1, 2)))


def step(win: np.ndarray, wout: np.ndarray, centers: np.ndarray,
         contexts: np.ndarray, negatives: np.ndarray, lr: float,
         neg_weight: float = 1.0) -> Tuple[float, Dict[str, np.ndarray]]:
    """One batch on float32 tables ``win``/``wout`` (any row count that
    the ids index). Returns ``(loss, {"in_ids", "in_delta", "out_ids",
    "out_delta"})``: the unique touched rows of each table and the delta
    the SGD push adds to them."""
    win = np.asarray(win, np.float32)
    wout = np.asarray(wout, np.float32)
    with jax.default_matmul_precision("highest"):
        v, up, un = (jnp.asarray(win[centers]), jnp.asarray(wout[contexts]),
                     jnp.asarray(wout[negatives]))
        loss, (gv, gup, gun) = _value_and_grad(v, up, un, neg_weight)
    d = win.shape[1]
    in_ids, in_delta = rules.dedupe(centers, -lr * np.asarray(gv))
    out_ids, out_delta = rules.dedupe(
        np.concatenate([contexts, np.asarray(negatives).reshape(-1)]),
        -lr * np.concatenate([np.asarray(gup),
                              np.asarray(gun).reshape(-1, d)]))
    return float(loss) / centers.size, {
        "in_ids": in_ids, "in_delta": in_delta,
        "out_ids": out_ids, "out_delta": out_delta}


def dynamic_window_pairs(ids: np.ndarray, window: int,
                         rng: np.random.Generator
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """(centers, contexts) of a token stream as word2vec draws them: each
    position takes a width b uniform in 1..window and pairs with the b
    tokens on either side; in the order of the centres' positions."""
    n = ids.size
    b = rng.integers(1, window + 1, size=n)
    at, other = [], []
    for d in range(1, window + 1):
        right = np.flatnonzero(b[:n - d] >= d)        # context at i + d
        left = np.flatnonzero(b[d:] >= d) + d         # context at i - d
        at += [right, left]
        other += [right + d, left - d]
    at, other = np.concatenate(at), np.concatenate(other)
    order = np.argsort(at, kind="stable")
    return ids[at[order]], ids[other[order]]


def block_inputs(ids: np.ndarray, slots: np.ndarray, window: int,
                 negative: int, seed: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """What training one block of the stream needs, drawn plainly:
    (centers [P], contexts [P], negatives [P, negative]). The block's
    dynamic-window pairs, shuffled so that a minibatch mixes positions (a
    minibatch of neighbours would sum one word's updates at one stale
    value), and per pair ``negative`` words drawn uniformly from ``slots``
    (word2vec.c's sampling table: word ids, a word's slots in proportion
    to its count to the power 3/4)."""
    rng = np.random.default_rng([int(seed), 0x626C6B])       # "blk"
    centers, contexts = dynamic_window_pairs(ids, window, rng)
    order = rng.permutation(centers.size)
    negs = slots[rng.integers(0, slots.size, size=(centers.size, negative))]
    return centers[order], contexts[order], negs


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _sgd_batch(win, wout, centers, contexts, negatives, lr):
    v, up, un = win[centers], wout[contexts], wout[negatives]
    loss, (gv, gup, gun) = jax.value_and_grad(
        _objective, argnums=(0, 1, 2))(v, up, un, 1.0)
    # .at[].add sums the deltas of duplicate ids, as the push does
    win = win.at[centers].add(-lr * gv)
    wout = wout.at[contexts].add(-lr * gup)
    wout = wout.at[negatives.reshape(-1)].add(
        -lr * gun.reshape(-1, gun.shape[-1]))
    return win, wout, loss / centers.size


def train_pairs(win: np.ndarray, wout: np.ndarray, centers: np.ndarray,
                contexts: np.ndarray, negatives: np.ndarray, batch: int,
                lr: float) -> Tuple[np.ndarray, np.ndarray, float]:
    """Train the pairs on copies of ``win``/``wout`` in whole minibatches
    of ``batch`` (a last partial one is dropped, as the program drops
    it), one SGD step of the objective above after another, in
    ``jax.numpy`` at ``highest`` precision: :func:`step`'s arithmetic
    without the walk through NumPy, which at 37 minibatches of 49,152
    negative rows took most of a minute. Returns the trained tables and
    the mean loss."""
    with jax.default_matmul_precision("highest"):
        win, wout = jnp.array(win, jnp.float32), jnp.array(wout, jnp.float32)
        losses = []
        for lo in range(0, centers.size - batch + 1, batch):
            win, wout, loss = _sgd_batch(
                win, wout, jnp.asarray(centers[lo:lo + batch]),
                jnp.asarray(contexts[lo:lo + batch]),
                jnp.asarray(negatives[lo:lo + batch]), lr)
            losses.append(loss)
    return np.asarray(win), np.asarray(wout), float(np.mean(losses))
