"""Bytes and operations that the stream maps of a hyper-connected decoder
(``multiverso_tpu/models/mla_moe.block`` under several residual streams)
must move and do, from shapes alone, so that ``hc.stream_hbm_share.lm`` can
be checked by hand. What is counted is the least ANY implementation must
move, as ``shapes.py`` counts for the tables: a later fused kernel is read
by the same yardstick and cannot read over 100%. A pass made again for
rematerialisation is time and not bytes, as ``lm_shapes`` and ``attn_shapes``
count operations. ``c`` is the configuration file's dictionary.

A position's streams ``X`` are ``n x C`` float32 (``hc_mult`` x
``hidden_size``); a sublayer's branch ``F`` reads ``u`` and writes ``y``,
``C`` float32 each. ``F`` stands between a sublayer's read of ``X`` (the
norm, the projection, ``u = H_pre X``) and its write (``X' = H_res X +
outer(H_post, y)``), and a step's streams (235 MB at 4,096 positions) stay
in no cache, so:

* forward, a sublayer: the write ``X'`` needs ``X`` and ``y`` again: read
  ``X`` (nC) and ``y`` (C), write ``X'`` (nC). The NEXT sublayer's maps and
  its ``u`` are a position's own business and can be made while ``X'`` is
  written: one more write of ``u`` (C), and no second read. ``(2n + 2) C``
  floats.
* backward, a sublayer: before ``F``'s backward pass ``dy = H_post . dX'``
  and the gradients of ``H_post`` and ``H_res`` need ``dX'`` (nC), ``X``
  (nC) and ``y`` (C), and write ``dy`` (C); after it ``dX = H_res^T dX' +
  outer(H_pre, du)`` + what flows through the maps (whose norm's gradient
  is a multiple of ``X``, and whose ``dH_pre = du . X``) needs ``du`` (C),
  ``X`` (nC) and ``dX'`` (nC) again, and writes ``dX`` (nC). ``(5n + 3) C``
  floats.

The maps themselves (``n^2 + 2n`` floats a position), the projection's
table and the embedding's copy are left out: under a thousandth of the
streams.
"""

from __future__ import annotations


def sublayers(c) -> int:
    """The hyper-connected sublayers of a step: two a block, the prediction
    module's block among them."""
    return 2 * (c["num_hidden_layers"] + c["num_nextn_predict_layers"])


def sublayer_bytes(c, positions: int) -> int:
    """What ONE sublayer's stream maps must move over ``positions``,
    forward and backward: ``(2n + 2) + (5n + 3) = 7n + 5`` arrays of
    ``hidden_size`` float32 a position."""
    n = c["hc_mult"]
    return positions * (7 * n + 5) * c["hidden_size"] * 4


def step_bytes(c, sequences: int, positions: int) -> int:
    """What a training step's stream maps must move."""
    return sublayers(c) * sublayer_bytes(c, sequences * positions)


def sublayer_flops(c, positions: int) -> int:
    """What ONE sublayer's maps must compute over ``positions``, forward
    and backward (each product has two behind it: times 3): the mean square
    (2 nC), the projection (2 nC (n^2 + 2n)), ``H_pre X`` (2 nC), ``H_res
    X`` (2 n^2 C), ``outer(H_post, y)`` added (2 nC) and Sinkhorn's
    ``hc_sinkhorn_iters`` x 2 normalisations of n^2 numbers (a sum and a
    quotient each)."""
    n, d = c["hc_mult"], c["hidden_size"]
    outs = n * n + 2 * n
    once = (2 * n * d * (3 + outs) + 2 * n * n * d
            + c["hc_sinkhorn_iters"] * 2 * 2 * n * n)
    return 3 * positions * once
