"""Operations that a looped decoder's stack and exits (``multiverso_tpu/
models/mla_moe._passes`` and ``_exit_loss``, ``models/ouro.py``) do a
training step, from shapes alone, so that ``loop.stack_mxu_share.lm`` and
``loop.head_mxu_share.lm`` can be checked by hand. ``c`` is the
configuration file's dictionary. 2 operations a multiply-add.

The stack's count is of every pass of its products that RUNS, unlike
``lm_shapes``' and ``attn_shapes``' (which leave out what is made again):
the seconds it is held against are those of the whole looped stack outside
its attention cores, the blocks made again for the backward pass among
them, and the question the share answers is how near the matrix unit's peak
those seconds run. A block's products run four times a block application:
forward, forward made again under ``jax.checkpoint``, and the backward
pass's two (the input's gradient and the weight's). A kernel that made
nothing again would read LOWER by this count and is judged by the seconds.
The exits' count is the three products of positions x vocabulary the
chunked loss makes (logits, the gradient to the state, the gradient to the
head), once each: nothing of the loss is made again.
"""

from __future__ import annotations

RUNS_OF_A_PRODUCT = 4      # forward, made again, two backward


def block_weights(c) -> int:
    """The numbers in one block's matrices: q and o ``hidden x heads x
    head``, k and v ``hidden x key-value heads x head``, the gated MLP's
    three ``hidden x intermediate``."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd
            + 3 * d * c["intermediate_size"])


def block_runs(c) -> int:
    """The block applications of a step: every layer, every pass."""
    return c["num_hidden_layers"] * c["total_ut_steps"]


def stack_flops(c, sequences: int, positions: int) -> int:
    """The products of the blocks' projections and MLPs over a step, every
    pass of them that runs: ``block_runs x positions x 2 x block_weights x
    RUNS_OF_A_PRODUCT`` (the attention cores' are ``attn_shapes``')."""
    return (block_runs(c) * sequences * positions * 2 * block_weights(c)
            * RUNS_OF_A_PRODUCT)


def head_flops(c, sequences: int, positions: int) -> int:
    """The exits' products over a step: 3 products x ``total_ut_steps``
    exits x positions x vocabulary x hidden."""
    return (3 * c["total_ut_steps"] * sequences * positions * 2
            * c["vocab_size"] * c["hidden_size"])

