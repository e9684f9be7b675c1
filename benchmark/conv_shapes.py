"""Operations and bytes that the short-convolution cell's own parts must do,
from shapes alone, so that a share can be checked by hand. What is counted
is what the algorithm needs, as ``lm_shapes``, ``attn_shapes`` and
``ssm_shapes`` count: a product recomputed in the backward pass is time and
not operations. ``c`` is the configuration file's dictionary.
"""

from __future__ import annotations


def mixer_flops(dim: int) -> int:
    """One gated short-convolution mixer's matrix products, forward, a
    token: the in-projection ``dim -> 3 dim`` and the out-projection ``dim
    -> dim``, 2 operations a multiply-add. The gates and the taps between
    them (``2 + 2 taps`` operations a channel) are no matrix product and
    are left out, as a norm is."""
    return 2 * dim * 3 * dim + 2 * dim * dim


def mixer_bytes(sequences: int, positions: int, dim: int,
                operand_bytes: int = 2) -> int:
    """What the pass between a mixer's two products must read and write,
    forward: the in-projection's result ``[positions, 3 dim]`` in and the
    gated, convolved ``[positions, dim]`` out, in the operands' width (the
    taps themselves are ``taps x dim`` floats and nothing beside them)."""
    return sequences * positions * (3 * dim + dim) * operand_bytes


def step_flops_token(c, positions: int) -> int:
    """The matrix products one token needs in a forward pass of the whole
    step on this chip: every block's mixer (a conv mixer's two products; an
    attention's four projections and its causal core's ``Q K^T`` and ``P
    V`` over the ``(positions + 1) / 2`` keys a query sees on average) and
    feed-forward (three matrices dense; a router and the held experts at
    the EVEN share of ``num_experts_per_tok x held / published`` experts a
    token), and the tied head's logits."""
    d, h, hkv = (c["hidden_size"], c["num_attention_heads"],
                 c["num_key_value_heads"])
    hd = d // h
    total = 2 * d * c["vocab_size"]
    for i, layer in enumerate(c["layers_run"]):
        if c["layer_types"][layer] == "conv":
            total += mixer_flops(d)
        else:
            total += 2 * d * hd * 2 * (h + hkv) + 2 * hd * h * (positions + 1)
        if i < c["num_dense_layers"]:
            total += 3 * 2 * d * c["intermediate_size"]
        else:
            published = c["published"]["num_experts"]
            total += 2 * d * published + (
                3 * 2 * d * c["moe_intermediate_size"]
                * c["num_experts_per_tok"] * c["num_experts"] // published)
    return total
