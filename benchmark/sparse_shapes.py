"""Operations and bytes that the learned-sparse-attention cell's own parts
must do, from shapes alone, so that a share can be checked by hand. What is
counted is what the algorithm needs, as ``lm_shapes``, ``attn_shapes``,
``ssm_shapes`` and ``conv_shapes`` count: a product recomputed in the
backward pass, and a key that a kernel computes and the selection masks,
are time and not operations. ``c`` is the configuration file's dictionary.
"""

from __future__ import annotations

from benchmark import attn_shapes


def selected_positions(positions: int, topk: int) -> int:
    """The (query, key) pairs a selection of ``topk`` keys a query keeps of
    one head's causal triangle: query ``t`` keeps ``min(topk, t + 1)``. The
    band's count at a window of ``topk`` (``attn_shapes.live_positions``),
    though the keys kept are the indexer's and not the nearest."""
    return attn_shapes.live_positions(positions, topk)


def core_flops(sequences: int, heads: int, positions: int, head_dim: int,
               topk: int) -> int:
    """One attention core over the SELECTED positions, forward and
    backward: ``attn_shapes.core_flops``'s six products, 2 operations a
    multiply-add over ``head_dim``, for every selected pair."""
    return (sequences * heads * 12 * head_dim
            * selected_positions(positions, topk))


def index_flops(c, positions: int) -> int:
    """One layer's indexer, forward, a token: its three products (``W_qI``,
    ``W_kI``, ``W_w``) and its scores, ``indexer_num_heads`` dots of
    ``indexer_head_dim`` for each of the ``(positions + 1) / 2`` keys a
    query sees on average."""
    sa, d = c["sa_config"], c["hidden_size"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return 2 * d * (hi * di + di + hi) + hi * di * (positions + 1)


def target_flops(c, positions: int) -> int:
    """One layer's target, forward, a token: the query heads' scores over
    the selected keys once more (``pbar`` needs the probabilities of every
    head, which no kernel hands out)."""
    return (2 * c["head_dim"] * c["num_attention_heads"]
            * selected_positions(positions, c["sa_config"]["topk"])
            // positions)


def select_bytes(sequences: int, positions: int) -> int:
    """A layer's selection as the kernels take it: int8 [B, S, S]."""
    return sequences * positions * positions


def step_flops_token(c, positions: int) -> int:
    """The matrix products one token needs in a forward pass of the whole
    step on this chip: every layer's four projections, indexer, selected
    core (``Q K^T`` and ``P V`` over the selected keys), target, router and
    held experts at the EVEN share of ``num_experts_per_tok x held /
    published`` experts a token, and the head's logits."""
    d, h, hkv, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    published = c["published"]["num_experts"]
    selected = selected_positions(positions, c["sa_config"]["topk"])
    layer = (2 * d * hd * 2 * (h + hkv) + index_flops(c, positions)
             + 4 * hd * h * selected // positions
             + target_flops(c, positions) + 2 * d * published
             + 3 * 2 * d * c["moe_intermediate_size"]
             * c["num_experts_per_tok"] * c["num_experts"] // published)
    return 2 * d * c["vocab_size"] + c["num_hidden_layers"] * layer
