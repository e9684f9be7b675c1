"""Operations and bytes that the delta-rule cell's own parts must do, from
shapes alone, so that a share can be checked by hand. What is counted is
what the algorithm needs, as ``lm_shapes``, ``attn_shapes``, ``ssm_shapes``
and ``conv_shapes`` count: a product recomputed in the backward pass is time
and not operations, and HOW the chunk's triangular inverse ``T`` is made is
no part of it (a kernel may make it otherwise). ``c`` is the configuration
file's dictionary.
"""

from __future__ import annotations


def rule_flops_chunk(c, chunk: int) -> int:
    """What the chunked rule must compute in ONE chunk of ``chunk``
    positions of one layer, forward: ``K K^T`` (the pairs ``i > j``) and ``q
    K^T`` (``i >= j``) once a KEY head; a value head's ``T Vb`` and ``T Kb``
    (``T`` is lower triangular), the masked product with ``V'``, and the
    three whole products with the state (``W S``, ``q S``, ``K~^T V'``). 2
    operations a multiply-add."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    low = chunk * (chunk + 1) // 2
    return (hk * 2 * dk * (low - chunk + low)
            + hv * (2 * low * (dv + dk) + 2 * low * dv
                    + 3 * 2 * chunk * dk * dv))


def rule_flops(c, sequences: int, positions: int, chunk: int) -> int:
    """One layer's chunked rule over ``sequences`` x ``positions``, forward
    and backward: each product has two more behind it in the backward pass
    (one a factor): times 3."""
    return 3 * sequences * (positions // chunk) * rule_flops_chunk(c, chunk)


def rule_bytes(c, sequences: int, positions: int,
               operand_bytes: int = 2) -> int:
    """What one layer's rule must read and write, forward and backward:
    ``q``, ``k`` [positions, key heads, dk], ``v`` and ``o`` [positions,
    value heads, dv] in the operands' width and ``g``, ``beta`` [positions,
    value heads] in float32, once forward (read q, k, v, g, beta; write o)
    and twice backward (read them and ``do``; write the five gradients).
    The chunks' states stay out: a kernel may keep them on the chip."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    once = positions * ((2 * hk * dk + 2 * hv * dv) * operand_bytes
                        + 2 * hv * 4)
    return sequences * 3 * once


def mixer_flops(c, chunk: int) -> int:
    """One delta-rule mixer's operations a token, forward: the three
    projections, the taps (2 a tap and channel) and the chunked rule's
    products over the chunk's positions."""
    d = c["hidden_size"]
    key = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    value = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    return (2 * d * (2 * key + 2 * value + 2 * c["linear_num_value_heads"])
            + 2 * value * d
            + 2 * c["linear_conv_kernel_dim"] * (2 * key + value)
            + rule_flops_chunk(c, chunk) // chunk)


def step_flops_token(c, positions: int, chunk: int) -> int:
    """The operations one token needs in a forward pass of the whole step
    on this chip: every layer's mixer (a delta mixer's; an attention's five
    projections and its causal core's ``Q K^T`` and ``P V`` over the
    ``(positions + 1) / 2`` keys a query sees on average) and expert layer
    (the router, the shared expert and its gate, the held experts at the
    EVEN share of ``num_experts_per_tok x held / published`` experts a
    token), and the head's logits."""
    d, h, hkv, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    published = c["published"]["num_experts"]
    ffn = (2 * d * published + 6 * d * c["shared_expert_intermediate_size"]
           + 2 * d + 6 * d * c["moe_intermediate_size"]
           * c["num_experts_per_tok"] * c["num_experts"] // published)
    total = 2 * d * c["vocab_size"]
    for i in range(c["num_hidden_layers"]):
        if (i + 1) % c["full_attention_interval"]:
            total += mixer_flops(c, chunk)
        else:
            total += (2 * d * hd * (3 * h + 2 * hkv)
                      + 2 * hd * h * (positions + 1))
        total += ffn
    return total
