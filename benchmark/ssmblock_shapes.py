"""Operations that a block of a state-space mixer AND a dense MLP
(``multiverso_tpu/models/granite_h.py`` on ``models/mla_moe.block``) must do
a training step, from the configuration file's dictionary ``c`` alone, so
that ``ssm.proj_mxu_share.lm``, ``ffn.dense_mxu_share.lm`` and the cell's
count by hand (``benchmark/LM_SSM.md``) can be checked. What is counted is
what the algorithm needs, as ``lm_shapes``, ``attn_shapes`` and
``ssm_shapes`` count (the scan's own operations and bytes are
``ssm_shapes.scan_flops`` / ``scan_bytes``, which take ``groups`` and
``chunk``): 2 operations a multiply-add and three products a matrix
(forward, the input's gradient, the weight's); a product made again under
``jax.checkpoint`` is time and not operations, so a share reads under what
the matrix unit does.
"""

from __future__ import annotations

PRODUCTS_OF_A_MATRIX = 3      # forward, the input's gradient, the weight's


def mixers(c) -> int:
    """The layers run whose first branch is a state-space mixer."""
    return c["layer_types"][:c["num_hidden_layers"]].count("mamba")


def proj_weights(c) -> int:
    """The numbers in ONE mixer's two projections: ``[z | xBC | dt]``
    (``hidden x (2 inner + 2 groups x state + heads)``) and the
    out-projection (``inner x hidden``)."""
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    return c["hidden_size"] * (
        2 * inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
        + c["mamba_n_heads"]) + inner * c["hidden_size"]


def proj_flops(c, sequences: int, positions: int) -> int:
    """Every mixer's in- and out-projection over a step."""
    return (mixers(c) * sequences * positions * 2 * proj_weights(c)
            * PRODUCTS_OF_A_MATRIX)


def dense_flops(c, sequences: int, positions: int) -> int:
    """Every layer's gated MLP over a step: three matrices of ``hidden x
    shared_intermediate_size``."""
    return (c["num_hidden_layers"] * sequences * positions * 2
            * 3 * c["hidden_size"] * c["shared_intermediate_size"]
            * PRODUCTS_OF_A_MATRIX)


def forward_flops_token(c, positions: int) -> dict:
    """The needed forward products a token, by part (LM_SSM.md's count by
    hand): the MLPs, the mixers' projections, the scans' four products at
    the configuration's chunk, the attention blocks' projections and their
    causal core (two products over ``positions`` keys of which a query sees
    half on average), and the head."""
    from benchmark import ssm_shapes

    d, h = c["hidden_size"], c["num_attention_heads"]
    hd = d // h
    layers = c["num_hidden_layers"]
    attention = layers - mixers(c)
    chunk = c["mamba_chunk_size"]
    return {
        "mlp": layers * 2 * 3 * d * c["shared_intermediate_size"],
        "mixer_proj": mixers(c) * 2 * proj_weights(c),
        "scan": mixers(c) * ssm_shapes.scan_flops(
            1, chunk, c["mamba_n_heads"], c["mamba_d_head"],
            c["mamba_n_groups"], c["mamba_d_state"], chunk) // (3 * chunk),
        "attention_proj": attention * 2 * (
            2 * d * h * hd + 2 * d * c["num_key_value_heads"] * hd),
        "attention_core": attention * 2 * 2 * h * hd * positions // 2,
        "head": 2 * c["vocab_size"] * d}
