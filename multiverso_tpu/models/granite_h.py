"""A decoder of two-branch blocks whose FIRST branch is a Mamba-2
state-space mixer (or, one layer in ten, a grouped-query attention without
positions) and whose second is a dense gated MLP, under four published
multipliers, trained through Adam tables: the tenth model on
``models/mla_moe.py``'s one decoder path (the ``granitemoehybrid`` family:
Granite 4.0-H Micro, whose "experts" are none: ``num_local_experts`` 0 and a
``shared_mlp`` in every layer).

This file is the model's configuration and nothing else. The state-space
mixer is ``nemotron_h.mamba2`` at ``ssm_groups`` 1 (ALL the heads read one
``B`` and ``C``, and the gated norm's mean runs over the whole inner width:
one group is the whole width), over ``ops/ssd.py``'s chunked scan, which
walks a group's heads in blocks; the attention is ``gqa_moe.gqa`` with every
switch off, no layer kind that takes positions, and the scores' multiplier
a published number (``softmax_scale``); the block (``mla_moe.block``, whose
residual sums take ``residual_scale``), the dense MLP, the chunked loss
(whose logits take ``logit_scale``), the tied head, the tables, the step and
the ``Trainer`` are ``mla_moe``'s, used as they are. The equations, for
tokens ``t`` [B, S], every product without a bias:

* ``x = embed_scale * Emb[t]`` (``embedding_multiplier`` 12).
* block ``i``, of ``layer_types[i]``: ``h = x + residual_scale *
  Mixer_i(RMSNorm(x))``, ``x' = h + residual_scale * MLP(RMSNorm(h))``
  (``residual_multiplier`` 0.22 on BOTH branches).
* ``MLP(u) = (silu(u W_g) * (u W_u)) W_d`` (the family's ``input_linear`` is
  ``[W_g | W_u]``, the gated half first).
* ``mamba``: ``nemotron_h.mamba2``'s equations with one group; no clamp on
  ``dt`` (``time_step_limit`` (0, inf)).
* ``attention``: q ``n_heads`` heads, k and v ``n_kv_heads`` heads of
  ``head_dim``, NO positions (``nope``), causal ``softmax(q k^T *
  softmax_scale)`` (``attention_multiplier`` 0.015625 = 1 / head_dim in the
  place of ``1 / sqrt(head_dim)``), ``o W_o``.
* ``logits = RMSNorm(x_last) Emb^T * logit_scale`` (``1 /
  logits_scaling`` = 1 / 8; the head IS the embedding's table), mean
  cross-entropy over the held slice of ids.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from multiverso_tpu.models import gqa_moe, nemotron_h
from multiverso_tpu.models.mla_moe import Layer

# a block's first branch by its entry in ``layer_types``
KINDS = {"mamba": "ssm", "attention": "full"}


class GraniteHConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    layer_types: Tuple[str, ...] = ("mamba", "attention", "mamba")
    # the state-space mixer (``nemotron_h.mamba2``'s fields)
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_groups: int = 1
    ssm_state: int = 16
    conv_kernel: int = 4
    chunk: int = 16
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    a_init: Tuple[float, float] = (1.0, 16.0)
    # the attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    dense_ffn: int = 96
    # the four multipliers: on the embedding, on a branch's result as it
    # joins the stream, on the attention's scores and on the logits
    embed_scale: float = 12.0
    residual_scale: float = 0.22
    softmax_scale: Optional[float] = 0.125      # 1 / head_dim
    logit_scale: float = 0.125                  # 1 / logits_scaling
    eps: float = 1e-5
    attn: Optional[str] = None       # as MLAMoEConfig's
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(Layer(f"L{i}", KINDS[kind], "dense")
                     for i, kind in enumerate(self.layer_types))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return (nemotron_h.mamba2_shapes(self) if kind == "ssm"
                else gqa_moe.gqa_shapes(self))

    def attend(self, u, p, kind: str):
        return (nemotron_h.mamba2(u, p, self) if kind == "ssm"
                else gqa_moe.gqa(u, p, self, kind))

    ssm_grid = nemotron_h.NemotronHConfig.ssm_grid
    # the Mamba-2 rule: ``A``, the step sizes and the skip
    first_values = nemotron_h.NemotronHConfig.first_values

    tied_head = True
    # ``gqa_moe.gqa``'s switches: all off, and no kind takes positions
    qk_norm = attn_gate = False
    rope_kinds = ()
    window = yarn = None
    post_norms = False               # ``mla_moe.block``'s
    # no expert layer: nothing for a block to keep, no term in the loss
    keeps_products = False
    balance_coef = 0.0

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads
