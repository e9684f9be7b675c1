"""A decoder of FOUR residual streams mixed by manifold-constrained
hyper-connections round latent attention and routed experts beside a
shared one, trained through Adam tables: the eighth model on
``models/mla_moe.py``'s one decoder path (the ``xing4_0`` family:
Xing4.0-29B-A4B).

This file is the model's configuration and nothing else. The attention is
``mla_moe.mla`` at two head sizes (queries and keys of ``qk_nope_dim +
qk_rope_dim``, values of ``v_head_dim``) under YaRN's frequencies and a
softmax scale of its own; the leading dense layers, the shared expert, the
sigmoid route and its bias rule, the prediction module, the chunked loss,
the tables, the step and the ``Trainer`` are ``mla_moe``'s, used as they
are; the residual path is ``mla_moe.block``'s under ``streams`` > 1. The
equations, with ``X`` [n, C] a position's streams (n = ``streams``):

* ``X_0`` = the embedding copied to every stream; after the last block
  the streams are summed, and the sum goes to the last norm and the head.
* a sublayer ``F`` (attention, then the feed-forward; each with a ``phi``
  [n^2 + 2n, nC], ``b`` [n^2 + 2n] and ``alpha`` [3] of its own):
  ``h = (vec(X) / sqrt(mean(vec(X)^2) + eps)) phi^T``, split as pre (n),
  post (n), res (n^2, row by row); ``H_pre = sigmoid(alpha_0 h_pre +
  b_pre)``; ``H_post = 2 sigmoid(alpha_1 h_post + b_post)``; ``H_res`` =
  ``exp(clip(alpha_2 h_res + b_res, res_clamp))`` after ``sinkhorn_iters``
  rounds of (every column over its sum + ``hc_eps``, then every row over
  its sum + ``hc_eps``); ``u = H_pre X``; ``y = F(RMSNorm(u))``; ``X' =
  H_res X + outer(H_post, y)`` (arXiv:2512.24880, equations 7 and 8).
* attention: ``mla_moe.mla``; rotary frequencies by YaRN (``factor`` over
  ``original_max_position_embeddings``), cos and sin unscaled (``mscale ==
  mscale_all_dim``), scores times ``(0.1 mscale_all_dim ln(factor) + 1)^2
  / sqrt(nope + rope)`` (DeepSeek-V3's modelling convention).
* the prediction module takes the SUM of the trunk's streams where
  DeepSeek-V3's takes ``h_i``, copies ``eh_proj``'s result to every
  stream, runs its block under hyper-connections of its own and sums
  before its output norm (an assumption: the configuration's file says
  so).
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from multiverso_tpu.models import mla_moe
from multiverso_tpu.models.mla_moe import Layer, Yarn


class Xing4Config(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    n_heads: int = 2
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_dim: int = 8
    qk_rope_dim: int = 4
    v_head_dim: int = 8
    rope_theta: float = 1e4
    # YaRN: cos and sin take ``attention_factor`` (1: mscale ==
    # mscale_all_dim), the scores ``softmax_scale``
    yarn: Optional[Yarn] = Yarn(64.0, 4096, 32.0, 1.0, 1.0)
    mscale_all_dim: float = 1.0
    dense_ffn: int = 160
    n_dense_layers: int = 1
    n_moe_layers: int = 2
    moe_ffn: int = 32
    n_experts: int = 16              # the router's outputs
    experts_held: int = 2
    expert_offset: int = 0
    top_k: int = 4
    routed_scale: float = 2.0
    n_mtp: int = 0                   # prediction modules (0 or 1)
    mtp_weight: float = 0.3
    bias_speed: float = 1e-3
    eps: float = 1e-6
    streams: int = 4                 # ``hc_mult``
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    res_clamp: Tuple[float, float] = (-30.0, 30.0)
    attn: Optional[str] = None       # as MLAMoEConfig's
    expert_kernel: Optional[str] = None
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16

    # the layer list, the latent attention and its shapes are GLM's
    layers = mla_moe.MLAMoEConfig.layers
    head_size = mla_moe.MLAMoEConfig.head_size
    attn_shapes = mla_moe.MLAMoEConfig.attn_shapes
    attend = mla_moe.MLAMoEConfig.attend

    @property
    def softmax_scale(self) -> float:
        mscale = 1.0
        if self.yarn is not None and self.yarn.factor > 1:
            mscale = 0.1 * self.mscale_all_dim * math.log(
                self.yarn.factor) + 1.0
        return mscale * mscale / math.sqrt(self.qk_nope_dim
                                           + self.qk_rope_dim)

    # a hyper-connection's three gains start at 1 (the file's
    # ``assumed.hc_init`` says why not small)
    first_values = {"hc_alpha": "ones"}
    # the grouped products' row tile (``mla_moe.held``). A held expert
    # sees 256 rows of a step's 4,096 tokens: at the widths' own 512 a
    # group computes one tile or two by where its first row falls, two to
    # four times its rows, and a step's time went by the seed's loads
    # (``words_per_s`` spread 0.48% over six seeds, half its bound: PERF.md
    # section 6, PR 60)
    product_rows = 128
    kv_group = 1                     # query heads a key-value head
    route = "sigmoid"                # parallel/moe.HeldExperts.route
    expert_form = "gated_silu"       # parallel/moe.HeldExperts.form
    balance_coef = 0.0               # no load-balance term in the loss
    post_norms = False               # a block norms its branches' inputs
    embed_scale = 1.0

    @property
    def shared_ffn(self) -> int:     # the shared expert's width
        return self.moe_ffn
