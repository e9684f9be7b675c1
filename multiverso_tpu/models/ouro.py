"""A looped decoder: a stack of sandwich-normed dense blocks run several
times with the SAME tables, an exit gate after every pass, and a loss that
is the expected cross-entropy over the exits less an entropy term, trained
through Adam tables: the ninth model on ``models/mla_moe.py``'s one decoder
path (the ``ouro`` family: Ouro-2.6B, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741), and the first with no expert layer.

This file is the model's configuration and nothing else. The attention is
``gqa_moe.gqa`` with every switch off and rotary over the whole head; the
block (``post_norms``: four norms, the family's sandwich), the dense
feed-forward, the loop over the passes (``mla_moe._passes``: ONE loop in
the program), the exit gate and its loss (``mla_moe._exit_loss``), the
chunked loss, the tables, the step and the ``Trainer`` are ``mla_moe``'s,
used as they are. The equations, every ``N`` an RMSNorm with a gain of its
own, ``T = passes``:

* ``x_0 = Emb(tok)``.
* pass ``t = 1..T``: ``z = x_{t-1}``; for every layer in order ``h = z +
  N(Attn(N(z)))``, ``z = h + N(MLP(N(h)))``; then ``x_t = N_f(z)``: the
  normed stream is the pass's exit state AND the next pass's input, under
  the same ``N_f``, the same layer tables and the same positions.
* Attn: q, k, v as ``gqa_moe``'s, no bias; rotary over the whole head of q
  and k (plain frequencies); causal; scores over ``sqrt(head_dim)``;
  float32 softmax; ``o W_o``. MLP: ``(silu(u W_g) * (u W_u)) W_d``.
* exit gate, a position at a time: ``lambda_t = sigmoid(x_t . w_e + b_e)``
  for ``t < T``; ``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t} (1 -
  lambda_j)``, ``p_T = prod_{j<T} (1 - lambda_j)``.
* ``loss = (1/n) sum_i [sum_t p_t(i) CE(x_t(i) W_head^T, tok_{i+1}) -
  exit_coef H(p(i))]`` over the ``n`` positions that have a target;
  gradients flow through ``p`` into the gate and the streams, and back
  through every pass.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from multiverso_tpu.models import gqa_moe
from multiverso_tpu.models.mla_moe import Layer


class OuroConfig(NamedTuple):
    vocab: int = 512
    dim: int = 64
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 16
    n_layers: int = 2
    passes: int = 4                  # ``total_ut_steps``
    exit_coef: float = 0.05          # on the exit distribution's entropy
    rope_theta: float = 1e6
    dense_ffn: int = 176
    eps: float = 1e-6
    attn: Optional[str] = None       # as MLAMoEConfig's
    attn_block: int = 512
    # four exits' positions walk the chunked loss as one: a chunk of 2,048
    # positions over 49,152 ids is 0.4 GB of float32 logits
    loss_chunk: int = 2048
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(Layer(f"L{i}", "full", "dense")
                     for i in range(self.n_layers))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return gqa_moe.gqa_shapes(self)

    def attend(self, u, p, kind: str):
        return gqa_moe.gqa(u, p, self, kind)

    # ``gqa_moe.gqa``'s switches, as this model has them
    qk_norm = attn_gate = False
    rope_kinds = ("full",)
    yarn = None                      # ``rope_scaling: null``
    post_norms = True                # ``mla_moe.block``'s: the sandwich
    embed_scale = 1.0
    # no expert layer: nothing for a block to keep, no term in the loss
    keeps_products = False
    balance_coef = 0.0

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads
