"""A decoder of gated, q/k-normed grouped-query attention with rotary
positions in its window layers alone, four norms a block, and experts
chosen by a sigmoid under a selection bias beside a shared one, trained
through Adam tables: the third model on ``models/mla_moe.py``'s one
decoder path (the ``afmoe`` family: Trinity-Mini).

This file is the model's configuration and nothing else. The attention
is ``gqa_moe.gqa`` under this model's switches (``qk_norm``,
``attn_gate``, ``rope_kinds``); the block (``post_norms``), the embedding
(``embed_scale``), the leading dense layer, the shared expert, the
sigmoid route and its bias rule, the chunked loss, the tables, the step
and the ``Trainer`` are ``mla_moe``'s, used as they are. The equations,
for a block with input ``x`` [B, S, D], every ``N`` an RMSNorm with a
gain of its own:

* ``x0 = Emb(t) * embed_scale`` (``sqrt(dim)`` in the published model).
* ``h = x + N(Attn(N(x)))``, ``y = h + N(F(N(h)))``; ``F`` the gated MLP
  of width ``dense_ffn`` in the leading dense layers and ``Shared(u) +
  held experts' part`` after them.
* Attn: q, k, v as ``gqa_moe``'s; ``q = N(q)``, ``k = N(k)`` over
  ``head_dim``, one gain for q and one for k; rotary positions (plain
  frequencies) in a ``window`` layer, NONE in a ``full`` layer; the
  core as ``gqa_moe``'s; ``o = core * sigmoid(u W_gate)``; ``o W_o``.
* Experts: ``parallel/moe.held_expert_layer`` under its sigmoid route:
  scores over all ``n_experts``, the ``top_k`` largest of score + bias
  chosen, gates the chosen scores over their sum times ``routed_scale``;
  the bias moves by ``bias_speed`` a step toward the even load and takes
  no gradient; no load-balance term in the loss.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from multiverso_tpu.models import gqa_moe
from multiverso_tpu.models.mla_moe import Layer


class AFMoEConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    window: int = 16
    # every layer's attention kind, the leading dense layers' first
    layer_kinds: Tuple[str, ...] = ("window", "window", "window", "window",
                                    "full")
    n_dense_layers: int = 1
    rope_theta: float = 1e4
    dense_ffn: int = 192
    moe_ffn: int = 32
    n_experts: int = 16              # the router's outputs
    experts_held: int = 2
    expert_offset: int = 0
    top_k: int = 4
    routed_scale: float = 2.826
    bias_speed: float = 1e-3
    embed_scale: float = 8.0         # sqrt(dim)
    eps: float = 1e-5
    attn: Optional[str] = None       # as MLAMoEConfig's
    expert_kernel: Optional[str] = None
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(
            Layer(f"L{i}", kind,
                  "dense" if i < self.n_dense_layers else "shared+experts")
            for i, kind in enumerate(self.layer_kinds))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return gqa_moe.gqa_shapes(self)

    def attend(self, u, p, kind: str):
        return gqa_moe.gqa(u, p, self, kind)

    # ``gqa_moe.gqa``'s switches, as this model has them
    qk_norm = attn_gate = True
    rope_kinds = ("window",)
    yarn = None                      # ``rope_scaling: null``
    post_norms = True                # ``mla_moe.block``'s
    route = "sigmoid"                # parallel/moe.HeldExperts.route
    expert_form = "gated_silu"       # parallel/moe.HeldExperts.form
    balance_coef = 0.0               # no load-balance term in the loss

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads

    @property
    def shared_ffn(self) -> int:     # the shared expert's width
        return self.moe_ffn
