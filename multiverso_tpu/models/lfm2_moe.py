"""A decoder of gated short-convolution mixers, three to one grouped-query
attention layer of q/k-normed heads, under a tied head, with experts chosen
by a sigmoid under a selection bias and no shared one, trained through Adam
tables: the fifth model on ``models/mla_moe.py``'s one decoder path (the
``lfm2_moe`` family: LFM2-8B-A1B).

This file is the model's configuration and its convolution mixer. The block
(two branches: a mixer of either kind, then a feed-forward), the products,
the norms, rotary positions, the leading dense layer, the expert layer's
call, the sigmoid route and its bias rule, the chunked loss (its head the
embedding's table: ``tied_head``), the tables, the step and the ``Trainer``
are ``mla_moe``'s; the attention is ``gqa_moe.gqa`` with ``qk_norm`` on, the
gate off and every attention layer of the ``full`` kind. The equations, for
a block with input ``x`` [B, S, D]:

* ``h = x + Mixer(RMSNorm(x))``, ``y = h + F(RMSNorm(h))``; ``F`` the gated
  MLP of width ``dense_ffn`` in the leading dense layers and the held
  experts' part after them; a final RMSNorm; logits ``h Emb^T``.
* conv mixer (:func:`short_conv`): ``[B | C | x'] = u W_in`` (D -> 3D, no
  bias); ``z = B * x'``; ``c_t = sum_i w[i] * z_{t - (taps - 1) + i}``, a
  causal depthwise convolution of ``conv_taps`` taps a channel, zeros before
  the sequence's first position, no bias, no activation; ``(C * c) W_out``.
* attention: q, k, v as ``gqa_moe``'s; an RMSNorm over ``head_dim`` on every
  head of q and of k, one gain each, before the positions; rotary positions
  (plain frequencies); causal softmax over ``sqrt(head_dim)``; ``o W_o``.
* experts: ``parallel/moe.held_expert_layer`` under its sigmoid route:
  scores over all ``n_experts``, the ``top_k`` largest of score + bias
  chosen, gates the chosen scores over their sum times ``routed_scale``.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from multiverso_tpu.models import gqa_moe, mla_moe
from multiverso_tpu.models.mla_moe import Layer


class LFM2MoEConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    # every layer's mixer kind, the leading dense layers' first
    layer_kinds: Tuple[str, ...] = ("conv", "full", "conv", "conv", "conv")
    n_dense_layers: int = 1
    conv_taps: int = 3
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    rope_theta: float = 1e6
    dense_ffn: int = 192
    moe_ffn: int = 32
    n_experts: int = 16              # the router's outputs
    experts_held: int = 4
    expert_offset: int = 0
    top_k: int = 4
    routed_scale: float = 1.0
    bias_speed: float = 1e-3
    eps: float = 1e-5
    attn: Optional[str] = None       # as MLAMoEConfig's
    expert_kernel: Optional[str] = None
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(
            Layer(f"L{i}", kind,
                  "dense" if i < self.n_dense_layers else "experts")
            for i, kind in enumerate(self.layer_kinds))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return (short_conv_shapes(self) if kind == "conv"
                else gqa_moe.gqa_shapes(self))

    def attend(self, u, p, kind: str):
        return short_conv(u, p, self) if kind == "conv" else gqa_moe.gqa(
            u, p, self, kind)

    def conv_grid(self, s: int) -> Dict[str, int]:
        """The convolution mixers' static counts over ``s`` positions, as
        ``lm.step`` spans carry them: how many there are, the taps, the
        in-projection's width, the tie, and the operations a token of the
        matrix products a forward pass needs, the conv mixers' (two
        products a mixer) and the whole step's on this chip: every mixer's
        and feed-forward's products, a router's, the held experts' at the
        even share of ``top_k * experts_held / n_experts`` experts a token,
        the causal core's two products over ``(s + 1) / 2`` keys a query,
        and the head's."""
        d, hd = self.dim, self.head_dim
        mixer = {"conv": 2 * d * 3 * d + 2 * d * d,
                 "full": (2 * d * hd * 2 * (self.n_heads + self.n_kv_heads)
                          + 2 * hd * self.n_heads * (s + 1))}
        ffn = {"dense": 6 * d * self.dense_ffn,
               "experts": (2 * d * self.n_experts + 6 * d * self.moe_ffn
                           * self.top_k * self.experts_held
                           // self.n_experts)}
        layers = self.layers()
        convs = sum(layer.attn == "conv" for layer in layers)
        return {"conv_layers": convs, "conv_taps": self.conv_taps,
                "conv_width": 3 * d,
                "tied_head": int(self.tied_head),
                "mixer_flops_token": convs * mixer["conv"],
                "step_flops_token": 2 * d * self.vocab + sum(
                    mixer[layer.attn] + ffn[layer.ffn] for layer in layers)}

    # ``gqa_moe.gqa``'s switches, as this model has them
    qk_norm = True
    attn_gate = False
    rope_kinds = ("full",)
    window = yarn = None
    post_norms = False               # ``mla_moe.block``'s
    embed_scale = 1.0
    tied_head = True                 # the head is the embedding's table
    route = "sigmoid"                # parallel/moe.HeldExperts.route
    expert_form = "gated_silu"       # parallel/moe.HeldExperts.form
    balance_coef = 0.0               # no load-balance term in the loss

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads


def short_conv_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    """The parameters of a convolution mixer and its block's two input
    norms."""
    d = cfg.dim
    return {"attn_norm": (d,), "win": (d, 3 * d),          # [B | C | x']
            # a row a tap: tap i reads position t - (taps - 1) + i
            "conv_w": (cfg.conv_taps, d), "wout": (d, d), "ffn_norm": (d,)}


def gated_taps(proj, w):
    """What lies between the mixer's two products: ``proj`` [B, S, 3 D] =
    ``[B | C | x']`` and the taps ``w`` [taps, D] -> ``C * conv(B * x')``
    [B, S, D], the taps as shifted slices of an array padded on the left of
    the position axis (zeros before a sequence's first position)."""
    s, taps = proj.shape[1], w.shape[0]
    with jax.named_scope("mv.lm.conv.taps"):
        gate_in, gate_out, x = jnp.split(proj, 3, -1)
        past = jnp.pad(gate_in * x, ((0, 0), (taps - 1, 0), (0, 0)))
        return gate_out * sum(past[:, i:i + s] * w[i] for i in range(taps))


def short_conv(u, p, cfg):
    """The gated short convolution on the normed input ``u`` [B, S, D] ->
    [B, S, D] float32. The two products take ``cfg.compute_dtype`` operands
    and sum in float32; the gates and the taps are float32. The taps read a
    sequence's own positions alone."""
    with jax.named_scope("mv.lm.conv"):
        with jax.named_scope("mv.lm.conv.in"):
            proj = mla_moe.matmul(u, p["win"], False, cfg.compute_dtype,
                                  jnp.float32)
        y = gated_taps(proj, p["conv_w"])
        with jax.named_scope("mv.lm.conv.out"):
            return mla_moe.matmul(y, p["wout"], False, cfg.compute_dtype,
                                  jnp.float32)
