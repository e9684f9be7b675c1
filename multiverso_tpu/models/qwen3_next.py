"""A decoder of three gated delta-rule linear-attention layers to one
gated, partly rotary grouped-query attention layer, every layer an expert
layer beside a GATED shared expert, trained through Adam tables: the
seventh model on ``models/mla_moe.py``'s one decoder path (the
``qwen3_next`` family: Qwen3-Next-80B-A3B).

This file is the model's configuration and its linear-attention mixer. The
block, the products, the norms, rotary positions, the expert layer's call
(``parallel/moe.py``'s softmax route, the shared expert and its gate), the
chunked loss, the tables, the step and the ``Trainer`` are ``mla_moe``'s;
the attention is ``gqa_moe.gqa`` with ``qk_norm`` and ``attn_gate`` on and
rotary over the first ``rope_dim`` of a head. The equations, for a block
with input ``x`` [B, S, D] (``transformers`` 4.57.6,
``models/qwen3_next/modeling_qwen3_next.py``, which
``tests/test_qwen3_next.py`` holds the reference to):

* ``h = x + Mixer(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; a final norm;
  an untied head. The family's norm is ``x / rms(x) * (1 + w)`` with ``w``
  drawn at 0; the tables store the gain ``1 + w``, drawn at 1.
* ``delta`` (:func:`gated_delta_net`): ``[q | k | v | z] = u W_qkvz``,
  widths ``Hk dk | Hk dk | Hv dv | Hv dv`` (the family stores the columns
  interleaved by key head: a permutation); ``[b | a] = u W_ba``, ``Hv``
  each; ``[q | k | v] = silu(conv([q | k | v]))``, a causal depthwise
  convolution of ``conv_kernel`` taps, NO bias, zeros before the start;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``q, k
  <- x rsqrt(sum x^2 + 1e-6)`` a head, ``q <- q / sqrt(dk)``; key head ``h``
  is read by value heads ``h R .. h R + R - 1``; the gated delta rule
  (``ops/delta_rule.py``, chunked); an RMSNorm over each value head's ``dv``
  with one gain an element, THEN ``* silu(z)`` (Mamba-2's gated norm in
  ``nemotron_h.mamba2`` gates first); ``W_out``. No projection bias. The
  state and the convolution run on across packed documents.
* ``full``: ``gqa_moe.gqa``: q, k, v projections, an RMSNorm over every
  head of q and of k, rotary at ``rope_theta`` on the first ``rope_dim`` of
  a head (half-split pairing inside them; the rest passes), causal softmax
  over ``sqrt(head_dim)``, ``o * sigmoid(u W_gate)``, ``W_o``.
* MoE: ``sigmoid(u . w_sg) Shared(u)`` + the held experts' part under the
  softmax route (the ``top_k`` largest of all ``n_experts``, renormalised);
  the loss gains ``balance_coef`` times the sum over the layers of the
  route's load-balance term.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multiverso_tpu.models import gqa_moe, mla_moe
from multiverso_tpu.models.mla_moe import Layer
from multiverso_tpu.ops.delta_rule import gated_delta_chunked
from multiverso_tpu.ops.short_conv import causal_taps, step_counts

# what a rematerialised block keeps of a linear-attention mixer: the rule's
# result, float32 [B, S, Hv, dv] (``mla_moe.kept_names``)
KEPT_NAMES = ("mv.lm.delta.rule.out",)


class Qwen3NextConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    n_layers: int = 4
    full_every: int = 4              # layer i is ``full`` where (i+1) % it == 0
    # the linear-attention mixer
    lin_key_heads: int = 2
    lin_value_heads: int = 4
    lin_key_dim: int = 16
    lin_value_dim: int = 16
    conv_kernel: int = 4
    delta_chunk: int = 64
    # the attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    rope_dim: int = 4                # the leading part of a head that turns
    rope_theta: float = 1e7
    # the expert layer
    moe_ffn: int = 48
    shared_ffn: int = 48             # the shared expert's own width
    n_experts: int = 16              # the router's outputs
    experts_held: int = 4
    expert_offset: int = 0
    top_k: int = 4
    balance_coef: float = 1e-3       # on the routers' load-balance terms
    eps: float = 1e-6
    attn: Optional[str] = None       # as MLAMoEConfig's
    expert_kernel: Optional[str] = None
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(
            Layer(f"L{i}", "full" if (i + 1) % self.full_every == 0
                  else "delta", "shared+experts")
            for i in range(self.n_layers))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return (delta_shapes(self) if kind == "delta"
                else gqa_moe.gqa_shapes(self))

    def attend(self, u, p, kind: str):
        return (gated_delta_net(u, p, self) if kind == "delta"
                else gqa_moe.gqa(u, p, self, kind))

    def delta_grid(self, s: int) -> Dict[str, int]:
        """The linear-attention mixers' static counts over ``s`` positions,
        as ``lm.step`` spans carry them: how many there are, a layer's
        chunks, value heads, chunk and state (``dk x dv`` floats a head),
        the scan's DEPENDENT steps a group (``s / chunk``); and the
        operations a token needs in a forward pass: the mixers'
        (:func:`mixer_flops_token`) and the whole step's on this chip
        (every mixer's and feed-forward's products, a router's, the held
        experts' at the even share of ``top_k * experts_held / n_experts``
        experts a token, the shared expert and its gate, the causal core's
        two products over ``(s + 1) / 2`` keys a query, and the head's);
        and the mixers' short convolution's (``short_conv.step_counts``)."""
        d, hd, h = self.dim, self.head_dim, self.n_heads
        mixer = {"delta": mixer_flops_token(self),
                 # q, gate, k, v, o; then Q K^T and P V
                 "full": (2 * d * hd * (3 * h + 2 * self.n_kv_heads)
                          + 2 * hd * h * (s + 1))}
        ffn = (2 * d * self.n_experts + 6 * d * self.shared_ffn + 2 * d
               + 6 * d * self.moe_ffn * self.top_k * self.experts_held
               // self.n_experts)
        layers = self.layers()
        deltas = sum(layer.attn == "delta" for layer in layers)
        return {**step_counts(deltas, s, delta_shapes(self)["conv_w"][1]),
                "delta_layers": deltas, "delta_chunks": s // self.delta_chunk,
                "delta_heads": self.lin_value_heads,
                "delta_chunk": self.delta_chunk,
                "delta_state": self.lin_key_dim * self.lin_value_dim,
                "delta_steps": s // self.delta_chunk,
                "delta_flops_token": deltas * mixer["delta"],
                "step_flops_token": 2 * d * self.vocab + sum(
                    mixer[layer.attn] + ffn for layer in layers)}

    @property
    def first_values(self) -> Dict[str, Any]:
        """``mla_moe._draw``'s rules for what is not Normal(0, scale): the
        family's ``_init_weights``: ``A`` uniform in (0, 16) (no less than
        1e-6: the log of a draw of exactly 0 is no number), ``dt_bias`` at
        1."""
        return {"a_log": ("log_uniform", 1e-6, 16.0), "dt_bias": "ones"}

    # ``gqa_moe.gqa``'s switches, as this model has them
    qk_norm = attn_gate = True
    rope_kinds = ("full",)
    window = yarn = None
    post_norms = False               # ``mla_moe.block``'s
    embed_scale = 1.0
    shared_gate = True               # ``mla_moe.expert_ffn``'s
    route = "softmax"                # parallel/moe.HeldExperts.route
    routed_scale = 1.0               # the gates sum to 1
    expert_form = "gated_silu"       # parallel/moe.HeldExperts.form
    kept_names = KEPT_NAMES          # ``mla_moe.kept_names``

    def kept_bytes(self, b: int, s: int) -> int:
        """What :data:`KEPT_NAMES` keeps a step of ``b`` sequences of ``s``
        positions (``mla_moe.kept_grid``): a float32 for every element of
        a value head, every delta layer."""
        deltas = sum(layer.attn == "delta" for layer in self.layers())
        return deltas * 4 * b * s * self.lin_value_heads * self.lin_value_dim

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads


def delta_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    """The parameters of a linear-attention mixer and its block's two input
    norms."""
    d, hv = cfg.dim, cfg.lin_value_heads
    key, value = cfg.lin_key_heads * cfg.lin_key_dim, hv * cfg.lin_value_dim
    return {"attn_norm": (d,), "ffn_norm": (d,),
            "wqkvz": (d, 2 * key + 2 * value),      # [q | k | v | z]
            "wba": (d, 2 * hv),                     # [b | a]
            # a row a tap: tap i reads position t - (taps - 1) + i
            "conv_w": (cfg.conv_kernel, 2 * key + value),
            "a_log": (hv,), "dt_bias": (hv,),
            "gate_norm": (cfg.lin_value_dim,), "wout": (value, d)}


def rule_flops_chunk(cfg) -> int:
    """What the chunked rule must compute in ONE chunk of a layer,
    forward: ``K K^T`` (the pairs ``i > j``) and ``q K^T`` (``i >= j``)
    once a key head; a value head's ``T Vb`` and ``T Kb`` (``T`` is lower
    triangular), the masked product with ``V'``, and the three whole
    products with the state (``W S``, ``q S``, ``K~^T V'``). 2 operations a
    multiply-add. How ``T`` is MADE is not counted."""
    q, dk, dv = cfg.delta_chunk, cfg.lin_key_dim, cfg.lin_value_dim
    low = q * (q + 1) // 2
    return (cfg.lin_key_heads * 2 * dk * (low - q + low)
            + cfg.lin_value_heads * (2 * low * (dv + dk) + 2 * low * dv
                                     + 3 * 2 * q * dk * dv))


def mixer_flops_token(cfg) -> int:
    """One linear-attention mixer's operations a token, forward: the three
    projections, the taps (2 a tap and channel) and the chunked rule's
    products (:func:`rule_flops_chunk` over the chunk's positions)."""
    d, shapes = cfg.dim, delta_shapes(cfg)
    return (2 * d * (shapes["wqkvz"][1] + shapes["wba"][1])
            + 2 * shapes["wout"][0] * d
            + 2 * cfg.conv_kernel * shapes["conv_w"][1]
            + rule_flops_chunk(cfg) // cfg.delta_chunk)


def gated_delta_net(u, p, cfg):
    """The linear-attention mixer on the normed input ``u`` [B, S, D] ->
    [B, S, D] float32. What feeds the rule and what follows it are each
    rematerialised in the backward pass, as the rule's groups are: the
    mixer's float32 arrays of 8,192 to 12,288 columns are 0.5 to 0.8 GB
    each at 16,384 positions, and kept for a backward pass they were 7.0
    GB of a step that has 16 (a v5e's compiler, PR 56). The rule's RESULT
    is named (:data:`KEPT_NAMES`) and a rematerialised block keeps it
    (268 MB a layer at the cell's sizes): the groups' backward pass reads
    their inputs alone and what follows the rule its value alone, so the
    block made again does not run the rule: its forward scan runs twice a
    step, in the forward pass and where each group is made again for its
    own backward pass."""
    b, s, _ = u.shape
    hk, hv = cfg.lin_key_heads, cfg.lin_value_heads
    dk, dv, dt_ = cfg.lin_key_dim, cfg.lin_value_dim, cfg.compute_dtype
    key, value = hk * dk, hv * dv

    def feed(u, wqkvz, wba, conv_w, a_log, dt_bias):
        # the convolution's operand is a product of its own, from the
        # table's column window: a kernel takes no fusion, and a window of
        # ONE product's result would be copied out for it (0.5 GB a pass)
        wide = 2 * key + value
        qkv = mla_moe.matmul(u, wqkvz[:, :wide], False, dt_, jnp.float32)
        z = mla_moe.matmul(u, wqkvz[:, wide:], False, dt_, jnp.float32)
        ba = mla_moe.matmul(u, wba, False, dt_, jnp.float32)
        with jax.named_scope("mv.lm.delta.conv"):
            qkv = causal_taps(qkv, conv_w, None, True)
        q, k, v = jnp.split(qkv, (key, 2 * key), axis=-1)
        with jax.named_scope("mv.lm.delta.gates"):
            unit = lambda t: t * jax.lax.rsqrt(
                jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            q = unit(q.reshape(b, s, hk, dk)) * dk ** -0.5
            k = unit(k.reshape(b, s, hk, dk))
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        return q, k, v.reshape(b, s, hv, dv), g, beta, z

    def close(o, z, gain, wout):
        with jax.named_scope("mv.lm.delta.norm"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + cfg.eps) * gain
            o = (o * jax.nn.silu(z.reshape(b, s, hv, dv))).reshape(
                b, s, value)
        return mla_moe.matmul(o, wout, False, dt_, jnp.float32)

    with jax.named_scope("mv.lm.delta"):
        q, k, v, g, beta, z = jax.checkpoint(feed)(
            u, p["wqkvz"], p["wba"], p["conv_w"], p["a_log"], p["dt_bias"])
        with jax.named_scope("mv.lm.delta.rule"):
            o = checkpoint_name(
                gated_delta_chunked(q, k, v, g, beta, cfg.delta_chunk, dt_),
                KEPT_NAMES[0])
        return jax.checkpoint(close)(o, z, p["gate_norm"], p["wout"])
