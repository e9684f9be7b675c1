"""A decoder of grouped-query attention, window layers interleaved with
full ones, and routed experts alone in every layer, trained through Adam
tables: the second model on ``models/mla_moe.py``'s one decoder path.

This file says what is this model's own: its configuration, the shapes
of its attention's parameters, and the attention, which
``models/afmoe.py``'s configuration takes too, under other switches
(:func:`gqa`, :func:`gqa_shapes`). The block, the
products, the norms, rotary positions, the expert layer's call, the
chunked loss, the tables, the step and the ``Trainer`` are
``mla_moe``'s, used as they are. The equations, for a block with input
``x`` [B, S, D]:

* ``h = x + Attn(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``.
* Attn: ``q = u W_q`` -> ``n_heads`` heads of ``head_dim``; ``k = u W_k``,
  ``v = u W_v`` -> ``n_kv_heads`` heads; no bias; rotary on q and k
  (half-split pairing), plain in a ``window`` layer and under YaRN's
  frequencies and factor in a ``full`` one (``mla_moe.rotary``); query
  head ``h`` reads key-value head ``h // (n_heads / n_kv_heads)``, and K
  and V are never repeated: the flash kernel's index maps know the group.
  Scores over ``sqrt(head_dim)`` (or times ``cfg.softmax_scale`` where a
  configuration has that field); position ``i`` sees ``j`` where ``0 <=
  i - j`` and, in a ``window`` layer, ``i - j < window``; float32
  softmax; ``o W_o``. Three switches of the configuration, all off here:
  ``qk_norm`` (an RMSNorm over ``head_dim`` on every head of q and of k
  before the positions, one gain each), ``attn_gate`` (the core's output
  times ``sigmoid(u W_gate)``, element for element, before ``W_o``) and
  ``rope_kinds`` (the layer kinds that take rotary positions: a kind left
  out sees none). A fourth, which this model's configuration has no field
  for: ``rope_dim``, rotary over a leading part of the head
  (``models/qwen3_next.py``).
* Experts: ``parallel/moe.held_expert_layer`` under its softmax route:
  probabilities over all ``n_experts``, the ``top_k`` largest
  renormalised, no bias, no shared expert; this chip computes the part of
  the experts it holds. The loss gains ``balance_coef`` times the sum
  over the layers of the route's load-balance term.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from multiverso_tpu.models import mla_moe
from multiverso_tpu.models.mla_moe import Layer, Yarn
from multiverso_tpu.ops.attention_kernels import flash_attention


class GQAMoEConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    window: int = 16
    layer_kinds: Tuple[str, ...] = ("window", "window", "window", "full")
    rope_theta: float = 5e5
    yarn: Optional[Yarn] = Yarn(16.0, 32, 32.0, 1.0, 1.2772588722239782)
    moe_ffn: int = 48
    n_experts: int = 16              # the router's outputs
    experts_held: int = 4
    expert_offset: int = 0
    top_k: int = 8
    balance_coef: float = 1e-3       # on the routers' load-balance terms
    eps: float = 1e-6
    attn: Optional[str] = None       # as MLAMoEConfig's
    expert_kernel: Optional[str] = None
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16
    # :func:`gqa`'s switches, as this model has them
    rope_kinds: Tuple[str, ...] = ("window", "full")
    qk_norm: bool = False
    attn_gate: bool = False

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(Layer(f"L{i}", kind, "experts")
                     for i, kind in enumerate(self.layer_kinds))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return gqa_shapes(self)

    def attend(self, u, p, kind: str):
        return gqa(u, p, self, kind)

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads

    @property
    def route(self) -> str:          # parallel/moe.HeldExperts.route
        return "softmax"

    @property
    def routed_scale(self) -> float:     # the gates sum to 1
        return 1.0

    @property
    def post_norms(self) -> bool:    # a block norms its branches' inputs
        return False

    @property
    def embed_scale(self) -> float:
        return 1.0

    @property
    def expert_form(self) -> str:    # parallel/moe.HeldExperts.form
        return "gated_silu"


def gqa_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    """The parameters of a block's attention and its two input norms."""
    d, hd = cfg.dim, cfg.head_dim
    out = {"attn_norm": (d,), "wq": (d, cfg.n_heads * hd),
           "wk": (d, cfg.n_kv_heads * hd), "wv": (d, cfg.n_kv_heads * hd),
           "wo": (cfg.n_heads * hd, d), "ffn_norm": (d,)}
    if cfg.qk_norm:
        out.update(q_norm=(hd,), k_norm=(hd,))
    if cfg.attn_gate:
        out["wgate"] = (d, cfg.n_heads * hd)
    return out


def heads_of(u, p, cfg, kind: str):
    """The core's operands from the normed input ``u`` [B, S, D]: q [B, H,
    S, hd] and k, v [B, Hkv, S, hd] in the compute dtype, after the
    projections, the q/k norms where the configuration has them and the
    positions where ``kind`` takes them (over the first ``cfg.rope_dim`` of
    a head where the configuration has such a field, the rest passing
    through; over the whole head without it). Each is ONE product with
    the weight viewed [D, heads, hd], written once with the head axis before
    the positions (``mla_moe.heads``). Called inside ``mv.lm.attn``."""
    h, hkv, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.compute_dtype
    plain = mla_moe.Heads(dt, eps=cfg.eps)
    turned = plain
    if kind in cfg.rope_kinds:
        r = getattr(cfg, "rope_dim", None)      # the first r of a head turn
        turned = plain._replace(
            rope=hd if r is None else r, theta=cfg.rope_theta,
            yarn=cfg.yarn if kind == "full" else None)
    gains = (p["q_norm"], p["k_norm"]) if cfg.qk_norm else (None, None)
    of = lambda name, n, how, gain=None: mla_moe.heads(
        u, p[name].reshape(-1, n, hd), how, gain)
    return (of("wq", h, turned, gains[0]), of("wk", hkv, turned, gains[1]),
            of("wv", hkv, plain))


def out_of(o, u, p, cfg):
    """The core's output ``o`` [B, H, S, hd] -> [B, S, D] float32: the gate
    where the configuration has one, then ``W_o`` viewed [H, hd, D], with no
    transposed copy of ``o`` between (``mla_moe.out_of_heads``). Called
    inside ``mv.lm.attn``."""
    _, h, _, hd = o.shape
    gate = (p["wgate"].reshape(-1, h, hd) if cfg.attn_gate else None)
    return mla_moe.out_of_heads(o, p["wo"].reshape(h, hd, -1),
                                cfg.compute_dtype, u if cfg.attn_gate
                                else None, gate)


def gqa(u, p, cfg, kind: str):
    """Grouped-query attention of ``kind`` (``"full"`` or ``"window"``) on
    the normed input ``u`` [B, S, D] -> [B, S, D] float32; ``cfg`` is a
    :class:`GQAMoEConfig` or another configuration with its attention's
    fields. What lies round the core (:func:`heads_of`, :func:`out_of`) is
    ``models/keye_moe.sparse_gqa``'s too."""
    s = u.shape[1]
    window = cfg.window if kind == "window" else None
    # the scores' multiplier where the configuration publishes one
    # (``models/granite_h.py``: 1 / head_dim), ``1 / sqrt(head_dim)`` where
    # not, as ``mla_moe.mla`` reads the same field
    scale = getattr(cfg, "softmax_scale", None)
    with jax.named_scope("mv.lm.attn"):
        q, k, v = heads_of(u, p, cfg, kind)
        with jax.named_scope("mv.lm.attn." + kind):
            if mla_moe.attn_core(cfg) == "flash":
                o = flash_attention(q, k, v, True,
                                    *mla_moe.attn_blocks(cfg, s), None,
                                    window, scale=scale)
            else:
                o = mla_moe._xla_attention(q, k, v, window, scale=scale)
        return out_of(o, u, p, cfg)
