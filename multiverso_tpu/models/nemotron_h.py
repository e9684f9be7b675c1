"""A decoder of ONE mixer a block (a Mamba-2 state-space mixer, an expert
layer, or a grouped-query attention without positions) trained through
Adam tables: the fourth model on ``models/mla_moe.py``'s one decoder path
(the ``nemotron_h`` family: Nemotron-3-Nano-30B-A3B).

This file is the model's configuration and its state-space mixer. The
block (``Layer`` with one branch left out), the products, the norms, the
expert layer's call (experts of the ``relu2`` form, two matrices, beside a
shared one at its own width), the sigmoid route and its bias rule, the
chunked loss, the tables, the step and the ``Trainer`` are ``mla_moe``'s;
the attention is ``gqa_moe.gqa`` with every switch off and no layer kind
that takes positions. The equations, for a block with input ``x`` [B, S, D]:

* ``y = x + Mixer(RMSNorm(x))``; no embedding multiplier; a final norm.
* ``M`` (:func:`mamba2`): ``[z | xBC | dt] = u W_in``, widths ``heads x
  head_dim`` | ``heads x head_dim + 2 groups x state`` | ``heads``; ``xBC =
  silu(conv(xBC))``, a causal depthwise convolution of ``conv_kernel``
  taps with a bias, zeros before the sequence's start; ``xBC -> x`` [heads,
  head_dim], ``B``, ``C`` [groups, state]; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``, one each a head; the scan ``H_t = exp(dt_t A) H_{t-1}
  + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D x_t`` (``ops/ssd.py``, chunked:
  two Pallas kernels on a TPU that take any number of groups, a group's
  heads in blocks of at most 1,024 lanes where they do not fit one grid
  step, and a chunk of any whole number of lane tiles);
  ``y = GroupRMSNorm(y * silu(z)) * g``: the gate first, then an RMSNorm
  over each group's ``heads x head_dim / groups``; ``y W_out``. No
  projection bias. The state runs on across packed documents.
* ``E``: ``Shared(u) + held experts' part``, ``parallel/moe.py``'s sigmoid
  route; an expert is ``relu(u W_up)^2 W_down``.
* ``*``: ``gqa_moe.gqa``: q, k, v projections, a causal softmax over
  ``sqrt(head_dim)``, NO positions, ``o W_o``.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from multiverso_tpu.models import gqa_moe, mla_moe
from multiverso_tpu.models.mla_moe import Layer
from multiverso_tpu.ops import ssd
from multiverso_tpu.ops.short_conv import causal_taps, step_counts

# a block's kind by its letter in ``hybrid_override_pattern``
KINDS = {"M": ("ssm", None), "E": (None, "shared+experts"),
         "*": ("full", None)}


class NemotronHConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 48
    pattern: str = "MEM*E"           # a letter a block: KINDS
    # the state-space mixer
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_groups: int = 2
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
    # the expert layer
    moe_ffn: int = 24
    shared_ffn: int = 40             # the shared expert's own width
    n_experts: int = 16              # the router's outputs
    experts_held: int = 2
    expert_offset: int = 0
    top_k: int = 3
    routed_scale: float = 2.5
    bias_speed: float = 1e-3
    eps: float = 1e-5
    attn: Optional[str] = None       # as MLAMoEConfig's
    expert_kernel: Optional[str] = None
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(Layer(f"L{i}", *KINDS[letter])
                     for i, letter in enumerate(self.pattern))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return (mamba2_shapes(self) if kind == "ssm"
                else gqa_moe.gqa_shapes(self))

    def attend(self, u, p, kind: str):
        return mamba2(u, p, self) if kind == "ssm" else gqa_moe.gqa(
            u, p, self, kind)

    def ssm_grid(self, s: int) -> Dict[str, int]:
        """The scan's static counts over ``s`` positions, as ``lm.step``
        spans carry them (``ssd.step_counts`` says whether its kernels
        run, why where they do not, the chunk they walk where they do, and
        the blocks a group's heads are walked in), and the mixers' short
        convolution's (``short_conv.step_counts``). ``models/granite_h.py``'s
        configuration takes this method as it is."""
        mixers = sum(layer.attn == "ssm" for layer in self.layers())
        return {"ssm_chunks": s // self.chunk, "ssm_chunk": self.chunk,
                "ssm_heads": self.ssm_heads, "ssm_groups": self.ssm_groups,
                "ssm_state": self.ssm_state,
                **step_counts(mixers, s, mamba2_shapes(self)["conv_w"][1]),
                **ssd.step_counts(mixers, s, self.ssm_heads,
                                  self.ssm_head_dim, self.ssm_groups,
                                  self.ssm_state, self.chunk)}

    @property
    def first_values(self) -> Dict[str, Any]:
        """``mla_moe._draw``'s rules for what is not Normal(0, scale): the
        Mamba-2 rule. ``A`` uniform in ``a_init``, the step sizes
        log-uniform in [``time_step_min``, ``time_step_max``] and no less
        than ``time_step_floor``, the skip at 1: they decide how far the
        scan remembers."""
        return {"a_log": ("log_uniform",) + tuple(self.a_init),
                "dt_bias": ("softplus_inverse", self.time_step_min,
                            self.time_step_max, self.time_step_floor),
                "skip": "ones"}

    # ``gqa_moe.gqa``'s switches: all off, and no kind takes positions
    qk_norm = attn_gate = False
    rope_kinds = ()
    window = yarn = None
    post_norms = False               # ``mla_moe.block``'s
    embed_scale = 1.0
    route = "sigmoid"                # parallel/moe.HeldExperts.route
    expert_form = "relu2"            # parallel/moe.HeldExperts.form
    # ``mla_moe.kept_names``: a block keeps NOTHING here. What it keeps is
    # reserved with the step's program, and beside this model's step
    # ``nemotron3n-train-16k`` has 0.26 GB of 16.9 free (PERF.md, PR 51:
    # with ``w_up``'s results kept, 46 MB a layer, the step no longer loads
    # a second time after the reference's programs). Goes when the cell
    # has room (ROADMAP Speed 7(c))
    keeps_products = False
    balance_coef = 0.0               # no load-balance term in the loss

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads


def mamba2_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    """The parameters of a state-space mixer and its block's norm."""
    d, h = cfg.dim, cfg.ssm_heads
    inner = h * cfg.ssm_head_dim
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {"attn_norm": (d,), "ffn_norm": (d,),
            "win": (d, inner + conv + h),           # [z | xBC | dt]
            # a row a tap: tap i reads position t - (taps - 1) + i
            "conv_w": (cfg.conv_kernel, conv), "conv_b": (conv,),
            "a_log": (h,), "dt_bias": (h,), "skip": (h,),
            "gate_norm": (inner,), "wout": (inner, d)}


def mamba2(u, p, cfg):
    """The state-space mixer on the normed input ``u`` [B, S, D] -> [B, S,
    D] float32."""
    b, s, _ = u.shape
    h, hd, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, dt_ = h * hd, cfg.compute_dtype
    conv = inner + 2 * g * n
    with jax.named_scope("mv.lm.ssm"):
        # the convolution's operand is a product of its own, from the
        # table's column window: a kernel takes no fusion, and a window of
        # ONE product's result would be copied out for it (0.4 GB a pass)
        z_w, xbc_w, dt_w = jnp.split(p["win"], (inner, inner + conv), axis=-1)
        xbc = mla_moe.matmul(u, xbc_w, False, dt_, jnp.float32)
        z, dt = jnp.split(
            mla_moe.matmul(u, jnp.concatenate([z_w, dt_w], -1), False, dt_,
                           jnp.float32), (inner,), axis=-1)
        with jax.named_scope("mv.lm.ssm.conv"):
            xbc = causal_taps(xbc, p["conv_w"], p["conv_b"], True)
        x, bm, cm = jnp.split(xbc, (inner, inner + g * n), axis=-1)
        x = x.reshape(b, s, h, hd)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        with jax.named_scope("mv.lm.ssm.scan"):
            # the scan's kernels read x, B and C out of ``xbc`` as it lies
            # and add the skip; the plain form takes the windows
            y = ssd.ssd_chunked(
                x, dt, -jnp.exp(p["a_log"]), bm.reshape(b, s, g, n),
                cm.reshape(b, s, g, n), cfg.chunk, dt_, skip=p["skip"],
                whole=xbc)
        with jax.named_scope("mv.lm.ssm.norm"):
            y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(
                b, s, g, inner // g)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.eps)
            y = y.reshape(b, s, inner) * p["gate_norm"]
        return mla_moe.matmul(y, p["wout"], False, dt_, jnp.float32)
