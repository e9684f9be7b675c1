"""A decoder with latent attention (MLA), routed experts beside a shared
one, and a multi-token-prediction module, trained through Adam tables.

One chip's share of a deployment in which several chips share each
layer: this chip holds ``experts_held`` of a layer's ``n_experts`` routed
experts (``parallel/moe.held_expert_layer``: it routes over all of them
and computes its own experts' part, no token dropped) and a slice of the
vocabulary; attention, the shared expert, the router and the norms are
whole. The equations, for a block with input ``x``:

* ``h = x + Attn(RMSNorm(x))``, ``y = h + F(RMSNorm(h))``; ``F`` is the
  gated MLP ``(silu(u W_g) * (u W_u)) W_d`` in the leading dense layers
  and ``Shared(u) + held experts' part`` after them.
* MLA: ``c_q = RMSNorm(x W_DQ)``, ``q = c_q W_UQ`` -> heads of
  [nope | rope]; ``[c_kv | k_r] = x W_DKV``, ``c_kv = RMSNorm(c_kv)``,
  ``[k_n | v] = c_kv W_UKV`` per head; rotary on the query's rope part
  and on ``k_r``, which every head shares; causal softmax of
  ``(q . k) / sqrt(nope + rope)``; output ``o W_O``. Keys and values are
  materialised per head, so the flash kernel computes the core.
* the prediction module: ``eh_proj([RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)])``
  through one block of the expert kind and its own output norm to the
  SAME head, predicting ``t_{i+2}``; loss ``CE(main) + w * CE(module)``.

The decoder itself is one code path for more than this model: a
configuration says its layers as data (``cfg.layers()``: a
:class:`Layer` names each block's attention kind, ``latent`` here,
``full`` or ``window`` grouped-query heads in ``models/gqa_moe.py``,
``ssm`` in ``models/nemotron_h.py``, ``conv`` in ``models/lfm2_moe.py``,
and its feed-forward kind, ``dense``, ``shared+experts`` or ``experts``
alone), how its routers score (``cfg.route`` and ``cfg.routed_scale``:
``parallel/moe.HeldExperts``'s), the shapes of an attention kind's
parameters (``cfg.attn_shapes(kind)``), the attention itself
(``cfg.attend(u, p, kind)``), where a block's norms stand
(``cfg.post_norms``: before each branch alone, or on its way out as
well, as ``models/afmoe.py`` has them), what the embedding is
multiplied by (``cfg.embed_scale``) and, where it has such a field,
whether the embedding's table is the head as well (``cfg.tied_head``),
how many residual streams a position has (``cfg.streams``) and how often
the stack of blocks runs with the same tables (``cfg.passes``:
:func:`_passes`, one loop of the program, an exit gate after every pass
and :func:`_exit_loss`'s loss over the exits; ``models/ouro.py``, which
has no expert layer at all: the step then has no router, bias or counts);
:func:`block`, :func:`matmul`,
:func:`rms_norm`, :func:`rotary`, the chunked loss, the tables, the step
and :class:`Trainer` below are shared by every such configuration.

Every trained parameter lies in a ``Table`` (:func:`make_tables`);
:func:`make_train_step` is to this model what
``models/dlrm.make_train_step`` is to DLRM: one program takes the tables'
states donated, computes loss and float32 gradients, and hands each
gradient to its table's updater through ``functional_add``. Matrix
products take bfloat16 operands (``compute_dtype``) and sum in float32;
router, softmax, norms, loss and tables are float32.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.ops.attention_kernels import (causal_pairs,
                                                   flash_attention, sub_tile)
from multiverso_tpu.ops import head_turns, stream_walks
from multiverso_tpu.parallel import moe
from multiverso_tpu.telemetry import devstats as _devstats
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.updaters import AddOption


class Layer(NamedTuple):
    """One block of a decoder, as data. A block has two branches, ``attn``
    then ``ffn``, or ONE mixer (the other is ``None``: one norm, one
    residual sum)."""
    name: str                   # its parameters' prefix: "L<i>", or "mtp"
    attn: Optional[str]         # "latent", "full", "window", "ssm", "conv",
                                # "sparse", "delta"
    ffn: Optional[str]          # "dense", "shared+experts" or "experts"


class MLAMoEConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    n_heads: int = 2
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_dim: int = 6
    qk_rope_dim: int = 2
    v_head_dim: int = 8
    rope_theta: float = 1e6
    dense_ffn: int = 320
    n_dense_layers: int = 1
    n_moe_layers: int = 2
    moe_ffn: int = 48
    n_experts: int = 16              # the router's outputs
    experts_held: int = 4
    expert_offset: int = 0
    top_k: int = 4
    routed_scale: float = 1.8
    n_mtp: int = 1                   # prediction modules (0 or 1)
    mtp_weight: float = 0.3
    bias_speed: float = 1e-3
    eps: float = 1e-5
    # the attention core: "flash" (the Pallas kernel) or "xla"; ``None``
    # takes the kernel on a TPU and XLA off it, where the kernel's
    # interpreter takes minutes a step
    attn: Optional[str] = None
    expert_kernel: Optional[str] = None  # parallel/moe.held_expert_layer's
    attn_block: int = 512            # the flash kernel's q block (attn_blocks)
    loss_chunk: int = 4096           # positions a chunk of the two losses
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        """Dense layers, then expert layers, then the prediction module."""
        first = self.n_dense_layers
        return (tuple(Layer(f"L{i}", "latent", "dense")
                      for i in range(first))
                + tuple(Layer(f"L{i}", "latent", "shared+experts")
                        for i in range(first, first + self.n_moe_layers))
                + ((Layer("mtp", "latent", "shared+experts"),)
                   if self.n_mtp else ()))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        return _attn_shapes(self)

    def attend(self, u, p, kind: str):
        return mla(u, p, self)

    # rotary frequencies as they are and scores over ``sqrt(nope + rope)``:
    # :func:`mla`'s two switches (``models/xing4.py`` sets both)
    yarn = softmax_scale = None

    @property
    def head_size(self) -> int:      # the wider of the core's two
        return max(self.qk_nope_dim + self.qk_rope_dim, self.v_head_dim)

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return 1

    @property
    def route(self) -> str:          # parallel/moe.HeldExperts.route
        return "sigmoid"

    @property
    def balance_coef(self) -> float:     # no load-balance term in the loss
        return 0.0

    @property
    def post_norms(self) -> bool:    # a block norms its branches' inputs
        return False

    @property
    def embed_scale(self) -> float:
        return 1.0

    @property
    def expert_form(self) -> str:    # parallel/moe.HeldExperts.form
        return "gated_silu"

    @property
    def shared_ffn(self) -> int:     # the shared expert's width
        return self.moe_ffn


# The held experts' buffer, in rows, for a layer's ``tokens``: twice what
# an even router sends here, and never under the floor (the loads of a few
# hundred tokens swing far more than a batch's), nor over what the routing
# can send at all. With balanced routers one window in 24 had a batch of
# 16,384 tokens that passed a buffer of 9,728 rows by 220, where 8,192 is
# even (PERF.md section 6, PR 33); a row past the buffer is counted
# (``overflow_rows``) and left out.
BUFFER_OVER_EVEN, BUFFER_FLOOR = 2, 2048


def held(cfg, tokens: int) -> moe.HeldExperts:
    """The expert layer's part that lies here, for ``tokens`` a layer. The
    grouped products' tile is ``moe.product_tile``'s from the widths, over
    rows ``cfg.product_rows`` where the configuration says so
    (``models/xing4.py``: a held expert sees 256 rows a step)."""
    most = tokens * min(cfg.top_k, cfg.experts_held)
    even = tokens * cfg.top_k * cfg.experts_held // cfg.n_experts
    tile = moe.product_tile(cfg.dim, cfg.moe_ffn)
    tile = (getattr(cfg, "product_rows", tile[0]),) + tile[1:]
    return moe.HeldExperts(
        num_experts=cfg.n_experts, experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset, top_k=cfg.top_k,
        routed_scale=cfg.routed_scale, route=cfg.route, form=cfg.expert_form,
        tile=tile, dtype=cfg.compute_dtype,
        buffer_rows=min(most, max(BUFFER_OVER_EVEN * even, BUFFER_FLOOR)))


def expert_layers(cfg) -> Tuple[str, ...]:
    """The layers that have a router, in the order of the bias rows and of
    the step's counts (the prediction module, where there is one, last)."""
    return tuple(layer.name for layer in cfg.layers()
                 if layer.ffn not in (None, "dense"))


# ---------------------------------------------------------------------- #
# parameters
# ---------------------------------------------------------------------- #
def _attn_shapes(cfg: MLAMoEConfig) -> Dict[str, Tuple[int, ...]]:
    d, h = cfg.dim, cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "attn_norm": (d,), "wdq": (d, cfg.q_lora_rank),
        "q_norm": (cfg.q_lora_rank,), "wuq": (cfg.q_lora_rank, h * qk),
        # a row an output: [c_kv | k_r] = x W_DKV^T
        "wdkv": (cfg.kv_lora_rank + cfg.qk_rope_dim, d),
        "kv_norm": (cfg.kv_lora_rank,),
        "wukv": (cfg.kv_lora_rank, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": (h * cfg.v_head_dim, d), "ffn_norm": (d,)}


def _ffn_shapes(cfg, kind: str) -> Dict[str, Tuple[int, ...]]:
    d = cfg.dim
    if kind == "dense":
        return {"wg": (d, cfg.dense_ffn), "wu": (d, cfg.dense_ffn),
                "wd": (cfg.dense_ffn, d)}
    f, e = cfg.moe_ffn, cfg.experts_held
    out = {"router": (cfg.n_experts, d),        # a row an expert
           "eg": (e, d, f), "eu": (e, d, f), "ed": (e, f, d)}
    if kind == "shared+experts":
        f = cfg.shared_ffn
        out.update(sg=(d, f), su=(d, f), sd=(f, d))
    if cfg.expert_form == "relu2":              # two matrices: no gate
        del out["eg"]
        out.pop("sg", None)
    if kind == "shared+experts" and getattr(cfg, "shared_gate", False):
        out["sgate"] = (d,)                     # one number a token
    return out


def streams_of(cfg) -> int:
    """The residual streams a position has between blocks: 1, or the
    configuration's ``streams`` (``models/xing4.py``: hyper-connections)."""
    return int(getattr(cfg, "streams", 1))


def passes_of(cfg) -> int:
    """How often the stack of blocks runs, with the same tables: 1, or the
    configuration's ``passes`` (``models/ouro.py``: a looped decoder)."""
    return int(getattr(cfg, "passes", 1))


def _stream_shapes(cfg, branch: str) -> Dict[str, Tuple[int, ...]]:
    """A sublayer's hyper-connection under several streams (none under
    one): ``hc_phi``, a row an output as the router's are, [n + n + n^2,
    n x dim] (pre, post, then the n x n residual mix row by row);
    ``hc_b``, an offset an output; ``hc_alpha``, one gain for each of the
    three groups."""
    n = streams_of(cfg)
    if n == 1:
        return {}
    outs = n * n + 2 * n
    return {f"{branch}.hc_phi": (outs, n * cfg.dim),
            f"{branch}.hc_b": (outs,), f"{branch}.hc_alpha": (3,)}


def param_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    """Every trained parameter by name. Layers are ``L<i>.``; the
    prediction module is ``mtp.``; ``embed`` and ``head`` have a row a
    token id, and under a tied head (``cfg.tied_head``) there is no
    ``head``: the embedding's table is both. Under several residual
    streams every sublayer has its hyper-connection's three tables
    (``<layer>.attn.hc_*``, ``<layer>.ffn.hc_*``: :func:`_stream_shapes`).
    A stack run several times (:func:`passes_of`) has the exit gate's
    ``exit.w`` [dim] and ``exit.b`` [1], and every layer's tables ONCE."""
    d = cfg.dim
    out = {"embed": (cfg.vocab, d), "final_norm": (d,)}
    if not tied_head(cfg):
        out["head"] = (cfg.vocab, d)
    if passes_of(cfg) > 1:
        out.update({"exit.w": (d,), "exit.b": (1,)})
    for layer in cfg.layers():
        # an attention kind's shapes bring the block's two input norms
        block = (dict(cfg.attn_shapes(layer.attn)) if layer.attn
                 else {"ffn_norm": (d,)})
        if layer.ffn:
            block.update(_ffn_shapes(cfg, layer.ffn))
        else:
            del block["ffn_norm"]
        if cfg.post_norms:
            block.update(attn_post_norm=(d,), ffn_post_norm=(d,))
        for branch in (("attn",) if layer.attn else ()) + (
                ("ffn",) if layer.ffn else ()):
            block.update(_stream_shapes(cfg, branch))
        if layer.name == "mtp":
            block.update(enorm=(d,), hnorm=(d,), eh_proj=(2 * d, d),
                         out_norm=(d,))
        out.update({f"{layer.name}.{k}": v for k, v in block.items()})
    return out


def tied_head(cfg) -> bool:
    """Whether the logits' head is the embedding's table."""
    return bool(getattr(cfg, "tied_head", False))


def residual_scale(cfg) -> float:
    """What a branch's result is multiplied by as it joins the stream."""
    return float(getattr(cfg, "residual_scale", 1.0))


def logit_scale(cfg) -> float:
    """What the head's logits are multiplied by before the softmax."""
    return float(getattr(cfg, "logit_scale", 1.0))


def _draw(shape, key, scale: float, rule, pad: int = 0) -> jax.Array:
    """A parameter's first values by ``rule`` (:func:`_rule_of`): ``"ones"``,
    ``"normal"`` (Normal(0, scale)), ``("log_uniform", lo, hi)`` (the log of
    a uniform draw from [lo, hi]) or ``("softplus_inverse", lo, hi, floor)``
    (``b`` with ``softplus(b)`` log-uniform in [lo, hi], no less than
    ``floor``); ``pad`` zero rows after them (a table's padding)."""
    if rule == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif rule == "normal":
        x = scale * jax.random.normal(key, shape, jnp.float32)
    elif rule[0] == "log_uniform":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, *rule[1:]))
    elif rule[0] == "softplus_inverse":
        lo, hi, floor = rule[1:]
        step = jnp.maximum(floor, jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, np.log(lo), np.log(hi))))
        x = step + jnp.log(-jnp.expm1(-step))
    else:
        raise ValueError(f"no first values by the rule {rule!r}")
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (len(shape) - 1))


def _rule_of(cfg, name: str):
    """:func:`_draw`'s rule for a parameter: ones for a norm, what the
    configuration's ``first_values`` says of the last part of its name
    where it has such a table, else Normal(0, scale)."""
    if name.endswith("norm"):
        return "ones"
    return getattr(cfg, "first_values", {}).get(name.split(".")[-1], "normal")


def _keys(cfg, seed: int):
    """(name, shape, key) of every parameter, by name; ``seed`` is any
    whole number (a benchmark's pass 2**31)."""
    seed = int(seed)
    # XLA's own bit generator: a table's draw compiles and runs in a
    # fraction of threefry's time, and one seed still gives one model
    key = jax.random.fold_in(jax.random.key(seed % (2 ** 31), impl="rbg"),
                             seed // (2 ** 31))
    return [(name, shape, jax.random.fold_in(key, i)) for i, (name, shape)
            in enumerate(sorted(param_shapes(cfg).items()))]


def _scale_of(name: str, scale: float,
              scales: Optional[Dict[str, float]]) -> float:
    return (scales or {}).get(name.split(".")[-1], scale)


def init(cfg, seed: int = 0, scale: float = 0.02,
         scales: Optional[Dict[str, float]] = None) -> Dict[str, jax.Array]:
    """Parameters from ``seed``: Normal(0, scale) matrices, norms of ones,
    and what the configuration's ``first_values`` draws by a rule of its
    own (:func:`_draw`); ``scales`` gives a kind of parameter its own
    scale by the last part of
    its name (``{"router": 0.01, "embed": 1.0}``). The same values
    :func:`make_tables` puts into the tables."""
    return {name: _draw(table_shape(shape), key,
                        _scale_of(name, scale, scales),
                        _rule_of(cfg, name)).reshape(shape)
            for name, shape, key in _keys(cfg, seed)}


def init_bias(cfg) -> jax.Array:
    """The routers' selection biases, a row a layer of
    :func:`expert_layers`: not trained, moved by ``moe.bias_update``."""
    return jnp.zeros((len(expert_layers(cfg)), getattr(cfg, "n_experts", 0)),
                     jnp.float32)


def table_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """A parameter's shape as its table holds it: the held experts' stack
    lies as rows of one matrix (a table pads its leading dimension by
    one, and a ninth expert would be 12%)."""
    return shape if len(shape) < 3 else (shape[0] * shape[1], shape[2])


def make_tables(cfg, seed: int = 0, scale: float = 0.02,
                updater: Any = "adam",
                scales: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """One table a parameter (a ``MatrixTable`` for matrices, whose rows
    are token ids for ``embed`` and ``head``; an ``ArrayTable`` for a
    norm), filled on the device with :func:`init`'s values."""
    import multiverso_tpu as mv

    tables = {}
    for name, shape, key in _keys(cfg, seed):
        shape = table_shape(shape)
        table = (mv.ArrayTable(shape[0], updater=updater, name=name)
                 if len(shape) == 1 else
                 mv.MatrixTable(shape[0], shape[1], updater=updater,
                                name=name))
        # one program a shape, not one a table
        draw = jax.jit(_draw, static_argnums=(0, 2, 3, 4),
                       out_shardings=table.sharding)
        data = draw(shape, key, _scale_of(name, scale, scales),
                    _rule_of(cfg, name), table.padded_shape[0] - shape[0])
        table.adopt({"data": data, "ustate": table.state["ustate"]})
        tables[name] = table
    return tables


# ---------------------------------------------------------------------- #
# layers
# ---------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def matmul(x, w, transpose_w: bool, dtype, out_dtype):
    """``x @ w`` (``x @ w.T`` with ``transpose_w``) with operands in
    ``dtype`` and a float32 sum, forward and backward; ``w`` is float32
    (a table's data) and takes a float32 gradient."""
    return _matmul_fwd(x, w, transpose_w, dtype, out_dtype)[0]


def _dot(a, b, contract, out_dtype):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        preferred_element_type=jnp.float32).astype(out_dtype)


def _matmul_fwd(x, w, transpose_w, dtype, out_dtype):
    xc, wc = x.astype(dtype), w.astype(dtype)
    y = _dot(xc, wc, ((x.ndim - 1,), (1 if transpose_w else 0,)), out_dtype)
    return y, (xc, wc, jnp.zeros((0,), x.dtype))


def _matmul_bwd(transpose_w, dtype, out_dtype, res, g):
    xc, wc, like = res
    g = g.astype(dtype)
    lead = tuple(range(xc.ndim - 1))
    dx = _dot(g, wc, ((g.ndim - 1,), (0 if transpose_w else 1,)), like.dtype)
    dw = (_dot(g, xc, (lead, lead), jnp.float32) if transpose_w
          else _dot(xc, g, (lead, lead), jnp.float32))
    return dx, dw


matmul.defvjp(_matmul_fwd, _matmul_bwd)


def rms_norm(x, w, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


class Yarn(NamedTuple):
    """YaRN's rescaling of rotary frequencies, under the names the
    published ``rope_parameters`` give its numbers."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


def rotary_frequencies(r: int, theta: float, yarn: Optional[Yarn] = None):
    """(frequencies [r / 2], the factor on cos and sin). Plain:
    ``theta^(-2i/r)`` and 1. YaRN: a dimension that turns ``beta_fast``
    times or more within the original length keeps its frequency, one
    that turns ``beta_slow`` times or fewer has it divided by ``factor``,
    and those between blend by a linear ramp over the dimension's number;
    cos and sin are multiplied by ``attention_factor``."""
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if yarn is None:
        return jnp.asarray(freq, jnp.float32), 1.0

    def dimension_turning(turns: float) -> float:
        return (r * np.log(yarn.original_max_position_embeddings
                           / (turns * 2 * np.pi)) / (2 * np.log(theta)))

    low = max(np.floor(dimension_turning(yarn.beta_fast)), 0)
    high = min(np.ceil(dimension_turning(yarn.beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq = freq / yarn.factor * ramp + freq * (1 - ramp)
    return jnp.asarray(freq, jnp.float32), float(yarn.attention_factor)


def rotary_tables(s: int, r: int, theta: float, yarn: Optional[Yarn] = None,
                  positions=None,
                  sections: Optional[Tuple[int, ...]] = None):
    """(cos, sin) of :func:`rotary`'s angles over ``s`` positions and ``r``
    columns, YaRN's factor in them: [S, r/2], or [B, S, r/2] under
    ``positions`` [A, B, S] dealt by ``sections``."""
    freq, factor = rotary_frequencies(r, theta, yarn)
    if positions is None:
        angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    else:
        if sum(sections) != r // 2 or len(sections) != positions.shape[0]:
            raise ValueError(f"sections {sections} do not deal {r // 2} "
                             f"frequencies over {positions.shape[0]} axes")
        axis = np.repeat(np.arange(len(sections)), sections)    # [R/2]
        # frequency i's own axis: [B, S, R/2]
        angle = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[
            ..., axis] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor != 1.0:
        cos, sin = factor * cos, factor * sin
    return cos, sin


def rotary(x, theta: float, yarn: Optional[Yarn] = None, positions=None,
           sections: Optional[Tuple[int, ...]] = None):
    """Rotary positions on the last axis of ``x`` [B, S, ..., R], pairing
    element ``i`` with ``i + R/2``; float32. ``yarn``: see
    :func:`rotary_frequencies`. A token's position is its place in the
    sequence, or, with ``positions`` [A, B, S] and ``sections`` (A counts
    that add up to R/2), one id an axis: the first ``sections[0]``
    frequencies turn by the first axis's id, the next ``sections[1]`` by
    the second's, and so on (three axes, time, height and width, in a
    model that reads images; a text token's ids are equal, and that is
    the plain form)."""
    s, r = x.shape[1], x.shape[-1]
    cos, sin = rotary_tables(s, r, theta, yarn, positions, sections)
    shape = ((1, s) if positions is None else cos.shape[:2]) + (
        1,) * (x.ndim - 3) + (r // 2,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attn_core(cfg) -> str:
    """The attention core that runs: ``cfg.attn``, or by the device."""
    return cfg.attn or (
        "flash" if jax.devices()[0].platform == "tpu" else "xla")


def attn_blocks(cfg, s: int) -> Tuple[int, int]:
    """The flash kernel's (q, k) blocks over ``s`` positions, from ``s``
    and the head size: ``attn_block`` rows of q, and twice as many of k
    where a k block of at most 1,024 rows at a head size of at most 256
    divides ``s``; at a head size of at most 192 (the wider of a core's
    two) the q block doubles with it (what fits the kernels' VMEM: at 256,
    1,024 x 1,024 and 512 x 2,048 do not). At (8192, 256) on a v5e the forward, dQ and dK/dV kernels
    read 13.3 / 13.9 / 17.9 ms a call at 512 x 512, 12.0 / 13.4 / 17.7 at
    1,024 x 512 and 10.9 / 13.4 / 17.6 at 512 x 1,024: an accumulator is
    rescaled once a k block. At (8192, 128) with 32 query heads over 4
    key-value heads (PERF.md section 6, PR 35) 20.5 / 12.9 / 16.8 at 512 x
    512, 11.9 / 12.0 / 15.3 at 512 x 1,024 and 10.3 / 11.1 / 14.9 at 1,024 x
    1,024. Under a window of 1,024 the same three read 7.5 / 4.8 / 6.1,
    5.7 / 5.8 / 6.9 and 5.1 / 5.4 / 6.8 with a crossed pair one tile under
    one mask: 15 pairs of 1,024 x 1,024 a head are twice the band's area
    and 45 of 512 x 512 one and a half times, but a k block's rescale and
    a grid step's cost weigh more than the masked half of a tile, so the
    blocks stay wide and the band's edges are cut INSIDE the pair, where
    a step costs nothing: ``attention_kernels.sub_tile`` gives a crossed
    pair sub-tiles of 256, of which it computes the live ones (1.25 of
    the band, not 2.00) and masks those an edge passes through: 3.3 / 3.5
    / 4.7 at 1,024 x 1,024 (PERF.md section 6, PR 44), and the causal call
    above 9.3 / 10.1 / 13.7 for 10.1 / 11.0 / 14.7. At (8192, 64) with 32
    query heads over 8 key-value heads (PERF.md section 6, PR 50) a block is
    half a lane tile, which Mosaic lowers as it is, and the same three read
    9.9 / 11.6 / 14.9 at 1,024 x 1,024, 10.3 / 12.5 / 15.5 at 512 x 1,024
    and 10.7 / 13.9 / 17.2 at 512 x 512 with sub-tiles of 256 (10.7 / 12.6 /
    15.9, 11.1 / 13.2 / 16.2 and 10.8 / 14.1 / 17.2 whole): a head of 64
    takes the blocks of a head of 128, and costs no less than one (the
    products halve; the lanes, the exps, the masks and the rescales do
    not). At (16384, 256) with 16 query heads over 2 key-value heads, a
    group of 8 (PERF.md section 6, PR 56), the three read 15.4 / 20.6 /
    26.6 at 512 x 512, 14.9 / 19.1 / 25.0 at 512 x 1,024, 14.3 / 19.6 /
    25.3 at 1,024 x 512 and 14.5 / 18.3 / 24.4 at 1,024 x 1,024 with
    sub-tiles of 256 (15.7 / 20.8 / 27.0, 15.5 / 19.9 / 26.0, 14.9 / 19.9
    / 26.2 and 15.2 / 19.1 / 25.5 whole): every shape holds the
    dK-with-dV kernel's group of 8 query heads against one k block in VMEM,
    and the rule's 512 x 1,024 is within 3% of the fastest, so a head of
    256 keeps one rule whatever its group. At (4096, 192) for queries and
    keys and 128 for values, 32 heads (PERF.md section 6, PR 60), a block
    of a lane tile and a half with sub-tiles of 256: 2.12 / 2.95 / 3.48 at
    512 x 512, 2.05 / 2.82 / 3.32 at 512 x 1,024, 2.01 / 2.91 / 3.33 at
    1,024 x 512, **1.96 / 2.66 / 3.15 at 1,024 x 1,024**, which fits at
    these sizes, 2.39 / 3.10 / 3.68 at 256 x 1,024 (whole tiles: 2.17 /
    3.06 / 3.52, 2.30 / 3.12 / 3.61, 2.26 / 3.11 / 3.61, 2.24 / 2.99 /
    3.51): a head of 192 takes the blocks of a head of 128."""
    bq = min(cfg.attn_block, s)
    wide = 2 * bq <= 1024 and cfg.head_size <= 256 and s % (2 * bq) == 0
    if not wide:
        return bq, bq
    return (2 * bq if cfg.head_size <= 192 else bq), 2 * bq


def turned_parts(cfg, kind: str) -> Tuple[Tuple[int, int, bool], ...]:
    """(heads, head size, whether normed) of every projection of a ``kind``
    layer that takes :func:`heads`'s pass on its way to the core (a norm
    or rotary positions): the latent layer's q; a grouped-query layer's q
    and k where the configuration norms them or the kind takes positions;
    none where a layer's parts all go as their products write them."""
    if kind == "latent":
        return ((cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim, False),)
    if not (cfg.qk_norm or kind in cfg.rope_kinds):
        return ()
    return tuple((n, cfg.head_dim, bool(cfg.qk_norm))
                 for n in (cfg.n_heads, cfg.n_kv_heads))


def heads_grid(cfg, kinds, sequences: int, s: int) -> Dict[str, int]:
    """What :func:`heads` does a step, from the shapes: the layers whose
    core operands it makes, the bytes its turning pass reads and writes
    (float32 in and the compute dtype out, forward and made again under
    ``jax.checkpoint``; the core's cotangent in, beside the float32 sum
    where there is a norm, and the product's out, backward), and the
    layers whose pass is ``ops/head_turns.py``'s kernels on this device
    (0: XLA runs the plain form)."""
    item = jnp.dtype(cfg.compute_dtype).itemsize
    moved = kernel_layers = 0
    for kind in kinds:
        parts = turned_parts(cfg, kind)
        for n, hd, normed in parts:
            moved += sequences * s * n * hd * (
                2 * (4 + item) + 2 * item + 4 * normed)
        kernel_layers += bool(parts) and all(
            head_turns.kernel_tile(s, hd, cfg.compute_dtype)
            for _, hd, _ in parts)
    return {"heads_layers": len(kinds), "heads_turned_bytes": moved,
            "heads_kernel_layers": kernel_layers}


def attn_grid(cfg, s: int, sequences: int = 1) -> Dict[str, Any]:
    """What one flash kernel call over ``s`` positions does a (batch x
    head), as ``lm.step`` spans carry it: the layers' attention kinds,
    the query heads a key-value head, a block's norms and the embedding's
    multiplier, the causal walk's counts and, where some layer is of the
    window kind, the band's beside what a causal walk of the band's blocks
    would visit and what the grouped-query attention does around its core
    (``gqa_moe.gqa``'s switches); the positions a forward call computes
    (whole tiles, and of a crossed pair the sub-tiles of
    ``attention_kernels.sub_tile`` that hold a live position) beside those
    it needs; what lies between the projections and the core over
    ``sequences`` of them (:func:`heads_grid`); nothing where XLA is the
    core."""
    if attn_core(cfg) != "flash":
        return {}
    layers = cfg.layers()
    # the kinds whose core is the flash kernel
    kinds = [layer.attn for layer in layers
             if layer.attn not in (None, "ssm", "conv", "delta")]
    branches = max(bool(layer.attn) + bool(layer.ffn) for layer in layers)
    blocks = attn_blocks(cfg, s)
    # a selection masks every pair by its own tile: whole tiles
    sub = None if "sparse" in kinds else sub_tile(*blocks, cfg.head_size)
    n = causal_pairs(s, *blocks, sub=sub)
    out = {"attn_grid_steps": n["grid_steps"], "attn_pairs_live": n["live"],
           "attn_pairs_masked": n["masked"],
           "attn_positions_computed": n["computed"],
           "attn_positions_needed": n["needed"],
           "attn_kinds": ",".join(kinds),
           "kv_group": cfg.kv_group,
           "block_norms": (2 if cfg.post_norms else 1) * branches,
           "embed_scale": float(cfg.embed_scale),
           # a layer run ``passes`` times makes its operands as often
           **heads_grid(cfg, kinds * passes_of(cfg), sequences, s)}
    if "window" in kinds:
        band = causal_pairs(s, *blocks, cfg.window, sub)
        out.update(attn_pairs_live_window=band["live"],
                   attn_pairs_masked_window=band["masked"],
                   attn_pairs_causal_window=n["live"],
                   attn_positions_computed_window=band["computed"],
                   attn_positions_needed_window=band["needed"],
                   attn_gated=int(cfg.attn_gate), qk_norm=int(cfg.qk_norm),
                   rope_kinds=",".join(cfg.rope_kinds))
    return out


def mixer_grid(cfg, s: int) -> Dict[str, Any]:
    """What a layer list with a mixer that is no attention, or of
    one-mixer blocks, adds to the ``lm.step`` span: every block's kinds in
    order (a two-branch block's joined by ``+``), the experts' form and,
    where some block is a state-space mixer, its scan's static counts over
    ``s`` positions (``cfg.ssm_grid``), where some is a convolution mixer,
    that mixer's (``cfg.conv_grid``), where some attends under a learned
    selection (``sparse``), the indexer's and the selection's
    (``cfg.index_grid``), where some is a delta-rule linear-attention
    mixer, that mixer's (``cfg.delta_grid``); nothing for a list of
    two-branch attention blocks of the other kinds. The experts' form is
    left out where no layer has experts."""
    layers = cfg.layers()
    mixers = {kind: sum(layer.attn == kind for layer in layers)
              for kind in ("ssm", "conv", "sparse", "delta")}
    if not any(mixers.values()) and all(layer.attn and layer.ffn
                                        for layer in layers):
        return {}
    out = {"block_kinds": ",".join(
               "+".join(kind for kind in (layer.attn, layer.ffn) if kind)
               for layer in layers),
           **({"expert_form": cfg.expert_form} if expert_layers(cfg)
              else {})}
    if mixers["ssm"]:
        out.update(ssm_layers=mixers["ssm"], **cfg.ssm_grid(s))
    if mixers["conv"]:
        out.update(cfg.conv_grid(s))
    if mixers["sparse"]:
        out.update(cfg.index_grid(s))
    if mixers["delta"]:
        out.update(cfg.delta_grid(s))
    return out


def multiplier_grid(cfg) -> Dict[str, Any]:
    """What a configuration that multiplies its residual sums or its logits
    (``models/granite_h.py``) adds to the ``lm.step`` span: the four
    published multipliers as the program applies them (the scores'
    ``softmax_scale`` as the number the core multiplies by) and whether the
    head is the embedding's table; nothing for every other
    configuration."""
    if residual_scale(cfg) == 1.0 and logit_scale(cfg) == 1.0:
        return {}
    return {"embed_scale": float(cfg.embed_scale),
            "residual_scale": residual_scale(cfg),
            "logit_scale": logit_scale(cfg),
            "softmax_scale": float(getattr(cfg, "softmax_scale", None)
                                   or cfg.head_size ** -0.5),
            "tied_head": int(tied_head(cfg))}


def _xla_attention(q, k, v, window: Optional[int] = None, select=None,
                   scale: Optional[float] = None):
    """Causal attention over q [B, H, S, D] and k [B, Hkv, S, D], v [B,
    Hkv, S, Dv] in plain XLA, float32 softmax, under a ``window`` where one
    is given and under a selection (``select`` [B, S, S], nonzero where a
    query may see a key, shared by the heads) where one is given, the
    scores times ``scale`` where one is given and over ``sqrt(D)`` where
    not: the CPU tests' core, and the flash kernel's stand-in off the
    chip."""
    b, h, n, d = q.shape
    grouped = q.reshape(b, k.shape[1], h // k.shape[1], n, d)
    s = jnp.einsum("bkgqd,bkjd->bkgqj", grouped, k,
                   preferred_element_type=jnp.float32)
    s = s / d ** 0.5 if scale is None else s * scale
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = i >= j
    if window is not None:
        seen &= i - j < window
    if select is not None:
        seen = seen & (select != 0)[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1).astype(v.dtype)
    return jnp.einsum("bkgqj,bkjd->bkgqd", p, v,
                      preferred_element_type=jnp.float32
                      ).astype(q.dtype).reshape(q.shape[:3] + v.shape[3:])


# ---------------------------------------------------------------------- #
# between a projection and the core
# ---------------------------------------------------------------------- #
class Heads(NamedTuple):
    """How one projection's result reaches the attention core (hashable:
    :func:`heads`'s statics). Columns ``[lo, lo + rope)`` of every head
    take rotary positions (``rope`` 0: none); ``pad`` zero columns follow
    the product's own, where :func:`heads`'s ``beside`` goes."""
    dtype: Any
    eps: float = 0.0
    lo: int = 0
    rope: int = 0
    theta: float = 1.0
    yarn: Optional[Yarn] = None
    sections: Optional[Tuple[int, ...]] = None
    pad: int = 0


def _turning(how: Heads, s: int, hd: int, positions):
    """(``C``, ``S``) [S, hd] float32 ([B, S, hd] under ``positions``):
    :func:`rotary`'s cos on the
    columns that turn and 1 on the others; ``-sin`` on the first half of
    them, ``sin`` on the second and 0 on the others. ``y * C +
    partner(y) * S`` is the turn, element for element what :func:`rotary`
    computes."""
    cos, sin = rotary_tables(s, how.rope, how.theta, how.yarn, positions,
                             how.sections)
    lead = cos.shape[:-1]
    one = jnp.ones(lead + (how.lo,), jnp.float32)
    rest = jnp.ones(lead + (hd - how.lo - how.rope,), jnp.float32)
    c = jnp.concatenate([one, cos, cos, rest], -1)
    sg = jnp.concatenate([0 * one, -sin, sin, 0 * rest], -1)
    return c, sg


def _turn_of(how: Heads) -> head_turns.Turn:
    return head_turns.Turn(how.lo, how.rope, how.eps, how.dtype)


def _pass_scope(gain):
    """The pass's own scope in a reading by scope: a normed part's (its
    positions with it) files where the q/k norm alone did, a turned part's
    under ``mv.lm.attn.turn``; the kernels of either then stand apart from
    the flash kernels' row (``<scope>:kernel``)."""
    return jax.named_scope("mv.lm.attn.turn" if gain is None
                           else "mv.lm.attn.qknorm")


def _heads_fwd(x, w, gain, beside, positions, how: Heads):
    dt, f32 = how.dtype, jnp.float32
    s, hd = x.shape[1], w.shape[2]
    xc, wc = x.astype(dt), w.astype(dt)
    if how.pad:
        wc = jnp.pad(wc, ((0, 0), (0, 0), (0, how.pad)))
    # [B, S, K] x [K, H, hd] -> [B, H, S, hd]: heads before positions
    y = jax.lax.dot_general(xc, wc, (((2,), (0,)), ((), ())),
                            preferred_element_type=f32).transpose(0, 2, 1, 3)
    kept = None
    if gain is not None or how.rope:
        if how.pad:
            raise ValueError("a part with columns beside it takes no norm "
                             "and no positions of its own")
        tables = _turning(how, s, hd, positions) if how.rope else (None, None)
        kept = (y if gain is not None else None,) + tables
        with _pass_scope(gain):
            z = head_turns.forward(y, *tables, gain, _turn_of(how))
    else:
        if beside is not None:
            y = y + jnp.pad(beside.astype(f32),
                            ((0, 0), (0, 0), (hd, 0)))[:, None]
        z = y.astype(dt)
    return z, (xc, wc, kept, gain, positions, jnp.zeros((0,), x.dtype))


def _heads_bwd(how: Heads, res, g):
    xc, wc, kept, gain, positions, like = res
    f32 = jnp.float32
    hd = g.shape[3] - how.pad
    dgain = dbeside = None
    if how.pad:
        # the shared part's: a sum over the heads of its columns
        dbeside = jnp.sum(g[..., hd:].astype(f32), 1)
    if kept is not None:
        with _pass_scope(gain):
            g, dgain = head_turns.backward(g, *kept, gain, _turn_of(how))
    dx = jax.lax.dot_general(g, wc, (((1, 3), (1, 2)), ((), ())),
                             preferred_element_type=f32).astype(like.dtype)
    dw = jax.lax.dot_general(xc, g, (((0, 1), (0, 2)), ((), ())),
                             preferred_element_type=f32)
    return (dx, dw[..., :hd] if how.pad else dw, dgain, dbeside,
            None if positions is None else jnp.zeros_like(positions))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _heads(x, w, gain, beside, positions, how: Heads):
    return _heads_fwd(x, w, gain, beside, positions, how)[0]


_heads.defvjp(_heads_fwd, _heads_bwd)


def heads(x, w, how: Heads, gain=None, beside=None, positions=None):
    """One operand of the attention core from one projection: ``x`` [B, S,
    K] times ``w`` [K, H, hd] (float32, a view or a column block of a
    table) -> [B, H, S, hd + pad] in ``how.dtype``, written once, the head
    axis before the positions as the core reads it. Operands of the
    product in ``how.dtype``, its sum float32; on that sum, in float32 and
    in this order: an RMSNorm over a head under ``gain`` [hd] (where one
    is given), rotary positions on the columns ``how`` names (:class:`Heads`;
    ``positions`` [A, B, S] under ``how.sections``), ``beside`` [B, S, pad]
    added into the ``pad`` columns of every head (MLA's shared rotary key);
    then ONE rounding.

    One differentiation rule: the core's cotangent, [B, H, S, hd + pad] in
    ``how.dtype`` as the flash kernels write it, goes into the two
    products of the backward pass as it lies, through the turn's and the
    norm's transposes in float32 where the part has them and through
    nothing where it has none."""
    return _heads(x, w, gain, beside, positions, how)


def _out_fwd(o, x, wgate, wo, dtype):
    f32 = jnp.float32
    sig = xc = wgc = None
    wc = wo.astype(dtype)
    oc = o
    if wgate is not None:
        with jax.named_scope("mv.lm.attn.gate"):
            xc, wgc = x.astype(dtype), wgate.astype(dtype)
            sig = jax.nn.sigmoid(jax.lax.dot_general(
                xc, wgc, (((2,), (0,)), ((), ())),
                preferred_element_type=f32).transpose(0, 2, 1, 3))
            oc = (o * sig).astype(dtype)
    y = jax.lax.dot_general(oc, wc, (((1, 3), (0, 1)), ((), ())),
                            preferred_element_type=f32)
    return y, (o, oc, wc, sig, xc, wgc,
               None if x is None else jnp.zeros((0,), x.dtype))


def _out_bwd(dtype, res, g):
    o, oc, wc, sig, xc, wgc, like = res
    f32 = jnp.float32
    gc = g.astype(dtype)
    do = jax.lax.dot_general(gc, wc, (((2,), (2,)), ((), ())),
                             preferred_element_type=f32
                             ).transpose(0, 2, 1, 3)       # [B, H, S, dv]
    dwo = jax.lax.dot_general(oc, gc, (((0, 2), (0, 1)), ((), ())),
                              preferred_element_type=f32)
    if sig is None:
        return do.astype(o.dtype), None, None, dwo
    with jax.named_scope("mv.lm.attn.gate"):
        dt_ = (do * o * (sig * (1 - sig))).astype(dtype)
        dx = jax.lax.dot_general(dt_, wgc, (((1, 3), (1, 2)), ((), ())),
                                 preferred_element_type=f32
                                 ).astype(like.dtype)
        dwg = jax.lax.dot_general(xc, dt_, (((0, 1), (0, 2)), ((), ())),
                                  preferred_element_type=f32)
        return (do * sig).astype(o.dtype), dx, dwg, dwo


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _out(o, x, wgate, wo, dtype):
    return _out_fwd(o, x, wgate, wo, dtype)[0]


_out.defvjp(_out_fwd, _out_bwd)


def out_of_heads(o, wo, dtype, x=None, wgate=None):
    """The way out of the core, :func:`heads`'s mirror: ``o`` [B, H, S, dv]
    as the core writes it times ``wo`` [H, dv, D] (float32), contracted
    over heads and columns -> [B, S, D] float32, no transposed copy of
    ``o`` between; under a gate (``wgate`` [K, H, dv], ``x`` [B, S, K])
    ``o`` is first multiplied by ``sigmoid(x wgate)``, float32, and
    rounded once."""
    return _out(o, x, wgate, wo, dtype)


def mla_heads_of(u, p, cfg: MLAMoEConfig):
    """The core's operands of :func:`mla` from the normed input ``u`` [B,
    S, D]: q, k [B, H, S, nope + rope] and v [B, H, S, dv] in the compute
    dtype, each written once (:func:`heads`). q: ONE product, its rope
    columns turned on the way; k: the nope columns' product with the
    shared rotary key beside it in every head; v: its own product."""
    h, dt = cfg.n_heads, cfg.compute_dtype
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    mm = functools.partial(matmul, dtype=dt)
    c_q = rms_norm(mm(u, p["wdq"], False, out_dtype=jnp.float32),
                   p["q_norm"], cfg.eps)
    down = mm(u, p["wdkv"], True, out_dtype=jnp.float32)
    c_kv = rms_norm(down[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.eps)
    k_r = rotary(down[..., cfg.kv_lora_rank:], cfg.rope_theta, cfg.yarn)
    wukv = p["wukv"].reshape(-1, h, nope + dv)
    q = heads(c_q, p["wuq"].reshape(-1, h, nope + rope),
              Heads(dt, lo=nope, rope=rope, theta=cfg.rope_theta,
                    yarn=cfg.yarn))
    k = heads(c_kv, wukv[..., :nope], Heads(dt, pad=rope), beside=k_r)
    return q, k, heads(c_kv, wukv[..., nope:], Heads(dt))


def mla(u, p, cfg: MLAMoEConfig):
    """Latent attention on the normed input ``u`` [B, S, D] -> [B, S, D]
    float32. Queries and keys are heads of ``qk_nope_dim + qk_rope_dim``,
    values and the core's output heads of ``v_head_dim``, equal or not;
    the rotary frequencies are ``cfg.yarn``'s where it has one, and the
    scores are multiplied by ``cfg.softmax_scale`` where it has one (by
    ``1 / sqrt(nope + rope)`` where not)."""
    s, scale = u.shape[1], cfg.softmax_scale
    with jax.named_scope("mv.lm.attn"):
        q, k, v = mla_heads_of(u, p, cfg)
        if attn_core(cfg) == "flash":
            o = flash_attention(q, k, v, True, *attn_blocks(cfg, s),
                                scale=scale)
        else:
            o = _xla_attention(q, k, v, scale=scale)
        return out_of_heads(o, p["wo"].reshape(cfg.n_heads, cfg.v_head_dim,
                                               -1), cfg.compute_dtype)


def gated_mlp(u, wg, wu, wd, cfg):
    mm = functools.partial(matmul, dtype=cfg.compute_dtype)
    hidden = (jax.nn.silu(mm(u, wg, False, out_dtype=jnp.float32))
              * mm(u, wu, False, out_dtype=jnp.float32))
    return mm(hidden, wd, False, out_dtype=jnp.float32)


def dense_ffn(u, p, cfg):
    with jax.named_scope("mv.lm.dense"):
        return gated_mlp(u, p["wg"], p["wu"], p["wd"], cfg), None


def relu2_mlp(u, wu, wd, cfg):
    """``relu(u W_u)^2 W_d``: the two-matrix MLP."""
    mm = functools.partial(matmul, dtype=cfg.compute_dtype)
    hidden = jnp.square(jax.nn.relu(mm(u, wu, False, out_dtype=jnp.float32)))
    return mm(hidden, wd, False, out_dtype=jnp.float32)


def expert_ffn(u, p, bias, cfg, shared: bool = True):
    """The held experts' part under the configuration's route, beside
    ``Shared(u)`` where the layer has a shared expert and alone where it
    has none; experts and shared expert are of the configuration's
    ``expert_form`` (gated silu, three matrices; or ``relu2``, two), the
    shared one at its own width; aux = (counts [E], overflow_rows, the
    route's load-balance term)."""
    b, s, d = u.shape
    f = cfg.moe_ffn
    gated = cfg.expert_form == "gated_silu"
    if shared:
        with jax.named_scope("mv.lm.moe.shared"):
            beside = (gated_mlp(u, p["sg"], p["su"], p["sd"], cfg) if gated
                      else relu2_mlp(u, p["su"], p["sd"], cfg))
            if getattr(cfg, "shared_gate", False):
                beside = beside * jax.nn.sigmoid(jnp.sum(
                    u.astype(jnp.float32) * p["sgate"], -1, keepdims=True))
    stacked = (("w_gate", "eg", (d, f)), ("w_up", "eu", (d, f)),
               ("w_down", "ed", (f, d)))
    weights = dict(router=p["router"], **{
        role: p[name].reshape((cfg.experts_held,) + shape)
        for role, name, shape in stacked if gated or role != "w_gate"})
    routed, counts, overflow, balance = moe.held_expert_layer(
        u.reshape(b * s, d), weights, bias, held(cfg, b * s),
        cfg.expert_kernel)
    routed = routed.reshape(b, s, d)
    return (beside + routed if shared else routed), (counts, overflow,
                                                     balance)


def _stream_scores(x, phi, cfg):
    """``(r [T], phi vec(x) [n^2 + 2n, T], the two multiplied)``: the norm's
    factor a position, the projection of the streams as they are (the
    product at the highest precision), and the scores ``h`` of
    :func:`stream_maps`: the factor is a number a position, taken after the
    product, so no normed copy of the streams is made."""
    bsz, s, n, c = x.shape
    flat = x.reshape(bsz * s, n * c).astype(jnp.float32)
    with jax.named_scope("mv.lm.hc.norm"):
        r = jax.lax.rsqrt(jnp.mean(flat * flat, -1) + cfg.eps)      # [T]
    with jax.named_scope("mv.lm.hc.project"):
        raw = jax.lax.dot_general(
            phi, flat, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return r, raw, raw * r[None, :]


# what is traced once a process and bound again from its jaxpr: the small
# mathematics and the mixes of a hyper-connected sublayer, the same
# equations in every sublayer of a step, in each of its passes
_ONCE: Dict[tuple, Any] = {}


def _once(key, fn, *operands):
    """``fn(*operands)``, traced once a process for ``key`` (hashable: what
    ``fn`` is and its statics), the operands' structure and their types,
    and bound again from its jaxpr, as ``ops/index_kernels._bind`` binds a
    kernel: Python's part of a trace is most of a warm set-up, and ten
    sublayers in three passes run these lines thirty times. ``fn`` closes
    over no array."""
    leaves, tree = jax.tree.flatten(operands)
    key = (key, tree) + tuple(jax.typeof(v) for v in leaves)
    found = _ONCE.get(key)
    if found is None:
        if len(_ONCE) >= 64:
            _ONCE.clear()
        closed, shapes = jax.make_jaxpr(fn, return_shape=True)(*operands)
        found = _ONCE[key] = (closed, jax.tree.structure(shapes))
    closed, out = found
    return jax.tree.unflatten(out, jax.core.eval_jaxpr(
        closed.jaxpr, closed.consts, *leaves))


class _MapsOf(NamedTuple):
    """The statics of a configuration's small mathematics (hashable, so
    that configurations that agree on them share one trace)."""
    streams: int
    sinkhorn_iters: int
    hc_eps: float
    res_clamp: Tuple[float, float]

    @classmethod
    def of(cls, cfg) -> "_MapsOf":
        return cls(streams_of(cfg), int(cfg.sinkhorn_iters),
                   float(cfg.hc_eps), tuple(map(float, cfg.res_clamp)))

    def __call__(self, h, b, alpha):
        """The three maps from the scores ``h`` [n^2 + 2n, T]: the small
        mathematics of :func:`stream_maps`, a position a lane."""
        n = self.streams
        with jax.named_scope("mv.lm.hc.project"):
            h = jnp.repeat(alpha, np.array([n, n, n * n]),
                           total_repeat_length=n * n + 2 * n)[:, None] * h \
                + b[:, None]
            pre = jax.nn.sigmoid(h[:n])
            post = 2.0 * jax.nn.sigmoid(h[n:2 * n])
        with jax.named_scope("mv.lm.hc.sinkhorn"):
            m = jnp.exp(jnp.clip(h[2 * n:], *self.res_clamp)).reshape(
                n, n, -1)
            for _ in range(self.sinkhorn_iters):
                m = m / (jnp.sum(m, 0, keepdims=True) + self.hc_eps)  # columns
                m = m / (jnp.sum(m, 1, keepdims=True) + self.hc_eps)  # rows
        return pre, post, m


def stream_maps(x, phi, b, alpha, cfg):
    """The three maps of one sublayer's hyper-connection (manifold-
    constrained, arXiv:2512.24880 equations 7 and 8) from the streams ``x``
    [B, S, n, C]: ``(pre [n, T], post [n, T], res [n, n, T])``, ``T = B x
    S``, float32, a POSITION A LANE (an [T, n, n] array would lie in tiles
    of 8 x 128 for its 16 numbers, and Sinkhorn's 40 normalisations keep
    two such a step for the backward pass).

    ``h = (vec(x) / sqrt(mean(vec(x)^2) + eps)) phi^T`` (no gain; the
    product at the highest precision, as the router's: 24 numbers a
    position steer everything after them), split as pre, post, res; ``pre =
    sigmoid(alpha_0 h + b)``, ``post = 2 sigmoid(alpha_1 h + b)``, ``res``
    = ``exp(clip(alpha_2 h + b, cfg.res_clamp))`` [n, n] made doubly
    stochastic by ``cfg.sinkhorn_iters`` rounds of (every column over its
    sum + ``hc_eps``, then every row over its sum + ``hc_eps``)."""
    maps_of = _MapsOf.of(cfg)
    return _once(maps_of, maps_of, _stream_scores(x, phi, cfg)[2], b, alpha)


def res_error(res):
    """The largest ``abs(row or column sum - 1)`` of ``res`` [n, n, T]."""
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, 0) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(res, 1) - 1.0)))


def block(x, p, attn, ffn, cfg):
    """The one block: ``attn`` and ``ffn`` take the normed input and the
    block's parameters; with ``cfg.post_norms`` each branch's output is
    normed as well before it joins the stream (four norms a block). A
    block of one mixer has ``None`` for the other branch, and one norm.
    Every layer of every kind calls it. Returns (y, ffn's aux). A mixer
    may hand back ``(out, term)``: a term of its own for the loss (a
    learned selection's, ``models/keye_moe.py``), which then rides behind
    the expert layer's aux as its fourth part.

    The residual path is the configuration's too. With one stream (every
    configuration but ``models/xing4.py``'s) a block is handed ``x`` [B,
    S, C] and a branch's result is added to it, times
    ``cfg.residual_scale`` where the configuration has such a multiplier
    (``models/granite_h.py``: ``x + 0.22 F(norm(x))``, both branches; at 1
    or without the field the product is not in the program). With
    ``cfg.streams`` = n of them it is handed, and hands back, ``x`` [B, S,
    n, C], four times
    a position what one stream weighs, and a sublayer ``F`` is, under its
    own :func:`stream_maps`: ``u = pre . x`` [B, S, C] (the branch reads a
    mix of the streams), ``y = F(norm(u))``, ``x' = res @ x + outer(post,
    y)`` (the streams mixed among themselves, the result written to all
    of them); the block then returns a third thing, the largest
    :func:`res_error` of its sublayers."""
    def out(branch, name, q):
        if not cfg.post_norms:
            return branch
        with jax.named_scope("mv.lm.norm.post"):
            return rms_norm(branch, q[name], cfg.eps)

    def normed(stream, name, q):
        with jax.named_scope("mv.lm.norm.pre"):
            return rms_norm(stream, q[name], cfg.eps)

    def sublayer(stream, name, branch, q):
        """``stream`` after the sublayer ``name`` whose ``branch(u, q)``
        gives (its result as it joins the streams, what else it hands
        back): (the streams, that, the hyper-connection's error or none)."""
        if streams_of(cfg) == 1:
            y, extra = branch(stream, q)
            scale = residual_scale(cfg)
            return stream + (y if scale == 1.0 else scale * y), extra, []
        hc = tuple(q[f"{name}.hc_{k}"] for k in ("phi", "b", "alpha"))
        h, extra, error = _hyper(branch, cfg, stream, hc, q)
        return h, extra, [error]

    def attention(u, q):
        mixed = attn(normed(u, "attn_norm", q), q)
        mixed, term = mixed if isinstance(mixed, tuple) else (mixed, None)
        return out(mixed, "attn_post_norm", q), term

    def feed_forward(u, q):
        f, aux = ffn(normed(u, "ffn_norm", q), q)
        return out(f, "ffn_post_norm", q), aux

    h, aux, term, errors = x, None, None, []
    if attn is not None:
        h, term, errors = sublayer(h, "attn", attention, p)
    if ffn is not None:
        h, aux, error = sublayer(h, "ffn", feed_forward, p)
        errors = errors + error
    if term is not None:
        if aux is None:
            raise ValueError("a mixer's term rides an expert layer's aux")
        aux = aux + (term,)
    if errors:
        return h, aux, functools.reduce(jnp.maximum, errors)
    return h, aux


def _pre_mix(x, pre):
    """``u = pre . x`` [B, S, C] of a hyper-connected sublayer from the
    streams ``x`` [B, S, n, C]: what the branch reads. The mixes are sums
    of n products a number, written out: float32 on the vector unit, where
    an einsum over 4 would go through the matrix unit at its default
    precision."""
    n, lead = x.shape[2], x.shape[:2]
    with jax.named_scope("mv.lm.hc.pre"):
        return sum(pre[i].reshape(*lead, 1) * x[:, :, i] for i in range(n))


def _post_mix(x, y, post, res):
    """``x' = res @ x + outer(post, y)``: the streams mixed among
    themselves, the branch's result written to all of them."""
    n, lead = x.shape[2], x.shape[:2]
    at = lambda m: m.reshape(*lead, 1)      # a map's [T] at [B, S, 1]
    with jax.named_scope("mv.lm.hc.post"):
        each = [x[:, :, i] for i in range(n)]
        return jnp.stack(
            [sum(at(res[i, j]) * each[j] for j in range(n))
             + at(post[i]) * y for i in range(n)], 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _hyper(branch, cfg, x, hc, q):
    """One hyper-connected sublayer: the streams ``x`` [B, S, n, C] under
    the maps of ``hc`` = (phi, b, alpha) round ``branch(u, q)`` -> (y [B, S,
    C], what else it hands back): ``(x', that, the maps' res_error)``.

    This is the forward pass as XLA makes it (and makes it again under
    ``jax.checkpoint``). ONE differentiation rule stands over the whole
    sublayer (:func:`_hyper_fwd`, :func:`_hyper_bwd`): plain autodiff of
    these lines reads the streams once for the norm, once for the
    projection and once for each mix, and writes the streams' gradient in
    parts that it then adds (13.2 ms a sublayer where the least is 2.4:
    PERF.md section 6, PR 60)."""
    pre, post, res = stream_maps(x, *hc, cfg)
    y, extra = branch(_once("pre", _pre_mix, x, pre), q)
    return _once("post", _post_mix, x, y, post, res), extra, res_error(res)


def _hyper_fwd(branch, cfg, x, hc, q):
    """:func:`_hyper` beside what its backward pass reads: the streams, the
    branch's result, the maps and what made them, and the maps' and the
    branch's own ``jax.vjp``."""
    phi, b, alpha = hc
    r, raw, h = _stream_scores(x, phi, cfg)
    maps_of = _MapsOf.of(cfg)
    maps, maps_back = _once((maps_of, "vjp"),
                            lambda *of: jax.vjp(maps_of, *of), h, b, alpha)
    pre, post, res = maps
    (y, extra), branch_back = jax.vjp(
        branch, _once("pre", _pre_mix, x, pre), q)
    out = (_once("post", _post_mix, x, y, post, res), extra, res_error(res))
    return out, (x, y, phi, r, raw, maps, maps_back, branch_back)


def _hyper_bwd(branch, cfg, kept, cts):
    """The streams' gradient ``g`` to every gradient of the sublayer, by
    the walks of ``ops/stream_walks.py`` (its docstring has the
    equations): :func:`stream_walks.gather` before the branch's own
    backward pass, :func:`stream_walks.dots` and the small mathematics on
    [n^2 + 2n, T] arrays after it (the maps' own ``jax.vjp``: Sinkhorn's
    rounds are 0.45% of a step and XLA's), :func:`stream_walks.spread`
    last, which writes the streams' gradient once."""
    x, y, phi, r, raw, (pre, post, res), maps_back, branch_back = kept
    g, dextra, _ = cts
    n, c = x.shape[2:]
    flat = lambda v: v.reshape((-1,) + v.shape[2:])
    g, xs = flat(g.astype(jnp.float32)), flat(x)
    with jax.named_scope("mv.lm.hc.bwd"):
        dy, dpost, dres = stream_walks.gather(g, xs, flat(y), post)
    du, dq = branch_back((dy.reshape(y.shape).astype(y.dtype), dextra))
    du = flat(du)
    with jax.named_scope("mv.lm.hc.bwd"):
        dpre = stream_walks.dots(du, xs)
    dh, db, dalpha = _once("pull", lambda back, cts: back(cts), maps_back,
                           (dpre, dpost, dres))
    with jax.named_scope("mv.lm.hc.bwd"):
        # h = r (phi x): a = r dh goes back through the product, and the
        # norm's factor r = (mean(x^2) + eps)^-1/2 takes <dh, phi x>
        a = r[None, :] * dh
        norm = jnp.sum(dh * raw, 0) * r * r * r / (n * c)
        dx = stream_walks.spread(g, xs, du, phi, pre, res, a, norm)
        dphi = jax.lax.dot_general(
            a, xs.reshape(-1, n * c), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return dx.reshape(x.shape).astype(x.dtype), (dphi, db, dalpha), dq


_hyper.defvjp(_hyper_fwd, _hyper_bwd)


def _sub(params: Dict[str, Any], prefix: str) -> Dict[str, Any]:
    return {k[len(prefix) + 1:]: v for k, v in params.items()
            if k.startswith(prefix + ".")}


def kept_names(cfg) -> Tuple[str, ...]:
    """What a rematerialised block of ``cfg`` keeps for its backward
    pass: ``moe.KEPT_NAMES``, all of them or none. None where the
    configuration says ``keeps_products = False`` (``NemotronHConfig``
    alone: a kept result is reserved with the step's program, and
    ``nemotron3n-train-16k`` has no room). And what the configuration's
    own mixer names beside them (``cfg.kept_names``): a value whose
    backward pass needs nothing of how it was made. ``KeyeMoEConfig``'s
    gradients of its indexer's term and its selection (no gradient), and
    ``Qwen3NextConfig``'s result of the delta rule (its groups are
    rematerialised by themselves from their inputs)."""
    return (moe.KEPT_NAMES if getattr(cfg, "keeps_products", True)
            else ()) + tuple(getattr(cfg, "kept_names", ()))


def _run_block(x, p, layer: Layer, bias, cfg, remat: bool = True):
    """A block of ``layer``'s kinds, rematerialised unless told not to:
    the backward pass makes the block again but for the results that
    :func:`kept_names` names (an expert layer's grouped products into the
    experts' width, its sort and its route's choice, and what the
    configuration's mixer names; a block that meets no such name is made
    again whole). ``bias`` is its router's (a dense layer has none).
    Under several streams what a block is handed, and so what the policy
    keeps of it beside the names, is the streams: :func:`block`."""
    attn = (None if layer.attn is None
            else lambda u, q: cfg.attend(u, q, layer.attn))
    if layer.ffn is None:
        ffn = None
    elif layer.ffn == "dense":
        ffn = lambda u, q: dense_ffn(u, q, cfg)
    else:
        ffn = lambda u, q: expert_ffn(u, q, bias, cfg,
                                      layer.ffn == "shared+experts")
    run = lambda x, p: block(x, p, attn, ffn, cfg)
    keep = jax.checkpoint_policies.save_only_these_names(*kept_names(cfg))
    return (jax.checkpoint(run, policy=keep) if remat else run)(x, p)


def _ce_chunks(h, head, targets, weights, cfg, grads: bool,
               each_too: bool = False):
    """The scan under :func:`_chunked_ce`: ``loss_chunk`` positions at a
    time, one logits product a chunk (operands in ``cfg.compute_dtype``,
    float32 sums, float32 log-sum-exp). Returns the loss and, with
    ``grads``, the three things it makes from the same logits while they
    exist (``None`` without): ``weights * (softmax - onehot)``, cast to
    the compute dtype, times the head (the gradient to ``h``, a chunk of
    the stacked result) and times the chunk's ``h`` (the gradient to
    ``head``, summed in a float32 carry), and each position's unweighted
    loss (the gradient to ``weights``); without ``grads`` and with
    ``each_too``, each position's unweighted loss alone. Where the
    configuration multiplies its logits (``cfg.logit_scale``,
    :func:`logit_scale`: ``models/granite_h.py``'s ``1 / 8``) the chunk's
    logits and the ``dl`` made from them take the multiplier; at 1 neither
    product is in the program."""
    n, d = h.shape
    scale = logit_scale(cfg)
    chunk = min(cfg.loss_chunk, n)
    if n % chunk:
        raise ValueError(f"{n} positions do not divide into chunks of {chunk}")
    dt = cfg.compute_dtype

    def body(carry, xs):
        total, dw = carry
        hc, tc, wc = xs
        hc, w = hc.astype(dt), head.astype(dt)
        logits = _dot(hc, w, ((1,), (1,)), jnp.float32)
        if scale != 1.0:
            logits = logits * scale
        lse = jax.nn.logsumexp(logits, -1)
        each = lse - jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
        total = total + jnp.sum(wc * each)
        if not grads:
            return (total, dw), (each if each_too else None)
        hit = jnp.arange(logits.shape[1])[None, :] == tc[:, None]
        dl = wc[:, None] * (jnp.exp(logits - lse[:, None]) - hit)
        dl = (dl if scale == 1.0 else dl * scale).astype(dt)
        dw = dw + _dot(dl, hc, ((0,), (0,)), jnp.float32)
        return (total, dw), (_dot(dl, w, ((1,), (0,)), h.dtype), each)

    xs = (h.reshape(n // chunk, chunk, d), targets.reshape(-1, chunk),
          weights.reshape(-1, chunk))
    dw = jnp.zeros(head.shape, jnp.float32) if grads else None
    (total, dw), out = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), dw), xs)
    if not grads:
        return total, (out.reshape(n) if each_too else None)
    return total, (out[0].reshape(n, d), dw, out[1].reshape(n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_ce(h, head, targets, weights, cfg):
    """Sum over positions of ``weights * CE(h @ head.T, targets)``, float32,
    ``loss_chunk`` positions at a time (the whole logits would be
    positions x vocabulary floats). Under a gradient a chunk's logits are
    made ONCE: the forward pass makes the chunk's gradients beside its
    loss and the backward pass scales them by the loss's cotangent, so a
    loss is three products of positions x vocabulary, nothing is kept but
    the two gradients and nothing is made again. A caller that folds its
    normaliser into ``weights`` hands back a cotangent of 1."""
    return _ce_chunks(h, head, targets, weights, cfg, grads=False)[0]


def _chunked_ce_fwd(h, head, targets, weights, cfg):
    return _ce_chunks(h, head, targets, weights, cfg, grads=True)


def _chunked_ce_bwd(cfg, grads, g):
    dh, dw, each = grads
    return g * dh, g * dw, None, g * each


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_ce_each(h, head, targets, weights, cfg):
    """:func:`_chunked_ce` beside each position's UNWEIGHTED loss [n],
    which the scan has anyway (it is the gradient to ``weights``): what a
    loss whose weights are trained reports of its parts (:func:`_exit_loss`:
    each pass's mean loss). No gradient flows through the second result."""
    return _ce_chunks(h, head, targets, weights, cfg, grads=False,
                      each_too=True)


def _chunked_ce_each_fwd(h, head, targets, weights, cfg):
    total, grads = _ce_chunks(h, head, targets, weights, cfg, grads=True)
    return (total, grads[2]), grads


_chunked_ce_each.defvjp(
    _chunked_ce_each_fwd,
    lambda cfg, grads, g: _chunked_ce_bwd(cfg, grads, g[0]))


def loss_grid(cfg, tokens: int) -> Dict[str, Any]:
    """What the chunked losses of a training step over ``tokens`` positions
    do, as ``lm.step`` spans carry it: the products of positions x
    vocabulary (three a loss: the logits, and the gradients to the hidden
    state and to the head) and the chunks a loss walks. A stack run
    several times has a loss an exit (:func:`passes_of`), walked as one:
    its products and its chunks count ``passes`` times."""
    passes = passes_of(cfg)
    losses = (1 + any(layer.name == "mtp" for layer in cfg.layers())) * passes
    walked = passes * tokens            # the positions of one walk
    return {"head_products": 3 * losses,
            "loss_chunks": walked // min(cfg.loss_chunk, walked)}


def kept_grid(cfg, batch: int, positions: int) -> Dict[str, int]:
    """What the rematerialised blocks of a training step over ``batch``
    sequences of ``positions`` keep by name, as ``lm.step`` spans carry
    it: ``kept_names``, how many names the blocks' policy holds
    (:func:`kept_names`); ``expert_products_kept``, the grouped products'
    results (those into the experts' width: two an expert layer, one of
    the ``relu2`` form); and ``kept_bytes``, from the shapes: theirs
    ([rows, ffn] each), the sorted buffers' row orders' (int32 [rows]),
    the routes' choices' (int32 [tokens, top_k]) and what the
    configuration's own names keep (``cfg.kept_bytes``). A block's INPUT is
    kept beside them whatever the names, and under several residual
    streams it is what weighs: :func:`stream_grid` says those bytes."""
    out = {"kept_names": len(kept_names(cfg)), "expert_products_kept": 0,
           "kept_bytes": 0}
    if getattr(cfg, "keeps_products", True) and expert_layers(cfg):
        tokens = batch * positions
        here = held(cfg, tokens)
        rows, layers = moe.buffer_length(here, tokens), len(expert_layers(cfg))
        products = 1 + (cfg.expert_form == "gated_silu")
        item = jnp.dtype(here.dtype).itemsize
        out.update(
            expert_products_kept=layers * products,
            kept_bytes=layers * (rows * (products * cfg.moe_ffn * item + 4)
                                 + 4 * tokens * cfg.top_k))
    if getattr(cfg, "kept_names", ()):
        out["kept_bytes"] += cfg.kept_bytes(batch, positions)
    return out


def stream_grid(cfg, batch: int, positions: int) -> Dict[str, int]:
    """What several residual streams add to the ``lm.step`` span (nothing
    under one): ``streams``, ``sinkhorn_iters``, ``hc_sublayers`` (the
    sublayers that have a hyper-connection of their own) and
    ``hc_stream_bytes``, a block's input [batch, positions, streams, dim]
    float32 times the blocks whose input the rematerialised step keeps
    (every one), beside ``kept_bytes``; and of the sublayers' backward pass
    (:func:`_hyper_bwd`) ``hc_kernel_sublayers``, those whose walks over the
    streams are ``ops/stream_walks.py``'s kernels on this device (all or
    none), and ``hc_bwd_stream_bytes``, what the walks read and write a
    step (``stream_walks.step_counts``)."""
    n = streams_of(cfg)
    if n == 1:
        return {}
    layers = cfg.layers()
    sublayers = sum(bool(layer.attn) + bool(layer.ffn) for layer in layers)
    return {"streams": n, "sinkhorn_iters": cfg.sinkhorn_iters,
            "hc_sublayers": sublayers,
            "hc_stream_bytes": 4 * batch * positions * n * cfg.dim
            * len(layers),
            **stream_walks.step_counts(sublayers, batch * positions, n,
                                       cfg.dim)}


def loop_grid(cfg) -> Dict[str, int]:
    """What a stack run several times adds to the ``lm.step`` span (nothing
    where it runs once): ``loop_passes``, ``loop_layers`` (the blocks the
    program holds) and ``loop_block_runs`` (those a step runs, each of
    whose inputs the rematerialised step keeps)."""
    passes, layers = passes_of(cfg), len(cfg.layers())
    if passes == 1:
        return {}
    return {"loop_passes": passes, "loop_layers": layers,
            "loop_block_runs": passes * layers}


def _embed(params, tokens, cfg):
    x = jnp.take(params["embed"], tokens, axis=0)
    return x if cfg.embed_scale == 1.0 else x * cfg.embed_scale


def _expand(x, cfg):
    """One vector a position [B, S, C] as the blocks take it: itself, or
    copied to each of ``cfg.streams`` [B, S, n, C]."""
    n = streams_of(cfg)
    if n == 1:
        return x
    with jax.named_scope("mv.lm.hc.expand"):
        return jnp.broadcast_to(x[:, :, None, :],
                                x.shape[:2] + (n,) + x.shape[2:])


def _reduce(x, cfg):
    """The blocks' result as one vector a position: itself, or the sum of
    the streams."""
    if streams_of(cfg) == 1:
        return x
    with jax.named_scope("mv.lm.hc.reduce"):
        return jnp.sum(x, 2)


def _passes(x, params, cfg):
    """The stack run ``cfg.passes`` times on the embedding ``x`` [B, S, D]
    with the SAME tables, the stream normed by ``final_norm`` after every
    pass: ``exits`` [passes, B, S, D], each pass's normed stream, which is
    that pass's exit state and the next pass's input. ONE loop in the
    program (a ``lax.scan`` over the passes whose body holds the blocks,
    each under its own ``jax.checkpoint``: the program text holds
    ``layers`` blocks, not ``passes x layers``, and its lowering costs what
    one pass's does), so a layer's table takes one gradient a step, the
    sum over its uses, which the scan's transpose adds up. What the
    backward pass keeps is every block's input of every pass."""
    layers = cfg.layers()
    if (expert_layers(cfg) or streams_of(cfg) > 1
            or not all(layer.attn and layer.ffn for layer in layers)):
        raise ValueError("a stack run several times holds two-branch dense "
                         "blocks on one stream and no prediction module")

    def one_pass(z, _):
        for layer in layers:
            z, _ = _run_block(z, _sub(params, layer.name), layer, None, cfg)
        with jax.named_scope("mv.lm.norm.final"):
            z = rms_norm(z, params["final_norm"], cfg.eps)
        return z, z

    with jax.named_scope("mv.lm.loop"):
        return jax.lax.scan(one_pass, x, None, length=passes_of(cfg))[1]


def exit_distribution(exits, w, b):
    """The exit gate on every pass's state ``exits`` [T, ..., D] but the
    last: ``lambda_t = sigmoid(x_t . w + b)``; ``p_1 = lambda_1``, ``p_t =
    lambda_t prod_{j<t} (1 - lambda_j)`` and the last pass takes what is
    left, ``p_T = prod_{j<T} (1 - lambda_j)``. Returns (p [T, ...], its
    entropy ``-sum_t p_t ln p_t`` [...]), float32, made from the logs of
    ``lambda`` and ``1 - lambda`` so that no factor rounds to 0."""
    z = jnp.sum(exits[:-1].astype(jnp.float32) * w, -1) + b
    go, stay = jax.nn.log_sigmoid(z), jax.nn.log_sigmoid(-z)
    stayed = jnp.cumsum(stay, 0)            # ln prod_{j<=t} (1 - lambda_j)
    log_p = jnp.concatenate([go + stayed - stay, stayed[-1:]], 0)
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, 0)


def _exit_loss(params, exits, tokens, cfg):
    """The loss of a stack run several times: (loss, what the step hands
    back of its exits). ``loss = (1/n) sum_i [sum_t p_t(i) l_t(i) -
    exit_coef H(p(i))]`` over the ``n`` positions that have a target:
    ``l_t`` the cross-entropy of pass ``t``'s state through the shared
    head, ``p`` the exit distribution (:func:`exit_distribution`), which is
    TRAINED through the loss: the chunked loss hands ``l_t(i)`` back as the
    gradient to its weights ``p_t(i) / n``, and the gate and the streams
    take it from there. The ``T`` exits go through the chunked loss as one
    walk of ``T x B x S`` positions (one float32 sum for the head's
    gradient, not one an exit). Handed back: ``loss`` [T], each pass's mean
    ``l_t``; ``p_mean`` [T]; ``entropy``, the mean ``H(p)``; ``p`` [T, B, S]."""
    t, b, s, d = exits.shape
    has_target = (jnp.arange(s) < s - 1).astype(jnp.float32) / (b * (s - 1))
    with jax.named_scope("mv.lm.loop.exit"):
        p, entropy = exit_distribution(exits, params["exit.w"],
                                       params["exit.b"])
    head = params["embed" if tied_head(cfg) else "head"]
    with jax.named_scope("mv.lm.head"):
        total, each = _chunked_ce_each(
            exits.reshape(t * b * s, d), head,
            jnp.tile(jnp.roll(tokens, -1, axis=1).reshape(-1), t),
            (p * has_target).reshape(-1), cfg)
    with jax.named_scope("mv.lm.loop.exit"):
        mean = lambda v: jnp.sum(v * has_target, (-2, -1))
        back = {"loss": mean(each.reshape(t, b, s)), "p_mean": mean(p),
                "entropy": mean(entropy), "p": p}
        return total - cfg.exit_coef * back["entropy"], back


def _trunk(params, bias, tokens, cfg, still: bool = False):
    """Embedding and every layer but the prediction module: (x, [each
    expert layer's aux]) and, under several streams, a third thing: the
    largest of the blocks' stream-mix errors. ``still``: each block's input
    is held fixed (no gradient flows from a layer into the one before it,
    so nothing is rematerialised either). Under several streams the
    embedding is copied to each before the first block and the last
    block's are summed. A stack run several times (:func:`passes_of`)
    hands back every pass's normed stream in ``x``'s place (:func:`_passes`:
    [passes, B, S, D])."""
    with jax.named_scope("mv.lm.embed"):
        x = _embed(params, tokens, cfg)
    if passes_of(cfg) > 1:
        if still:
            raise ValueError("a stack run several times has no router to "
                             "balance")
        return _passes(x, params, cfg), []
    x = _expand(x, cfg)
    rows = {name: row for row, name in enumerate(expert_layers(cfg))}
    aux, errors = [], []
    for layer in cfg.layers():
        if layer.name == "mtp":
            continue
        if still:
            x = jax.lax.stop_gradient(x)
        x, a, *error = _run_block(
            x, _sub(params, layer.name), layer,
            bias[rows[layer.name]] if layer.name in rows else None, cfg,
            remat=not still)
        errors += error
        if a is not None:
            aux.append(a)
    return (_reduce(x, cfg), aux) + (
        (functools.reduce(jnp.maximum, errors),) if errors else ())


def _no_experts():
    """(counts, overflow, balance) of a step without an expert layer: no
    rows."""
    return (jnp.zeros((0, 0), jnp.int32), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,), jnp.float32))


def loss_fn(params: Dict[str, jax.Array], bias: jax.Array,
            tokens: jax.Array, cfg):
    """tokens [B, S] -> (loss, (counts [layers, E], overflow [layers],
    balance [layers])), and where the mixers hand terms to the loss a
    fourth part, those terms [layers], of which the loss gains
    ``cfg.index_coef`` times the sum; under several residual streams a
    last part, the largest :func:`res_error` of the step's sublayers (the
    prediction module then takes the trunk's summed streams, copies
    ``eh_proj``'s result to each stream, runs its block under its own
    hyper-connections and sums again before ``out_norm``).

    ``CE(main, t_{i+1}) + mtp_weight * CE(module, t_{i+2})``, each a mean
    over the positions that have a target (S-1 and S-2 a sequence), plus
    ``balance_coef`` times the sum of the layers' load-balance terms where
    the configuration has such a coefficient. The module runs on all S
    positions, so that its attention has the main model's shape; the
    last, which has no next token, takes the sequence's first in its
    place and has no target.

    A stack run several times (:func:`passes_of`) has :func:`_exit_loss`'s
    loss and hands its exits back as the last part; a configuration
    without an expert layer hands back counts of no rows."""
    b, s = tokens.shape
    x, aux, *errors = _trunk(params, bias, tokens, cfg)
    if passes_of(cfg) > 1:
        loss, exits = _exit_loss(params, x, tokens, cfg)
        return loss, (*_no_experts(), exits)
    position = jnp.arange(s)[None, :]
    nxt = jnp.roll(tokens, -1, axis=1)
    # a loss's normaliser lies in its weights: the chunked loss's cotangent
    # is 1, and its gradients are cast where they were before
    weights = lambda has_target, scale: jnp.broadcast_to(
        jnp.where(has_target, jnp.float32(scale), 0.0), (b, s)).reshape(-1)
    # a tied head is the embedding's table: autodiff adds the lookup's
    # gradient to the chunked loss's
    head = params["embed" if tied_head(cfg) else "head"]
    with jax.named_scope("mv.lm.head"):
        main = _chunked_ce(
            rms_norm(x, params["final_norm"], cfg.eps).reshape(b * s, -1),
            head, nxt.reshape(-1),
            weights(position < s - 1, 1.0 / (b * (s - 1))), cfg)
    loss = main
    mtp = [layer for layer in cfg.layers() if layer.name == "mtp"]
    if mtp:
        with jax.named_scope("mv.lm.mtp"):
            p = _sub(params, "mtp")
            joined = jnp.concatenate(
                [rms_norm(_embed(params, nxt, cfg), p["enorm"], cfg.eps),
                 rms_norm(x, p["hnorm"], cfg.eps)], -1)
            y = matmul(joined, p["eh_proj"], False, cfg.compute_dtype,
                       jnp.float32)
            y, a, *error = _run_block(_expand(y, cfg), p, mtp[0],
                                      bias[len(aux)], cfg)
            aux.append(a)
            errors += error
            module = _chunked_ce(
                rms_norm(_reduce(y, cfg), p["out_norm"],
                         cfg.eps).reshape(b * s, -1),
                head, jnp.roll(tokens, -2, axis=1).reshape(-1),
                weights(position < s - 2, cfg.mtp_weight / (b * (s - 2))),
                cfg)
        loss = main + module
    counts, overflow, balance, *terms = (
        (jnp.stack(a) for a in zip(*aux)) if aux else _no_experts())
    if cfg.balance_coef:
        loss = loss + cfg.balance_coef * jnp.sum(balance)
    if terms:
        loss = loss + cfg.index_coef * jnp.sum(terms[0])
    if errors:
        terms = (*terms, functools.reduce(jnp.maximum, errors))
    return loss, (counts, overflow, balance, *terms)


# ---------------------------------------------------------------------- #
# the step through the tables
# ---------------------------------------------------------------------- #
def _params_of(states, shapes):
    out = {}
    for name, shape in shapes.items():
        rows = table_shape(shape)[0]
        out[name] = states[name]["data"][:rows]
    return out


def _with_overflow(counts, overflow):
    return jnp.concatenate([counts, overflow[:, None]], axis=1)


def make_train_step(cfg, tables: Dict[str, Any],
                    opt: Optional[AddOption] = None):
    """``step(states, bias, tokens) -> (states, bias, loss, counts,
    balance)``.

    ``states`` maps each table's name to its ``program_state()``; jit with
    ``donate_argnums=(0, 1)``. Reads ``state["data"]``, computes loss and
    float32 gradients (each block rematerialised, the two losses in
    chunks of positions), hands each gradient to its table's updater
    through ``functional_add`` (the learning rate is ``opt``'s), applies
    the selection biases' rule where a layer routes under one, and
    returns the states to be adopted, the new biases, the loss, one int32
    array [layers, E + 1] (the tokens that chose each expert, and in the
    last column the rows that overflowed the held experts' buffer) and
    the load-balance term as it stands in the loss (0 without one); where
    the mixers hand terms to the loss, their part of it as well
    (``index_loss``), a sixth result; under several residual streams the
    step's largest :func:`res_error` (``hc_res_error``) after those; and of
    a stack run several times what :func:`_exit_loss` hands back of its
    exits, the last. A configuration without an expert layer has counts of
    no rows, no bias and no rule for one."""
    shapes = param_shapes(cfg)
    opt = opt or AddOption(learning_rate=1e-4)
    # the route that selects under a bias
    biased = bool(expert_layers(cfg)) and cfg.route == "sigmoid"

    def step(states, bias, tokens):
        # mv.lm.params / mv.lm.update: the names the tables' side of a
        # step carries in the program's map (metadata only)
        with jax.named_scope("mv.lm.params"):
            params = _params_of(states, shapes)
        (loss, (counts, overflow, balance, *terms)), grads = (
            jax.value_and_grad(loss_fn, has_aux=True)(
                params, bias, tokens, cfg))
        exits = [terms.pop()] if passes_of(cfg) > 1 else []
        errors = [terms.pop()] if streams_of(cfg) > 1 else []
        new = {}
        with jax.named_scope("mv.lm.update"):
            for name, table in tables.items():
                delta = table.pad_delta(grads[name].reshape(
                    table_shape(shapes[name])))
                new[name] = table.functional_add(states[name], delta, opt)
        if biased:
            bias = moe.bias_update(bias, counts, cfg.bias_speed)
        return (new, bias, loss, _with_overflow(counts, overflow),
                cfg.balance_coef * jnp.sum(balance),
                *(cfg.index_coef * jnp.sum(t) for t in terms), *errors,
                *exits)

    return step


def make_forward(cfg):
    """``forward(states, bias, tokens) -> (loss, counts [layers, E + 1])``
    on the tables' states, nothing written: what a calibration of the
    selection biases runs."""
    shapes = param_shapes(cfg)

    def forward(states, bias, tokens):
        loss, (counts, overflow, *_) = loss_fn(
            _params_of(states, shapes), bias, tokens, cfg)
        return loss, _with_overflow(counts, overflow)

    return forward


def make_balance_step(cfg, tables: Dict[str, Any]):
    """``step(routers, others, bias, tokens, rate) -> (routers, counts
    [layers, E + 1], balance [layers])``: the load-balance terms ALONE
    move the routers' tables ALONE, through ``functional_add`` at the
    learning rate ``rate`` (a traced number: one program for a whole
    schedule). ``routers`` are the states of the ``<layer>.router``
    tables (jit with ``donate_argnums=(0,)``), ``others`` every other
    table's. Each layer's term is differentiated with the layer's input
    held fixed: the path through the router's own probabilities, which
    is what the term is for; what a router's choice does to the layers
    after it is left out. The prediction module is no part of it."""
    shapes = param_shapes(cfg)
    names = [name + ".router" for name in expert_layers(cfg)
             if name != "mtp"]

    def step(routers, others, bias, tokens, rate):
        params = _params_of({**others, **routers}, shapes)

        def terms(moved):
            aux = _trunk({**params, **moved}, bias, tokens, cfg,
                         still=True)[1]
            counts, overflow, balance = (jnp.stack(a)
                                         for a in tuple(zip(*aux))[:3])
            return jnp.sum(balance), (_with_overflow(counts, overflow),
                                      balance)

        (_, (counts, balance)), grads = jax.value_and_grad(
            terms, has_aux=True)({n: params[n] for n in names})
        opt = AddOption(learning_rate=rate)
        new = {n: tables[n].functional_add(
            routers[n], tables[n].pad_delta(grads[n]), opt) for n in names}
        return new, counts, balance

    return step


def routing_counts(counts: np.ndarray, cfg) -> Dict[str, Any]:
    """What a step's [layers, E + 1] array says: ``routed_rows`` (token x
    expert assignments over all layers), ``held_rows`` (those to experts
    held here), ``overflow_rows``, ``load_max_over_mean`` (the busiest of
    all E experts over the mean, worst layer), ``expert_rows`` (the
    array's [layers][E] part as lists, so that a reader can add steps up
    before it asks which expert was busiest) and, of the held experts'
    grouped products, ``product_tiles_visited`` (the row tiles ONE forward
    product visits, summed over the layers: ``moe.product_tiles``) beside
    ``product_tiles_buffer`` (the row tiles the layers' buffers hold) at
    ``product_tile`` (the tile that runs, rows x over ``dim`` x over
    ``ffn``: which of ``moe.product_tile``'s rules engaged),
    and of the passes round them ``buffer_rows_walked`` (the rows ONE
    gather or scatter over the sorted buffers walks, a layer's even load
    and whole chunks past it, summed over the layers:
    ``moe.rows_walked``) beside ``buffer_rows`` (the layers' buffers
    whole): 1.0 of it would say that the passes never stop short. Nothing
    where the step has no expert layer (counts of no rows)."""
    counts = np.asarray(counts)
    if not len(counts):
        return {}
    c = counts[:, :cfg.n_experts]
    lo = cfg.expert_offset
    mine = c[:, lo:lo + cfg.experts_held]
    # every token chooses top_k experts: a layer's counts say its tokens
    tokens = int(c[0].sum()) // cfg.top_k
    here = held(cfg, tokens)
    rows, tm = moe.buffer_length(here, tokens), here.tile[0]
    return {"routed_rows": int(c.sum()),
            "held_rows": int(mine.sum()),
            "overflow_rows": int(counts[:, cfg.n_experts].sum()),
            "load_max_over_mean": float(np.max(c.max(1) / c.mean(1))),
            "expert_rows": c.tolist(),
            "product_tiles_visited": moe.product_tiles(mine, rows, tm),
            "product_tiles_buffer": len(c) * rows // tm,
            "product_tile": "x".join(map(str, here.tile)),
            "buffer_rows_walked": moe.rows_walked(
                mine, rows, moe.even_rows(here, tokens)),
            "buffer_rows": len(c) * rows}


def exit_facts(exits: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """What a step's exits say on its ``lm.step`` span: ``exit_loss`` (each
    pass's mean loss), ``exit_p`` (the mean exit distribution),
    ``exit_entropy`` (the mean ``H(p)``: ``ln passes`` where every pass is
    as likely, 0 where the gate has collapsed onto one) and
    ``exit_expected_pass`` (``sum_t t p_t``, passes counted from 1)."""
    p = np.asarray(exits["p_mean"], np.float64)
    return {"exit_loss": [float(x) for x in exits["loss"]],
            "exit_p": [float(x) for x in p],
            "exit_entropy": float(exits["entropy"]),
            "exit_expected_pass": float(np.sum(p * (1 + np.arange(len(p)))))}


class Trainer:
    """The host's side of training through the tables: holds the states
    between steps (one donated program a step, no table copied) and
    records each step as an ``lm.step`` span and, while a capture or
    ``trace_ids`` can read it, the step's program as ``lm.step.device``
    (from its dispatch until the device is done with it). :meth:`adopt`
    hands the states back to the tables at the end."""

    def __init__(self, cfg, tables: Dict[str, Any],
                 opt: Optional[AddOption] = None,
                 bias: Optional[jax.Array] = None):
        self.cfg, self.tables = cfg, tables
        self.bias = init_bias(cfg) if bias is None else bias
        self._step = jax.jit(make_train_step(cfg, tables, opt),
                             donate_argnums=(0, 1))
        self.states = {n: t.program_state() for n, t in tables.items()}
        self.steps = 0
        # (loss, counts, balance[, index_loss][, hc_res_error][, exits]) of
        # a step not read back yet
        self._ahead = None
        # attn_grid and mixer_grid of the first step
        self._attn: Dict[str, Any] = {}
        # the largest stream-mix error of the steps read back so far
        self.hc_res_error = 0.0
        # of a stack run several times: what the last step read back
        # handed back of its exits (:func:`_exit_loss`)
        self.exits: Optional[Dict[str, np.ndarray]] = None
        # closes a step's ``lm.step.device`` span when the device is done
        # with it; idle unless a capture or ``trace_ids`` can read it
        self._watcher = _trace.DeviceWatcher()

    def _turn(self, tokens, ahead: bool):
        """Queue a step on ``tokens`` (where given), then read back the
        step that is due: this one, or with ``ahead`` the one before it."""
        self.steps += tokens is not None
        with _trace.span("lm.step", request=self.steps) as sp:
            due = self._ahead
            if tokens is not None:
                count = int(np.prod(tokens.shape))
                if self.steps == 1:     # one program, one shape
                    positions = int(tokens.shape[1])
                    self._attn = dict(
                        attn_grid(self.cfg, positions,
                                  int(tokens.shape[0])),
                        **mixer_grid(self.cfg, positions),
                        **multiplier_grid(self.cfg),
                        **loss_grid(self.cfg, count),
                        **kept_grid(self.cfg, *tokens.shape),
                        **stream_grid(self.cfg, *tokens.shape),
                        **loop_grid(self.cfg))
                sp.set(tokens=count)
                t0_ns = time.time_ns()
                self.states, self.bias, *back = self._step(
                    self.states, self.bias, tokens)
                self._watcher.watch("lm.step.device", back[0], t0_ns,
                                    request=self.steps, cause=sp.id)
                if self.steps == 1:
                    # the program's map, once, from JAX's caches, while
                    # the device runs the first step (xla.program)
                    _devstats.describe_program(
                        "lm.step", self._step, self.states, self.bias,
                        tokens)
                due, self._ahead = ((due, back) if ahead else (back, None))
            else:
                self._ahead = None
            sp.set(**self._attn)
            if due is None:
                return None
            with _trace.span("lm.step.wait"):
                # one read-back a step: it waits for the whole program
                loss, counts, balance, *terms = jax.device_get(due)
            if passes_of(self.cfg) > 1:
                self.exits = terms.pop()
                sp.set(**exit_facts(self.exits))
            if streams_of(self.cfg) > 1:
                error = float(terms.pop())
                self.hc_res_error = max(self.hc_res_error, error)
                sp.set(hc_res_error=error)
            sp.set(**routing_counts(counts, self.cfg))
            if self.cfg.balance_coef:
                sp.set(aux_loss=float(balance))
            if terms:
                sp.set(index_loss=float(terms[0]))
        return float(loss), counts

    def step(self, tokens) -> Tuple[float, np.ndarray]:
        """One step on ``tokens`` [B, S]; returns its (loss, counts)."""
        if self._ahead is not None:
            raise RuntimeError("a step is still ahead: drain() first")
        return self._turn(tokens, ahead=False)

    def step_ahead(self, tokens) -> Optional[Tuple[float, np.ndarray]]:
        """Queue a step on ``tokens`` and read back the step BEFORE it
        (``None`` the first time): the device has the next program while
        the host reads the last one's loss, so a stall of the host costs
        the device nothing. :meth:`drain` reads the last step back."""
        return self._turn(tokens, ahead=True)

    def drain(self) -> Optional[Tuple[float, np.ndarray]]:
        return self._turn(None, ahead=True)

    def adopt(self) -> None:
        """Hand the states back to their tables (end of training)."""
        self.drain()
        self._watcher.close()
        for name, table in self.tables.items():
            table.adopt(self.states[name])
