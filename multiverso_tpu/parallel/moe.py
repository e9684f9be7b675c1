"""Expert parallelism: a mixture-of-experts layer over a mesh axis.

Rounds out the modern-strategy surface (SURVEY §2.10: the 2015 reference has
DP + parameter-storage sharding only; SP/CP live in parallel/ring.py, EP
here). Experts are sharded over the ``ep`` mesh axis — each device owns
``num_experts / ep`` expert MLPs — and tokens travel to their experts and
back via ``all_to_all`` over ICI, the TPU-native equivalent of the
dispatch/combine messaging a parameter server would do per-row.

Design choices, TPU-first:

* **Static capacity**: each device sends exactly ``capacity`` tokens to each
  expert shard (truncate-and-pad, like every production TPU MoE) so all
  shapes are static for XLA; dropped tokens fall back to the residual path.
* **Top-1 (switch) or top-k (GShard) routing** with a jittable router —
  ``top_k=1`` gates by the raw expert probability, ``top_k>1`` by the
  renormalized top-k probabilities — plus the standard auxiliary
  load-balance loss returned to the caller.
* One ``all_to_all`` out, one back; expert compute is a single batched
  einsum over the local experts — MXU-shaped, no scalar loops.

The second layer here, :func:`held_expert_layer`, is one chip's share of
a layer whose experts are divided over several chips: it is told which
experts it holds (``experts_held``, ``expert_offset``), routes over all
of them (sigmoid scores under a selection bias that takes no gradient,
or a softmax with the load-balance term its family trains by), and
computes its own experts' part of the result without dropping a token.
The rows routed here lie sorted by expert in one buffer, and the three
products run over that buffer as grouped matrix products (Pallas
``megablox``) whose groups end where the routed rows end, so their cost
follows the rows routed here: not the busiest expert, and not the
buffer's padding. The passes round them do as the products do: the
sort's gather into the buffer and the scatter-add back to the tokens, and
the transpose of each, walk the rows an even load fills at once and the
rows past them ``CHUNK`` at a time for as many chunks as hold routed rows
(:func:`_walk`). On one chip it runs without its exchange.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _tgmm
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.zoo import Zoo


class MoEConfig(NamedTuple):
    num_experts: int
    dim: int
    hidden: int
    capacity_factor: float = 1.25
    axis: str = "ep"
    top_k: int = 1


def init_experts(cfg: MoEConfig, seed: int = 0, dtype=jnp.float32) -> Dict:
    """[E, ...]-stacked expert MLP params + router; shard E over the ep axis
    with :func:`shard_experts`."""
    rng = np.random.default_rng(seed)
    e, d, h = cfg.num_experts, cfg.dim, cfg.hidden
    mk = lambda *s, scale: jnp.asarray(rng.normal(0, scale, s), dtype)
    return {
        "w1": mk(e, d, h, scale=1 / np.sqrt(d)),
        "w2": mk(e, h, d, scale=1 / np.sqrt(h)),
        "router": mk(d, e, scale=1 / np.sqrt(d)),
    }


def shard_experts(params: Dict, cfg: MoEConfig,
                  mesh: Optional[Mesh] = None) -> Dict:
    """Place expert weights expert-sharded (router replicated)."""
    mesh = mesh or Zoo.get().mesh()
    shard = NamedSharding(mesh, P(cfg.axis))
    repl = NamedSharding(mesh, P())
    return {
        "w1": jax.device_put(params["w1"], shard),
        "w2": jax.device_put(params["w2"], shard),
        "router": jax.device_put(params["router"], repl),
    }


def top_k_gates(probs, kk: int):
    """Top-k expert selection with the gating convention shared by training
    (:func:`_route`) and decode (models/transformer.generate): raw top
    probability for k=1 (switch), renormalized top-k for k>1 (GShard).
    Returns (gates [T, K], topi [T, K])."""
    topv, topi = jax.lax.top_k(probs, kk)
    gates = topv if kk == 1 else topv / topv.sum(-1, keepdims=True)
    return gates, topi


def _route(probs, kk: int, capacity: int):
    """Priority routing over the [T, E] expert probabilities: assignments
    are flattened **k-major** ([all 1st choices, then all 2nd choices, ...])
    so every token's 1st choice wins the capacity race against any token's
    2nd choice — the GShard/Switch fill order. Returns (expert, gate, pos,
    keep, onehot), each over the K*T assignments; gates are the raw top
    probability for k=1 (switch) and renormalized for k>1 (GShard)."""
    t, e = probs.shape
    gates, topi = top_k_gates(probs, kk)                   # [T, K]
    expert = topi.T.reshape(-1)                            # [K*T]
    gate = gates.T.reshape(-1)
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.int32)    # [K*T, E]
    pos = (jnp.cumsum(onehot, 0) * onehot).sum(-1) - 1     # per-expert slot
    keep = pos < capacity
    return expert, gate, pos, keep, onehot


def _local_moe(x, w1, w2, router, cfg: MoEConfig, capacity: int,
               batch_axis: Optional[str] = None):
    """Per-shard body. x: [T_local, D]; w1/w2: local experts [E_local, ...]."""
    ax = cfg.axis
    n = jax.lax.axis_size(ax)
    e = cfg.num_experts
    e_local = e // n
    t = x.shape[0]

    kk = cfg.top_k
    logits = x @ router                                    # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    expert, gate, pos, keep, onehot = _route(probs, kk, capacity)

    # dispatch buffer: [E, capacity, D] (one slice per destination expert)
    x_rep = jnp.tile(x, (kk, 1))                           # [K*T, D] k-major
    slot = jnp.where(keep, pos, capacity)                  # overflow -> pad row
    dispatch = jnp.zeros((e, capacity + 1, x.shape[1]), x.dtype)
    dispatch = dispatch.at[expert, slot].add(x_rep)
    dispatch = dispatch[:, :capacity]                      # [E, C, D]

    # all_to_all: [E, C, D] -> group by shard -> each device ends up with
    # its local experts' tokens from every peer: [n, E_local, C, D]
    dispatch = dispatch.reshape(n, e_local, capacity, -1)
    recv = jax.lax.all_to_all(dispatch, ax, split_axis=0, concat_axis=0,
                              tiled=False)                 # [n, E_local, C, D]

    # expert compute, batched over local experts: [E_local, n*C, D]
    xin = recv.transpose(1, 0, 2, 3).reshape(e_local, n * capacity, -1)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xin, w1))
    out = jnp.einsum("ech,ehd->ecd", h, w2)                # [E_local, n*C, D]

    # route back: inverse all_to_all
    back = out.reshape(e_local, n, capacity, -1).transpose(1, 0, 2, 3)
    combined = jax.lax.all_to_all(back, ax, split_axis=0, concat_axis=0,
                                  tiled=False)             # [n, E_local, C, D]
    combined = combined.reshape(e, capacity, -1)           # [E, C, D]

    # gather each surviving assignment's expert output (dropped -> 0) and
    # sum a token's k contributions (k-major flatten)
    y = combined[expert, jnp.minimum(pos, capacity - 1)]   # [K*T, D]
    y = jnp.where(keep[:, None], y, 0.0) * gate[:, None].astype(x.dtype)
    y = y.reshape(kk, t, -1).sum(0)                        # [T, D]

    # load-balance aux loss (switch for k=1, GShard-normalized for k>1)
    me = probs.mean(0)                                     # [E]
    ce = onehot.astype(jnp.float32).reshape(kk, t, e).sum(0).mean(0) / kk
    aux = e * jnp.sum(me * ce)
    # reduce over every axis the tokens are sharded on, so the returned
    # scalars really are replicated (out_specs=P() asserts it)
    reduce_axes = (ax,) if batch_axis is None else (ax, batch_axis)
    aux = jax.lax.pmean(aux, reduce_axes)
    # dropped = tokens whose EVERY assignment overflowed (full residual
    # fallback), matching the "dropped tokens fall back" contract
    token_dropped = 1.0 - keep.reshape(kk, t).any(axis=0)
    frac_dropped = jax.lax.pmean(token_dropped.mean(), reduce_axes)
    return y, aux, frac_dropped


def moe_layer(x: jax.Array, params: Dict, cfg: MoEConfig,
              mesh: Optional[Mesh] = None,
              batch_axis: Optional[str] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the expert-parallel MoE to tokens [B, T, D] sharded over
    ``cfg.axis`` on T (and optionally ``batch_axis`` on B). Returns
    (output [B, T, D], aux_loss scalar, dropped_fraction scalar —
    the fraction of tokens whose every routed choice overflowed capacity
    and that therefore fell back to the residual path with zero output)."""
    mesh = mesh or Zoo.get().mesh()
    n = mesh.shape[cfg.axis]
    if cfg.num_experts % n:
        raise ValueError(
            f"{cfg.num_experts} experts not divisible by {n} shards")
    b, t, d = x.shape
    if t % n:
        raise ValueError(f"token dim {t} not divisible by {n} {cfg.axis!r} "
                         "shards")
    if batch_axis and b % mesh.shape[batch_axis]:
        raise ValueError(f"batch dim {b} not divisible by "
                         f"{mesh.shape[batch_axis]} {batch_axis!r} shards")
    if not 1 <= cfg.top_k <= cfg.num_experts:
        raise ValueError(f"top_k={cfg.top_k} out of range for "
                         f"{cfg.num_experts} experts")
    local_tokens = b * t // n // (mesh.shape[batch_axis] if batch_axis else 1)
    capacity = max(1, int(cfg.capacity_factor * local_tokens * cfg.top_k
                          / cfg.num_experts))

    xspec = P(batch_axis, cfg.axis, None)
    espec = P(cfg.axis)

    def body(x, w1, w2, router):
        xb = x.reshape(-1, d)
        y, aux, dropped = _local_moe(xb, w1, w2, router, cfg, capacity,
                                     batch_axis)
        return y.reshape(x.shape), aux, dropped

    y, aux, dropped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, espec, espec, P()),
        out_specs=(xspec, P(), P()), check_vma=False)(
            x, params["w1"], params["w2"], params["router"])
    return y, aux, dropped


# ---------------------------------------------------------------------- #
# one chip's share of an expert layer: no capacity, no dropped token
# ---------------------------------------------------------------------- #
class HeldExperts(NamedTuple):
    """Which part of an expert layer lies here. ``buffer_rows`` is the
    static length of the sorted buffer the held experts compute over;
    ``None`` sizes it for the most the routing can send (every token to
    ``min(top_k, experts_held)`` held experts), which cannot overflow."""
    num_experts: int             # the router's outputs: every expert
    experts_held: int
    expert_offset: int = 0
    top_k: int = 1
    routed_scale: float = 1.0
    buffer_rows: Optional[int] = None
    # the grouped products' tile (rows, over dim, over ffn):
    # ``product_tile`` chooses it from the widths
    tile: Tuple[int, int, int] = (128, 128, 128)
    dtype: Any = jnp.bfloat16    # the products' operands (float32 sums)
    route: str = "sigmoid"       # or "softmax"
    # an expert's MLP: "gated_silu" ((silu(x W_gate) * (x W_up)) W_down,
    # three matrices) or "relu2" (relu(x W_up)^2 W_down, two)
    form: str = "gated_silu"


def sigmoid_route(u: jax.Array, router: jax.Array, bias: jax.Array,
                  cfg: HeldExperts):
    """Scores ``sigmoid(u W_r)`` in float32 over every expert (``router``
    is [E, D]: a row an expert); the ``top_k`` largest of ``score + bias``
    are chosen, gates are the chosen scores over their sum, times
    ``routed_scale``. ``bias`` only selects: it is in no gate and takes no
    gradient. Returns (chosen [T, K], gates [T, K] float32, counts [E]
    int32: the tokens that chose each expert). The choice is named for a
    checkpoint's policy (``KEPT_NAMES``)."""
    with jax.named_scope("mv.lm.moe.route"):
        logits = jax.lax.dot_general(
            u.astype(jnp.float32), router.astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias)[None, :], cfg.top_k)
        chosen = checkpoint_name(chosen, KEEP_CHOSEN)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = cfg.routed_scale * picked / picked.sum(-1, keepdims=True)
        counts = (chosen[..., None] == jnp.arange(cfg.num_experts)).sum(
            (0, 1), dtype=jnp.int32)
    return chosen, gates, counts


def softmax_route(u: jax.Array, router: jax.Array, cfg: HeldExperts):
    """Probabilities ``softmax(u W_r)`` in float32 over every expert
    (``router`` is [E, D]); the ``top_k`` largest are chosen, gates are
    the chosen probabilities over their sum: no bias, no scale. Returns
    (chosen [T, K], gates [T, K] float32, counts [E] int32, balance): the
    last is the load-balance term ``E * sum_e f_e P_e``, ``f_e`` the share
    of the T x K assignments that chose ``e`` (no gradient) and ``P_e``
    the mean of ``p_e`` over the tokens: 1 where the load is even. The
    choice is named for a checkpoint's policy (``KEPT_NAMES``)."""
    with jax.named_scope("mv.lm.moe.route"):
        logits = jax.lax.dot_general(
            u.astype(jnp.float32), router.astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, -1)
        _, chosen = jax.lax.top_k(probs, cfg.top_k)
        chosen = checkpoint_name(chosen, KEEP_CHOSEN)
        # the gates are read AT the kept choice, so a backward pass that
        # keeps it runs no ``top_k`` and sends each gate's gradient to the
        # probability the forward pass chose; by a select over the experts
        # and not ``take_along_axis``: that is a gather of tokens x top_k
        # and its scatter, 10.7 ms a step of ``mellum2-train-8k``
        at = chosen[..., None] == jnp.arange(cfg.num_experts)
        picked = jnp.where(at, probs[:, None, :], 0.0).sum(-1)
        gates = picked / picked.sum(-1, keepdims=True)
        counts = at.sum((0, 1), dtype=jnp.int32)
        share = counts.astype(jnp.float32) / (u.shape[0] * cfg.top_k)
        balance = cfg.num_experts * jnp.sum(share * probs.mean(0))
    return chosen, gates, counts, balance


def bias_update(bias: jax.Array, counts: jax.Array, speed) -> jax.Array:
    """The selection bias's rule: ``b_e += speed * sign(mean(c) - c_e)``
    (an expert under the mean load is made likelier, one over it less)."""
    c = counts.astype(jnp.float32)
    return bias + speed * jnp.sign(c.mean(-1, keepdims=True) - c)


def _fewest_tiles(width: int) -> int:
    """The multiple of 128 up to 1,024 that covers ``width`` in the
    fewest tiles, and of those the smallest, whose last tile is the
    fullest (1,856: 1,024, two tiles 91% full). ``megablox`` takes a last
    tile that hangs over the width: masked where the width is contracted,
    its columns past the width dropped where it is not."""
    return min(range(128, 1025, 128), key=lambda t: (-(-width // t), t))


# the widest width a tile takes whole: a group's weight block of it by a
# tile of 1,024 is 4 MiB of bfloat16, held twice in 16 MiB of scoped VMEM
WHOLE_WIDTH = 2048


def product_tile(dim: int, ffn: int) -> Tuple[int, int, int]:
    """The grouped products' tile (over rows, over ``dim``, over ``ffn``)
    from the widths. A width that divides by 512 takes 512; another takes
    the largest multiple of 128 up to 1,024 that divides it (2,304 = 18 x
    128 takes 768, 896 = 7 x 128 takes itself: at 128 cubed a 65,536-row
    buffer is 64,512 grid steps a product). A width that is no multiple
    of 128 (1,856 = 14.5 x 128) is ONE tile, the whole width: a block may
    be as wide as its array whatever the lanes, so no tile hangs over and
    a group's weight block moves once; past ``WHOLE_WIDTH`` it takes
    :func:`_fewest_tiles`', as under 128 (the kernel's own 128). Rows go by
    512 where both widths' tiles reach it and by 128 where one does not;
    beside a whole width by 256: a step of 256 rows by 1,856 columns
    already multiplies 225 FLOP a byte it fetches, and a longer row tile
    only adds padding at each group's seam (``chip_smoke.product_kernels``
    read 128, 256, 384 and 512 on the chip: PERF.md section 6, PR 67)."""
    def of(width: int) -> int:
        if width % 512 == 0:
            return 512
        fits = [t for t in range(128, 1025, 128) if width % t == 0]
        if fits:
            return fits[-1]
        return width if 128 < width <= WHOLE_WIDTH else _fewest_tiles(width)

    over_dim, over_ffn = of(dim), of(ffn)
    whole = any(width % 128 and tile == width
                for tile, width in ((over_dim, dim), (over_ffn, ffn)))
    rows = 256 if whole else 512 if min(over_dim, over_ffn) >= 512 else 128
    return rows, over_dim, over_ffn


# the float32 elements of ``tgmm``'s result block (a matrix's gradient
# leaves the kernel in float32, k tile x n tile, held twice beside an
# accumulator as large: 12 bytes an element of 16 MiB of scoped VMEM)
WEIGHTS_BLOCK = 1 << 20


def weights_tile(tile: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """A product's tile (m, k, n) as ``tgmm`` takes it for the matrix's
    gradient: the same, unless k x n float32 is over ``WEIGHTS_BLOCK``;
    then the wider of the two is cut to :func:`_fewest_tiles`' (896 x
    1,856 becomes 896 x 1,024; every tile of widths that multiples of 128
    divide is under it and stays)."""
    m, k, n = tile
    while k * n > WEIGHTS_BLOCK and max(k, n) > 1024:
        k, n = ((_fewest_tiles(k), n) if k >= n else (k, _fewest_tiles(n)))
    return m, k, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def grouped_matmul(lhs, rhs, group_sizes, tile, interpret: bool,
                   dtype=jnp.bfloat16):
    """``lhs[rows of group g] @ rhs[g]`` for consecutive row groups of
    ``group_sizes`` as one Pallas kernel (``megablox``): operands and
    result in ``dtype`` (``lhs`` comes in it), float32 accumulation.
    ``rhs`` is float32 [G, K, N] (a table's data) and takes a float32
    gradient. ``tile``: (m, k, n) of THIS product.

    The groups may sum to fewer rows than ``lhs`` has: the rows past the
    last group belong to no group, the kernel's grid is the row tiles
    that hold a group's rows (:func:`product_tiles`), and the rows past
    the last group are UNWRITTEN memory in the result and in ``lhs``'s
    gradient (whatever the buffer held, a NaN perhaps), so select them
    away before anything sums over them. What ``lhs`` and the cotangent
    hold there reaches nothing: a row of the result reads its own row of
    ``lhs`` alone, and ``rhs``'s gradient selects each group's rows."""
    return _gmm_fwd(lhs, rhs, group_sizes, tile, interpret, dtype)[0]


def _gmm_fwd(lhs, rhs, group_sizes, tile, interpret, dtype):
    rhs = rhs.astype(dtype)
    out = _gmm(lhs, rhs, group_sizes, dtype, tile, interpret=interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(tile, interpret, dtype, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(dtype)
    m, k, n = tile
    d_lhs = _gmm(g, rhs, group_sizes, dtype, (m, n, k), transpose_rhs=True,
                 interpret=interpret)
    d_rhs = _tgmm(lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
                  weights_tile(tile), num_actual_groups=rhs.shape[0],
                  interpret=interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped_matmul_xla(lhs, rhs, group_sizes, dtype):
    """The same product off the chip, where the kernel's interpreter
    takes seconds a call: XLA's own ``ragged_dot``, which answers zero
    rows past the last group."""
    return jax.lax.ragged_dot(lhs, rhs.astype(dtype), group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(dtype)


def buffer_length(cfg: HeldExperts, tokens: int) -> int:
    """The rows of the sorted buffer for a layer's ``tokens``:
    ``cfg.buffer_rows`` (or the most the routing can send) in whole row
    tiles."""
    rows = cfg.buffer_rows or tokens * min(cfg.top_k, cfg.experts_held)
    return -(-rows // cfg.tile[0]) * cfg.tile[0]


def product_tiles(sizes, rows: int, tm: int) -> int:
    """The row tiles ONE forward grouped product visits (``megablox``'s
    ``make_group_metadata``: its ``num_tiles`` where empty groups are not
    visited) for held experts' loads ``sizes`` [..., H] cut at a buffer of
    ``rows`` rows, summed over the leading axes, on the host: a group that
    is not empty takes ``ceil(end / tm) - floor(start / tm)`` tiles, so a
    tile that two groups share is visited twice and the tiles past the
    last group not at all."""
    ends = np.minimum(np.cumsum(np.asarray(sizes, np.int64), -1), rows)
    starts = np.concatenate([np.zeros_like(ends[..., :1]), ends[..., :-1]],
                            -1)
    return int(np.where(ends > starts, -(-ends // tm) - starts // tm, 0)
               .sum())


# What a rematerialised block keeps (``models/mla_moe._run_block``'s
# policy, by ``checkpoint_name``; ``mla_moe.kept_names``): the result of
# each grouped product INTO the experts' width as it leaves the kernel, in ``HeldExperts.dtype`` ([rows, ffn] of
# ``w_gate`` and ``w_up``: neither is a residual of its own product, both
# are needed downstream, so without them the backward pass runs their
# forward kernels again), the sorted buffer's row order (int32 [rows]: the
# stable sort over tokens x top_k keys), and the route's CHOICE (int32
# [tokens, top_k]). The choice MUST be kept with the others: a backward
# pass that made the route again may choose otherwise at a near-tie (XLA
# fuses the two passes apart and a score differs in its last place), and
# the kept results' rows would then sit in another expert's group: a
# step's gradients are wrong and the loss is not a number some steps on.
# Everything else of a block is made again, ``w_down``'s forward product
# among it: its result is [rows, dim], the widest of the three, what is
# kept is reserved with the step's program, and a float kept costs a
# ``reduce_precision`` pass of its own over it, which at
# ``mellum2-train-8k``'s shapes is what that kernel costs (0.90 against
# 0.92 ms) and at ``lfm2-train-8k``'s half.
KEPT_NAMES = KEEP_GATE, KEEP_UP, KEEP_TAKE, KEEP_CHOSEN = (
    "mv.moe.gate", "mv.moe.up", "mv.moe.take", "mv.moe.chosen")


def expert_products(x: jax.Array, params: Dict, groups: jax.Array,
                    cfg: HeldExperts, kernel: str) -> jax.Array:
    """The held experts' MLPs over the sorted buffer ``x`` [rows, D] in
    ``cfg.dtype``, ``groups`` [H] rows an expert: two or three grouped
    products (``cfg.form``), those into the experts' width with their
    results named as they leave the kernel for a checkpoint's policy
    (``KEPT_NAMES``; an identity anywhere else). The rows past the groups
    are UNWRITTEN in the result, as they are in every intermediate (each
    product's result and the hidden rows between them): see
    :func:`grouped_matmul`."""
    tile = cfg.tile             # over rows, over dim and over ffn
    if kernel == "xla":
        up = down = functools.partial(
            _grouped_matmul_xla, group_sizes=groups, dtype=cfg.dtype)
    else:
        mm = functools.partial(grouped_matmul, group_sizes=groups,
                               dtype=cfg.dtype,
                               interpret=kernel == "interpret")
        up = functools.partial(mm, tile=tile)       # dim -> ffn
        down = functools.partial(mm, tile=(tile[0], tile[2], tile[1]))

    def kept(name: str, role: str) -> jax.Array:
        return checkpoint_name(up(x, params[role]),
                               name).astype(jnp.float32)

    if cfg.form == "gated_silu":
        h = jax.nn.silu(kept(KEEP_GATE, "w_gate")) * kept(KEEP_UP, "w_up")
    elif cfg.form == "relu2":
        h = jnp.square(jax.nn.relu(kept(KEEP_UP, "w_up")))
    else:
        raise ValueError(f"no expert form named {cfg.form!r}")
    return down(h.astype(cfg.dtype), params["w_down"])


# The rows of the sorted buffer that one trip of the dispatch's and the
# combine's loops walks (:func:`_walk`), past the head that an even load
# fills. A trip costs its control and a last chunk is walked whole, and
# XLA's scatter of a chunk inside a loop costs four times a slot what its
# scatter of a whole buffer does (it sorts a large scatter's slots and not
# a small one's), so the chunks are for the few rows past the head:
# ``chip_smoke.py``'s ``_expert_block`` at the five cells' shapes chose it
# (PERF.md section 6, PR 53).
CHUNK = 1024


def even_rows(cfg: HeldExperts, tokens: int) -> int:
    """The rows an even router sends the held experts for ``tokens``."""
    return tokens * cfg.top_k * cfg.experts_held // cfg.num_experts


def rows_walked(sizes, rows: int, head: int) -> int:
    """The rows of a buffer of ``rows`` that ONE pass of :func:`_walk`
    walks for held experts' loads ``sizes`` [..., H], summed over the
    leading axes, on the host: the ``head``, and whole chunks from there
    to the last routed row (the load cut at the buffer, as the layer
    cuts its groups)."""
    head = min(head, rows)
    chunk = min(CHUNK, max(rows - head, 1))
    past = np.maximum(
        np.minimum(np.asarray(sizes, np.int64).sum(-1), rows) - head, 0)
    return int((head + -(-past // chunk) * chunk).sum())


def _walk(take, held_rows, head: int, carry, step):
    """``step`` over the sorted buffer as far as routed rows lie: over
    its first ``head`` rows at once, routed or not (the even load, which
    a balanced router fills: XLA's own gather and scatter at the size
    they do best), then over what lies past them ``CHUNK`` rows at a time for
    ``ceil((held_rows - head) / chunk)`` trips of a ``lax.fori_loop``,
    counted on the device: none under an even load, and never a chunk
    past the last routed row. ``step(carry, start, idx, live, fresh)``
    gives the next ``carry`` from the first row of a stretch, its part
    ``idx`` of ``take`` and ``live``, which marks its rows under
    ``held_rows``. Where the rows past the head are no multiple of the
    chunk the last chunk starts early (``dynamic_slice`` would clamp it
    there anyway) and its first rows are the chunk before's: ``fresh``
    marks the live rows that no earlier stretch has walked, for a step
    that ADDS."""
    rows = take.shape[0]
    head = min(head, rows)
    if head:
        live = jnp.arange(head) < held_rows
        carry = step(carry, 0, take[:head], live, live)
    if head == rows:
        return carry
    chunk = min(CHUNK, rows - head)

    def trip(i, carry):
        first = head + i * chunk
        start = jnp.minimum(first, rows - chunk)
        at = start + jnp.arange(chunk)
        live = at < held_rows
        return step(carry, start,
                    jax.lax.dynamic_slice_in_dim(take, start, chunk),
                    live, live & (at >= first))

    return jax.lax.fori_loop(
        0, (jnp.maximum(held_rows - head, 0) + chunk - 1) // chunk, trip,
        carry)


_cut = jax.lax.dynamic_slice_in_dim               # (array, start, rows)
_put = functools.partial(jax.lax.dynamic_update_slice_in_dim, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(src, gates, take, held_rows, grid: Tuple[int, int, int]):
    """The sort's gather into the buffer: ``(src[take // top_k],
    gates[take])`` for the first ``held_rows`` of ``take`` [rows], zeros
    past them. ``grid`` is (tokens, top_k, the walk's head), ``src``
    [tokens, D], ``gates`` the tokens x top_k assignments' FLAT, ``take``
    indices into them. Both ways walk the routed rows alone
    (:func:`_walk`): the transpose adds the rows' cotangents to their
    tokens, in ``src``'s type, and the gates' to their assignments, and
    reads no row past the last chunk (what a product's gradient left
    there is UNWRITTEN)."""
    return _dispatch_fwd(src, gates, take, held_rows, grid)[0]


# The four rules are ``jax.jit`` functions so that a step's expert layers
# share one trace of each: traced a layer at a time they added 3.8 s of
# Python to a cell's set-up (``xla.lower_s.setup``; XLA inlines the calls).
@functools.partial(jax.jit, static_argnums=(4,))
def _dispatch_fwd(src, gates, take, held_rows, grid):
    _, top_k, head = grid

    def step(carry, start, idx, live, _):
        x, gate = carry
        return (_put(x, jnp.where(live[:, None], src[idx // top_k], 0),
                     start),
                _put(gate, jnp.where(live, gates[idx], 0.0), start))

    rows = take.shape[0]
    buffers = (jnp.zeros((rows, src.shape[1]), src.dtype),
               jnp.zeros((rows,), gates.dtype))
    return _walk(take, held_rows, head, buffers, step), (take, held_rows)


@functools.partial(jax.jit, static_argnums=(0,))
def _dispatch_bwd(grid, res, cts):
    (tokens, top_k, head), (dx, dgate) = grid, cts

    def step(carry, start, idx, _, fresh):
        d_src, d_gates = carry
        n = idx.shape[0]
        return (d_src.at[idx // top_k].add(
                    jnp.where(fresh[:, None], _cut(dx, start, n), 0)),
                d_gates.at[idx].add(
                    jnp.where(fresh, _cut(dgate, start, n), 0.0)))

    sums = (jnp.zeros((tokens, dx.shape[1]), dx.dtype),
            jnp.zeros((tokens * top_k,), dgate.dtype))
    return _walk(*res, head, sums, step) + (None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(y, gate, take, held_rows, grid: Tuple[int, int, int]):
    """The scatter-add back to the tokens: float32 [tokens, D] sums of
    ``gate[r] * y[r]`` at token ``take[r] // top_k`` over the first
    ``held_rows`` rows ``r`` of the buffer (``grid`` as
    :func:`_dispatch`'s); ``y`` past them is UNWRITTEN and reaches
    nothing. Both ways walk the routed rows alone (:func:`_walk`): the
    transpose gathers the sums' cotangent at their tokens and leaves
    ``y``'s and ``gate``'s zero past the last chunk."""
    return _combine_fwd(y, gate, take, held_rows, grid)[0]


@functools.partial(jax.jit, static_argnums=(4,))
def _combine_fwd(y, gate, take, held_rows, grid):
    tokens, top_k, head = grid

    def step(out, start, idx, _, fresh):
        n = idx.shape[0]
        return out.at[idx // top_k].add(
            jnp.where(fresh[:, None],
                      _cut(y, start, n).astype(jnp.float32), 0.0)
            * _cut(gate, start, n)[:, None])

    out = _walk(take, held_rows, head,
                jnp.zeros((tokens, y.shape[1]), jnp.float32), step)
    return out, (y, gate, take, held_rows)


@functools.partial(jax.jit, static_argnums=(0,))
def _combine_bwd(grid, res, g):
    y, gate, take, held_rows = res
    _, top_k, head = grid

    def step(carry, start, idx, live, _):
        dy, dgate = carry
        n, at = idx.shape[0], g[idx // top_k]
        scale = _cut(gate, start, n)[:, None]
        rows = _cut(y, start, n).astype(jnp.float32)
        return (_put(dy, jnp.where(live[:, None], at * scale,
                                   0.0).astype(y.dtype), start),
                _put(dgate, jnp.where(live, (at * rows).sum(-1), 0.0),
                     start))

    return _walk(take, held_rows, head,
                 (jnp.zeros_like(y), jnp.zeros_like(gate)),
                 step) + (None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def held_expert_layer(u: jax.Array, params: Dict, bias: jax.Array,
                      cfg: HeldExperts, kernel: Optional[str] = None):
    """The routed part of an expert layer that this chip computes.

    ``u`` [T, D]; ``params``: ``router`` [E, D], ``w_gate`` / ``w_up``
    [H, D, F] and ``w_down`` [H, F, D] for the H held experts (numbers
    ``expert_offset`` to ``expert_offset + H - 1`` of the E); an expert of
    the ``relu2`` form (``cfg.form``) has no ``w_gate``. Returns
    (``sum over the chosen experts held here of gate * expert(u)`` [T, D]
    float32, counts [E] int32, overflow_rows int32, balance float32).
    ``cfg.route`` names the route: ``"sigmoid"`` (:func:`sigmoid_route`
    under ``bias``; balance is 0) or ``"softmax"`` (:func:`softmax_route`,
    which has no bias; balance is its load-balance term). What the absent
    experts would add is left out. The rows routed here are sorted by
    expert into a buffer of ``buffer_rows`` rows; the padding after them
    belongs to no expert's group, so the products visit the row tiles
    that hold routed rows, and the passes round them (:func:`_dispatch`
    into the buffer, :func:`_combine` back to the tokens, and the
    transpose of each) walk an even load's rows and the chunks past them
    that hold routed rows (:func:`_walk`): the time of both follows
    ``held_rows``, not the buffer. Past ``held_rows``
    the products' results (``up``'s, the hidden rows ``h``, ``down``'s
    ``y``) and their gradients are UNWRITTEN memory: the two passes
    SELECT a chunk's rows by ``live`` on their way in and out (a product
    with zero would keep a NaN) and read no chunk past the last routed
    row, and nothing else may reduce over those rows; a backward pass
    that kept the products' results (``KEPT_NAMES``) reads there what
    the forward kernels left. ``overflow_rows`` counts rows
    that did not fit the buffer and were left out: 0 unless the buffer
    was sized under the load (it cannot be with ``buffer_rows=None``).
    ``kernel``: ``"pallas"`` (the chip's default), ``"interpret"`` (the
    same kernel in Pallas's interpreter) or ``"xla"`` (``ragged_dot``, the
    default off the chip)."""
    t = u.shape[0]
    held, k = cfg.experts_held, cfg.top_k
    if kernel is None:
        kernel = "pallas" if jax.devices()[0].platform == "tpu" else "xla"
    if cfg.route == "softmax":
        chosen, gates, counts, balance = softmax_route(u, params["router"],
                                                       cfg)
    elif cfg.route == "sigmoid":
        chosen, gates, counts = sigmoid_route(u, params["router"], bias, cfg)
        balance = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(f"no route named {cfg.route!r}")
    rows = buffer_length(cfg, t)
    with jax.named_scope("mv.lm.moe.dispatch"):
        local = chosen.reshape(-1) - cfg.expert_offset          # [T*K]
        here = (local >= 0) & (local < held)
        # held assignments first, grouped by expert, in token order
        order = jnp.argsort(jnp.where(here, local, held), stable=True)
        take = checkpoint_name(
            order[:rows] if rows <= t * k else jnp.pad(
                order, (0, rows - t * k)), KEEP_TAKE)
        sizes = jax.lax.dynamic_slice(counts, (cfg.expert_offset,), (held,))
        ends = jnp.minimum(jnp.cumsum(sizes), rows)
        held_rows = ends[-1]
        overflow = sizes.sum() - held_rows
        # the padding rows are in no group: the grid ends with the rows
        groups = jnp.diff(ends, prepend=0)
        grid = (t, k, even_rows(cfg, t))
        x, gate = _dispatch(u.astype(cfg.dtype), gates.reshape(-1), take,
                            held_rows, grid)
    with jax.named_scope("mv.lm.moe.experts"):
        y = expert_products(x, params, groups, cfg, kernel)
    with jax.named_scope("mv.lm.moe.combine"):
        out = _combine(y, gate, take, held_rows, grid)
    return out, counts, overflow, balance
