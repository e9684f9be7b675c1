"""The pass between a projection's float32 sum and the attention core's
operand, and its transpose: what ``models/mla_moe.heads`` runs on a part
that takes a norm or rotary positions.

    forward:   z = round(turn(norm(y)))          y  [B, H, S, hd] float32
    backward:  dy = norm'(unturn(float32(g)))    g  [B, H, S, hd] as the core
                                                    hands it (bfloat16)

``norm(y) = y * rsqrt(mean(y^2) + eps) * gain`` over a head (where a gain is
given) and ``turn(n) = n * C + partner(n) * S`` on the columns ``[lo, lo +
rope)``, where ``partner`` pairs column ``i`` of their first half with ``i +
rope / 2`` and back, ``C`` holds cos there and ``S`` holds ``-sin`` on the
first half and ``sin`` on the second (``mla_moe._turning`` makes both, [S,
hd] or, under position ids of a batch element's own, [B, S, hd]): element
for element what ``mla_moe.rms_norm`` and ``mla_moe.rotary`` compute.

:func:`plain_fwd` and :func:`plain_bwd` are the definition in plain
``jax.numpy``. XLA gives that form the positions on the lanes (the roll is
then a slice of a major axis) and pays a lane transpose into the core and
another out of it, float32 copies between (PERF.md section 6, PR 63). The
same two functions as Pallas kernels (``heads_turn_fwd``,
``heads_turn_bwd``): a grid over (batch, position tile, head), the heads
innermost so that a tile of ``C`` and ``S`` is fetched once for all of
them; a tile [positions, hd] is read once, normed, rolled along the lanes,
multiplied, rounded and written once, in the layout the flash kernels read.
BlockSpecs alone, no DMA or semaphore of their own. Their names hold no
``mv.lm.attn``: the benchmark counts every custom call whose name does as a
flash kernel.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multiverso_tpu.ops.index_kernels import _bind

FWD, BWD = "heads_turn_fwd", "heads_turn_bwd"
# positions a tile, the largest that divides the sequence
POSITION_TILES = (512, 256, 128)


class Turn(NamedTuple):
    """A pass's statics: columns ``[lo, lo + rope)`` turn (``rope`` 0:
    none), a norm under ``eps`` where a gain is given, the result's
    dtype."""
    lo: int
    rope: int
    eps: float
    dtype: object


def _partner(y, turn: Turn, roll):
    half, hd = turn.rope // 2, y.shape[-1]
    if turn.rope == hd:
        return roll(y, half)
    first = jax.lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 1) \
        < turn.lo + half
    return jnp.where(first, roll(y, hd - half), roll(y, half))


def _turns(shape, turn: Turn):
    at = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (at >= turn.lo) & (at < turn.lo + turn.rope)


def _fwd_math(y, c, sg, gain, turn: Turn, roll):
    """The forward pass on float32 ``y`` [.., hd], in the order
    ``rms_norm`` then ``rotary`` compute it."""
    if gain is not None:
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, -1, keepdims=True) + turn.eps) * gain
    if turn.rope:
        y = jnp.where(_turns(y.shape, turn),
                      y * c + _partner(y, turn, roll) * sg, y)
    return y.astype(turn.dtype)


def _bwd_math(g, y, c, sg, gain, turn: Turn, roll):
    """(dy in ``turn.dtype``, the gain's gradient summed over all but the
    last axis or ``None``) from the core's cotangent ``g``."""
    g = g.astype(jnp.float32)
    if turn.rope:
        g = jnp.where(_turns(g.shape, turn),
                      g * c + _partner(g * sg, turn, roll), g)
    dgain = None
    if gain is not None:
        r = jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + turn.eps)
        dn = g * gain
        dgain = jnp.sum((g * (y * r)).reshape(-1, g.shape[-1]), 0)
        g = r * dn - y * (r * r * r) * jnp.mean(dn * y, -1, keepdims=True)
    return g.astype(turn.dtype), dgain


_jnp_roll = lambda y, shift: jnp.roll(y, shift, -1)
_lane_roll = lambda y, shift: pltpu.roll(y, shift, y.ndim - 1)


def _heads_axis(table):
    """A table [S, hd] or [B, S, hd] against [B, H, S, hd]."""
    return None if table is None else (
        table if table.ndim == 2 else table[:, None])


def plain_fwd(y, c, sg, gain, turn: Turn):
    return _fwd_math(y, _heads_axis(c), _heads_axis(sg), gain, turn,
                     _jnp_roll)


def plain_bwd(g, y, c, sg, gain, turn: Turn):
    return _bwd_math(g, y, _heads_axis(c), _heads_axis(sg), gain, turn,
                     _jnp_roll)


# ---------------------------------------------------------------------- #
# the kernels
# ---------------------------------------------------------------------- #
def _fwd_kernel(*refs, turn: Turn, normed: bool):
    y_ref, refs = refs[0], list(refs[1:])
    c, sg = (refs.pop(0)[0], refs.pop(0)[0]) if turn.rope else (None, None)
    gain = refs.pop(0)[...] if normed else None
    refs[0][0, 0] = _fwd_math(y_ref[0, 0], c, sg, gain, turn, _lane_roll)


def _bwd_kernel(*refs, turn: Turn, normed: bool):
    g_ref, refs = refs[0], list(refs[1:])
    y = refs.pop(0)[0, 0] if normed else None
    c, sg = (refs.pop(0)[0], refs.pop(0)[0]) if turn.rope else (None, None)
    gain = refs.pop(0)[...] if normed else None
    dy, dgain = _bwd_math(g_ref[0, 0], y, c, sg, gain, turn, _lane_roll)
    refs[0][0, 0] = dy
    if normed:
        refs[1][0, 0, 0] = dgain[None]


def _calls(b: int, h: int, s: int, hd: int, tables_b: int, normed: bool,
           turn: Turn, tile: int, interpret: bool):
    """(the forward ``pallas_call``: ``(y[, C, S][, gain]) -> z``; the
    backward: ``(g[, y][, C, S][, gain]) -> (dy[, dgain [B, S / tile, H, 1,
    hd]])``). ``tables_b``: 1, or the batch where every element has its
    own ``C`` and ``S``."""
    if s % tile or (turn.rope and turn.lo + turn.rope > hd) or turn.rope % 2:
        raise ValueError(f"[{s}, {hd}] does not divide into tiles of {tile} "
                         f"positions, or columns [{turn.lo}, "
                         f"{turn.lo + turn.rope}) do not pair within a head")
    steps = s // tile
    grid = (b, steps, h)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))
    here = pl.BlockSpec((1, 1, tile, hd), lambda b, t, j: (b, j, t, 0))
    table = pl.BlockSpec((1, tile, hd), (lambda b, t, j: (b, t, 0))
                         if tables_b > 1 else (lambda b, t, j: (0, t, 0)))
    tables = [table, table] if turn.rope else []
    gains = [pl.BlockSpec((1, hd), lambda b, t, j: (0, 0))] if normed else []
    static = dict(turn=turn, normed=normed)
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, **static), grid=grid,
        in_specs=[here] + tables + gains, out_specs=here,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), turn.dtype),
        compiler_params=params, name=FWD, interpret=interpret)
    outs = [here] + ([pl.BlockSpec((1, 1, 1, 1, hd),
                                   lambda b, t, j: (b, t, j, 0, 0))]
                     if normed else [])
    shapes = [jax.ShapeDtypeStruct((b, h, s, hd), turn.dtype)] + (
        [jax.ShapeDtypeStruct((b, steps, h, 1, hd), jnp.float32)]
        if normed else [])
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, **static), grid=grid,
        in_specs=[here] + [here] * normed + tables + gains,
        out_specs=outs, out_shape=shapes,
        compiler_params=params, name=BWD, interpret=interpret)
    return fwd, bwd


def _call(which: int, operands, c, sg, gain, turn: Turn, tile: int,
          interpret: bool):
    """Kernel ``which`` of :func:`_calls`, traced once a process for its
    shapes and statics (``index_kernels._bind``)."""
    b, h, s, hd = operands[0].shape
    tables = () if not turn.rope else tuple(
        t if t.ndim == 3 else t[None] for t in (c, sg))
    static = (b, h, s, hd, tables[0].shape[0] if tables else 1,
              gain is not None, turn, tile, interpret)
    gains = () if gain is None else (gain.reshape(1, hd),)
    return _bind(((FWD, BWD)[which],) + static,
                 lambda *ops: _calls(*static)[which](*ops),
                 tuple(operands) + tables + gains)


def tile_of(s: int, hd: int, dtype) -> Optional[int]:
    """Positions a tile of the kernels, or ``None`` where the shape is
    none of theirs: a result that is not bfloat16, a head that is not
    whole half lane tiles (64: ``lfm2``'s; 192: ``xing4``'s), a sequence
    no tile divides."""
    if hd % 64 or jnp.dtype(dtype) != jnp.bfloat16:
        return None
    return next((t for t in POSITION_TILES if s % t == 0), None)


def kernel_tile(s: int, hd: int, dtype) -> Optional[int]:
    """:func:`tile_of` on a TPU; ``None`` (the plain form) off it."""
    if jax.devices()[0].platform != "tpu":
        return None
    return tile_of(s, hd, dtype)


def forward(y, c, sg, gain, turn: Turn, *, tile: Optional[int] = None,
            interpret: bool = False):
    """``round(turn(norm(y)))``: ``y`` [B, H, S, hd] float32 -> [B, H, S,
    hd] in ``turn.dtype``. The kernel where :func:`kernel_tile` finds a
    tile on this device (or a test hands one, with the interpreter),
    :func:`plain_fwd` anywhere else."""
    tile = tile or kernel_tile(y.shape[2], y.shape[3], turn.dtype)
    if tile is None:
        return plain_fwd(y, c, sg, gain, turn)
    return _call(0, (y,), c, sg, gain, turn, tile, interpret)[0]


def backward(g, y, c, sg, gain, turn: Turn, *, tile: Optional[int] = None,
             interpret: bool = False):
    """``(dy in turn.dtype, dgain [hd] or None)`` from the core's cotangent
    ``g`` [B, H, S, hd]; ``y`` is the forward's operand where there is a
    norm (``gain`` given), unread where not."""
    tile = tile or kernel_tile(g.shape[2], g.shape[3], turn.dtype)
    if tile is None:
        return plain_bwd(g, y, c, sg, gain, turn)
    out = _call(1, (g,) + (() if gain is None else (y,)), c, sg, gain, turn,
                tile, interpret)
    return out[0], None if gain is None else out[1].sum((0, 1, 2, 3))
