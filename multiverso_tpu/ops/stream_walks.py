"""The backward pass of a hyper-connected sublayer's stream maps as three
walks over the streams (``models/mla_moe.block`` under several residual
streams, whose differentiation rule calls them).

With ``X`` [T, n, C] the streams a sublayer was handed, ``y`` [T, C] its
branch's result, ``G`` [T, n, C] the gradient of what it handed back and the
maps ``pre``, ``post`` [n, T] and ``res`` [n, n, T] (a position a lane):

1. :func:`gather`, before the branch's backward pass: one read of ``G``,
   ``X`` and ``y``; writes ``dy = sum_i post_i G_i`` and the twenty numbers a
   position that need ``G``: ``dpost_i = <G_i, y>``, ``dres_ij = <G_i,
   X_j>``. ``(2n + 2) C`` floats a position.
2. :func:`dots`, after it: ``dpre_i = <du, X_i>`` needs all of a position's
   ``X`` against ``du`` before any of ``dX`` can be written, and Sinkhorn's
   backward pass stands between the two; so it is a read of its own, ``(n +
   1) C`` floats a position.
3. :func:`spread`: one read of ``G``, ``X`` and ``du``; writes ``dX_j =
   sum_i res_ij G_i + pre_j du + (a phi)_j - q X_j`` ONCE, where ``a = r
   ds`` [n^2 + 2n, T] and ``q = (r^3 / nC) <ds, phi x>`` [T] are what the
   norm and the projection hand back (``r`` the norm's factor, ``ds`` the
   gradient of the scores). ``(3n + 1) C`` floats a position.

Each has a plain ``jax.numpy`` form (the definition: what the CPU runs, the
tests' second opinion and the reference for the kernels' error) and a Pallas
kernel (``hc_bwd_gather``, ``hc_bwd_dots``, ``hc_bwd_spread``) taken where
:func:`walk_tiles` finds a TPU, float32 and whole lanes of positions. The
kernels read the streams as [n, C, T]: a stream at a time, channels on the
sublanes, POSITIONS ON THE LANES, which is how XLA lays a [B, S, n, C] array
out between its own fusions on a v5e (``{1,3,2,0:T(8,128)}``) and how the
maps lie, a position a lane: the transposes round a call are layout and not
passes, a map multiplies a tile as a row handed down the sublanes, and the
sums over the channels come out a position a lane as Sinkhorn's backward
pass reads them. (Read [n, T, C], which the kernels did first, XLA took that
layout for the forward pass too and lost there what the backward pass won:
PERF.md section 6, PR 62.) The branch's side (``y``, ``du``, ``dy``) is [T,
C] in the program and [C, T] in the kernels: XLA's transposes, most of them
folded into what makes or reads them. The mixes are sums of n products a
number on the vector unit; the one matrix product, ``a phi`` inside
:func:`spread`, is at the highest precision as ``stream_maps``'s projection
is. BlockSpecs alone: no DMA or semaphore of their own. A kernel is one
module-level function whose statics are its operands' shapes, traced once a
process and bound again from its jaxpr (``index_kernels._bind``): the ten
sublayers of a step, attention's and the feed-forward's alike, share one
equation's parameters and are lowered once a module. The names hold no
``mv.lm.attn``: the benchmark counts every custom call whose name does as a
flash kernel.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multiverso_tpu.ops.index_kernels import _bind

GATHER, DOTS, SPREAD = "hc_bwd_gather", "hc_bwd_dots", "hc_bwd_spread"
LANES, SUBLANES = 128, 8
_HIGHEST = jax.lax.Precision.HIGHEST
# what a step's blocks may hold of VMEM, both buffers of every operand
# (the default scoped limit is 16 MB; no ``vmem_limit_bytes`` is asked for:
# XLA sets it aside round the call, PERF.md section 6, PR 55)
_VMEM = 11 << 20


class Tiles(NamedTuple):
    """The channels a block of :func:`gather`, of :func:`dots` and of
    :func:`spread` holds; its positions are one lane tile (on the chip 512
    channels x 128 positions read what 256 x 512 and 128 x 512 read, 0.91
    / 0.45 / 1.27 ms, and a kernel's body is a quarter as long: PERF.md
    section 6, PR 62)."""
    gather: int
    dots: int
    spread: int


def tiles_for(t: int, n: int, c: int) -> Optional[Tiles]:
    """The blocks ``t`` positions of ``n`` streams of ``c`` float32 channels
    divide into, or ``None``: positions that are no whole lanes, channels
    that are no whole sublane tiles, or more maps a position (``2 n^2 + 3 n
    + 1``) than a lane tile holds. The most channels that both buffers of
    every operand leave inside :data:`_VMEM`."""
    if t % LANES or c % SUBLANES or 2 * n * n + 3 * n + 1 > LANES:
        return None

    def most(floats):
        return next((tc for tc in (512, 256, 128, 64, 32, 16, 8)
                     if c % tc == 0 and 2 * 4 * floats(tc) <= _VMEM), None)

    found = (most(lambda tc: (2 * n + 2) * tc * LANES),
             most(lambda tc: (n + 1) * tc * LANES),
             most(lambda tc: (3 * n + 2) * tc * LANES + LANES * LANES))
    return Tiles(*found) if all(found) else None


def walk_tiles(t: int, n: int, c: int, *dtypes) -> Optional[Tiles]:
    """:func:`tiles_for` where this process's device is a TPU and the
    streams, the branch's result and the gradients are all float32; ``None``
    where the plain forms run."""
    if (jax.devices()[0].platform != "tpu"
            or any(d != jnp.float32 for d in dtypes)):
        return None
    return tiles_for(t, n, c)


def step_counts(sublayers: int, t: int, n: int, c: int) -> dict:
    """What ``lm.step`` spans say of ``sublayers`` hyper-connected sublayers
    over ``t`` positions of ``n`` streams of ``c`` channels:
    ``hc_kernel_sublayers``, those whose backward walks are the kernels on
    this device (all of them or none), and ``hc_bwd_stream_bytes``, what the
    three walks and the projection's gradient (one more read of the streams)
    read and write of float32 [t, c] arrays a step: ``(2n + 2) + (n + 1) +
    (3n + 1) + n`` of them a sublayer."""
    return {"hc_kernel_sublayers": sublayers * bool(
                walk_tiles(t, n, c, jnp.float32)),
            "hc_bwd_stream_bytes": sublayers * (7 * n + 4) * 4 * t * c}


# ---------------------------------------------------------------------- #
# the definitions
# ---------------------------------------------------------------------- #
def _col(m):
    """A map's [T] beside a [T, C] array."""
    return m[:, None]


def gather_plain(g, x, y, post):
    n = x.shape[1]
    gs, xs = [g[:, i] for i in range(n)], [x[:, j] for j in range(n)]
    dy = sum(_col(post[i]) * gs[i] for i in range(n))
    dpost = jnp.stack([jnp.sum(gs[i] * y, -1) for i in range(n)])
    dres = jnp.stack([jnp.stack([jnp.sum(gs[i] * xs[j], -1)
                                 for j in range(n)]) for i in range(n)])
    return dy, dpost, dres


def dots_plain(du, x):
    return jnp.stack([jnp.sum(du * x[:, i], -1) for i in range(x.shape[1])])


def spread_plain(g, x, du, phi, pre, res, a, q):
    t, n, c = x.shape
    back = jax.lax.dot_general(
        a, phi, (((0,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32).reshape(t, n, c)
    return jnp.stack(
        [sum(_col(res[i, j]) * g[:, i] for i in range(n))
         + _col(pre[j]) * du + back[:, j] - _col(q) * x[:, j]
         for j in range(n)], 1)


# ---------------------------------------------------------------------- #
# the kernels
# ---------------------------------------------------------------------- #
# the sublane tiles (eight channels each) a trip of a kernel's loop over a
# block's channels takes: the loop is a ``lax.fori_loop`` (its body is traced
# once: written out, a kernel was 1,700 equations and seconds of a set-up's
# first trace on the chip's host), two tiles a trip so that the loads of one
# overlap the sums of the other (on the chip the write reads 1.31 ms at 2 a
# trip and 1.29 at 4 or 8: PERF.md section 6, PR 62)
UNROLL = (2, 1)


def _over_channels(channels: int, body, carry):
    """``carry = body(rows, carry)`` for every sublane tile of a block's
    ``channels``, :data:`UNROLL` tiles a trip."""
    tiles = channels // SUBLANES
    unroll = next(u for u in UNROLL if tiles % u == 0)

    def trip(at, carry):
        for k in range(unroll):
            start = pl.multiple_of((at * unroll + k) * SUBLANES, SUBLANES)
            carry = body(pl.ds(start, SUBLANES), carry)
        return carry

    return jax.lax.fori_loop(0, tiles // unroll, trip, carry)


def _down(row):
    """A map's [1, 128] positions down a sublane tile of channels."""
    return jnp.broadcast_to(row, (SUBLANES, LANES))


def _zeros(*lead):
    return jnp.zeros(lead + (SUBLANES, LANES), jnp.float32)


def _fold(part):
    """A [8, 128] partial sum over its channels: [1, 128]."""
    return jnp.sum(part, 0, keepdims=True)


def _gather_kernel(g_ref, x_ref, y_ref, post_ref, dy_ref, sums_ref):
    """A block is [n, channels, 128]: a lane tile of positions, as the maps
    have them. Over the block's channels: ``dy`` a tile, the n + n^2
    products' partial sums in registers, folded into ``sums_ref`` [n + 1,
    8, 128] at the end (plane 0, row i: ``dpost_i``; plane 1 + i, row j:
    ``dres_ij``), which stays where it is over the channels' blocks (the
    grid's inner axis)."""
    n, channels, _ = g_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _start():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    posts = [_down(post_ref[i:i + 1, :]) for i in range(n)]

    def tile(rows, sums):
        g, x = g_ref[:, rows, :], x_ref[:, rows, :]
        dy = posts[0] * g[0]
        for i in range(1, n):
            dy = dy + posts[i] * g[i]
        dy_ref[rows, :] = dy
        return (sums[0] + g * y_ref[rows, :][None],
                sums[1] + g[:, None] * x[None, :])

    by_y, by_x = _over_channels(channels, tile, (_zeros(n), _zeros(n, n)))
    for i in range(n):
        sums_ref[0, i:i + 1, :] += _fold(by_y[i])
        for j in range(n):
            sums_ref[1 + i, j:j + 1, :] += _fold(by_x[i, j])


def _dots_kernel(du_ref, x_ref, sums_ref):
    """``<du, x_i>`` at row ``i`` of ``sums_ref`` [8, 128]."""
    n, channels, _ = x_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _start():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    sums = _over_channels(
        channels, lambda rows, sums: sums + du_ref[rows, :][None]
        * x_ref[:, rows, :], _zeros(n))
    for i in range(n):
        sums_ref[i:i + 1, :] += _fold(sums[i])


def _spread_kernel(g_ref, x_ref, du_ref, maps_ref, phi_ref, dx_ref):
    """``maps_ref`` [128, 128 positions] rows: ``a_k`` at ``k`` < n^2 + 2n,
    then ``res_ij`` (row by row), ``pre_j`` and ``q``; ``phi_ref`` [n,
    channels, 128], a stream's window of the projection a channel a row,
    zeros past column n^2 + 2n, so its product with the whole of
    ``maps_ref`` is ``(a phi)_j`` alone. The product goes to ``dx_ref``
    whole; the mixes are then added to it eight channels at a time."""
    n, channels, _ = g_ref.shape
    outs = n * n + 2 * n
    maps = maps_ref[...]
    for j in range(n):
        dx_ref[j] = jnp.dot(phi_ref[j], maps, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    row = lambda k: _down(maps_ref[k:k + 1, :])
    # res[i]: row i of the mix across the streams it is added to
    res = [jnp.stack([row(outs + i * n + j) for j in range(n)])
           for i in range(n)]
    pre = jnp.stack([row(outs + n * n + j) for j in range(n)])
    q = row(outs + n * n + n)[None]

    def tile(rows, _):
        g = g_ref[:, rows, :]
        dx = (dx_ref[:, rows, :] + pre * du_ref[rows, :][None]
              - q * x_ref[:, rows, :])
        for i in range(n):
            dx = dx + res[i] * g[i][None]
        dx_ref[:, rows, :] = dx

    _over_channels(channels, tile, None)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _gather_call(t: int, n: int, c: int, tc: int, interpret: bool):
    """``(g [n, c, t], x [n, c, t], y [c, t], post [n, t]) -> (dy [c, t],
    sums [n + 1, 8, t])``: the channels' blocks innermost."""
    tt = LANES
    streams = pl.BlockSpec((n, tc, tt), lambda i, j: (0, j, i))
    flat = pl.BlockSpec((tc, tt), lambda i, j: (j, i))
    return pl.pallas_call(
        _gather_kernel, grid=(t // tt, c // tc),
        in_specs=[streams, streams, flat,
                  pl.BlockSpec((n, tt), lambda i, j: (0, i))],
        out_specs=[flat, pl.BlockSpec((n + 1, SUBLANES, tt),
                                      lambda i, j: (0, 0, i))],
        out_shape=[_f32(c, t), _f32(n + 1, SUBLANES, t)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=GATHER, interpret=interpret)


def _dots_call(t: int, n: int, c: int, tc: int, interpret: bool):
    """``(du [c, t], x [n, c, t]) -> sums [8, t]``."""
    tt = LANES
    return pl.pallas_call(
        _dots_kernel, grid=(t // tt, c // tc),
        in_specs=[pl.BlockSpec((tc, tt), lambda i, j: (j, i)),
                  pl.BlockSpec((n, tc, tt), lambda i, j: (0, j, i))],
        out_specs=pl.BlockSpec((SUBLANES, tt), lambda i, j: (0, i)),
        out_shape=_f32(SUBLANES, t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=DOTS, interpret=interpret)


def _spread_call(t: int, n: int, c: int, tc: int, interpret: bool):
    """``(g [n, c, t], x [n, c, t], du [c, t], maps [128, t], phi [n, c,
    128]) -> dx [n, c, t]``: the channels' blocks outermost, so a block of
    ``phi`` stays where it is over the positions."""
    tt = LANES
    streams = pl.BlockSpec((n, tc, tt), lambda j, i: (0, j, i))
    return pl.pallas_call(
        _spread_kernel, grid=(c // tc, t // tt),
        in_specs=[streams, streams,
                  pl.BlockSpec((tc, tt), lambda j, i: (j, i)),
                  pl.BlockSpec((LANES, tt), lambda j, i: (0, i)),
                  pl.BlockSpec((n, tc, LANES), lambda j, i: (0, j, 0))],
        out_specs=streams, out_shape=_f32(n, c, t),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=SPREAD, interpret=interpret)


def _by_stream(x):
    """[T, n, C] as the kernels read it: [n, C, T], a stream at a time,
    positions on the lanes."""
    return x.transpose(1, 2, 0)


# ---------------------------------------------------------------------- #
# the walks
# ---------------------------------------------------------------------- #
def _tiles_of(x, tiles, *others) -> Optional[Tiles]:
    t, n, c = x.shape
    return tiles or walk_tiles(t, n, c, x.dtype, *(o.dtype for o in others))


def gather(g, x, y, post, *, tiles: Optional[Tiles] = None,
           interpret: bool = False):
    """Walk 1: ``g``, ``x`` [T, n, C], ``y`` [T, C], ``post`` [n, T] ->
    ``(dy [T, C], dpost [n, T], dres [n, n, T])``."""
    tiles = _tiles_of(x, tiles, g, y)
    if tiles is None:
        return gather_plain(g, x, y, post)
    t, n, c = x.shape
    static = (t, n, c, tiles.gather, interpret)
    dy, sums = _bind((GATHER,) + static,
                     lambda *ops: _gather_call(*static)(*ops),
                     (_by_stream(g), _by_stream(x), y.T, post))
    return dy.T, sums[0, :n], sums[1:, :n]


def dots(du, x, *, tiles: Optional[Tiles] = None, interpret: bool = False):
    """``dpre`` [n, T]: ``du`` [T, C] against each stream of ``x`` [T, n,
    C]."""
    tiles = _tiles_of(x, tiles, du)
    if tiles is None:
        return dots_plain(du, x)
    t, n, c = x.shape
    static = (t, n, c, tiles.dots, interpret)
    sums, = _bind((DOTS,) + static, lambda *ops: [_dots_call(*static)(*ops)],
                  (du.T, _by_stream(x)))
    return sums[:n]


def spread(g, x, du, phi, pre, res, a, q, *, tiles: Optional[Tiles] = None,
           interpret: bool = False):
    """Walk 2: ``g``, ``x`` [T, n, C], ``du`` [T, C], ``phi`` [n^2 + 2n, n
    C], ``pre`` [n, T], ``res`` [n, n, T], ``a`` [n^2 + 2n, T], ``q`` [T]
    -> ``dx`` [T, n, C]."""
    tiles = _tiles_of(x, tiles, g, du)
    if tiles is None:
        return spread_plain(g, x, du, phi, pre, res, a, q)
    t, n, c = x.shape
    outs = a.shape[0]
    maps = jnp.concatenate([a, res.reshape(n * n, t), pre, q[None]], 0)
    maps = jnp.pad(maps, ((0, LANES - maps.shape[0]), (0, 0)))
    windows = jnp.pad(phi.reshape(outs, n, c).transpose(1, 2, 0),
                      ((0, 0), (0, 0), (0, LANES - outs)))
    static = (t, n, c, tiles.spread, interpret)
    dx, = _bind((SPREAD,) + static,
                lambda *ops: [_spread_call(*static)(*ops)],
                (_by_stream(g), _by_stream(x), du.T, maps, windows))
    return dx.transpose(2, 0, 1)
