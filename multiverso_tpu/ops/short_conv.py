"""The mixers' short causal depthwise convolution, one pass forward and one
backward: what ``models/qwen3_next.gated_delta_net`` (4 taps, no bias, a
silu) and ``models/nemotron_h.mamba2`` (4 taps, a bias, a silu) run between
their in-projection and their core.

    y[b, t] = act(bias + sum_i w[i] * x[b, t - (taps - 1) + i])

with ``x`` zero before position 0 of a sequence, everything float32.
:func:`plain` is the definition, shifted slices of a left-padded array in
plain ``jax.numpy``: XLA makes the pad a copy of its own, does not keep the
shifted reads and the silu one fusion, and pays it all again backward (3.8 ms
forward and 9.5 backward a layer where one read and one write of the array
are 1.3: PERF.md section 6, PR 59). :func:`causal_taps` is the same function
as two Pallas kernels under a ``jax.custom_vjp``:

1. :func:`_fwd_kernel` (``short_conv_fwd``): a grid over (sequence, channel
   tile, position tile), the positions innermost and in order; a tile of
   ``x`` is read once, the last rows of the tile before it ride in a VMEM
   scratch (zeros at a sequence's first tile), the shifted rows are made in
   VMEM by a sublane roll, and taps, bias and silu are applied in the sum's
   order; one write.
2. :func:`_bwd_kernel` (``short_conv_bwd``): the residuals are the inputs
   alone. It walks the position tiles in REVERSE (the first rows of ``g'``
   of the following tile ride in scratch), makes the pre-activation again
   on the tile (the rows before the tile arrive as a block of
   :data:`HALO` rows of their own), ``g' = g act'(pre)``, writes ``dx[t] =
   sum_i w[i] g'[t + (taps - 1) - i]`` and adds ``dw`` and ``dbias`` up over
   the walk in VMEM, a sequence and channel tile at a time.

Neither kernel takes a DMA or a semaphore of its own: blocks and scratch
alone. Their names hold no ``mv.lm.attn``: the benchmark counts every custom
call whose name does as a flash kernel.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multiverso_tpu.ops.index_kernels import _bind

FWD, BWD = "short_conv_fwd", "short_conv_bwd"

# a float32 sublane tile: the rows that cross a seam ride in whole tiles
HALO = 8
# positions a tile of the walk, and the rows of it a kernel holds in
# registers at a time (``chip_smoke.py`` stage ``taps`` reads others)
POSITION_TILE, ROWS = 512, 32
CHANNEL_TILES = (512, 256, 128)


def plain(x, w, bias, silu: bool):
    """The definition: ``x`` [B, S, C], ``w`` [taps, C] (tap ``i`` reads
    position ``t - (taps - 1) + i``), ``bias`` [C] or ``None``."""
    taps, s = w.shape[0], x.shape[1]
    past = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(past[:, i:i + s] * w[i] for i in range(taps))
    if bias is not None:
        y = bias + y
    return jax.nn.silu(y) if silu else y


def kernel_tiles(s: int, c: int, dtype=jnp.float32
                 ) -> Optional[Tuple[int, int]]:
    """The (position, channel) tile the kernels take ``s`` positions of
    ``c`` float32 channels in on this process's device, or ``None`` where
    the plain form runs: off a TPU, at channels that are no whole lanes, at
    positions that are no whole tiles."""
    if (jax.devices()[0].platform != "tpu" or dtype != jnp.float32
            or c % CHANNEL_TILES[-1] or s % POSITION_TILE):
        return None
    return POSITION_TILE, next(t for t in CHANNEL_TILES if c % t == 0)


def step_counts(mixers: int, s: int, c: int) -> dict:
    """What ``lm.step`` spans say of ``mixers`` layers whose convolution
    runs over ``s`` positions of ``c`` channels: ``conv_kernel_layers``,
    those that run the kernels on this device (all of them or none), and
    ``conv_bytes``, one read and one write of ONE mixer's float32 [s, c]
    array: the least a sequence's convolution moves a pass."""
    return {"conv_kernel_layers": mixers * bool(kernel_tiles(s, c)),
            "conv_bytes": 2 * 4 * s * c}


def _shifted(ext, taps: int):
    """Tap ``i``'s rows for the rows of ``ext`` past its first
    :data:`HALO`: ``x[t - (taps - 1) + i]``."""
    return [(ext if i == taps - 1
             else pltpu.roll(ext, taps - 1 - i, 0))[HALO:]
            for i in range(taps)]


def _pre(xs, w_ref, b_ref):
    """``bias + sum_i w[i] xs[i]`` in the definition's order."""
    acc = None
    for i, rows in enumerate(xs):
        term = rows * w_ref[i:i + 1, :]
        acc = term if acc is None else acc + term
    return acc if b_ref is None else b_ref[...] + acc


def _fwd_kernel(*refs, taps: int, silu: bool, has_bias: bool, rows: int):
    x_ref, w_ref = refs[:2]
    b_ref = refs[2] if has_bias else None
    o_ref, halo_ref = refs[-2:]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        halo_ref[...] = jnp.zeros_like(halo_ref)

    tile = x_ref.shape[1]
    for start in range(0, tile, rows):
        ext = (jnp.concatenate([halo_ref[...], x_ref[0, :rows, :]], 0)
               if start == 0 else x_ref[0, start - HALO:start + rows, :])
        pre = _pre(_shifted(ext, taps), w_ref, b_ref)
        o_ref[0, start:start + rows, :] = (
            pre * jax.nn.sigmoid(pre) if silu else pre)
    halo_ref[...] = x_ref[0, tile - HALO:, :]


def _fold(rows):
    """The sum of ``rows`` [n x HALO, C] over its sublane tiles: [HALO, C]
    (whole-register adds; the sum inside a tile waits for the walk's
    end)."""
    out = rows[:HALO]
    for at in range(HALO, rows.shape[0], HALO):
        out = out + rows[at:at + HALO]
    return out


def _bwd_kernel(*refs, taps: int, silu: bool, has_bias: bool, rows: int):
    x_ref, before_ref, g_ref, w_ref = refs[:4]
    b_ref = refs[4] if has_bias else None
    dx_ref, sums_ref, after_ref, acc_ref = refs[-4:]
    t, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(t == 0)
    def _start():       # a sequence's LAST tile: nothing follows it
        after_ref[...] = jnp.zeros_like(after_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = x_ref.shape[1]
    after = after_ref[...]              # g' of the HALO rows past the tile
    sums = [None] * (taps + 1)
    for start in range(tile - rows, -1, -rows):
        if start:
            ext = x_ref[0, start - HALO:start + rows, :]
        else:           # the rows before a sequence's first tile are zeros
            before = jnp.where(t == steps - 1, 0.0, before_ref[0])
            ext = jnp.concatenate([before, x_ref[0, :rows, :]], 0)
        xs = _shifted(ext, taps)
        gp = g_ref[0, start:start + rows, :]
        if silu:
            pre = _pre(xs, w_ref, b_ref)
            gate = jax.nn.sigmoid(pre)
            gp = gp * (gate * (1.0 + pre * (1.0 - gate)))
        # dx[t] = sum_i w[i] g'[t + (taps - 1) - i]
        ahead = jnp.concatenate([gp, after], 0)
        dx = None
        for i in range(taps):
            up = taps - 1 - i
            term = (ahead if up == 0 else pltpu.roll(
                ahead, rows + HALO - up, 0))[:rows] * w_ref[i:i + 1, :]
            dx = term if dx is None else dx + term
        dx_ref[0, start:start + rows, :] = dx
        for i, part in enumerate([gp * x for x in xs] + [gp]):
            part = _fold(part)
            sums[i] = part if sums[i] is None else sums[i] + part
        after = gp[:HALO]
    after_ref[...] = after
    for i, part in enumerate(sums):
        acc_ref[i] += part

    @pl.when(t == steps - 1)
    def _emit():        # rows 0 .. taps - 1: dw; row taps: dbias
        row = jax.lax.broadcasted_iota(jnp.int32, sums_ref.shape[1:], 0)
        out = jnp.zeros(sums_ref.shape[1:], jnp.float32)
        for i in range(taps + 1):
            out = jnp.where(row == i, jnp.sum(acc_ref[i], 0, keepdims=True),
                            out)
        sums_ref[0] = out


def _calls(b: int, s: int, c: int, taps: int, silu: bool, has_bias: bool,
           tile: Tuple[int, int], rows: int, interpret: bool):
    """(the forward ``pallas_call``: ``(x, w[, bias]) -> y``; the backward:
    ``(x, x, g, w[, bias]) -> (dx, sums [B, HALO, C])``)."""
    ts, tc = tile
    rows = min(rows, ts)
    if s % ts or c % tc or ts % rows or rows % HALO or taps > HALO:
        raise ValueError(f"[{s}, {c}] x {taps} taps does not divide into "
                         f"tiles of {tile} in steps of {rows} rows")
    steps, per = s // ts, ts // HALO
    grid = (b, c // tc, steps)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    static = dict(taps=taps, silu=silu, has_bias=has_bias, rows=rows)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def specs(at):      # at(t): the position tile grid step t visits
        here = pl.BlockSpec((1, ts, tc), lambda b, j, t: (b, at(t), j))
        weights = [pl.BlockSpec((taps, tc), lambda b, j, t: (0, j))] + (
            [pl.BlockSpec((1, tc), lambda b, j, t: (0, j))] * has_bias)
        return here, weights

    here, weights = specs(lambda t: t)
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, **static), grid=grid,
        in_specs=[here] + weights, out_specs=here,
        out_shape=f32(b, s, c),
        scratch_shapes=[pltpu.VMEM((HALO, tc), jnp.float32)],
        compiler_params=params, name=FWD, interpret=interpret)
    back, weights = specs(lambda t: steps - 1 - t)
    # the HALO rows before the tile (the tile's own first where none are)
    before = pl.BlockSpec(
        (1, HALO, tc),
        lambda b, j, t: (b, jnp.maximum((steps - 1 - t) * per - 1, 0), j))
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, **static), grid=grid,
        in_specs=[back, before, back] + weights,
        out_specs=[back, pl.BlockSpec((1, HALO, tc),
                                      lambda b, j, t: (b, 0, j))],
        out_shape=[f32(b, s, c), f32(b, HALO, c)],
        scratch_shapes=[pltpu.VMEM((HALO, tc), jnp.float32),
                        pltpu.VMEM((taps + 1, HALO, tc), jnp.float32)],
        compiler_params=params, name=BWD, interpret=interpret)
    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _kernels(x, w, bias, silu, tile, rows, interpret):
    return _forward(x, w, bias, silu, tile, rows, interpret)[0]


def _call(which: int, operands, w, bias, silu, tile, rows, interpret):
    """Kernel ``which`` of :func:`_calls` on ``operands`` and the weights,
    traced once a process for its shapes and static arguments
    (``index_kernels._bind``): a step calls it once a layer, and again
    under ``jax.checkpoint``."""
    static = (*operands[0].shape, w.shape[0], silu, bias is not None, tile,
              rows, interpret)
    weights = (w,) if bias is None else (w, bias[None])
    return _bind(((FWD, BWD)[which],) + static,
                 lambda *ops: _calls(*static)[which](*ops),
                 operands + weights)


def _forward(x, w, bias, silu, tile, rows, interpret):
    y, = _call(0, (x,), w, bias, silu, tile, rows, interpret)
    return y, (x, w, bias)


def _backward(silu, tile, rows, interpret, res, g):
    x, w, bias = res
    dx, sums = _call(1, (x, x, g), w, bias, silu, tile, rows, interpret)
    sums, taps = sums.sum(0), w.shape[0]
    return dx, sums[:taps], None if bias is None else sums[taps]


_kernels.defvjp(_forward, _backward)


def causal_taps(x, w, bias, silu: bool, *,
                tile: Optional[Tuple[int, int]] = None, rows: int = ROWS,
                interpret: bool = False):
    """``act(bias + sum_i w[i] x[t - (taps - 1) + i])`` over ``x`` [B, S,
    C] float32 with ``w`` [taps, C] and ``bias`` [C] or ``None``, zeros
    before position 0 of every sequence -> [B, S, C] float32.

    The two kernels where :func:`kernel_tiles` finds them a tile on this
    device (or a test hands one, with the interpreter), :func:`plain`
    anywhere else: one function either way."""
    tile = tile or kernel_tiles(x.shape[1], x.shape[2], x.dtype)
    if tile is None:
        return plain(x, w, bias, silu)
    return _kernels(x, w, bias, silu, tuple(tile), rows, interpret)
