"""Pallas TPU kernels for the index-score part of the indexer's term
(``models/keye_moe.index_loss``): a chunk of query rows against the key
tiles up to the chunk's diagonal, a tile at a time in VMEM, so that no
[index_heads, rows, S] float32 array of the heads' dots, and no [rows, S]
array of ``I``, its log-softmax or its gradient, is ever written to HBM.

For a chunk ``c`` of ``rows`` query rows with the indexer's operands ``qI``
[B, Hi, rows, Di], ``kI`` [B, S, Di] and ``w`` [B, rows, Hi], the chunk's
selection ``chosen`` [B, rows, S] (int8, causal by construction) and the
target ``pbar`` [B, rows, S] (float32, 0 off the selection), with ``I[t, s]
= sum_h w[t, h] relu(qI[h, t] . kI[s])``:

1. :func:`_stats_kernel` (``index_term_stats``): a row's log-sum-exp of
   ``I`` over its selected keys (a running max and sum of exponentials over
   the walk) and, from its ``sum_s pbar (log pbar - I)`` and ``sum_s pbar``
   over the same walk, the row's KL ``sum_s pbar (log pbar - log
   softmax(I))``, without a [rows, S] array of the log-softmax, and
   without a pass of XLA's over ``pbar`` for its entropy.
2. :func:`_grads_kernel` (``index_term_grads``): the heads' dots again,
   ``dI = chosen (exp(I - lse) - pbar) scale``, per head ``d_dots_h = dI w_h
   [dots_h > 0]``, ``dqI_h += d_dots_h kI`` and ``dw_h += rowsum(relu(dots_h)
   dI)`` accumulated over the walk in their resident output blocks, and
   ``dkI[tile] = carry[tile] + sum_h d_dots_h^T qI_h`` written a tile, in
   place (the carry is the scan's, aliased to the output: a tile the walk
   never reaches keeps what it held).

Both walk the key tiles ``0 .. last(c)`` and no further: the grid has a
step for every tile of ``S``, the chunk's number rides as a scalar-prefetch
operand, a step past ``last(c)`` runs no body, and its index maps are
clamped to ``last(c)``, so it copies no block either (a block whose index
does not change is not fetched again). A key tile never reaches past the
chunk's last row (it divides ``rows``), so keys the chunk cannot see are
not even read.

Precisions are ``keye_moe._scores``': the heads' dots with operands in the
compute dtype and float32 sums; relu, the weights, the sum over the heads,
the statistics and ``dw`` in float32; ``d_dots`` goes to the MXU in the
compute dtype with float32 sums, which is what XLA's default precision
gives the transposed products of ``jax.vjp(_scores)``.

The calls carry names of their own (:data:`STATS`, :data:`GRADS`): a Pallas
custom call without one takes its innermost ``jax.named_scope`` into the
trace as its instruction name, and the benchmark's ``layers/attn`` counts
every custom call whose name holds ``mv.lm.attn`` against four flash
kernels a layer a step (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multiverso_tpu.ops import attention_kernels
from multiverso_tpu.ops.attention_kernels import (_LANES, _NEG_INF,
                                                  _RES_LANES, _across)

STATS, GRADS = "index_term_stats", "index_term_grads"

# the kernel calls traced so far, as ``attention_kernels._TRACED``: the
# term runs once a layer on the same shapes
_TRACED: Dict[tuple, Any] = {}


def key_tile(rows: int) -> int:
    """The keys a tile of the walk holds, from the chunk's ``rows``: the
    largest part of ``rows`` by halving whose [rows, tile] float32
    temporaries (the dots of one head, ``I``, ``dI``) stay within 1 MB
    each. 512 at the published 512 rows (on the chip a last chunk's two
    kernels read 0.23 + 0.80 ms at 512 and 0.23 + 0.86 at 256: PERF.md
    section 6, PR 55)."""
    tile = rows
    while tile % 2 == 0 and rows * tile * 4 > (1 << 20):
        tile //= 2
    return tile


class Walk(NamedTuple):
    """A chunk's walk over the key tiles: ``s`` positions in chunks of
    ``rows`` query rows, ``tile`` keys a step (a divisor of ``rows``, so
    the walk's last tile ends with the chunk's last row)."""
    s: int
    rows: int
    tile: int

    @property
    def steps(self) -> int:          # the grid's: every tile of ``s``
        return self.s // self.tile

    def last(self, chunk):
        """The last key tile chunk ``chunk`` (a number or a traced
        scalar) sees."""
        per = self.rows // self.tile
        return chunk * per + per - 1

    def walked(self) -> int:
        """Key tiles one kernel visits over all the chunks of a sequence;
        ``steps`` a chunk would be the uncut rectangle (:meth:`whole`)."""
        return sum(self.last(c) + 1 for c in range(self.s // self.rows))

    def whole(self) -> int:
        return (self.s // self.rows) * self.steps


def walk_of(s: int, rows: int, tile: Optional[int] = None) -> Walk:
    tile = key_tile(rows) if tile is None else tile
    if s % rows or rows % tile:
        raise ValueError(f"{s} positions in chunks of {rows} rows do not "
                         f"divide into key tiles of {tile}")
    return Walk(s, rows, tile)


def _enter(c_ref, walk: Walk):
    """(this grid step's key tile, the chunk's last)."""
    return pl.program_id(1), walk.last(c_ref[0])


def _dots(q_ref, k, h: int):
    """Head ``h``'s ``qI_h . kI^T`` of the tile: [rows, tile] float32."""
    return jax.lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _index(q_ref, k, w):
    """``I`` of the tile: [rows, tile] float32 (``w``: [rows, Hi])."""
    total = None
    for h in range(q_ref.shape[1]):
        part = jnp.maximum(_dots(q_ref, k, h), 0.0) * w[:, h:h + 1]
        total = part if total is None else total + part
    return total


def _stats_kernel(c_ref, q_ref, k_ref, w_ref, sel_ref, p_ref, lse_ref,
                  kl_ref, m_ref, l_ref, a_ref, s_ref, *, walk: Walk):
    t, last = _enter(c_ref, walk)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        for ref in (l_ref, a_ref, s_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(t <= last)
    def _tile():
        index = _index(q_ref, k_ref[0], w_ref[0])
        live = sel_ref[0].astype(jnp.int32) != 0
        masked = jnp.where(live, index, _NEG_INF)
        # the statistics sit in every lane of their scratch, as the flash
        # kernels' do
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(masked, -1, keepdims=True))
        p = jnp.where(live, jnp.exp(masked - _across(m_next, walk.tile)),
                      0.0)
        l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_next)
                      + jnp.sum(p, -1, keepdims=True))
        m_ref[...] = m_next
        # the target is 0 off the selection: xlogy(pbar, pbar) - pbar I
        pbar = p_ref[0]
        seen = pbar > 0.0
        a_ref[...] += jnp.sum(jnp.where(
            seen, pbar * (jnp.log(jnp.where(seen, pbar, 1.0)) - index), 0.0),
            -1, keepdims=True)
        s_ref[...] += jnp.sum(pbar, -1, keepdims=True)

    @pl.when(t == last)
    def _emit():
        l = l_ref[...][:, :1]
        lse = m_ref[...][:, :1] + jnp.log(jnp.where(l == 0.0, 1.0, l))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])
        # sum_s pbar (log pbar - (I - lse))
        kl_ref[0] = (a_ref[...] + lse * s_ref[...])[:, :_RES_LANES]


def _grads_kernel(c_ref, q_ref, k_ref, w_ref, sel_ref, p_ref, lse_ref,
                  carry_ref, dq_ref, dk_ref, dw_ref, *, walk: Walk,
                  scale: float):
    t, last = _enter(c_ref, walk)

    @pl.when(t == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(t <= last)
    def _tile():
        k, w = k_ref[0], w_ref[0]
        live = sel_ref[0].astype(jnp.int32) != 0
        d_index = jnp.where(
            live, jnp.exp(_index(q_ref, k, w) - lse_ref[0][:, :1])
            - p_ref[0], 0.0) * scale
        head = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dk, dw = jnp.zeros(k.shape, jnp.float32), jnp.zeros_like(w)
        for h in range(q_ref.shape[1]):
            dots = _dots(q_ref, k, h)
            up = dots > 0.0
            dw = dw + jnp.where(head == h, jnp.sum(
                jnp.where(up, dots * d_index, 0.0), -1, keepdims=True), 0.0)
            d_dots = jnp.where(up, d_index * w[:, h:h + 1],
                               0.0).astype(k.dtype)
            dq_ref[0, h] += jax.lax.dot_general(
                d_dots, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (rows, Di)
            dk = dk + jax.lax.dot_general(
                d_dots, q_ref[0, h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (tile, Di)
        dk_ref[0] = carry_ref[0] + dk
        dw_ref[0] += dw


def _vmem_limit(walk: Walk, heads: int, dim: int, itemsize: int
                ) -> Optional[int]:
    """What the gradients' kernel may hold, where Mosaic's own 16 MiB is
    too little: the chunk's qI and dqI blocks and a tile's selection and
    target, each twice over for the pipeline, and eight [rows, tile]
    float32 temporaries. ``None`` keeps the default, which the cell's
    bfloat16 call does (it uses 11.8 MB; float32 operands need 16.5). A
    limit of its own is not free: XLA sets the whole of it aside round
    the call, out of the VMEM its own fusions beside the call would use
    (at 36 MB the target's scope beside the kernels read 93.8 ms a step
    for 87.5 at 17 MB: PERF.md section 6, PR 55)."""
    chunk = 2 * heads * walk.rows * dim * (itemsize + 4)
    tile = walk.rows * walk.tile
    need = chunk + 2 * tile * 5 + 8 * tile * 4
    return need if need > (16 << 20) else None


def _bind(key: tuple, call, operands):
    """``call(*operands)``, traced once for ``key`` and the operands'
    types and bound again from its jaxpr (``attention_kernels._Walk.call``
    says why)."""
    key += tuple(jax.typeof(o) for o in operands)
    traced = _TRACED.get(key)
    if traced is None:
        if len(_TRACED) >= 64:
            _TRACED.clear()
        traced = _TRACED[key] = jax.make_jaxpr(call)(*operands)
    return jax.core.eval_jaxpr(traced.jaxpr, traced.consts, *operands)


def chunk_calls(b: int, s: int, rows: int, heads: int, dim: int, dtype, *,
                tile: Optional[int] = None, interpret: bool = False):
    """(the walk, the statistics' ``pallas_call``, the gradients') for
    chunks of ``rows`` query rows over ``s`` keys, on operands as the
    kernels take them: ``stats(chunk [1], qI [B, Hi, rows, Di], kI, w,
    chosen, pbar) -> (lse, kl)``, both [B, rows, lanes], and
    ``grads(chunk, qI, kI, w, chosen, pbar, lse, dkI) -> (dqI [B, Hi, rows,
    Di], dkI, dw)``."""
    walk = walk_of(s, rows, tile)
    at = lambda t, c: jnp.minimum(t, walk.last(c[0]))
    # the chunk's own blocks, and a key tile's
    qspec = pl.BlockSpec((1, heads, rows, dim), lambda b, t, c: (b, 0, 0, 0))
    wspec = pl.BlockSpec((1, rows, heads), lambda b, t, c: (b, 0, 0))
    rspec = pl.BlockSpec((1, rows, _RES_LANES), lambda b, t, c: (b, 0, 0))
    kspec = pl.BlockSpec((1, walk.tile, dim),
                         lambda b, t, c: (b, at(t, c), 0))
    tspec = pl.BlockSpec((1, rows, walk.tile),
                         lambda b, t, c: (b, 0, at(t, c)))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(walk, heads, dim,
                                     jnp.dtype(dtype).itemsize))
    grid = (b, walk.steps)
    stats = pl.pallas_call(
        functools.partial(_stats_kernel, walk=walk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[qspec, kspec, wspec, tspec, tspec],
            out_specs=[rspec, rspec],
            scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.float32)] * 4),
        out_shape=[f32(b, rows, _RES_LANES)] * 2, compiler_params=params,
        name=STATS, interpret=interpret)
    grads = pl.pallas_call(
        functools.partial(_grads_kernel, walk=walk, scale=1.0 / (b * s)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[qspec, kspec, wspec, tspec, tspec, rspec, kspec],
            out_specs=[qspec, kspec, wspec]),
        out_shape=[f32(b, heads, rows, dim), f32(b, s, dim),
                   f32(b, rows, heads)],
        # the carry (operand 7, the chunk's number counted) is the second
        # result: tiles past the walk keep what they held
        input_output_aliases={7: 1},
        compiler_params=params, name=GRADS, interpret=interpret)
    return walk, stats, grads


def term_chunk(chunk, qi, ki, w, chosen, pbar, dki, *,
               tile: Optional[int] = None, interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, ...]:
    """The index-score part of the term for ONE chunk of query rows.

    ``chunk``: the chunk's number (an int32 scalar; its rows are ``chunk *
    rows .. + rows - 1``); ``qi`` [B, rows, Hi, Di] and ``ki`` [B, S, Di]
    in the compute dtype; ``w`` [B, rows, Hi] float32; ``chosen`` int8 and
    ``pbar`` float32 [B, rows, S]; ``dki`` [B, S, Di] float32, the sum of
    the earlier chunks' gradients to ``kI``. Returns ``(kl, dqi, dki,
    dw)``: the rows' ``sum_s pbar (log pbar - log softmax(I))`` over their
    selected keys, float32 [B, rows]; and the gradients of the mean of it
    over all B x S rows to this chunk's ``qI`` [B, rows, Hi, Di] and ``w``
    [B, rows, Hi], and ``dki`` with this chunk's part added, float32."""
    interpret = attention_kernels._resolve_interpret(interpret)
    b, rows, heads, dim = qi.shape
    s = ki.shape[1]

    def call(chunk, qi, ki, w, chosen, pbar, dki):
        _, stats, grads = chunk_calls(b, s, rows, heads, dim, qi.dtype,
                                      tile=tile, interpret=interpret)
        lse, kl = stats(chunk, qi, ki, w, chosen, pbar)
        dqi, dki, dw = grads(chunk, qi, ki, w, chosen, pbar, lse, dki)
        return kl[..., 0], dqi, dki, dw

    kl, dqi, dki, dw = _bind(
        (walk_of(s, rows, tile), interpret), call,
        (jnp.asarray(chunk, jnp.int32).reshape(1), qi.transpose(0, 2, 1, 3),
         ki, w, chosen, pbar, dki))
    return kl, dqi.transpose(0, 2, 1, 3), dki, dw
